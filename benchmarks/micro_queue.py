"""Paper Figures 10/11: CircularQueue and FastQueue microbenchmarks.

Variants (paper naming):
  push_pushpop / pop_pushpop    CircularQueue fully atomic (2A + nW/nR)
  push_push / pop_pop           CircularQueue phase-relaxed
  fq_push / fq_pop              FastQueue (A + nW/nR)
  *_many                        one queue per rank, all ranks pushing

The ``--fused`` arm adds the ExchangePlan fusion pair:
  cq_push_pop_fused             push + pop flows sharing one plan (2
                                collectives per wave)
  cq_push_pop_fine              the Promise.FINE sequential oracle (3)

The ``--skew zipf`` arm adds the skew-tolerance pair (mean-load wire
capacity, zipf-sized waves into one hot ring — the hottest (src,dst)
bucket the paper's aggregation can produce):
  fq_push_skew_drop             drop-mode: overflow is counted data loss
  fq_push_skew_retry            carryover retry rounds: zero drops at
                                the same per-round capacity

The ``--async`` arm adds the split-phase pair (DESIGN.md section 1.9):
  cq_push_pop_sync              one-shot commit baseline
  cq_push_pop_async             commit_async/finish: identical results
                                and cost columns, plus the
                                overlap_launches observable

The ``--wire {scatter,fused}`` arm re-runs every variant with the
send-buffer construction pinned (DESIGN.md section 1.10): rows gain the
``_scatter`` / ``_fused`` suffix.

The ``--faults`` arm (DESIGN.md section 1.8) pushes through a
FaultInjectingTransport with a seeded corrupt spec under the integrity
checksum, heals the invalidated arrivals with a carry re-push, and
probes a degraded commit; the lost_bytes / recovered / unreachable
columns report the loss, the heal, and the dead-rank mask.

Each row carries the collective/bytes/rounds observables (and
rounds_per_op) of one jitted call so exchange-layer regressions show up
next to wall time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import ShapeDtypeStruct as SDS

from benchmarks.util import (emit, resolve_transport, resolve_wire,
                             time_fn, trace_costs)
from repro.core import ConProm, Promise, get_backend
from repro.containers import queue as q

N_OPS = 1 << 14
WAVES = 8


def run(smoke: bool = False, fused: bool = False, skew: str = "none",
        transport: str = "dense", faults: bool = False,
        async_: bool = False, wire: str = "auto"):
    tr, sfx = resolve_transport(transport)
    impl, wsfx = resolve_wire(wire)
    sfx = sfx + wsfx
    n_ops = 1 << 8 if smoke else N_OPS
    bk = get_backend(None)
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.integers(0, 1 << 30, n_ops), jnp.uint32)
    dest = jnp.zeros(n_ops, jnp.int32)
    wave = n_ops // WAVES
    results = {}
    obs = {}

    def bench_push(circular, promise, tag):
        spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32),
                                   circular=circular)

        @jax.jit
        def pushes(st, vals, dest):
            for i in range(WAVES):
                st, _, _ = q.push(bk, spec, st,
                                  vals[i * wave:(i + 1) * wave],
                                  dest[i * wave:(i + 1) * wave],
                                  capacity=wave, promise=promise,
                                  transport=tr, impl=impl)
            return st

        obs[tag] = trace_costs(pushes, st0, vals, dest)
        t = time_fn(pushes, st0, vals, dest)
        results[tag] = t / n_ops * 1e6
        return spec, pushes

    bench_push(True, ConProm.CircularQueue.push_pop, "cq_push_pushpop")
    bench_push(True, ConProm.CircularQueue.push, "cq_push_push")
    bench_push(False, ConProm.FastQueue.push, "fq_push")

    def bench_pop(circular, promise, tag):
        spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32),
                                   circular=circular)
        st0, _, _ = q.push(bk, spec, st0, vals, dest, capacity=n_ops)

        @jax.jit
        def pops(st):
            outs = []
            for _ in range(WAVES):
                st, out, got = q.pop(bk, spec, st, wave, 0, promise=promise,
                                     transport=tr, impl=impl)
                outs.append(out)
            return st, outs

        obs[tag] = trace_costs(pops, st0)
        t = time_fn(pops, st0)
        results[tag] = t / n_ops * 1e6

    bench_pop(True, ConProm.CircularQueue.push_pop, "cq_pop_pushpop")
    bench_pop(True, ConProm.CircularQueue.pop, "cq_pop_pop")
    bench_pop(False, ConProm.FastQueue.pop, "fq_pop")

    # local nonatomic pop (Table 2: l)
    spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32))
    st0, _, _ = q.push(bk, spec, st0, vals, dest, capacity=n_ops)

    @jax.jit
    def local_pops(st):
        for _ in range(WAVES):
            st, out, got = q.local_nonatomic_pop(spec, st, wave)
        return st, out

    obs["fq_local_pop"] = trace_costs(local_pops, st0)
    results["fq_local_pop"] = time_fn(local_pops, st0) / n_ops * 1e6

    # --- fused arm: push+pop sharing one plan vs the FINE oracle ---
    if fused:
        def pp(promise, tag):
            spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32),
                                       circular=True)

            @jax.jit
            def waves(st, vals, dest):
                outs = []
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    st, _, _, out, _ = q.push_pop(
                        bk, spec, st, vals[sl], dest[sl], wave, wave, 0,
                        promise=promise, transport=tr)
                    outs.append(out)
                return st, outs

            obs[tag] = trace_costs(waves, st0, vals, dest)
            # 2 ops (one push + one pop) per wave item
            results[tag] = time_fn(waves, st0, vals, dest) \
                / (2 * n_ops) * 1e6

        pp(ConProm.CircularQueue.push_pop, "cq_push_pop_fused")
        pp(ConProm.CircularQueue.push_pop | Promise.FINE, "cq_push_pop_fine")

    # --- async arm: split-phase push_pop (DESIGN.md section 1.9) ---
    if async_:
        def ppa(split, tag):
            spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32),
                                       circular=True)

            @jax.jit
            def waves(st, vals, dest):
                outs = []
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    if split:
                        pend = q.push_pop(
                            bk, spec, st, vals[sl], dest[sl], wave, wave, 0,
                            promise=ConProm.CircularQueue.push_pop,
                            transport=tr, async_=True)
                        st, _, _, out, _ = pend.finish()
                    else:
                        st, _, _, out, _ = q.push_pop(
                            bk, spec, st, vals[sl], dest[sl], wave, wave, 0,
                            promise=ConProm.CircularQueue.push_pop,
                            transport=tr)
                    outs.append(out)
                return st, outs

            obs[tag] = trace_costs(waves, st0, vals, dest)
            results[tag] = time_fn(waves, st0, vals, dest) \
                / (2 * n_ops) * 1e6

        ppa(False, "cq_push_pop_sync")
        ppa(True, "cq_push_pop_async")

    # --- skew arm: mean-load capacity, drop-mode vs carryover retries ---
    if skew == "zipf":
        from benchmarks.util import (bench_skew_arm, mean_load_cap,
                                     skew_retry_rounds, zipf_wave_mask)
        zcap = mean_load_cap(wave)
        valid = zipf_wave_mask(WAVES, wave, n_ops)         # (WAVES, wave)
        n_skew = int(valid.sum())      # actual ops (hot waves saturate)
        # observed trajectory: the all-to-one hot bucket's load is each
        # wave's valid count; suggest_rounds picks R off the peak
        rr = skew_retry_rounds(
            [int(x) for x in np.asarray(valid.sum(axis=1))], zcap)

        def bench_skew(rounds, tag):
            spec, st0 = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32))

            @jax.jit
            def pushes(st, vals, dest):
                dropped = jnp.int32(0)
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    st, _, d = q.push(bk, spec, st, vals[sl], dest[sl],
                                      capacity=zcap, valid=valid[i],
                                      max_rounds=rounds, transport=tr)
                    dropped = dropped + d
                return st, dropped

            bench_skew_arm(pushes, tag, rounds, n_skew, results,
                           st0, vals, dest,
                           derived="zipf waves @ mean-load capacity")

        bench_skew(1, "fq_push_skew_drop" + sfx)
        bench_skew(rr, "fq_push_skew_retry" + sfx)

    # --- faults arm: seeded corruption healed by integrity + carry ---
    if faults:
        from repro.core import FaultInjectingTransport, FaultSpec, costs
        fspec = FaultSpec(seed=7, corrupt=((0, 0, 0),))
        ftr = FaultInjectingTransport(tr, fspec)
        spec_f, st_f = q.queue_create(bk, n_ops * 2, SDS((), jnp.uint32))

        @jax.jit
        def faulty_push(st, vals, dest):
            # first shot over the faulty fabric: the corrupted segment's
            # arrivals fail their checksum, get no ack, land in carry
            st, _, _, carry = q.push(
                bk, spec_f, st, vals, dest, capacity=n_ops,
                overflow="carry", transport=ftr, integrity=True)
            # heal: re-inject exactly the carried rows over a clean wire
            st, _, _, carry2 = q.push(
                bk, spec_f, st, vals, dest, capacity=n_ops, valid=carry,
                overflow="carry", transport=tr, integrity=True)
            return st, carry.sum().astype(jnp.int32), \
                carry2.sum().astype(jnp.int32)

        with costs.recording() as flog:
            out = faulty_push(st_f, vals, dest)
            # degraded-commit probe: rank 0 declared dead at admission
            q.push(bk, spec_f, out[0], vals[:8], dest[:8], capacity=8,
                   dead_ranks=(0,))
            jax.block_until_ready(out)
        lost_items = int(out[1])
        recovered = lost_items - int(out[2])
        row_bytes = 4 * (spec_f.lanes + 1)       # payload + meta lane
        t = time_fn(faulty_push, st_f, vals, dest, warmup=1, iters=3)
        emit("fq_push_faults" + sfx, t / n_ops * 1e6,
             "seeded corrupt + carry heal + degraded probe",
             cost=flog.total(), n_ops=n_ops,
             lost_bytes=lost_items * row_bytes, recovered=recovered,
             unreachable=int(flog.total().unreachable))

    for k in ("cq_push_pushpop", "cq_push_push", "fq_push",
              "cq_pop_pushpop", "cq_pop_pop", "fq_pop", "fq_local_pop"):
        emit(k + sfx, results[k],
             "2A" if "pushpop" in k else ("A" if k.startswith("fq") else "2A"),
             cost=obs[k], n_ops=n_ops)
    if fused:
        emit("cq_push_pop_fused" + sfx, results["cq_push_pop_fused"],
             "2 collectives/wave", cost=obs["cq_push_pop_fused"],
             n_ops=2 * n_ops)
        emit("cq_push_pop_fine" + sfx, results["cq_push_pop_fine"],
             "FINE oracle: 3 collectives", cost=obs["cq_push_pop_fine"],
             n_ops=2 * n_ops)
    if async_:
        emit("cq_push_pop_sync" + sfx, results["cq_push_pop_sync"],
             "one-shot commit", cost=obs["cq_push_pop_sync"],
             n_ops=2 * n_ops)
        emit("cq_push_pop_async" + sfx, results["cq_push_pop_async"],
             "split-phase commit_async/finish",
             cost=obs["cq_push_pop_async"], n_ops=2 * n_ops)
    return results


if __name__ == "__main__":
    run()
