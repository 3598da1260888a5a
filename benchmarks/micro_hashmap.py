"""Paper Figure 9: HashMap operation microbenchmarks.

Variants (paper naming):
  insert          fully-atomic insert (Table 3a: 2A + W)
  insert_buffer   HashMapBuffer staged insert + flush (the 10x mechanism)
  find_atomic     fully-atomic find (Table 3c: 2A + R)
  find            phase-local find (Table 3d: R)
  find_2attempt   speculative dual-attempt find (2 collectives, not 4)

The ``--fused`` arm adds the ExchangePlan fusion pair:
  find_insert_fused   find + insert flows sharing one plan (2 collectives)
  find_insert_fine    the Promise.FINE sequential oracle (4 collectives)

The ``--skew zipf`` arm adds the skew-tolerance pair (zipf-sized waves
at mean-load wire capacity):
  insert_skew_drop    drop-mode: overflowed inserts fail (counted)
  insert_skew_retry   carryover retry rounds: every insert lands

The ``--async`` arm adds the split-phase pair (DESIGN.md section 1.9):
  find_insert_sync    one-shot commit baseline
  find_insert_async   commit_async/finish: identical results and cost
                      columns, plus the overlap_launches observable

The ``--faults`` arm (DESIGN.md section 1.8) inserts through a
FaultInjectingTransport with a seeded corrupt spec under the integrity
checksum, re-sends the unacked inserts over a clean wire, and probes a
degraded commit; the lost_bytes / recovered / unreachable columns
report the loss, the heal, and the dead-rank mask.

The ``--wire {scatter,fused}`` arm re-runs every variant with the
send-buffer construction pinned (DESIGN.md section 1.10): ``scatter``
forces the two-pass scatter_rows fallback, ``fused`` the one-kernel
Pallas pack; rows gain the suffix (identical bytes/collectives either
way).

Reported as microseconds per operation (amortized over the batch) plus
the collective/bytes/rounds observables and rounds_per_op, so the
paper's relative claims (buffer >> insert; find 2-3x over find_atomic)
and the fused schedules' round reduction are directly checkable from
the CSV.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import ShapeDtypeStruct as SDS

from benchmarks.util import (emit, resolve_transport, resolve_wire,
                             time_fn, trace_costs)
from repro.core import ConProm, Promise, get_backend
from repro.containers import hashmap as hm
from repro.containers import hashmap_buffer as hb

N_OPS = 1 << 14
TABLE = 1 << 17
WAVES = 8                      # fine-grained ops issue per-wave


def run(smoke: bool = False, fused: bool = False, skew: str = "none",
        transport: str = "dense", faults: bool = False,
        async_: bool = False, wire: str = "auto"):
    tr, sfx = resolve_transport(transport)
    impl, wsfx = resolve_wire(wire)
    sfx = sfx + wsfx
    n_ops = 1 << 8 if smoke else N_OPS
    table = 1 << 11 if smoke else TABLE
    bk = get_backend(None)
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.permutation(1 << 22)[:n_ops], jnp.uint32)
    vals = keys * 3 + 1
    results = {}
    obs = {}

    def fresh():
        return hm.hashmap_create(bk, table, SDS((), jnp.uint32),
                                 SDS((), jnp.uint32), block_size=64,
                                 impl=impl)

    def bench(tag, fn, *args):
        obs[tag] = trace_costs(fn, *args)
        results[tag] = time_fn(fn, *args) / n_ops * 1e6

    # --- insert (fully atomic), issued in WAVES batches ---
    spec, st0 = fresh()
    wave = n_ops // WAVES

    @jax.jit
    def insert_waves(st, keys, vals):
        for i in range(WAVES):
            st, _ = hm.insert(bk, spec, st, keys[i * wave:(i + 1) * wave],
                              vals[i * wave:(i + 1) * wave], capacity=wave,
                              promise=ConProm.HashMap.find_insert,
                              attempts=1, transport=tr)
        return st

    bench("hashmap_insert", insert_waves, st0, keys, vals)

    # --- insert through the HashMapBuffer ---
    spec, st0 = fresh()
    bspec, bst0 = hb.create(bk, spec, st0, queue_capacity=n_ops,
                            buffer_cap=n_ops)

    @jax.jit
    def insert_buffered(bst, keys, vals):
        for i in range(WAVES):
            bst, _ = hb.insert(bspec, bst, keys[i * wave:(i + 1) * wave],
                               vals[i * wave:(i + 1) * wave])
        bst, _ = hb.flush(bk, bspec, bst, capacity=n_ops, transport=tr)
        return bst

    bench("hashmap_insert_buffer", insert_buffered, bst0, keys, vals)

    # --- finds against a populated table ---
    spec, st = fresh()
    st, _ = hm.insert(bk, spec, st, keys, vals, capacity=n_ops)

    @jax.jit
    def find_atomic(st, keys):
        for i in range(WAVES):
            st, v, f = hm.find(bk, spec, st, keys[i * wave:(i + 1) * wave],
                               capacity=wave,
                               promise=ConProm.HashMap.find_insert,
                               attempts=1, transport=tr)
        return v, f

    @jax.jit
    def find_relaxed(st, keys):
        for i in range(WAVES):
            _, v, f = hm.find(bk, spec, st, keys[i * wave:(i + 1) * wave],
                              capacity=wave, promise=ConProm.HashMap.find,
                              attempts=1, transport=tr)
        return v, f

    @jax.jit
    def find_2attempt(st, keys):
        for i in range(WAVES):
            _, v, f = hm.find(bk, spec, st, keys[i * wave:(i + 1) * wave],
                              capacity=wave, promise=ConProm.HashMap.find,
                              attempts=2, transport=tr)
        return v, f

    bench("hashmap_find_atomic", find_atomic, st, keys)
    bench("hashmap_find", find_relaxed, st, keys)
    bench("hashmap_find_2attempt", find_2attempt, st, keys)

    # --- fused arm: find+insert sharing one plan vs the FINE oracle ---
    if fused:
        keys2 = jnp.asarray(rng.permutation(1 << 22)[n_ops:2 * n_ops],
                            jnp.uint32)

        def fi(promise):
            spec_f, st_f = fresh()
            st_f, _ = hm.insert(bk, spec_f, st_f, keys, vals, capacity=n_ops)

            @jax.jit
            def rounds(st, fk, ik, iv):
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    st, _, _, _ = hm.find_insert(
                        bk, spec_f, st, fk[sl], ik[sl], iv[sl],
                        capacity=wave, promise=promise, transport=tr)
                return st

            return rounds, st_f

        for tag, prom in (
                ("hashmap_find_insert_fused", ConProm.HashMap.find_insert),
                ("hashmap_find_insert_fine",
                 ConProm.HashMap.find_insert | Promise.FINE)):
            fn, st_f = fi(prom)
            obs[tag] = trace_costs(fn, st_f, keys, keys2, keys2 * 5 + 1)
            # 2 ops (one find + one insert) per wave item
            results[tag] = time_fn(fn, st_f, keys, keys2, keys2 * 5 + 1) \
                / (2 * n_ops) * 1e6

    # --- async arm: split-phase find_insert (DESIGN.md section 1.9) ---
    if async_:
        keys3 = jnp.asarray(rng.permutation(1 << 22)[2 * n_ops:3 * n_ops],
                            jnp.uint32)

        def fia(split, tag):
            spec_a, st_a = fresh()
            st_a, _ = hm.insert(bk, spec_a, st_a, keys, vals, capacity=n_ops)

            @jax.jit
            def rounds(st, fk, ik, iv):
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    if split:
                        pend = hm.find_insert(
                            bk, spec_a, st, fk[sl], ik[sl], iv[sl],
                            capacity=wave,
                            promise=ConProm.HashMap.find_insert,
                            transport=tr, async_=True)
                        st, _, _, _ = pend.finish()
                    else:
                        st, _, _, _ = hm.find_insert(
                            bk, spec_a, st, fk[sl], ik[sl], iv[sl],
                            capacity=wave,
                            promise=ConProm.HashMap.find_insert,
                            transport=tr)
                return st

            obs[tag] = trace_costs(rounds, st_a, keys, keys3, keys3 * 5 + 1)
            results[tag] = time_fn(rounds, st_a, keys, keys3, keys3 * 5 + 1) \
                / (2 * n_ops) * 1e6

        fia(False, "hashmap_find_insert_sync")
        fia(True, "hashmap_find_insert_async")

    # --- skew arm: mean-load capacity, drop-mode vs carryover retries ---
    if skew == "zipf":
        from benchmarks.util import (bench_skew_arm, mean_load_cap,
                                     skew_retry_rounds, zipf_wave_mask)
        zcap = mean_load_cap(wave)
        zvalid = zipf_wave_mask(WAVES, wave, n_ops)
        n_skew = int(zvalid.sum())     # actual ops (hot waves saturate)
        # observed trajectory: each wave's hot-block load; suggest_rounds
        # picks R off the peak (ROADMAP adaptive rounds)
        rr = skew_retry_rounds(
            [int(x) for x in np.asarray(zvalid.sum(axis=1))], zcap)

        def bench_skew(rounds, tag):
            spec_s, st_s = fresh()

            @jax.jit
            def inserts(st, keys, vals):
                okn = jnp.int32(0)
                nval = jnp.int32(0)
                for i in range(WAVES):
                    sl = slice(i * wave, (i + 1) * wave)
                    st, ok = hm.insert(bk, spec_s, st, keys[sl], vals[sl],
                                       capacity=zcap, valid=zvalid[i],
                                       attempts=1, max_rounds=rounds,
                                       transport=tr)
                    okn = okn + ok.sum().astype(jnp.int32)
                    nval = nval + zvalid[i].sum().astype(jnp.int32)
                return st, nval - okn       # failed == dropped-on-wire

            bench_skew_arm(inserts, tag, rounds, n_skew, results,
                           st_s, keys, vals,
                           derived="zipf waves @ mean-load capacity")

        bench_skew(1, "hashmap_insert_skew_drop" + sfx)
        bench_skew(rr, "hashmap_insert_skew_retry" + sfx)

    # --- faults arm: seeded corruption healed by integrity + re-send ---
    if faults:
        from repro.core import FaultInjectingTransport, FaultSpec, costs
        fspec = FaultSpec(seed=7, corrupt=((0, 0, 0),))
        ftr = FaultInjectingTransport(tr, fspec)
        spec_f, st_f = fresh()

        @jax.jit
        def faulty_insert(st, keys, vals):
            # first shot over the faulty fabric: checksum-failed arrivals
            # never ack, so their inserts come back unsuccessful
            st, ok1 = hm.insert(bk, spec_f, st, keys, vals,
                                capacity=n_ops, attempts=1, transport=ftr,
                                integrity=True)
            lost = (~ok1).sum().astype(jnp.int32)
            # heal: re-send exactly the unacked inserts over a clean wire
            st, ok2 = hm.insert(bk, spec_f, st, keys, vals,
                                capacity=n_ops, valid=~ok1, attempts=1,
                                transport=tr, integrity=True)
            return st, lost, ok2.sum().astype(jnp.int32)

        with costs.recording() as flog:
            out = faulty_insert(st_f, keys, vals)
            # degraded-commit probe: rank 0 declared dead at admission
            hm.insert(bk, spec_f, out[0], keys[:8], vals[:8], capacity=8,
                      attempts=1, dead_ranks=(0,))
            jax.block_until_ready(out)
        lost_items = int(out[1])
        row_bytes = 4 * (1 + spec_f.key_packer.lanes
                         + spec_f.val_packer.lanes + 1)  # body + meta lane
        t = time_fn(faulty_insert, st_f, keys, vals, warmup=1, iters=3)
        emit("hashmap_insert_faults" + sfx, t / n_ops * 1e6,
             "seeded corrupt + clean re-send + degraded probe",
             cost=flog.total(), n_ops=n_ops,
             lost_bytes=lost_items * row_bytes, recovered=int(out[2]),
             unreachable=int(flog.total().unreachable))

    emit("hashmap_insert" + sfx, results["hashmap_insert"], "2A+W",
         cost=obs["hashmap_insert"], n_ops=n_ops)
    emit("hashmap_insert_buffer" + sfx, results["hashmap_insert_buffer"],
         f"speedup={results['hashmap_insert'] / results['hashmap_insert_buffer']:.2f}x",
         cost=obs["hashmap_insert_buffer"], n_ops=n_ops)
    emit("hashmap_find_atomic" + sfx, results["hashmap_find_atomic"], "2A+R",
         cost=obs["hashmap_find_atomic"], n_ops=n_ops)
    emit("hashmap_find" + sfx, results["hashmap_find"],
         f"speedup={results['hashmap_find_atomic'] / results['hashmap_find']:.2f}x",
         cost=obs["hashmap_find"], n_ops=n_ops)
    emit("hashmap_find_2attempt" + sfx, results["hashmap_find_2attempt"],
         "2 rounds/wave", cost=obs["hashmap_find_2attempt"], n_ops=n_ops)
    if fused:
        emit("hashmap_find_insert_fused" + sfx, results["hashmap_find_insert_fused"],
             "2 collectives/round-trip",
             cost=obs["hashmap_find_insert_fused"], n_ops=2 * n_ops)
        emit("hashmap_find_insert_fine" + sfx, results["hashmap_find_insert_fine"],
             "FINE oracle: 4 collectives",
             cost=obs["hashmap_find_insert_fine"], n_ops=2 * n_ops)
    if async_:
        emit("hashmap_find_insert_sync" + sfx,
             results["hashmap_find_insert_sync"], "one-shot commit",
             cost=obs["hashmap_find_insert_sync"], n_ops=2 * n_ops)
        emit("hashmap_find_insert_async" + sfx,
             results["hashmap_find_insert_async"],
             "split-phase commit_async/finish",
             cost=obs["hashmap_find_insert_async"], n_ops=2 * n_ops)
    return results


if __name__ == "__main__":
    run()
