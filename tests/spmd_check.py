"""Multi-device correctness battery; run under 8 fake CPU devices.

Invoked by test_multidevice.py as a subprocess (the parent test process
keeps its 1-device world).  Exits non-zero on any failure.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import ShapeDtypeStruct as SDS
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import make_mesh

from repro.core import ConProm, Promise, get_backend, route
from repro.containers import bloom as bl
from repro.containers import hashmap as hm
from repro.containers import queue as q


def check(name, ok):
    print(f"{'PASS' if ok else 'FAIL'} {name}")
    if not ok:
        sys.exit(1)


def main():
    assert len(jax.devices()) == 8
    mesh = make_mesh((8,), ("bcl",))
    np.random.seed(0)
    PROCS, NLOC = 8, 64

    # ---- hashmap across devices vs dict oracle ----
    def build_and_query(keys, vals, queries):
        bk = get_backend("bcl")
        spec, st = hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                     SDS((), jnp.uint32), block_size=16)
        st, ok = hm.insert(bk, spec, st, keys, vals, capacity=NLOC)
        st, v, found = hm.find(bk, spec, st, queries, capacity=NLOC)
        return ok, v, found

    keys = jnp.asarray(np.random.permutation(1 << 20)[:PROCS * NLOC],
                       jnp.uint32)
    vals = keys * 7 + 1
    queries = jnp.concatenate([keys[:PROCS * NLOC // 2],
                               keys[:PROCS * NLOC // 2] + (1 << 21)])
    f = jax.jit(jax.shard_map(build_and_query, mesh=mesh,
                              in_specs=(P("bcl"),) * 3,
                              out_specs=(P("bcl"),) * 3))
    ok, v, found = f(keys, vals, queries)
    nf, nv, nq = map(np.asarray, (found, v, queries))
    present = nq < (1 << 21)
    check("hashmap.insert_all", bool(np.asarray(ok).all()))
    check("hashmap.find_present", bool(nf[present].all()))
    check("hashmap.find_absent", not bool(nf[~present].any()))
    check("hashmap.values", bool((nv[present] == nq[present] * 7 + 1).all()))

    # ---- ISx-style queue exchange preserves the multiset ----
    def isx(values, dest):
        bk = get_backend("bcl")
        spec, st = q.queue_create(bk, 512, SDS((), jnp.uint32))
        st, _, dropped = q.push(bk, spec, st, values, dest, capacity=128)
        rows, got = q.local_drain(spec, st)
        return rows, got, dropped[None]

    vals2 = jnp.asarray(np.random.randint(0, 1 << 20, PROCS * 100),
                        jnp.uint32)
    dest2 = (vals2 // ((1 << 20) // 8)).astype(jnp.int32).clip(0, 7)
    g = jax.jit(jax.shard_map(isx, mesh=mesh, in_specs=(P("bcl"),) * 2,
                              out_specs=(P("bcl"),) * 3))
    rows, got, dropped = g(vals2, dest2)
    rec = np.asarray(rows)[np.asarray(got)]
    check("queue.multiset",
          sorted(rec.tolist()) == sorted(np.asarray(vals2).tolist()))
    check("queue.nodrop", int(np.asarray(dropped).sum()) == 0)
    # destination correctness: each received value belongs to its rank
    rows2 = np.asarray(rows).reshape(8, -1)
    got2 = np.asarray(got).reshape(8, -1)
    ok_dest = all(
        (rows2[r][got2[r]] // ((1 << 20) // 8)).clip(0, 7).astype(int)
        .tolist() == [r] * got2[r].sum() for r in range(8))
    check("queue.destinations", ok_dest)

    # ---- fused plans == Promise.FINE oracle on 8 ranks, random data ----
    def fused_or_fine(fine):
        extra = Promise.FINE if fine else Promise.NONE

        def body(keys, vals, fk, ik, iv, qv, qd):
            bk = get_backend("bcl")
            spec, st = hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                         SDS((), jnp.uint32), block_size=16)
            st, _ = hm.insert(bk, spec, st, keys, vals, capacity=NLOC)
            st, v, f, ok = hm.find_insert(
                bk, spec, st, fk, ik, iv, capacity=NLOC,
                promise=ConProm.HashMap.find_insert | extra)
            qspec, qst = q.queue_create(bk, 512, SDS((), jnp.uint32),
                                        circular=True)
            # every rank pops its right neighbor's ring
            nbr = (jax.lax.axis_index("bcl") + 1) % PROCS
            qst, pushed, dropped, out, got = q.push_pop(
                bk, qspec, qst, qv, qd, 32, 24, nbr,
                promise=ConProm.CircularQueue.push_pop | extra)
            return (v, f, ok, out, got, pushed[None], dropped[None],
                    st.status)

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 7,
                                 out_specs=(P("bcl"),) * 8))

    rngf = np.random.default_rng(42)
    base = jnp.asarray(rngf.permutation(1 << 20)[:PROCS * NLOC], jnp.uint32)
    fi_args = (base, base * 5 + 2,
               jnp.asarray(np.where(rngf.random(PROCS * NLOC) < 0.5,
                                    np.asarray(base),
                                    np.asarray(base) + (1 << 21)),
                           jnp.uint32),
               jnp.asarray(rngf.permutation(1 << 20)[:PROCS * NLOC]
                           + (1 << 21), jnp.uint32),
               jnp.asarray(rngf.integers(0, 1 << 30, PROCS * NLOC),
                           jnp.uint32),
               jnp.asarray(rngf.integers(0, 1 << 30, PROCS * 64), jnp.uint32),
               jnp.asarray(rngf.integers(0, PROCS, PROCS * 64), jnp.int32))
    got_fused = fused_or_fine(False)(*fi_args)
    got_fine = fused_or_fine(True)(*fi_args)
    check("plan.fused_equals_fine_8rank",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got_fused, got_fine)))

    # ---- ragged mixed-width plan == FINE on 8 ranks, random dests ----
    # a 1-lane flow and a 3-lane flow with different reply widths share
    # one plan under carryover retries: the ragged wire (per-flow word
    # segments, DESIGN.md section 1.5) must be bit-identical to the
    # sequential oracle on views, replies, and drop counts.
    from repro.core import ExchangePlan

    def ragged_or_fine(fine):
        extra = Promise.FINE if fine else Promise.NONE

        def body(p1, p3, d1, d3):
            bk = get_backend("bcl")
            plan = ExchangePlan(promise=extra, name="ragged")
            h1 = plan.add(p1, d1, 8, reply_lanes=1, op_name="narrow")
            h3 = plan.add(p3, d3, 8, reply_lanes=2, op_name="wide")
            c = plan.commit(bk, max_rounds=2)
            c.set_reply(h1, c.view(h1).payload[:, 0] * 3 + 1)
            c.set_reply(h3, c.view(h3).payload[:, :2] + 5)
            outs = c.finish(bk)
            v1, v3 = c.view(h1), c.view(h3)
            return (outs[h1][0], outs[h1][1], outs[h3][0], outs[h3][1],
                    v1.payload, v1.valid, v3.payload, v3.valid,
                    v1.dropped[None], v3.dropped[None])

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 4,
                                 out_specs=(P("bcl"),) * 10))

    rr = np.random.default_rng(23)
    rg_args = (jnp.asarray(rr.integers(0, 1 << 30, PROCS * 96), jnp.uint32),
               jnp.asarray(rr.integers(0, 1 << 30, (PROCS * 48, 3)),
                           jnp.uint32),
               jnp.asarray(rr.integers(0, PROCS, PROCS * 96), jnp.int32),
               jnp.asarray(rr.integers(0, PROCS, PROCS * 48), jnp.int32))
    got_rf = ragged_or_fine(False)(*rg_args)
    got_rs = ragged_or_fine(True)(*rg_args)
    check("plan.ragged_equals_fine_8rank",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got_rf, got_rs)))

    # ---- split-phase commit_async == sync commit on 8 ranks ----
    # the same mixed-width plan issued split-phase (DESIGN.md section
    # 1.9): commit_async starts the wire, finish() completes it — views,
    # replies, answered masks, and drop counts must be bit-identical to
    # the one-shot commit above, on both physical transports (the
    # hierarchical one overlaps its two hops across retry rounds).
    def ragged_async(transport):
        def body(p1, p3, d1, d3):
            bk = get_backend("bcl")
            plan = ExchangePlan(name="ragged")
            h1 = plan.add(p1, d1, 8, reply_lanes=1, op_name="narrow")
            h3 = plan.add(p3, d3, 8, reply_lanes=2, op_name="wide")
            c = plan.commit_async(bk, max_rounds=2,
                                  transport=transport).finish(bk)
            c.set_reply(h1, c.view(h1).payload[:, 0] * 3 + 1)
            c.set_reply(h3, c.view(h3).payload[:, :2] + 5)
            outs = c.finish(bk)
            v1, v3 = c.view(h1), c.view(h3)
            return (outs[h1][0], outs[h1][1], outs[h3][0], outs[h3][1],
                    v1.payload, v1.valid, v3.payload, v3.valid,
                    v1.dropped[None], v3.dropped[None])

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 4,
                                 out_specs=(P("bcl"),) * 10))

    for tag, tr_a in (("plan.async_equals_sync_8rank", None),
                      ("plan.async_equals_sync_8rank_hier", "hier")):
        got_ra = ragged_async(tr_a)(*rg_args)
        check(tag, all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(got_ra, got_rf)))

    # ---- zipf-skewed destinations: retry rounds make push lossless ----
    # mean-load capacity (n_loc / P) with zipf destination draws: the
    # hot rank overflows every (src, hot) bucket; carryover retries
    # recover exactly the overflow, with no second binning pass.
    n_loc = 128
    zw = 1.0 / (np.arange(1, PROCS + 1) ** 1.3)
    zdest = np.random.default_rng(13).choice(
        PROCS, size=PROCS * n_loc, p=zw / zw.sum())
    zvals = jnp.asarray(np.arange(PROCS * n_loc), jnp.uint32)
    zdest = jnp.asarray(zdest, jnp.int32)
    mean_cap = n_loc // PROCS

    def zpush(rounds):
        def body(values, dest):
            bk = get_backend("bcl")
            spec, st = q.queue_create(bk, 4 * n_loc, SDS((), jnp.uint32))
            st, _, dropped = q.push(bk, spec, st, values, dest,
                                    capacity=mean_cap, max_rounds=rounds)
            rows, got = q.local_drain(spec, st)
            return rows, got, dropped[None]

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 2,
                                 out_specs=(P("bcl"),) * 3))(zvals, zdest)

    _, _, zdrop1 = zpush(1)
    zrows, zgot, zdrop8 = zpush(8)
    rec = np.asarray(zrows)[np.asarray(zgot)]
    check("exchange.zipf_drop_mode_loses", int(np.asarray(zdrop1).sum()) > 0)
    check("exchange.zipf_retry_lossless",
          int(np.asarray(zdrop8).sum()) == 0 and
          sorted(rec.tolist()) == sorted(np.asarray(zvals).tolist()))

    # ---- hierarchical transport == dense on a 2-D factorization ----
    # the full container battery (hashmap find/insert/find_insert, queue
    # push/pop/push_pop, bloom insert_find, a raw retry plan) over the
    # two-stage Pr x Pc exchange must be bit-identical to the dense
    # one-shot all-to-all (DESIGN.md section 1.7)
    from repro.core import HierarchicalTransport, costs as _costs

    def transport_battery(transport):
        def body(keys, vals, fk, ik, iv, qv, qd, p3, d3):
            bk = get_backend("bcl")
            spec, st = hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                         SDS((), jnp.uint32), block_size=16)
            st, ins_ok = hm.insert(bk, spec, st, keys, vals, capacity=NLOC,
                                   transport=transport)
            st, fv, ff = hm.find(bk, spec, st, fk, capacity=NLOC,
                                 transport=transport)
            st, v, f, ok = hm.find_insert(
                bk, spec, st, fk, ik, iv, capacity=NLOC,
                promise=ConProm.HashMap.find_insert, transport=transport)
            qspec, qst = q.queue_create(bk, 512, SDS((), jnp.uint32),
                                        circular=True)
            nbr = (jax.lax.axis_index("bcl") + 1) % PROCS
            qst, pushed, dropped, out, got = q.push_pop(
                bk, qspec, qst, qv, qd, 32, 24, nbr,
                promise=ConProm.CircularQueue.push_pop,
                transport=transport)
            qst, pv, pg = q.pop(bk, qspec, qst, 8, nbr,
                                transport=transport)
            bspec, bst = bl.bloom_create(bk, 1 << 14, SDS((), jnp.uint32),
                                         k=4)
            bst, already, present = bl.insert_find(
                bk, bspec, bst, qv, fk, 64, NLOC, transport=transport)
            # raw plan with carryover retry rounds (max_rounds > 1)
            plan = ExchangePlan(name="retry3")
            h3 = plan.add(p3, d3, 8, reply_lanes=2, op_name="retry3")
            c = plan.commit(bk, max_rounds=3, transport=transport)
            c.set_reply(h3, c.view(h3).payload[:, :2] + 9)
            o3 = c.finish(bk)[h3]
            v3 = c.view(h3)
            return (ins_ok, fv, ff, v, f, ok, pushed[None], dropped[None],
                    out, got, pv, pg, already, present, st.status,
                    bst.words, o3[0], o3[1], v3.payload, v3.valid,
                    v3.dropped[None])

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 9,
                                 out_specs=(P("bcl"),) * 21))

    tb_args = fi_args + (rg_args[1], rg_args[3])
    got_dense = transport_battery(None)(*tb_args)
    for pr, pc in ((2, 4), (4, 2)):
        got_hier = transport_battery(HierarchicalTransport(pr, pc))(*tb_args)
        check(f"exchange.hier_equals_dense_8rank_{pr}x{pc}",
              all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(got_dense, got_hier)))

    # ---- one-kernel wire == scatter oracle on 8 ranks (§1.10) ----
    # the SAME container battery run with impl="jnp" (the declared
    # fallback wire, object_container.scatter_rows) and impl="pallas"
    # (the fused slot+pack kernel that builds the wire in one pass):
    # dense and two-stage transports, one-shot and split-phase commits,
    # integrity checksums on, carryover retry rounds — every output must
    # be bit-identical, raw table state included.
    def wire_battery(impl, transport, split):
        def body(keys, vals, fk, ik, iv, qv, qd, p3, d3):
            bk = get_backend("bcl")
            spec, st = hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                         SDS((), jnp.uint32), block_size=16,
                                         impl=impl)
            st, ins_ok = hm.insert(bk, spec, st, keys, vals, capacity=NLOC,
                                   max_rounds=2, transport=transport,
                                   integrity=True)
            st, fv, ff = hm.find(bk, spec, st, fk, capacity=NLOC,
                                 transport=transport, integrity=True)
            fi = hm.find_insert(
                bk, spec, st, fk, ik, iv, capacity=NLOC,
                promise=ConProm.HashMap.find_insert, transport=transport,
                integrity=True, async_=split)
            st, v, f, ok = fi.finish() if split else fi
            qspec, qst = q.queue_create(bk, 512, SDS((), jnp.uint32),
                                        circular=True)
            nbr = (jax.lax.axis_index("bcl") + 1) % PROCS
            pp = q.push_pop(bk, qspec, qst, qv, qd, 32, 24, nbr,
                            promise=ConProm.CircularQueue.push_pop,
                            transport=transport, integrity=True,
                            impl=impl, async_=split)
            qst, pushed, dropped, out, got = pp.finish() if split else pp
            # raw plan with carryover retries, integrity on
            plan = ExchangePlan(name="wire3")
            h3 = plan.add(p3, d3, 8, reply_lanes=2, op_name="wire3")
            if split:
                c = plan.commit_async(bk, impl=impl, max_rounds=3,
                                      transport=transport,
                                      integrity=True).finish(bk)
            else:
                c = plan.commit(bk, impl=impl, max_rounds=3,
                                transport=transport, integrity=True)
            c.set_reply(h3, c.view(h3).payload[:, :2] + 9)
            o3 = c.finish(bk)[h3]
            v3 = c.view(h3)
            return (ins_ok, fv, ff, v, f, ok, pushed[None], dropped[None],
                    out, got, st.tkeys, st.tvals, st.status,
                    o3[0], o3[1], v3.payload, v3.valid, v3.dropped[None])

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 9,
                                 out_specs=(P("bcl"),) * 18))

    for tag, tr_w, split in (("dense_sync", None, False),
                             ("dense_async", None, True),
                             ("hier_sync", HierarchicalTransport(2, 4),
                              False),
                             ("hier_async", HierarchicalTransport(2, 4),
                              True)):
        got_sc = wire_battery("jnp", tr_w, split)(*tb_args)
        got_fu = wire_battery("pallas", tr_w, split)(*tb_args)
        check(f"wire.fused_equals_scatter_8rank_{tag}",
              all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(got_sc, got_fu)))

    # faults arm: the same seeded corruption (integrity checksums on)
    # must produce bit-identical arrivals AND loss accounting on both
    # wires — fusion may not move bytes across checksum windows
    from repro.core import (FaultInjectingTransport as _FIT,
                            FaultSpec as _FSpec,
                            make_transport as _mk_tr)

    def wire_fault(impl):
        ftr = _FIT(_mk_tr("dense"), _FSpec(seed=7, corrupt=((0, 2, 5),)))

        def body(pay, dst):
            bk = get_backend("bcl")
            res = route(bk, pay, dst, capacity=64, op_name="wf", impl=impl,
                        transport=ftr, integrity=True)
            return res.payload, res.valid, res.lost[None], res.dropped[None]

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 2,
                                 out_specs=(P("bcl"),) * 4))

    wf_rng = np.random.default_rng(9)
    wf_pay = jnp.asarray(wf_rng.integers(0, 1 << 30, (PROCS * 64, 2)),
                         jnp.uint32)
    wf_dst = jnp.asarray(wf_rng.integers(0, PROCS, PROCS * 64), jnp.int32)
    got_wj = wire_fault("jnp")(wf_pay, wf_dst)
    got_wp = wire_fault("pallas")(wf_pay, wf_dst)
    check("wire.fused_equals_scatter_faults",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got_wj, got_wp))
          and int(np.asarray(got_wj[2]).sum()) > 0)

    # ---- per-hop byte attribution + the sparse-destination wire pin ----
    # every rank sends all n items to ONE rank ((r+1) % 8): per-stage
    # loads are 8, so explicit stage caps (8, 8) are lossless while the
    # dense wire must pad EVERY (src, dst) pair to the hottest bucket.
    # 4-lane rows: hier = Pc*c1*(L+2) + Pr*c2*(L+2) = 48*6 words/rank,
    # dense = P*C*(L+1) = 64*5 — the two-stage wire is strictly below.
    n_sp, lanes_sp = 8, 4
    sp_pay = jnp.asarray(
        np.random.default_rng(5).integers(0, 1 << 19,
                                          (PROCS * n_sp, lanes_sp)),
        jnp.uint32)

    def sparse_push(transport):
        def body(pay):
            bk = get_backend("bcl")
            dest = jnp.full((n_sp,), (jax.lax.axis_index("bcl") + 1)
                            % PROCS, jnp.int32)
            res = route(bk, pay, dest, capacity=n_sp, op_name="sp",
                        transport=transport)
            return res.payload, res.valid, res.dropped[None]

        with _costs.recording() as log:
            out = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=(P("bcl"),),
                                    out_specs=(P("bcl"),) * 3))(sp_pay)
        return out, log

    hier_sp = HierarchicalTransport(2, 4, stage_caps={"sp": (8, 8)})
    (dp, dv, dd), dlog = sparse_push(None)
    (hp, hv, hd), hlog = sparse_push(hier_sp)
    check("exchange.hier_sparse_results_equal",
          np.array_equal(np.asarray(dp), np.asarray(hp))
          and np.array_equal(np.asarray(dv), np.asarray(hv))
          and int(np.asarray(hd).sum()) == 0)
    dense_words = PROCS * n_sp * (lanes_sp + 1)
    hier_words = (4 * 8 + 2 * 8) * (lanes_sp + 2)
    c_d, c_h = dlog.by_op("sp"), hlog.by_op("sp")
    c_rel = hlog.by_op("sp.relay")
    check("exchange.hier_hop_bytes_exact",
          c_h.bytes_out == 4 * 8 * (lanes_sp + 2) * 4
          and c_rel.bytes_out == 2 * 8 * (lanes_sp + 2) * 4
          and c_h.hops == 2 and c_h.collectives == 2
          and c_d.hops == 1 and c_d.collectives == 1)
    check("exchange.hier_sparse_wire_below_dense",
          c_h.bytes_out + c_rel.bytes_out < c_d.bytes_out
          and hier_words < dense_words
          and c_h.bytes_out + c_rel.bytes_out == hier_words * 4
          and c_d.bytes_out == dense_words * 4)

    # ---- bloom: distributed atomicity of duplicate insertion ----
    def bloomdup(items):
        bk = get_backend("bcl")
        spec, st = bl.bloom_create(bk, 1 << 16, SDS((), jnp.uint32), k=4)
        st, already = bl.insert(bk, spec, st, items, capacity=64)
        return already

    dup = jnp.full((PROCS * 16,), 777, jnp.uint32)
    fb = jax.jit(jax.shard_map(bloomdup, mesh=mesh, in_specs=(P("bcl"),),
                               out_specs=P("bcl")))
    already = np.asarray(fb(dup))
    check("bloom.dup_atomicity", int((~already).sum()) == 1)

    # ---- SPMD == serial semantics (portability across backends) ----
    def serial_hashmap(keys, vals, queries):
        bk = get_backend(None)
        spec, st = hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                     SDS((), jnp.uint32), block_size=16)
        st, _ = hm.insert(bk, spec, st, keys, vals, capacity=len(keys))
        st, v, found = hm.find(bk, spec, st, queries, capacity=len(queries))
        return v, found

    vs, fs = serial_hashmap(keys, vals, queries)
    check("portability.same_found",
          np.array_equal(np.asarray(fs), nf))
    check("portability.same_values",
          np.array_equal(np.asarray(vs)[np.asarray(fs)], nv[nf]))

    # ---- mini production-style dry-run on a (2,4) mesh ----
    from repro.configs import get_config, reduced
    from repro.configs.shapes import ShapeSpec, input_specs
    from repro.launch.steps import (batch_shardings, make_train_step,
                                    train_shardings)
    mesh2 = make_mesh((2, 4), ("data", "model"))
    for arch in ("qwen3-4b", "arctic-480b"):
        cfg = reduced(get_config(arch), n_heads=4, n_kv_heads=4,
                      d_model=64, vocab=512)
        shape = ShapeSpec("t", 64, 4, "train")
        specs = input_specs(cfg, shape)
        pshape, oshape, psh, osh = train_shardings(cfg, mesh2)
        bsh = batch_shardings(cfg, mesh2, specs)
        step = make_train_step(cfg, mesh2)
        compiled = jax.jit(step, in_shardings=(psh, osh, bsh),
                           out_shardings=(psh, osh, None)).lower(
            pshape, oshape, specs).compile()
        check(f"mini_dryrun.{arch}",
              compiled.memory_analysis() is not None)

    # ---- MoE exchange dispatch == dense-expert reference ----
    def moe_equiv():
        import dataclasses
        from repro.models import moe as moe_mod
        from repro.models.sharding import Axes
        cfg = reduced(get_config("arctic-480b"), d_model=32, vocab=256)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2,
                                         expert_d_ff=16),
            moe_capacity_slack=8.0)
        rng = jax.random.PRNGKey(0)
        params = moe_mod.moe_init(rng, cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        axes8 = Axes.from_mesh(mesh2)
        y_spmd, _, st_spmd = moe_mod.moe_apply(params, x, cfg, mesh2, axes8)

        mesh1 = make_mesh((1, 1), ("data", "model"))
        axes1 = Axes.from_mesh(mesh1)
        y_ser, _, st_ser = moe_mod.moe_apply(params, x, cfg, mesh1, axes1)
        cfg_dd = dataclasses.replace(cfg, moe_dedup_dispatch=True)
        y_dd, _, st_dd = moe_mod.moe_apply(params, x, cfg_dd, mesh2, axes8)
        n_assign = x.shape[0] * x.shape[1] * cfg.moe.top_k
        # the fused stats flow reports true global served counts: with
        # ample capacity every assignment is served, on every schedule
        loads_ok = all(
            float(st["expert_load"].sum()) == n_assign
            for st in (st_spmd, st_ser, st_dd))
        loads_eq = np.array_equal(np.asarray(st_spmd["expert_load"]),
                                  np.asarray(st_ser["expert_load"]))
        return (np.allclose(np.asarray(y_spmd), np.asarray(y_ser),
                            atol=1e-4),
                np.allclose(np.asarray(y_dd), np.asarray(y_ser),
                            atol=1e-4),
                loads_ok and loads_eq)

    eq_std, eq_dd, eq_load = moe_equiv()
    check("moe.spmd_equals_serial", eq_std)
    check("moe.dedup_dispatch_parity", eq_dd)
    check("moe.stats_flow_load", eq_load)

    # ---- GPipe pipeline: 4 stages over a 'stage' axis == sequential ----
    from repro.parallel import gpipe
    smesh = make_mesh((4,), ("stage",))
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.4
    xmb = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 8))

    def stage(params, xx):
        return jnp.tanh(xx @ params)

    out = gpipe(stage, ws, xmb, smesh, axis="stage")
    expect = xmb
    for i in range(4):
        expect = jnp.tanh(expect @ ws[i])
    check("gpipe.4stage_sequential_parity",
          bool(np.allclose(np.asarray(out), np.asarray(expect),
                           atol=1e-5)))

    # ---- ISx weak scaling shape: per-rank keys constant, 8 ranks ----
    def isx_weak(values):
        bk = get_backend("bcl")
        spec, st = q.queue_create(bk, 2048, SDS((), jnp.uint32))
        dest = (values // ((1 << 20) // 8)).astype(jnp.int32).clip(0, 7)
        st, _, dropped = q.push(bk, spec, st, values, dest, capacity=512)
        rows, got = q.local_drain(spec, st)
        return jnp.sort(jnp.where(got, rows, jnp.uint32(0xFFFFFFFF))), \
            got.sum()[None]

    keys8 = jnp.asarray(np.random.randint(0, 1 << 20, 8 * 1024), jnp.uint32)
    fw = jax.jit(jax.shard_map(isx_weak, mesh=mesh, in_specs=(P("bcl"),),
                               out_specs=(P("bcl"), P("bcl"))))
    srted, counts = fw(keys8)
    merged = np.asarray(srted).reshape(8, -1)
    cnts = np.asarray(counts)
    glob = np.concatenate([merged[r][: cnts[r]] for r in range(8)])
    check("isx.weak_scaling_sorted",
          np.array_equal(np.sort(np.asarray(keys8)), np.sort(glob)) and
          all(np.all(np.diff(merged[r][: cnts[r]]) >= 0) for r in range(8)))

    # ---- elastic checkpoint: save on (2,4), restore onto (4,2) ----
    import tempfile
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from jax.sharding import NamedSharding
    mesh_a = make_mesh((2, 4), ("data", "model"))
    mesh_b = make_mesh((4, 2), ("data", "model"))
    w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    w_a = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, 3, {"w": w_a})
        got, step = restore_checkpoint(
            td, None, {"w": jnp.zeros((8, 8))},
            shardings={"w": NamedSharding(mesh_b, P("data", "model"))})
    ok_val = np.array_equal(np.asarray(got["w"]), np.asarray(w))
    ok_shard = got["w"].sharding.mesh.shape["data"] == 4
    check("elastic.reshard_on_restore", ok_val and ok_shard and step == 3)

    # ---- chaos battery: kill / corrupt / checkpoint-recover (§1.8) ----
    # A rank dies mid-run.  The battery drives the full recovery story:
    # phase A builds containers and checkpoints their exported state;
    # phase B keeps running against the dead rank (degraded commit +
    # fault-injected wire + integrity checksums) and pins EXACTLY which
    # inserts ack; the FT control plane detects the silence, plans the
    # remesh, and does not re-fail anyone on the next tick; recovery
    # restores every shard from the checkpoint (the survivors re-inject
    # the dead rank's shard) and replays the killed batch — the final
    # container state is bit-identical to a run where nothing died.
    from repro.core import FaultInjectingTransport, FaultSpec, make_transport
    from repro.core.hashing import hash_lanes
    from repro.containers.hashmap import (export_state as hm_export,
                                          restore_state as hm_restore)
    from repro.containers.queue import (export_state as q_export,
                                        restore_state as q_restore)
    from repro.runtime.elastic import plan_remesh
    from repro.runtime.ft import FaultToleranceManager

    KILLED = 3
    crng = np.random.default_rng(3)
    cperm = crng.permutation(1 << 20)
    b1k = jnp.asarray(cperm[:PROCS * NLOC], jnp.uint32)
    b2k = jnp.asarray(cperm[PROCS * NLOC:2 * PROCS * NLOC], jnp.uint32)
    b1v, b2v = b1k * 11 + 3, b2k * 11 + 3
    qv1 = jnp.asarray(crng.integers(0, 1 << 30, PROCS * 48), jnp.uint32)
    qd1 = jnp.asarray(crng.integers(0, PROCS, PROCS * 48), jnp.int32)
    qv2 = jnp.asarray(crng.integers(0, 1 << 30, PROCS * 48), jnp.uint32)
    qd2 = jnp.asarray(crng.integers(0, PROCS, PROCS * 48), jnp.int32)

    def hm_fresh(bk):
        return hm.hashmap_create(bk, 8192, SDS((), jnp.uint32),
                                 SDS((), jnp.uint32), block_size=16)

    def phase_a(k1, v1, qv, qd):
        bk = get_backend("bcl")
        spec, st = hm_fresh(bk)
        st, ok = hm.insert(bk, spec, st, k1, v1, capacity=NLOC)
        qspec, qst = q.queue_create(bk, 512, SDS((), jnp.uint32))
        qst, _, qdrop = q.push(bk, qspec, qst, qv, qd, capacity=96)
        ex, qex = hm_export(spec, st), q_export(qspec, qst)
        return (ok, qdrop[None], ex["tkeys"], ex["tvals"], ex["status"],
                qex["data"], qex["head"], qex["tail"], qex["tail_ready"],
                qex["head_ready"])

    a = jax.jit(jax.shard_map(phase_a, mesh=mesh, in_specs=(P("bcl"),) * 4,
                          out_specs=(P("bcl"),) * 10))(b1k, b1v, qv1, qd1)
    check("chaos.phase_a_clean", bool(np.asarray(a[0]).all())
          and int(np.asarray(a[1]).sum()) == 0)
    ck_tree = {"hm": {"tkeys": a[2], "tvals": a[3], "status": a[4]},
               "q": {"data": a[5], "head": a[6], "tail": a[7],
                     "tail_ready": a[8], "head_ready": a[9]}}

    # phase B: rank KILLED dies.  Its memory is gone, its wire sends
    # arrive as zeros (FaultSpec kill), and the plan is committed
    # degraded (dead_ranks).  The integrity checksums turn the zeroed
    # segments into invalid arrivals instead of silent garbage, so the
    # ack mask is EXACT: an insert succeeded iff neither its source nor
    # its attempt-0 owner is the dead rank.
    ktr = FaultInjectingTransport(make_transport("dense"),
                                  FaultSpec(seed=11, kill_ranks=(KILLED,)))

    def phase_b(tk, tv, stt, k2, v2):
        bk = get_backend("bcl")
        spec, _ = hm_fresh(bk)
        dead = jax.lax.axis_index("bcl") == KILLED
        st = hm.HashMapState(
            jnp.where(dead, jnp.zeros_like(tk), tk),
            jnp.where(dead, jnp.zeros_like(tv), tv),
            jnp.where(dead, jnp.zeros_like(stt), stt))
        st, ok2 = hm.insert(bk, spec, st, k2, v2, capacity=NLOC,
                            attempts=1, transport=ktr,
                            dead_ranks=(KILLED,), integrity=True)
        return ok2

    ok2 = jax.jit(jax.shard_map(phase_b, mesh=mesh, in_specs=(P("bcl"),) * 5,
                            out_specs=P("bcl")))(a[2], a[3], a[4], b2k, b2v)
    g0 = np.asarray(hash_lanes(b2k[:, None], seed=1)) % 512
    owner0 = g0 // 64                       # 512 blocks, 64 per rank
    src = np.repeat(np.arange(PROCS), NLOC)
    expect_ok = (src != KILLED) & (owner0 != KILLED)
    check("chaos.kill_acks_exact",
          np.array_equal(np.asarray(ok2), expect_ok)
          and int((~expect_ok).sum()) > 0)

    # the FT control plane sees the silence, plans recovery, and the
    # promoted world is stable on the next tick
    ftm = FaultToleranceManager(n_nodes=PROCS, heartbeat_interval=1.0,
                                timeout_beats=2)
    for nd in range(PROCS):
        ftm.heartbeat(nd, 0.0)
    for nd in range(PROCS):
        if nd != KILLED:
            ftm.heartbeat(nd, 2.5)
    dec = ftm.tick(2.5, last_ckpt_step=1)
    check("chaos.ft_detects_kill",
          dec.action == "restart" and dec.failed_nodes == [KILLED]
          and dec.restart_step == 1)
    rplan = plan_remesh(("data", "model"), (PROCS, 1), PROCS - 1)
    check("chaos.remesh_plan",
          rplan.new_shape == (PROCS - 1, 1) and rplan.dropped_devices == 0
          and abs(rplan.batch_per_shard_scale - PROCS / (PROCS - 1)) < 1e-9)
    for nd in range(PROCS):
        if nd != KILLED:
            ftm.heartbeat(nd, 3.0)
    check("chaos.no_refail_next_tick",
          ftm.tick(3.1, last_ckpt_step=1).action == "none")

    # recovery: restore every shard from the checkpoint (survivors
    # re-inject the dead rank's shard via restore_state), replay the
    # killed batch, and compare against the fault-free reference
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, dec.restart_step, ck_tree)
        like = jax.tree_util.tree_map(jnp.zeros_like, ck_tree)
        got_ck, rstep = restore_checkpoint(td, None, like)
    check("chaos.ckpt_roundtrip", rstep == dec.restart_step)

    def recover(tk, tv, stt, qdata, qh, qt, qtr_, qhr, k2, v2, qv, qd):
        bk = get_backend("bcl")
        spec, _ = hm_fresh(bk)
        st = hm_restore(spec, {"tkeys": tk, "tvals": tv, "status": stt})
        qspec, _ = q.queue_create(bk, 512, SDS((), jnp.uint32))
        qst = q_restore(qspec, {"data": qdata, "head": qh, "tail": qt,
                                "tail_ready": qtr_, "head_ready": qhr})
        st, ok = hm.insert(bk, spec, st, k2, v2, capacity=NLOC)
        qst, _, qdrop = q.push(bk, qspec, qst, qv, qd, capacity=96)
        ex, qex = hm_export(spec, st), q_export(qspec, qst)
        return (ok, qdrop[None], ex["tkeys"], ex["tvals"], ex["status"],
                qex["data"], qex["head"], qex["tail"])

    def reference(k1, v1, k2, v2, qva, qda, qvb, qdb):
        bk = get_backend("bcl")
        spec, st = hm_fresh(bk)
        st, _ = hm.insert(bk, spec, st, k1, v1, capacity=NLOC)
        st, _ = hm.insert(bk, spec, st, k2, v2, capacity=NLOC)
        qspec, qst = q.queue_create(bk, 512, SDS((), jnp.uint32))
        qst, _, _ = q.push(bk, qspec, qst, qva, qda, capacity=96)
        qst, _, _ = q.push(bk, qspec, qst, qvb, qdb, capacity=96)
        ex, qex = hm_export(spec, st), q_export(qspec, qst)
        return (ex["tkeys"], ex["tvals"], ex["status"],
                qex["data"], qex["head"], qex["tail"])

    rec = jax.jit(jax.shard_map(recover, mesh=mesh, in_specs=(P("bcl"),) * 12,
                            out_specs=(P("bcl"),) * 8))(
        jnp.asarray(got_ck["hm"]["tkeys"]), jnp.asarray(got_ck["hm"]["tvals"]),
        jnp.asarray(got_ck["hm"]["status"]), jnp.asarray(got_ck["q"]["data"]),
        jnp.asarray(got_ck["q"]["head"]), jnp.asarray(got_ck["q"]["tail"]),
        jnp.asarray(got_ck["q"]["tail_ready"]),
        jnp.asarray(got_ck["q"]["head_ready"]),
        b2k, b2v, qv2, qd2)
    ref = jax.jit(jax.shard_map(reference, mesh=mesh, in_specs=(P("bcl"),) * 8,
                            out_specs=(P("bcl"),) * 6))(
        b1k, b1v, b2k, b2v, qv1, qd1, qv2, qd2)
    check("chaos.recovery_replay_clean", bool(np.asarray(rec[0]).all())
          and int(np.asarray(rec[1]).sum()) == 0)
    check("chaos.recovered_bit_identical",
          all(np.array_equal(np.asarray(x), np.asarray(y))
              for x, y in zip(rec[2:], ref)))

    # ---- corruption: integrity + carry heals, no-retry loses loudly ----
    cspec = FaultSpec(seed=7, corrupt=((0, 2, 5),))
    lrng = np.random.default_rng(3)
    lv = jnp.asarray(lrng.integers(0, 1 << 30, PROCS * 64), jnp.uint32)
    ld = jnp.asarray(lrng.integers(0, PROCS, PROCS * 64), jnp.int32)

    # no-retry arm: the corrupted segment's items are LOST, and the lost
    # counter accounts for every one of them — never silent
    ltr = FaultInjectingTransport(make_transport("dense"), cspec)

    def corrupt_lose(pay, dst):
        bk = get_backend("bcl")
        res = route(bk, pay, dst, capacity=64, op_name="lose",
                    transport=ltr, integrity=True)
        return (res.valid.sum()[None], res.lost[None], res.dropped[None])

    arr, lost, drp = jax.jit(jax.shard_map(
        corrupt_lose, mesh=mesh, in_specs=(P("bcl"),) * 2,
        out_specs=(P("bcl"),) * 3))(lv[:, None], ld)
    n_lost = int(np.asarray(lost)[0])
    check("chaos.corrupt_lost_accounted",
          n_lost > 0 and int(np.asarray(drp).sum()) == 0
          and int(np.asarray(arr).sum()) + n_lost == PROCS * 64)

    # heal arm: same fault under overflow="carry" — the unacked items
    # ride the carry mask into a re-push and NOTHING is lost
    htr = FaultInjectingTransport(make_transport("dense"), cspec)

    def corrupt_heal(vals_, dst):
        bk = get_backend("bcl")
        qspec, qst = q.queue_create(bk, 1024, SDS((), jnp.uint32))
        qst, _, _, carry = q.push(bk, qspec, qst, vals_, dst, capacity=64,
                                  max_rounds=2, overflow="carry",
                                  transport=htr, integrity=True)
        qst, _, _, carry2 = q.push(bk, qspec, qst, vals_, dst, capacity=64,
                                   valid=carry, overflow="carry",
                                   transport=htr, integrity=True)
        rows, got = q.local_drain(qspec, qst)
        return carry.sum()[None], carry2.sum()[None], rows, got

    c1, c2, hrows, hgot = jax.jit(jax.shard_map(
        corrupt_heal, mesh=mesh, in_specs=(P("bcl"),) * 2,
        out_specs=(P("bcl"),) * 4))(lv, ld)
    healed = np.asarray(hrows)[np.asarray(hgot)]
    check("chaos.corrupt_carry_heals",
          int(np.asarray(c1).sum()) > 0 and int(np.asarray(c2).sum()) == 0
          and sorted(healed.tolist()) == sorted(np.asarray(lv).tolist()))

    # ---- faults x split-phase (DESIGN.md sections 1.8 + 1.9) ----
    # the same seeded corruption driven through commit_async/finish:
    # with double-buffered retry rounds the next round's wire is already
    # in flight while the previous round's checksum windows are being
    # verified, and the loss accounting must not change.  A FRESH
    # FaultInjectingTransport re-bases the trace-time launch counter, so
    # the injected faults hit the same launches as the sync arm.
    from repro.core import ExchangePlan as _EP
    astr = FaultInjectingTransport(make_transport("dense"), cspec)

    def corrupt_lose_async(pay, dst):
        bk = get_backend("bcl")
        plan = _EP(name="lose")
        h = plan.add(pay, dst, 64, op_name="lose")
        c = plan.commit_async(bk, transport=astr, integrity=True).finish(bk)
        res = c.view(h)
        return (res.valid.sum()[None], res.lost[None], res.dropped[None])

    arr_a, lost_a, drp_a = jax.jit(jax.shard_map(
        corrupt_lose_async, mesh=mesh, in_specs=(P("bcl"),) * 2,
        out_specs=(P("bcl"),) * 3))(lv[:, None], ld)
    check("chaos.async_corrupt_lost_accounted",
          int(np.asarray(lost_a)[0]) == n_lost
          and np.array_equal(np.asarray(arr_a), np.asarray(arr))
          and int(np.asarray(drp_a).sum()) == 0)

    # heal arm, split-phase: a fused push_pop under overflow="carry" +
    # integrity, issued via commit_async with 2 retry rounds — round 2
    # is committed while round 1's checksums settle.  The async and
    # sync schedules of the SAME program must agree bit-for-bit, the
    # first shot must lose loudly (carry > 0), the re-push must heal
    # (carry2 == 0), and drain + pops must recover the full multiset.
    def heal_pair(split):
        ptr = FaultInjectingTransport(make_transport("dense"), cspec)

        def body(vals_, dst):
            bk = get_backend("bcl")
            qspec, qst = q.queue_create(bk, 1024, SDS((), jnp.uint32),
                                        circular=True)

            def pp(st, valid):
                if split:
                    return q.push_pop(
                        bk, qspec, st, vals_, dst, 64, 8, 0, valid=valid,
                        max_rounds=2, overflow="carry", transport=ptr,
                        integrity=True, async_=True).finish()
                return q.push_pop(
                    bk, qspec, st, vals_, dst, 64, 8, 0, valid=valid,
                    max_rounds=2, overflow="carry", transport=ptr,
                    integrity=True)

            qst, _, _, out1, got1, carry = pp(qst, None)
            qst, _, _, out2, got2, carry2 = pp(qst, carry)
            rows, got = q.local_drain(qspec, qst)
            return (carry.sum()[None], carry2.sum()[None], rows, got,
                    out1, got1, out2, got2)

        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("bcl"),) * 2,
                                 out_specs=(P("bcl"),) * 8))(lv, ld)

    hp_sync = heal_pair(False)
    hp_async = heal_pair(True)
    recovered = np.concatenate(
        [np.asarray(hp_async[2])[np.asarray(hp_async[3])],
         np.asarray(hp_async[4])[np.asarray(hp_async[5])],
         np.asarray(hp_async[6])[np.asarray(hp_async[7])]])
    check("chaos.async_corrupt_carry_heals",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(hp_sync, hp_async))
          and int(np.asarray(hp_async[0]).sum()) > 0
          and int(np.asarray(hp_async[1]).sum()) == 0
          and sorted(recovered.tolist()) == sorted(np.asarray(lv).tolist()))

    print("ALL SPMD CHECKS PASSED")


if __name__ == "__main__":
    main()
