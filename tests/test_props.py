"""Hypothesis property tests on the system's invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st
from jax import ShapeDtypeStruct as SDS

from repro.core import ExchangePlan, Promise, get_backend, route
from repro.containers import bloom as bl
from repro.containers import hashmap as hm
from repro.containers import queue as q
from repro.kernels import ops, ref

_keys = st.lists(st.integers(0, 200), min_size=1, max_size=80)


@given(_keys)
@settings(max_examples=20, deadline=None)
def test_hashmap_insert_then_find_total(keys):
    """forall K: find(insert(table, K), K) succeeds with the last value."""
    bk = get_backend(None)
    spec, state = hm.hashmap_create(bk, 2048, SDS((), jnp.uint32),
                                    SDS((), jnp.uint32), block_size=16)
    ks = jnp.asarray(keys, jnp.uint32)
    vs = jnp.arange(len(keys), dtype=jnp.uint32) + 1
    state, ok = hm.insert(bk, spec, state, ks, vs, capacity=len(keys))
    assert bool(ok.all())
    state, v, found = hm.find(bk, spec, state, ks, capacity=len(keys))
    assert bool(found.all())
    oracle = {}
    for k_, v_ in zip(keys, range(1, len(keys) + 1)):
        oracle[k_] = v_
    for k_, got in zip(keys, np.asarray(v)):
        assert got == oracle[k_]


@given(_keys)
@settings(max_examples=20, deadline=None)
def test_bloom_no_false_negatives(keys):
    bk = get_backend(None)
    spec, state = bl.bloom_create(bk, 1 << 14, SDS((), jnp.uint32), k=4)
    ks = jnp.asarray(keys, jnp.uint32)
    state, _ = bl.insert(bk, spec, state, ks, capacity=len(keys))
    present = bl.find(bk, spec, state, ks, capacity=len(keys))
    assert bool(present.all())


@given(st.lists(st.integers(0, 1 << 20), min_size=1, max_size=60))
@settings(max_examples=20, deadline=None)
def test_queue_preserves_multiset(vals):
    bk = get_backend(None)
    spec, state = q.queue_create(bk, 128, SDS((), jnp.uint32))
    v = jnp.asarray(vals, jnp.uint32)
    state, pushed, dropped = q.push(bk, spec, state, v,
                                    jnp.zeros(len(vals), jnp.int32),
                                    capacity=len(vals))
    assert int(dropped) == 0
    state, out, got = q.local_nonatomic_pop(spec, state, len(vals))
    assert sorted(np.asarray(out)[np.asarray(got)].tolist()) == sorted(vals)


@given(st.lists(st.tuples(st.integers(0, 30), st.integers(1, 100)),
                min_size=1, max_size=60),
       st.sampled_from([ref.MODE_SET, ref.MODE_ADD, ref.MODE_KEEP]))
@settings(max_examples=20, deadline=None)
def test_bulk_insert_impls_agree(pairs, mode):
    """jnp and pallas implementations match the sequential oracle on
    arbitrary (dup-heavy) batches."""
    nb, B = 4, 8
    tk = jnp.zeros((nb, 1, B), jnp.uint32)
    tv = jnp.zeros((nb, 1, B), jnp.uint32)
    stt = jnp.zeros((nb, B), jnp.uint32)
    qk = jnp.asarray([[k] for k, _ in pairs], jnp.uint32)
    qv = jnp.asarray([[v] for _, v in pairs], jnp.uint32)
    qb = qk[:, 0] % nb
    valid = jnp.ones(len(pairs), bool)
    o = ref.hash_probe_insert_ref(tk, tv, stt, qb, qk, qv, valid, mode)
    for impl in ("jnp", "pallas"):
        j = ops.bulk_insert(tk, tv, stt, qb, qk, qv, valid, mode, impl=impl)
        for a, b_ in zip(o, j):
            assert np.array_equal(np.asarray(a), np.asarray(b_)), impl


@given(st.integers(1, 1 << 30), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_bloom_dup_atomicity(value, n_dups):
    """Exactly one inserter of n duplicates observes 'not present'."""
    bk = get_backend(None)
    spec, state = bl.bloom_create(bk, 1 << 12, SDS((), jnp.uint32), k=4)
    dup = jnp.full((n_dups,), value, jnp.uint32)
    state, already = bl.insert(bk, spec, state, dup, capacity=n_dups)
    assert int((~already).sum()) == 1


@given(st.lists(st.integers(0, 3), min_size=1, max_size=64),
       st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_route_multiset_preserved(dests, ncopies):
    """Property: with enough capacity, routing preserves the multiset."""
    bk = get_backend(None)
    n = len(dests)
    pay = jnp.arange(n, dtype=jnp.uint32) * ncopies
    res = route(bk, pay, jnp.zeros(n, jnp.int32), capacity=n)
    got = sorted(np.asarray(res.payload[res.valid][:, 0]).tolist())
    assert got == sorted(np.asarray(pay).tolist())


@given(st.lists(st.integers(0, 1 << 20), min_size=1, max_size=60),
       st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_route_retry_rounds_preserve_multiset(vals, rounds):
    """Property: whenever rounds x capacity covers the hottest bucket,
    carryover retries make routing lossless at any per-round capacity."""
    bk = get_backend(None)
    n = len(vals)
    cap = max(1, -(-n // rounds))
    pay = jnp.asarray(vals, jnp.uint32)
    res = route(bk, pay, jnp.zeros(n, jnp.int32), capacity=cap,
                max_rounds=rounds)
    got = sorted(np.asarray(res.payload[res.valid][:, 0]).tolist())
    assert got == sorted(vals)
    assert int(res.dropped) == 0


def _tree_equal(a, b):
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_tree_equal(x, y)
                                        for x, y in zip(a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_fused_plan_interleavings_match_fine_schedule(data):
    """Any interleaving of fused-plan ops is bit-identical to the
    Promise.FINE sequential schedule — outputs AND container state —
    over random keys, values, capacities, AND carryover retry rounds
    (including the overflow regime: the same per-flow binning drops the
    same items on both schedules, and each retry round ships the same
    rank window).  The 8-rank version of this check, with random dests,
    runs in tests/spmd_check.py."""
    ops_seq = []
    for _ in range(data.draw(st.integers(1, 4), label="n_ops")):
        kind = data.draw(st.sampled_from(
            ["find_insert", "push_pop", "bloom_insert_find"]), label="kind")
        n = data.draw(st.integers(1, 24), label="n")
        cap = data.draw(st.integers(max(1, n // 2), n + 8), label="cap")
        rounds = data.draw(st.integers(1, 3), label="rounds")
        a = data.draw(st.lists(st.integers(0, 300), min_size=n, max_size=n),
                      label="a")
        b = data.draw(st.lists(st.integers(0, 300), min_size=n, max_size=n),
                      label="b")
        ops_seq.append((kind, cap, rounds, a, b))

    def run(fine):
        bk = get_backend(None)
        extra = Promise.FINE if fine else Promise.NONE
        spec, hst = hm.hashmap_create(bk, 512, SDS((), jnp.uint32),
                                      SDS((), jnp.uint32), block_size=8)
        qspec, qst = q.queue_create(bk, 64, SDS((), jnp.uint32),
                                    circular=True)
        bspec, bst = bl.bloom_create(bk, 1 << 10, SDS((), jnp.uint32), k=4)
        outs = []
        for kind, cap, rounds, a, b in ops_seq:
            av = jnp.asarray(a, jnp.uint32)
            bv = jnp.asarray(b, jnp.uint32)
            if kind == "find_insert":
                hst, v, f, ok = hm.find_insert(
                    bk, spec, hst, av, bv, bv * 7 + 1, capacity=cap,
                    promise=Promise.FIND | Promise.INSERT | extra,
                    max_rounds=rounds)
                outs.append((v, f, ok))
            elif kind == "push_pop":
                qst, pushed, dropped, out, got = q.push_pop(
                    bk, qspec, qst, av, jnp.zeros(len(a), jnp.int32),
                    cap, len(b), 0,
                    promise=Promise.PUSH | Promise.POP | extra,
                    max_rounds=rounds)
                outs.append((pushed, dropped, out, got))
            else:
                bst, already, present = bl.insert_find(
                    bk, bspec, bst, av, bv, cap, cap, promise=extra,
                    max_rounds=rounds)
                outs.append((already, present))
        return outs, (tuple(hst), tuple(qst), tuple(bst))

    fused_out, fused_state = run(False)
    fine_out, fine_state = run(True)
    assert _tree_equal(fused_out, fine_out)
    assert _tree_equal(fused_state, fine_state)


@given(st.data())
@settings(max_examples=15, deadline=None)
def test_ragged_fused_plans_equal_fine_over_flow_mixes(data):
    """Ragged fused wire == Promise.FINE oracle over random flow mixes:
    1-4 flows of lane widths 1..4 and reply widths 0..3, random
    capacities and carryover retry rounds 1..3 — owner views, replies,
    answered masks, and per-flow drop counts are all bit-identical, so
    the ragged layout is pure wire compression, never a semantic
    change."""
    bk = get_backend(None)
    nflows = data.draw(st.integers(1, 4), label="nflows")
    rounds = data.draw(st.integers(1, 3), label="rounds")
    flows = []
    for i in range(nflows):
        n = data.draw(st.integers(1, 20), label=f"n{i}")
        lanes = data.draw(st.integers(1, 4), label=f"lanes{i}")
        cap = data.draw(st.integers(1, n + 4), label=f"cap{i}")
        rl = data.draw(st.integers(0, 3), label=f"rl{i}")
        pay = jnp.asarray(
            data.draw(st.lists(st.integers(0, 1 << 30),
                               min_size=n * lanes, max_size=n * lanes),
                      label=f"pay{i}"), jnp.uint32).reshape(n, lanes)
        valid = jnp.asarray(
            data.draw(st.lists(st.booleans(), min_size=n, max_size=n),
                      label=f"valid{i}"))
        flows.append((pay, valid, cap, rl))

    def run(promise):
        plan = ExchangePlan(promise=promise, name="mix")
        hs = [plan.add(p, jnp.zeros(p.shape[0], jnp.int32), cap,
                       reply_lanes=rl, valid=v, op_name=f"f{i}")
              for i, (p, v, cap, rl) in enumerate(flows)]
        c = plan.commit(bk, max_rounds=rounds)
        for h, (p, v, cap, rl) in zip(hs, flows):
            if rl:
                c.set_reply(h, jnp.tile(
                    c.view(h).payload[:, :1] * 3 + h + 1, (1, rl)))
        fin = c.finish(bk)
        return ([tuple(c.view(h)) for h in hs],
                sorted(fin.items()))

    fused = run(Promise.NONE)
    fine = run(Promise.FINE)
    assert _tree_equal(fused[0], fine[0])
    for (hf, (of, af)), (hs_, (os_, as_)) in zip(fused[1], fine[1]):
        assert hf == hs_
        assert np.array_equal(np.asarray(of), np.asarray(os_))
        assert np.array_equal(np.asarray(af), np.asarray(as_))


@given(st.data())
@settings(max_examples=15, deadline=None)
def test_hierarchical_transport_equals_dense_over_flow_mixes(data):
    """HierarchicalTransport == DenseTransport over random flow mixes:
    1-4 flows of lane widths 1..4, reply widths 0..3, random validity,
    capacities, and carryover retry rounds 1..3 — owner views, replies,
    answered masks, and drop counts are bit-identical, so the two-stage
    movement is pure physical re-routing, never a semantic change.  (The
    8-rank 2-D mesh version, with random destinations, runs in
    tests/spmd_check.py as ``exchange.hier_equals_dense_8rank``.)"""
    from repro.core import HierarchicalTransport
    bk = get_backend(None)
    nflows = data.draw(st.integers(1, 4), label="nflows")
    rounds = data.draw(st.integers(1, 3), label="rounds")
    flows = []
    for i in range(nflows):
        n = data.draw(st.integers(1, 20), label=f"n{i}")
        lanes = data.draw(st.integers(1, 4), label=f"lanes{i}")
        cap = data.draw(st.integers(1, n + 4), label=f"cap{i}")
        rl = data.draw(st.integers(0, 3), label=f"rl{i}")
        pay = jnp.asarray(
            data.draw(st.lists(st.integers(0, 1 << 19),
                               min_size=n * lanes, max_size=n * lanes),
                      label=f"pay{i}"), jnp.uint32).reshape(n, lanes)
        valid = jnp.asarray(
            data.draw(st.lists(st.booleans(), min_size=n, max_size=n),
                      label=f"valid{i}"))
        flows.append((pay, valid, cap, rl))

    def run(transport):
        plan = ExchangePlan(name="mix")
        hs = [plan.add(p, jnp.zeros(p.shape[0], jnp.int32), cap,
                       reply_lanes=rl, valid=v, op_name=f"f{i}")
              for i, (p, v, cap, rl) in enumerate(flows)]
        c = plan.commit(bk, max_rounds=rounds, transport=transport)
        for h, (p, v, cap, rl) in zip(hs, flows):
            if rl:
                c.set_reply(h, jnp.tile(
                    c.view(h).payload[:, :1] * 3 + h + 1, (1, rl)))
        fin = c.finish(bk)
        return ([tuple(c.view(h)) for h in hs], sorted(fin.items()))

    dense = run(None)
    hier = run(HierarchicalTransport())
    assert _tree_equal(dense[0], hier[0])
    for (hd, (od, ad)), (hh, (oh, ah)) in zip(dense[1], hier[1]):
        assert hd == hh
        assert np.array_equal(np.asarray(od), np.asarray(oh))
        assert np.array_equal(np.asarray(ad), np.asarray(ah))


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_commit_async_equals_sync_over_flow_mixes(data):
    """Split-phase commit_async -> finish == synchronous commit == the
    Promise.FINE sequential oracle over random flow mixes (1-4 flows,
    lane widths 1..4, reply widths 0..3, carryover retry rounds 1..3)
    on BOTH physical transports — owner views, replies, answered masks,
    and per-flow drop counts are all bit-identical, so deferring the
    wait is pure scheduling, never a semantic change (DESIGN.md
    section 1.9).  FINE + async stays the sequential oracle (run
    eagerly, wrapped in a degenerate pending)."""
    bk = get_backend(None)
    nflows = data.draw(st.integers(1, 4), label="nflows")
    rounds = data.draw(st.integers(1, 3), label="rounds")
    transport = data.draw(st.sampled_from(["dense", "hier"]),
                          label="transport")
    flows = []
    for i in range(nflows):
        n = data.draw(st.integers(1, 16), label=f"n{i}")
        lanes = data.draw(st.integers(1, 4), label=f"lanes{i}")
        cap = data.draw(st.integers(1, n + 4), label=f"cap{i}")
        rl = data.draw(st.integers(0, 3), label=f"rl{i}")
        pay = jnp.asarray(
            data.draw(st.lists(st.integers(0, 1 << 19),
                               min_size=n * lanes, max_size=n * lanes),
                      label=f"pay{i}"), jnp.uint32).reshape(n, lanes)
        valid = jnp.asarray(
            data.draw(st.lists(st.booleans(), min_size=n, max_size=n),
                      label=f"valid{i}"))
        flows.append((pay, valid, cap, rl))

    def run(promise, async_):
        plan = ExchangePlan(promise=promise, name="mix")
        hs = [plan.add(p, jnp.zeros(p.shape[0], jnp.int32), cap,
                       reply_lanes=rl, valid=v, op_name=f"f{i}")
              for i, (p, v, cap, rl) in enumerate(flows)]
        if async_:
            c = plan.commit_async(bk, max_rounds=rounds,
                                  transport=transport).finish(bk)
        else:
            c = plan.commit(bk, max_rounds=rounds, transport=transport)
        for h, (p, v, cap, rl) in zip(hs, flows):
            if rl:
                c.set_reply(h, jnp.tile(
                    c.view(h).payload[:, :1] * 3 + h + 1, (1, rl)))
        fin = c.finish(bk)
        return ([tuple(c.view(h)) for h in hs], sorted(fin.items()))

    sync = run(Promise.NONE, False)
    asyn = run(Promise.NONE, True)
    fine = run(Promise.FINE, True)
    for other in (sync, fine):
        assert _tree_equal(asyn[0], other[0])
        for (ha, (oa, aa)), (ho, (oo, ao)) in zip(asyn[1], other[1]):
            assert ha == ho
            assert np.array_equal(np.asarray(oa), np.asarray(oo))
            assert np.array_equal(np.asarray(aa), np.asarray(ao))


@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=2,
                max_size=64))
@settings(max_examples=20, deadline=None)
def test_int8_error_feedback_invariant(vals):
    """dequantized + residual == original (EF preserves information)."""
    from repro.optim.compress import int8_compress, int8_decompress
    g = jnp.asarray(vals, jnp.float32)
    q, scale, res = int8_compress(g)
    recon = int8_decompress(q, scale).reshape(g.shape) + res
    np.testing.assert_allclose(np.asarray(recon), np.asarray(g),
                               rtol=1e-5, atol=1e-5)
