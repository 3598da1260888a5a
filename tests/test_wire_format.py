"""Wire-format and collective-round assertions for the fused exchange.

Pins the tentpole optimizations quantitatively via ``costs.recording()``:

  * route ships exactly ONE metadata lane (L+1 u32 lanes per item);
  * reply ships ZERO metadata lanes (L u32 lanes per item) — the
    inverse-permutation all-to-all needs no src_pos on the wire;
  * a 2-attempt hashmap find costs 2 collectives (two speculative
    flows on one ExchangePlan), down from 4 for the sequential loop,
    at the SAME wire bytes as the pre-plan hand-fused dual batch;
  * a fused find+insert under ``ConProm.HashMap.find_insert`` costs 2
    collectives per round trip where ``Promise.FINE`` costs 4;
  * a fused push+pop costs 2 collectives where ``Promise.FINE`` costs 3;

and pins the semantics of every fusion against the serial oracle.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct as SDS

from repro.core import (ConProm, ExchangePlan, Promise, costs, get_backend,
                        route)
from repro.core.exchange import reply
from repro.containers import hashmap as hm
from repro.containers import queue as q
from repro.kernels import ops as kops
from repro.kernels import ref


# ---------------------------------------------------------------------------
# bytes per item: one metadata lane out, zero lanes back
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [1, 3])
def test_route_ships_one_metadata_lane(lanes):
    bk = get_backend(None)
    n, cap = 16, 16
    pay = jnp.zeros((n, lanes), jnp.uint32)
    with costs.recording() as log:
        route(bk, pay, jnp.zeros(n, jnp.int32), capacity=cap, op_name="op")
    c = log.by_op("op")
    # P * C * (L + 1) u32 lanes: payload + packed (valid | src_pos) meta
    assert c.bytes_out == 1 * cap * (lanes + 1) * 4
    assert c.bytes_moved == c.bytes_out and c.bytes_in == 0
    assert c.collectives == 1 and c.rounds == 1


@pytest.mark.parametrize("lanes", [1, 3])
def test_reply_ships_zero_metadata_lanes(lanes):
    bk = get_backend(None)
    n, cap = 16, 16
    req = route(bk, jnp.zeros((n, 2), jnp.uint32), jnp.zeros(n, jnp.int32),
                capacity=cap)
    with costs.recording() as log:
        reply(bk, req, jnp.zeros((cap, lanes), jnp.uint32), orig_n=n,
              op_name="op")
    c = log.by_op("op")
    # pure inverse all-to-all: P * C * L u32 lanes, no src_pos, no valid
    assert c.bytes_in == 1 * cap * lanes * 4
    assert c.bytes_moved == c.bytes_in and c.bytes_out == 0
    assert c.collectives == 1 and c.rounds == 1


def test_request_reply_direction_split():
    bk = get_backend(None)
    n = 8
    with costs.recording() as log:
        req = route(bk, jnp.zeros((n, 1), jnp.uint32),
                    jnp.zeros(n, jnp.int32), capacity=n, op_name="op")
        reply(bk, req, req.payload[:, :1], orig_n=n, op_name="op")
    c = log.by_op("op")
    assert c.bytes_out == n * 2 * 4          # 1 payload lane + meta lane
    assert c.bytes_in == n * 1 * 4           # 1 payload lane only
    assert c.bytes_moved == c.bytes_out + c.bytes_in
    assert c.rounds == 2


# ---------------------------------------------------------------------------
# collective rounds: speculative dual-attempt find
# ---------------------------------------------------------------------------

def _loaded_map(nkeys=200, capacity=256, block_size=4):
    """A hash map loaded to ~0.8 so many keys need attempt-1/2 homes."""
    bk = get_backend(None)
    spec, st = hm.hashmap_create(bk, capacity, SDS((), jnp.uint32),
                                 SDS((), jnp.uint32), block_size=block_size)
    keys = jnp.asarray(np.random.default_rng(7).permutation(1 << 20)[:nkeys],
                       jnp.uint32)
    vals = keys * 3 + 1
    st, ok = hm.insert(bk, spec, st, keys, vals, capacity=nkeys, attempts=3)
    return bk, spec, st, keys, vals, ok


def test_find_two_attempts_two_collectives():
    bk, spec, st, keys, _, _ = _loaded_map()
    with costs.recording() as log:
        hm.find(bk, spec, st, keys, capacity=keys.shape[0], attempts=2)
    c = log.by_op("hashmap.find")
    assert c.collectives == 2 and c.rounds == 2


def test_find_sequential_attempts_four_collectives():
    bk, spec, st, keys, _, _ = _loaded_map()
    with costs.recording() as log:
        hm.find(bk, spec, st, keys, capacity=keys.shape[0], attempts=2,
                speculative=False)
    c = log.by_op("hashmap.find")
    assert c.collectives == 4 and c.rounds == 4


def test_speculative_find_matches_serial_oracle():
    bk, spec, st, keys, vals, ok = _loaded_map()
    n = keys.shape[0]
    # mix of present keys (including attempt-1 residents) and absent keys
    queries = jnp.concatenate([keys, keys + jnp.uint32(1 << 21)])
    _, v_spec, f_spec = hm.find(bk, spec, st, queries, capacity=2 * n)
    _, v_ser, f_ser = hm.find(bk, spec, st, queries, capacity=2 * n,
                              speculative=False)
    assert np.array_equal(np.asarray(f_spec), np.asarray(f_ser))
    assert np.array_equal(np.asarray(v_spec), np.asarray(v_ser))
    # inserted keys found at 2 attempts must carry the inserted value
    fs = np.asarray(f_spec[:n])
    assert fs.sum() > 0
    assert (np.asarray(v_spec[:n])[fs] ==
            (np.asarray(keys) * 3 + 1)[fs]).all()
    # absent keys are never "found"
    assert not np.asarray(f_spec[n:]).any()


def test_speculative_find_atomic_promise():
    bk, spec, st, keys, _, _ = _loaded_map()
    st1, v1, f1 = hm.find(bk, spec, st, keys, capacity=keys.shape[0],
                          promise=ConProm.HashMap.find_insert)
    st2, v2, f2 = hm.find(bk, spec, st, keys, capacity=keys.shape[0],
                          promise=ConProm.HashMap.find_insert,
                          speculative=False)
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    # the read-bit dance is net-zero on the status word either way
    assert np.array_equal(np.asarray(st1.status), np.asarray(st2.status))


def test_speculative_find_two_flow_lane_counts():
    """The two-flow plan ships EXACTLY the bytes of the old hand-fused
    dual batch: request 2C rows x (1 + Lk + meta) lanes, reply 2C rows x
    (Lv + found) lanes — the plan refactor changes the scheduler, not
    the wire."""
    bk, spec, st, keys, _, _ = _loaded_map()
    n = keys.shape[0]
    lk = spec.key_packer.lanes        # 1
    lv = spec.val_packer.lanes        # 1
    with costs.recording() as log:
        hm.find(bk, spec, st, keys, capacity=n, attempts=2)
    c = log.by_op("hashmap.find")
    assert c.bytes_out == 2 * n * (1 + lk + 1) * 4    # two C-row segments
    assert c.bytes_in == 2 * n * (lv + 1) * 4
    assert c.collectives == 2 and c.rounds == 2


# ---------------------------------------------------------------------------
# collective rounds: fused find+insert (the plan/commit acceptance pin)
# ---------------------------------------------------------------------------

def test_find_insert_fused_two_collectives_fine_four():
    """ConProm.HashMap.find_insert fuses both ops into 2 collectives per
    round trip; the Promise.FINE sequential schedule costs exactly 4."""
    bk, spec, st, keys, _, _ = _loaded_map()
    n = keys.shape[0]
    ins = keys + jnp.uint32(1 << 22)
    with costs.recording() as log_f:
        hm.find_insert(bk, spec, st, keys, ins, ins * 9, capacity=n,
                       promise=ConProm.HashMap.find_insert)
    with costs.recording() as log_s:
        hm.find_insert(bk, spec, st, keys, ins, ins * 9, capacity=n,
                       promise=ConProm.HashMap.find_insert | Promise.FINE)
    assert log_f.total().collectives == 2 and log_f.total().rounds == 2
    assert log_s.total().collectives == 4 and log_s.total().rounds == 4


def test_find_insert_fused_matches_fine_oracle():
    bk, spec, st, keys, _, _ = _loaded_map()
    n = keys.shape[0]
    queries = jnp.concatenate([keys[:100], keys[:100] + jnp.uint32(1 << 21)])
    ins = keys + jnp.uint32(1 << 22)
    f = hm.find_insert(bk, spec, st, queries, ins, ins * 9, capacity=n,
                       promise=ConProm.HashMap.find_insert)
    s = hm.find_insert(bk, spec, st, queries, ins, ins * 9, capacity=n,
                       promise=ConProm.HashMap.find_insert | Promise.FINE)
    for got, want in zip(f[1:], s[1:]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(f[0], s[0]):         # table state, bit-identical
        assert np.array_equal(np.asarray(got), np.asarray(want))
    found = np.asarray(f[2])
    # single-attempt probe: attempt-0 residents found, attempt-1 homes
    # legitimately missed (the op documents attempts=1 semantics)
    assert found[:100].sum() > 50
    assert not found[100:].any()              # absent keys never found
    vals = np.asarray(f[1])[:100]
    keys_np = np.asarray(queries)[:100]
    assert (vals[found[:100]] == (keys_np * 3 + 1)[found[:100]]).all()


# ---------------------------------------------------------------------------
# collective rounds: fused push+pop
# ---------------------------------------------------------------------------

def test_push_pop_fused_two_collectives_fine_three():
    bk = get_backend(None)
    spec, st = q.queue_create(bk, 128, SDS((), jnp.uint32), circular=True)
    vals = jnp.arange(32, dtype=jnp.uint32) + 1
    dest = jnp.zeros(32, jnp.int32)
    with costs.recording() as log_f:
        f = q.push_pop(bk, spec, st, vals, dest, 32, 16, 0)
    with costs.recording() as log_s:
        s = q.push_pop(bk, spec, st, vals, dest, 32, 16, 0,
                       promise=ConProm.CircularQueue.push_pop | Promise.FINE)
    assert log_f.total().collectives == 2 and log_f.total().rounds == 2
    assert log_s.total().collectives == 3 and log_s.total().rounds == 3
    for got, want in zip(f[1:], s[1:]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(f[0], s[0]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # push lands before pop: this round's pushes are poppable
    assert int(f[4].sum()) == 16
    assert np.array_equal(np.asarray(f[3])[np.asarray(f[4])],
                          np.arange(16, dtype=np.uint32) + 1)


# ---------------------------------------------------------------------------
# ragged per-flow wire segments: byte-exact pins (DESIGN.md section 1.5)
# ---------------------------------------------------------------------------

def test_find_insert_ragged_bytes_exact_and_below_rectangular():
    """Mixed-width fused plan: each flow ships exactly C_f*(L_f+1) u32
    request words and C_f*R_f reply words — the analytic formula — and
    the plan total is strictly below the rectangular (max-width padded)
    layout in both directions."""
    bk, spec, st, keys, _, _ = _loaded_map()
    n = keys.shape[0]
    lk, lv = spec.key_packer.lanes, spec.val_packer.lanes       # 1, 1
    ins = keys + jnp.uint32(1 << 22)
    with costs.recording() as log:
        hm.find_insert(bk, spec, st, keys, ins, ins * 9, capacity=n,
                       promise=ConProm.HashMap.find_insert)
    lf, li = 1 + lk, 1 + lk + lv               # payload lanes per flow
    assert log.by_op("hashmap.find").bytes_out == n * (lf + 1) * 4
    assert log.by_op("hashmap.insert").bytes_out == n * (li + 1) * 4
    assert log.by_op("hashmap.find").bytes_in == n * (lv + 1) * 4
    assert log.by_op("hashmap.insert").bytes_in == n * 1 * 4
    tot = log.total()
    assert tot.bytes_out == n * ((lf + 1) + (li + 1)) * 4
    assert tot.bytes_in == n * ((lv + 1) + 1) * 4
    # PR 3 rectangular layout: every flow padded to the widest
    assert tot.bytes_out < 2 * n * (max(lf, li) + 1) * 4
    assert tot.bytes_in < 2 * n * max(lv + 1, 1) * 4


def test_push_pop_ragged_bytes_exact_and_below_rectangular():
    """Wide values make push the wide flow; pop's unit requests and the
    value-width pop replies each ship their own exact widths."""
    bk = get_backend(None)
    lanes = 3                                   # 3-lane values
    spec, st = q.queue_create(bk, 128, lanes, circular=True)
    nv, npop = 32, 16
    vals = jnp.arange(nv * lanes, dtype=jnp.uint32).reshape(nv, lanes)
    with costs.recording() as log:
        q.push_pop(bk, spec, st, vals, jnp.zeros(nv, jnp.int32), nv,
                   npop, 0)
    assert log.by_op("queue.push").bytes_out == nv * (lanes + 1) * 4
    assert log.by_op("queue.pop").bytes_out == npop * (1 + 1) * 4
    assert log.by_op("queue.push").bytes_in == 0     # fire-and-forget
    assert log.by_op("queue.pop").bytes_in == npop * (lanes + 1 + 0) * 4
    # rectangular: pop's unit requests would pay the push flow's width
    assert log.total().bytes_out < (nv + npop) * (lanes + 1) * 4


def test_bloom_insert_find_ragged_bytes_exact():
    """Same-width flows: the ragged formula reduces to the rectangular
    one — sum_f C_f*(L_f+1) words out, C_f*1 words back."""
    bk = get_backend(None)
    from repro.containers import bloom as bl
    spec, st = bl.bloom_create(bk, 1 << 12, SDS((), jnp.uint32), k=4)
    ins = jnp.arange(24, dtype=jnp.uint32) + 1
    qry = jnp.arange(16, dtype=jnp.uint32) + 5
    with costs.recording() as log:
        bl.insert_find(bk, spec, st, ins, qry, 24, 16)
    body = 3                                    # lblock + 2 bit-words
    assert log.by_op("bloom.insert").bytes_out == 24 * (body + 1) * 4
    assert log.by_op("bloom.find").bytes_out == 16 * (body + 1) * 4
    assert log.by_op("bloom.insert").bytes_in == 24 * 1 * 4
    assert log.by_op("bloom.find").bytes_in == 16 * 1 * 4


def test_plan_commit_bytes_equal_sum_of_single_flow_routes():
    """The acceptance criterion that makes fusion unconditionally
    profitable: a fused mixed-width plan moves EXACTLY the bytes of its
    flows' standalone route()/reply() lowerings — fusing saves rounds
    and collectives, never costs wire."""
    bk = get_backend(None)
    rng = np.random.default_rng(21)
    widths, caps, rls = (1, 2, 4), (8, 5, 9), (1, 0, 3)
    pays = [jnp.asarray(rng.integers(0, 1 << 30, (12, w)), jnp.uint32)
            for w in widths]
    dest = jnp.zeros(12, jnp.int32)

    with costs.recording() as log_f:
        plan = ExchangePlan(name="plan")
        hs = [plan.add(p, dest, c, reply_lanes=rl, op_name=f"f{i}")
              for i, (p, c, rl) in enumerate(zip(pays, caps, rls))]
        c = plan.commit(bk)
        for h, rl in zip(hs, rls):
            if rl:
                c.set_reply(h, jnp.tile(c.view(h).payload[:, :1], (1, rl)))
        c.finish(bk)
    with costs.recording() as log_s:
        for i, (p, cap, rl) in enumerate(zip(pays, caps, rls)):
            res = route(bk, p, dest, cap, op_name=f"f{i}")
            if rl:
                reply(bk, res, jnp.tile(res.payload[:, :1], (1, rl)),
                      orig_n=12, op_name=f"f{i}")
    for i in range(3):
        assert log_f.by_op(f"f{i}").bytes_out == \
            log_s.by_op(f"f{i}").bytes_out
        assert log_f.by_op(f"f{i}").bytes_in == log_s.by_op(f"f{i}").bytes_in
    assert log_f.total().bytes_moved == log_s.total().bytes_moved
    # ...while the collective counts are where fusion wins
    assert log_f.total().collectives == 2
    assert log_s.total().collectives == 3 + 2   # 3 routes + 2 replies


def test_moe_dispatch_stats_ragged_bytes_exact():
    """The motivating mixed-width plan: the 1-lane MoE stats flow rides
    the token plan at 2 request words + 1 reply word per row instead of
    the token flow's width — its wire cost is now independent of
    d_model."""
    import dataclasses
    from repro.compat import make_mesh
    from repro.configs import get_config, reduced
    from repro.models import moe as moe_mod
    from repro.models.sharding import Axes
    import jax

    cfg = reduced(get_config("arctic-480b"), d_model=32, vocab=256)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2,
                                     expert_d_ff=16),
        moe_capacity_slack=8.0)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    with costs.recording() as log:
        moe_mod.moe_apply(params, x, cfg, mesh, Axes.from_mesh(mesh))

    b, t, k, e = 2, 8, cfg.moe.top_k, cfg.moe.n_experts
    act_lanes = cfg.d_model                     # float32 payload
    cap = max(1, int(b * t * k * cfg.moe_capacity_slack) + 1)
    l_tok = act_lanes + 1                       # activations + expert id
    assert log.by_op("moe.dispatch").bytes_out == cap * (l_tok + 1) * 4
    assert log.by_op("moe.dispatch").bytes_in == cap * act_lanes * 4
    assert log.by_op("moe.stats").bytes_out == e * 2 * 4
    assert log.by_op("moe.stats").bytes_in == e * 1 * 4
    # rectangular: stats rows were padded to the token flow's width
    assert log.by_op("moe.stats").bytes_out < e * (l_tok + 1) * 4


# ---------------------------------------------------------------------------
# transports: dense-vs-hierarchical pins (DESIGN.md section 1.7)
# ---------------------------------------------------------------------------

def test_dense_default_records_one_hop_per_launch():
    """The hops observable: every dense launch is one physical stage —
    request and reply each record hops=1 under the op."""
    bk = get_backend(None)
    n = 16
    with costs.recording() as log:
        req = route(bk, jnp.zeros((n, 1), jnp.uint32),
                    jnp.zeros(n, jnp.int32), capacity=n, op_name="op")
        reply(bk, req, req.payload[:, :1], orig_n=n, op_name="op")
    c = log.by_op("op")
    assert c.hops == 2 and c.collectives == 2
    assert log.by_op("op.relay").bytes_moved == 0   # dense has no relay


def test_hier_transport_hop_and_byte_pins_serial():
    """HierarchicalTransport per-hop attribution, exact (1x1
    factorization on the serial backend: c1 = min(Pr*C, N), c2 =
    Pc*min(C, N), rows carry ONE extra hop lane):

      request: op       = Pc * c1 * (L+2) * 4 bytes out
               op.relay = Pr * c2 * (L+2) * 4 bytes out
      reply:   op       = Pc * c1 * R * 4 bytes in
               op.relay = Pr * c2 * R * 4 bytes in

    and each direction is 2 collectives / 2 rounds / 2 hops."""
    from repro.core import ExchangePlan, HierarchicalTransport
    bk = get_backend(None)
    n, cap, lanes, rl = 12, 16, 3, 2
    c1 = min(1 * cap, n)                 # 12
    c2 = 1 * min(cap, n)                 # 12
    with costs.recording() as log:
        plan = ExchangePlan(name="op")
        h = plan.add(jnp.zeros((n, lanes), jnp.uint32),
                     jnp.zeros(n, jnp.int32), cap, reply_lanes=rl,
                     op_name="op")
        c = plan.commit(bk, transport=HierarchicalTransport())
        c.set_reply(h, c.view(h).payload[:, :rl])
        c.finish(bk)
    w1 = lanes + 2                       # payload + meta + hop lane
    cop, crel = log.by_op("op"), log.by_op("op.relay")
    assert cop.bytes_out == 1 * c1 * w1 * 4
    assert crel.bytes_out == 1 * c2 * w1 * 4
    assert cop.bytes_in == 1 * c1 * rl * 4
    assert crel.bytes_in == 1 * c2 * rl * 4
    assert cop.collectives == 4 and cop.rounds == 4 and cop.hops == 4
    assert crel.collectives == 0         # relay records bytes only


def test_hier_transport_matches_dense_serial():
    """Containers over transport="hier" are bit-identical to dense on
    the serial backend (the 8-rank 2-D mesh version runs in
    spmd_check.py); the hier run burns extra binning passes (2 per hop
    pair) but the SAME logical admission."""
    from repro.core import HierarchicalTransport
    bk = get_backend(None)
    hier = HierarchicalTransport()
    spec, st = hm.hashmap_create(bk, 512, SDS((), jnp.uint32),
                                 SDS((), jnp.uint32), block_size=8)
    keys = jnp.arange(40, dtype=jnp.uint32) * 7 + 1
    d_st, d_ok = hm.insert(bk, spec, st, keys, keys * 3, capacity=40)
    h_st, h_ok = hm.insert(bk, spec, st, keys, keys * 3, capacity=40,
                           transport=hier)
    assert np.array_equal(np.asarray(d_ok), np.asarray(h_ok))
    for a, b in zip(d_st, h_st):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    d = hm.find(bk, spec, d_st, keys, capacity=40)
    h = hm.find(bk, spec, h_st, keys, capacity=40, transport=hier)
    for a, b in zip(d[1:], h[1:]):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_hier_transport_guards():
    """A rank axis the factorization cannot split fails loudly."""
    from repro.core import ExchangePlan, HierarchicalTransport
    bk = get_backend(None)
    plan = ExchangePlan(name="op")
    plan.add(jnp.zeros((4, 1), jnp.uint32), jnp.zeros(4, jnp.int32), 4,
             op_name="op")
    with pytest.raises(ValueError, match="factor"):
        plan.commit(bk, transport=HierarchicalTransport(3, 5))


def test_make_transport_knob():
    from repro.core import (DenseTransport, HierarchicalTransport,
                            make_transport)
    assert make_transport(None) is make_transport("dense")
    assert isinstance(make_transport("dense"), DenseTransport)
    t = make_transport("hier", 2, 4)
    assert isinstance(t, HierarchicalTransport)
    assert t._factor(8) == (2, 4)
    assert make_transport(t) is t
    with pytest.raises(ValueError, match="transport"):
        make_transport("mesh3d")


# ---------------------------------------------------------------------------
# one-kernel wire path: jaxpr-level scatter census (DESIGN.md section 1.10)
# ---------------------------------------------------------------------------

def _commit_census(impl, transport=None, integrity=False):
    """Primitive counts of ONE traced plan commit (request + owner view)."""
    from repro.launch import jaxpr_stats
    bk = get_backend(None)

    def go(pay, dest):
        plan = ExchangePlan(name="op")
        h = plan.add(pay, dest, 16, reply_lanes=1, op_name="op")
        c = plan.commit(bk, impl=impl, transport=transport,
                        integrity=integrity)
        v = c.view(h)
        return v.payload, v.valid

    return jaxpr_stats.op_counts(go, jnp.zeros((12, 2), jnp.uint32),
                                 jnp.zeros(12, jnp.int32))


def test_fused_wire_traces_zero_scatter_ops():
    """The tentpole pin: with ``impl="pallas"`` a commit writes the wire
    exactly once — the traced program contains ZERO standalone XLA
    scatter ops, dense AND both hierarchical hops; the jnp fallback
    keeps its exact two-pass scatter counts (4 dense: pack + 2 send
    maps + owner assembly; 8 hier: both hops' packs + maps).  Any new
    ``.at[].set`` on the commit path moves these numbers and fails
    here."""
    from repro.core import HierarchicalTransport
    dense_p = _commit_census("pallas")
    hier_p = _commit_census("pallas", transport=HierarchicalTransport())
    assert dense_p.get("scatter", 0) == 0
    assert hier_p.get("scatter", 0) == 0
    # the fused lowering really is Pallas, not an elided wire
    assert dense_p.get("pallas_call", 0) == 4
    assert hier_p.get("pallas_call", 0) == 8
    dense_j = _commit_census("jnp")
    hier_j = _commit_census("jnp", transport=HierarchicalTransport())
    assert dense_j.get("scatter", 0) == 4
    assert hier_j.get("scatter", 0) == 8
    assert dense_j.get("pallas_call", 0) == 0


def test_integrity_checksum_is_scatter_add_not_scatter():
    """Wire checksums (segment-summed row hashes) lower to scatter-add —
    a reduction, not a wire pack — and stay OUT of the fused-wire pin:
    the pallas commit keeps zero plain-scatter ops with integrity on."""
    c = _commit_census("pallas", integrity=True)
    assert c.get("scatter", 0) == 0
    assert c.get("scatter-add", 0) == 1


def test_op_counts_pallas_bodies_opaque_by_default():
    """The census treats a pallas_call as one opaque primitive: in-kernel
    functional stores are vector writes, not XLA scatter passes — the
    raw (non-opaque) census still sees them, pinning that the distinction
    is real."""
    from repro.launch import jaxpr_stats
    raw = _commit_census("pallas")
    assert raw.get("scatter", 0) == 0
    bk = get_backend(None)

    def go(pay, dest):
        plan = ExchangePlan(name="op")
        h = plan.add(pay, dest, 16, op_name="op")
        return plan.commit(bk, impl="pallas").view(h).payload

    full = jaxpr_stats.op_counts(go, jnp.zeros((12, 2), jnp.uint32),
                                 jnp.zeros(12, jnp.int32),
                                 opaque_kernels=False)
    assert full.get("scatter", 0) > 0        # the in-kernel stores


# ---------------------------------------------------------------------------
# fused reply == oracle alignment
# ---------------------------------------------------------------------------

def test_fused_reply_aligns_with_request_batch():
    bk = get_backend(None)
    n = 32
    pay = jnp.asarray(np.random.default_rng(3).permutation(n), jnp.uint32)
    valid = jnp.asarray(np.random.default_rng(4).random(n) < 0.7)
    req = route(bk, pay, jnp.zeros(n, jnp.int32), capacity=n, valid=valid)
    out, answered = reply(bk, req, req.payload[:, 0] * 5 + 2, orig_n=n)
    ans = np.asarray(answered)
    assert np.array_equal(ans, np.asarray(valid))
    assert np.array_equal(np.asarray(out[:, 0])[ans],
                          np.asarray(pay)[ans] * 5 + 2)


# ---------------------------------------------------------------------------
# send-buffer construction kernel: all impls agree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_bin_offsets_impls_match_oracle(impl):
    rng = np.random.default_rng(11)
    nbins = 8
    bins = jnp.asarray(rng.integers(0, nbins, 300), jnp.int32)
    valid = jnp.asarray(rng.random(300) < 0.8)
    oc, oo = ref.bin_offsets_ref(bins, nbins, valid)
    c, o = kops.bin_offsets(bins, nbins, valid, impl=impl)
    assert np.array_equal(np.asarray(oc), np.asarray(c)), impl
    ov = np.asarray(valid)
    assert np.array_equal(np.asarray(oo)[ov], np.asarray(o)[ov]), impl


@pytest.mark.parametrize("impl", ["oracle", "jnp", "pallas"])
def test_multi_bin_offsets_impls_agree(impl):
    """Segmented multi-flow slot assignment: every impl bins the same
    composite (dest, flow) buckets with stable within-bucket ranks."""
    rng = np.random.default_rng(17)
    nbins, nflows, n = 4, 3, 200
    bins = jnp.asarray(rng.integers(0, nbins, n), jnp.int32)
    flow = jnp.asarray(rng.integers(0, nflows, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    counts, offs = kops.multi_bin_offsets(bins, flow, nbins, nflows, valid,
                                          impl=impl)
    b, f, v, o = map(np.asarray, (bins, flow, valid, offs))
    c = np.asarray(counts)
    for d in range(nbins):
        for fl in range(nflows):
            sel = (b == d) & (f == fl) & v
            assert c[d, fl] == sel.sum(), impl
            assert np.array_equal(np.sort(o[sel]),
                                  np.arange(sel.sum())), impl  # dense+stable


def test_bin_offsets_slots_are_unique_per_bin():
    rng = np.random.default_rng(13)
    nbins = 4
    bins = jnp.asarray(rng.integers(0, nbins, 100), jnp.int32)
    _, offs = kops.bin_offsets(bins, nbins, impl="jnp")
    b, o = np.asarray(bins), np.asarray(offs)
    for d in range(nbins):
        mine = np.sort(o[b == d])
        assert np.array_equal(mine, np.arange(mine.size))  # dense + stable
