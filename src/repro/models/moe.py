"""Mixture-of-Experts with BCL-exchange token dispatch.

This is the paper's technique as a first-class framework feature
(DESIGN.md section 3): expert dispatch IS the many-to-many
redistribution pattern of BCL queues / ISx.  The layer:

  1. registers token routing AND a per-expert stats flow on one
     ``repro.core.exchange.ExchangePlan`` — bucket-by-owner, prefix-sum
     slot reservation, one tiled all-to-all for both flows (the
     FastQueue.push_many program).  The stats flow asks each expert's
     owner for its post-capacity served-token count, so every rank
     learns the true global expert load (the DeepSeek aux-loss-free
     bias-update signal) with ZERO extra collectives;
  2. bins arrivals per local expert (the same binning the hash kernel
     uses) and runs a batched expert FFN;
  3. the combine and the stats replies share one inverse all-to-all
     (``plan.finish``) and results merge with router weights.

Parallelism: experts sharded over 'model' (EP); per-expert weights
FSDP-sharded over the data axes and all-gathered just-in-time (EP x
ZeRO-3 — how 671B of expert weights fit 256 chips, DESIGN.md section 5).
Tokens are sequence-split over 'model' before dispatch so no rank
duplicates work.

Everything is differentiable: route/reply are built from sort/scatter/
all_to_all, all of which have transpose rules, so expert gradients flow
through the exchange exactly like activations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.backend import SpmdBackend
from repro.core.exchange import ExchangePlan
from repro.core.transport import make_transport
from repro.models.sharding import Axes


_F32 = jnp.float32
_U32 = jnp.uint32
_I32 = jnp.int32


def moe_init(rng, cfg, dtype):
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.expert_d_ff, mo.n_experts
    ks = jax.random.split(rng, 6)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(_F32),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
            "w_in": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
            "w_out": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
        },
    }
    if mo.shared_experts:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, mo.expert_d_ff * mo.shared_experts,
                               cfg.activation, dtype)
    if mo.dense_residual:
        from repro.models.layers import mlp_init
        p["dense"] = mlp_init(ks[5], d, cfg.d_ff, cfg.activation, dtype)
    if mo.bias_update_rate > 0:
        p["moe_bias"] = jnp.zeros((e,), _F32)
    return p


def _pack_act(x, bf16: bool):
    """(N, D) activations -> u32 lanes; bf16 packs 2 values per lane
    (halves exchange wire bytes — EXPERIMENTS.md section Perf)."""
    if not bf16:
        return jax.lax.bitcast_convert_type(x.astype(_F32), _U32)
    n, d = x.shape
    h = jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)
    return jax.lax.bitcast_convert_type(h.reshape(n, d // 2, 2), _U32)


def _unpack_act(lanes, bf16: bool):
    if not bf16:
        return jax.lax.bitcast_convert_type(lanes, _F32)
    n, dh = lanes.shape
    h = jax.lax.bitcast_convert_type(lanes, jnp.uint16).reshape(n, dh * 2)
    return jax.lax.bitcast_convert_type(h, jnp.bfloat16).astype(_F32)


def _bin_by_expert(rows, expert, valid, n_groups: int, cap: int):
    """Group rows (M, D) into (n_groups, cap, D) by expert id."""
    binned_idx, slot, ok = _bin_indices(expert, valid, n_groups, cap,
                                        rows.shape[0])
    binned = jnp.where((binned_idx >= 0)[:, None],
                       rows[jnp.maximum(binned_idx, 0)], 0)
    return binned.reshape(n_groups, cap, -1), slot, ok


def _bin_indices(expert, valid, n_groups: int, cap: int, m: int):
    """Slot assignment only: (flat_row_index (n_groups*cap,), slot (M,),
    ok (M,)); -1 marks empty bin slots."""
    g = jnp.where(valid, expert.astype(_I32), n_groups)
    counts_full = jnp.zeros((n_groups + 1,), _I32).at[g].add(1)
    start = jnp.concatenate([jnp.zeros((1,), _I32),
                             jnp.cumsum(counts_full)[:-1].astype(_I32)])
    order = jnp.argsort(g, stable=True)
    pos = jnp.arange(m, dtype=_I32) - start[g[order]]
    pos_orig = jnp.zeros((m,), _I32).at[order].set(pos)
    ok = valid & (pos_orig < cap)
    slot = jnp.where(ok, g * cap + pos_orig, n_groups * cap)
    binned_idx = jnp.full((n_groups * cap,), -1, _I32)
    binned_idx = binned_idx.at[slot].set(jnp.arange(m, dtype=_I32),
                                         mode="drop")
    return binned_idx, slot, ok


def _stats_flow(plan: ExchangePlan, e: int, e_loc: int) -> int:
    """Register the per-expert stats flow: one row per global expert,
    asking that expert's owner for its served-token count.  Capacity is
    exact (every rank sends exactly ``e_loc`` rows per owner), so the
    flow can never drop.

    The ragged fused wire (DESIGN.md section 1.5) makes this flow's
    cost independent of the token payload: its segment is exactly 2 u32
    request words (expert id + meta) and 1 reply word per row — byte-
    pinned in tests/test_wire_format.py — so global expert-load
    observability is genuinely free of d_model-width wire.
    ``max_rounds=1``: the capacity is exact, so the flow opts out of
    any retry rounds the token flow requests."""
    eid = jnp.arange(e, dtype=_I32)
    return plan.add((eid % e_loc).astype(_U32)[:, None], eid // e_loc,
                    e_loc, reply_lanes=1, op_name="moe.stats",
                    max_rounds=1)


def _stats_reply(committed, handle: int, served: jax.Array):
    """Owner side: answer each stats request with its expert's count."""
    sv = committed.view(handle)
    lid = jnp.where(sv.valid, sv.payload[:, 0].astype(_I32), 0)
    committed.set_reply(handle, jnp.where(sv.valid, served[lid], 0)
                        .astype(_U32))


def _make_expert_ffn(cfg):
    def _expert_ffn(binned, wg, wi, wo_):
        if cfg.activation in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
            h = act(jnp.einsum("ecd,edf->ecf", binned, wg)) * \
                jnp.einsum("ecd,edf->ecf", binned, wi)
        else:
            from repro.models.layers import activation_fn
            h = activation_fn(cfg.activation)(
                jnp.einsum("ecd,edf->ecf", binned, wi))
        return jnp.einsum("ecf,efd->ecd", h, wo_)
    return _expert_ffn


def moe_apply(params, x, cfg, mesh: Mesh, axes: Axes):
    """x (B, T, D) sharded over data -> same.

    Returns ``(y, aux, stats)``: the aux load-balance loss plus a stats
    dict with ``expert_load`` — the true global post-capacity
    served-token count per expert (E,), delivered by the stats flow that
    rides the dispatch plan's collectives — and ``dispatch_dropped``,
    the global count of token copies the exchange wire could not admit
    (the trajectory ``exchange.suggest_rounds`` reads to pick
    ``cfg.moe_dispatch_rounds``).  This is the observability signal
    DeepSeek-style bias routing (``moe_bias``) updates from; it costs
    zero extra collectives.
    """
    mo = cfg.moe
    b, t, d = x.shape
    e = mo.n_experts
    k = mo.top_k

    # ---- router (global) ----
    gate_logits = jnp.einsum("btd,de->bte", x.astype(_F32),
                             params["router"])
    if "moe_bias" in params:
        scores = jax.nn.sigmoid(gate_logits) + params["moe_bias"]
        _, top_idx = jax.lax.top_k(scores, k)
        top_p = jnp.take_along_axis(jax.nn.sigmoid(gate_logits), top_idx,
                                    axis=-1)
        top_w = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(gate_logits, axis=-1)
        top_w, top_idx = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (GShard)
    probs_mean = jax.nn.softmax(gate_logits, -1).mean(axis=(0, 1))
    hard = jnp.zeros((e,), _F32).at[top_idx.reshape(-1)].add(1.0)
    hard = hard / jnp.maximum(hard.sum(), 1.0)
    aux = mo.aux_loss_coef * e * jnp.sum(probs_mean * hard)

    # ---- dispatch over the model axis (the BCL exchange) ----
    nm = mesh.shape[axes.model]
    e_loc = -(-e // nm)
    seq_split = t % nm == 0 and nm > 1
    _expert_ffn = _make_expert_ffn(cfg)
    # physical collective layer for the dispatch plan (DESIGN.md §1.7)
    transport = make_transport(cfg.exchange_transport)
    # split-phase dispatch (DESIGN.md §1.9): commit_async issues the
    # wire, the always-on row-wise paths (shared/dense MLP) run in the
    # overlap window on the local shard, then finish() completes the
    # exchange before the owner-side expert compute
    async_ = bool(cfg.moe_async_dispatch)
    extra_keys = tuple(kk for kk in ("shared", "dense") if kk in params)

    def _overlap_window(xl, extras):
        from repro.models.layers import mlp
        out = None
        for p in extras:
            o = mlp(p, xl, cfg.activation)
            out = o if out is None else out + o
        return out

    def dispatch_dedup(xl, idxl, wl, wg, wi, wo_, *extras):
        """One exchange row per (token, distinct owner rank): the owner
        runs ALL of its local experts for the token and replies the
        weighted partial sum — for top-8 over 16 ranks the expected
        distinct-owner count is ~6.5, a ~19% cut of exchange rows in
        each direction (EXPERIMENTS.md section Perf iteration 6)."""
        bk = SpmdBackend(axes.model)
        bl, tl = xl.shape[0], xl.shape[1]
        n_tok = bl * tl
        n = n_tok * k
        exp_owners = nm * (1.0 - (1.0 - 1.0 / nm) ** k)
        cap = max(1, int(n_tok * min(k, exp_owners) / nm
                         * cfg.moe_capacity_slack) + 1)
        # retry rounds admit up to rounds x cap arrivals per (src,dst),
        # so the owner-side expert bins must scale with them too or the
        # rescued tokens would be silently zeroed at the bin stage
        e_cap = max(1, int(n_tok * k * nm / e * cfg.moe_capacity_slack)
                    + 1) * max(1, cfg.moe_dispatch_rounds)
        bf16 = cfg.moe_payload_dtype == "bfloat16"
        act_lanes = d // 2 if bf16 else d

        xx = xl.reshape(n_tok, d)
        ee = idxl.reshape(n_tok, k).astype(_I32)
        ww = wl.reshape(n_tok, k).astype(_F32)
        owners = ee // e_loc                                  # (n_tok, k)
        same = owners[:, :, None] == owners[:, None, :]       # (n_tok,j,i)
        first = ~jnp.triu(same, 1).any(axis=2)                # j is first
        # per (token, j) row: local expert ids + weights for MY owner
        ids = jnp.where(same, (ee % e_loc)[:, None, :], e_loc)  # (n_tok,j,i)
        wts = jnp.where(same, ww[:, None, :], 0.0)
        payload = jnp.concatenate(
            [_pack_act(jnp.repeat(xx, k, axis=0), bf16),
             ids.reshape(n, k).astype(_U32),
             jax.lax.bitcast_convert_type(wts.reshape(n, k), _U32)], axis=1)
        plan = ExchangePlan(name="moe.dispatch")
        h_tok = plan.add(payload, owners.reshape(-1), cap,
                         reply_lanes=act_lanes, valid=first.reshape(-1),
                         op_name="moe.dispatch")
        h_st = _stats_flow(plan, e, e_loc)
        if async_:
            pend = plan.commit_async(bk, max_rounds=cfg.moe_dispatch_rounds,
                                     transport=transport)
            win = _overlap_window(xl, extras)
            c = pend.finish(bk)
        else:
            win = None
            c = plan.commit(bk, max_rounds=cfg.moe_dispatch_rounds,
                            transport=transport)
        res = c.view(h_tok)

        m = res.payload.shape[0]
        rows = _unpack_act(res.payload[:, :act_lanes], bf16)   # (M, D)
        ids_m = res.payload[:, act_lanes:act_lanes + k].astype(_I32)
        wts_m = jax.lax.bitcast_convert_type(
            res.payload[:, act_lanes + k:act_lanes + 2 * k], _F32)
        flat_ids = ids_m.reshape(-1)
        flat_valid = jnp.repeat(res.valid, k) & (flat_ids < e_loc)
        flat_row = jnp.repeat(jnp.arange(m, dtype=_I32), k)
        flat_w = wts_m.reshape(-1)

        bin_idx, slot, okb = _bin_indices(flat_ids, flat_valid, e_loc,
                                          e_cap, m * k)
        src_row = jnp.where(bin_idx >= 0, flat_row[jnp.maximum(bin_idx, 0)],
                            0)
        binned = jnp.where((bin_idx >= 0)[:, None], rows[src_row], 0)
        binned = binned.reshape(e_loc, e_cap, d).astype(wg.dtype)
        y = _expert_ffn(binned, wg, wi, wo_)                   # (e_loc,cap,D)

        flat_y = y.reshape(e_loc * e_cap, d).astype(_F32)
        take = jnp.minimum(slot, e_loc * e_cap - 1)
        out_rows = jnp.zeros((m, d), _F32).at[
            jnp.where(okb, flat_row, m)].add(
            flat_y[take] * flat_w[:, None] * okb[:, None], mode="drop")

        served = jnp.zeros((e_loc,), _I32).at[
            jnp.where(okb, flat_ids, e_loc)].add(1, mode="drop")
        _stats_reply(c, h_st, served)
        c.set_reply(h_tok, _pack_act(out_rows, bf16))
        outs = c.finish(bk)
        out_lanes, _ = outs[h_tok]
        load = outs[h_st][0][:, 0].astype(_F32)[None]          # (1, e)
        yk = _unpack_act(out_lanes, bf16).reshape(n_tok, k, d)
        # weights applied at owner
        ybt = yk.sum(axis=1).reshape(bl, tl, d)
        if win is not None:
            ybt = ybt.astype(xl.dtype) + win
        return ybt, load, res.dropped[None]

    def dispatch(xl, idxl, wl, wg, wi, wo_, *extras):
        # xl (b_loc, t_loc, D); idxl/wl (b_loc, t_loc, K) — PER-DEVICE
        # shapes, so the static exchange capacities are sized from the
        # tokens this rank actually holds (uniform expectation x slack).
        if cfg.moe_dedup_dispatch:
            return dispatch_dedup(xl, idxl, wl, wg, wi, wo_, *extras)
        bk = SpmdBackend(axes.model)
        bl, tl = xl.shape[0], xl.shape[1]
        cap = max(1, int(bl * tl * k / nm * cfg.moe_capacity_slack) + 1)
        # expert bins scale with retry rounds (see dispatch_dedup)
        e_cap = max(1, int(bl * tl * k * nm / e * cfg.moe_capacity_slack)
                    + 1) * max(1, cfg.moe_dispatch_rounds)
        xx = jnp.repeat(xl.reshape(bl * tl, d), k, axis=0)     # (n, D)
        ee = idxl.reshape(-1).astype(_I32)                      # (n,)
        dest = ee // e_loc                                      # owner rank
        bf16 = cfg.moe_payload_dtype == "bfloat16"
        act_lanes = d // 2 if bf16 else d
        payload = jnp.concatenate(
            [_pack_act(xx, bf16),
             (ee % e_loc).astype(_U32)[:, None]], axis=1)
        plan = ExchangePlan(name="moe.dispatch")
        h_tok = plan.add(payload, dest, cap, reply_lanes=act_lanes,
                         op_name="moe.dispatch")
        h_st = _stats_flow(plan, e, e_loc)
        if async_:
            pend = plan.commit_async(bk, max_rounds=cfg.moe_dispatch_rounds,
                                     transport=transport)
            win = _overlap_window(xl, extras)
            c = pend.finish(bk)
        else:
            win = None
            c = plan.commit(bk, max_rounds=cfg.moe_dispatch_rounds,
                            transport=transport)
        res = c.view(h_tok)

        rows = _unpack_act(res.payload[:, :act_lanes], bf16)
        le = jnp.where(res.valid, res.payload[:, act_lanes].astype(_I32),
                       e_loc)
        binned, slot, okb = _bin_by_expert(rows, le, res.valid, e_loc, e_cap)
        binned = binned.astype(wg.dtype)

        # batched expert FFN (weights already all-gathered over fsdp axes
        # by the sharding constraint on entry — XLA inserts the gather)
        y = _expert_ffn(binned, wg, wi, wo_)                    # (e_loc,cap,D)

        flat = y.reshape(e_loc * e_cap, d)
        take = jnp.minimum(slot, e_loc * e_cap - 1)
        back_rows = jnp.where((slot < e_loc * e_cap)[:, None],
                              flat[take], 0).astype(_F32)
        served = jnp.zeros((e_loc,), _I32).at[
            jnp.where(okb, le, e_loc)].add(1, mode="drop")
        _stats_reply(c, h_st, served)
        c.set_reply(h_tok, _pack_act(back_rows, bf16))
        outs = c.finish(bk)
        out_lanes, _ = outs[h_tok]
        load = outs[h_st][0][:, 0].astype(_F32)[None]           # (1, e)
        yk = _unpack_act(out_lanes, bf16)                       # (n, D)
        yk = yk.reshape(bl, tl, k, d)
        ybt = jnp.einsum("btkd,btk->btd", yk, wl.astype(_F32))
        if win is not None:
            ybt = ybt.astype(xl.dtype) + win
        return ybt, load, res.dropped[None]

    din = axes.data
    if seq_split:
        in_x = P(din, axes.model, None)
        in_i = P(din, axes.model, None)
    else:
        in_x = P(din, None, None)
        in_i = P(din, None, None)
    espec = lambda *rest: P(axes.model, *rest)
    # under split-phase dispatch the shared/dense trees ride into the
    # shard_map (replicated) so the window can compute them on xl rows
    extra_args = tuple(params[kk] for kk in extra_keys) if async_ else ()
    y, load, drops = jax.shard_map(
        dispatch, mesh=mesh,
        in_specs=(in_x, in_i, in_i,
                  espec(None, None), espec(None, None), espec(None, None))
                 + tuple(P() for _ in extra_args),
        out_specs=(in_x, P(din, None), P(din)),
        # every model rank dispatches the same tokens and gets the same
        # replies back through the exchange's all_to_all, so the outputs
        # are replicated over 'model' by construction; vma typing cannot
        # see through the all_to_all, so the check stays off here
        check_vma=False,
    )(x, top_idx.astype(_I32), top_w,
      params["experts"]["w_gate"], params["experts"]["w_in"],
      params["experts"]["w_out"], *extra_args)
    y = y.astype(x.dtype)
    expert_load = load.sum(axis=0)        # (E,) summed over data shards
    # wire drops of the token flow (already global over the model axis);
    # summed over data shards — the skew observability signal the
    # suggest_rounds heuristic and the --skew benchmarks read
    dispatch_dropped = drops.sum()

    # ---- always-on paths ----
    # (under async dispatch these were already folded in per shard,
    # inside the overlap window between commit_async and finish)
    if not async_:
        from repro.models.layers import mlp
        if "shared" in params:
            y = y + mlp(params["shared"], x, cfg.activation)
        if "dense" in params:
            y = y + mlp(params["dense"], x, cfg.activation)
    return y, aux, {"expert_load": expert_load,
                    "dispatch_dropped": dispatch_dropped}
