"""Attention: GQA/MQA with qk-norm, sliding windows, MLA, and KV caches.

Training/prefill uses *blockwise* attention: an unrolled loop over query
blocks, each scanning only the key blocks its mask can reach (causal
block-skipping is static, so HLO FLOPs match the causal ideal), with an
online-softmax accumulator.  This is flash attention expressed in XLA —
memory-bounded, differentiable, and visible to ``cost_analysis`` for the
roofline (the Pallas kernel in kernels/flash_attention.py is the TPU
fast path and is numerically validated against the same oracle).

Decode attends one query against the cache with a plain einsum (that
step is gather/bandwidth-bound, not compute-bound).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


_F32 = jnp.float32
_NEG = -1e30


# ---------------------------------------------------------------------------
# blockwise attention (train / prefill)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        q_block: int = 2048, k_block: int = 1024,
                        q_offset: int = 0, probs_bf16: bool = False):
    """q (B,Hq,Tq,hd), k/v (B,Hkv,Tk,hd) -> (B,Hq,Tq,hd).

    ``q_offset``: global position of q[0] relative to k[0] (suffix
    alignment: q_offset = Tk - Tq for decode-style calls).
    """
    b, hq, tq, hd = q.shape
    _, hkv, tk, _ = k.shape
    dv = v.shape[-1]          # may differ from hd (MLA)
    rep = hq // hkv
    qb = min(q_block, tq)
    kb = min(k_block, tk)
    scale = hd ** -0.5

    # pad K/V once to a block multiple; padded keys masked by position
    pad_k = (-tk) % kb
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_qb = -(-tq // qb)

    # grouped view avoids materializing repeated K/V
    qg = q.reshape(b, hkv, rep, tq, hd)

    outs = []
    for i in range(n_qb):
        q0 = i * qb
        cur_qb = min(qb, tq - q0)
        qi = jax.lax.dynamic_slice_in_dim(qg, q0, cur_qb, axis=3)
        # static key range reachable from this q block (causal block skip)
        hi = min(tk, q0 + q_offset + cur_qb) if causal else tk
        lo = 0
        if window > 0:
            lo = max(0, q0 + q_offset - window + 1)
        lo = (lo // kb) * kb
        hi = -(-max(hi, lo + 1) // kb) * kb
        n_kb = max(1, (hi - lo) // kb)

        m0 = jnp.full((b, hkv, rep, cur_qb, 1), _NEG, _F32)
        l0 = jnp.zeros((b, hkv, rep, cur_qb, 1), _F32)
        a0 = jnp.zeros((b, hkv, rep, cur_qb, dv), _F32)

        def body(carry, j, q0=q0, cur_qb=cur_qb, lo=lo, qi=qi):
            m_p, l_p, acc = carry
            k0 = lo + j * kb
            kj = jax.lax.dynamic_slice_in_dim(k, k0, kb, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, k0, kb, axis=2)
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qi.astype(_F32),
                           kj.astype(_F32)) * scale
            qpos = q0 + q_offset + jnp.arange(cur_qb)[:, None]
            kpos = k0 + jnp.arange(kb)[None, :]
            mask = kpos < tk
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_n = jnp.maximum(m_p, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_n)
            alpha = jnp.exp(m_p - m_n)
            l_n = alpha * l_p + p.sum(axis=-1, keepdims=True)
            if probs_bf16:
                # halve the PV-matmul operand bytes; the normalizer and
                # accumulator stay f32 so the softmax is still exact
                pv = jnp.einsum("bgrqk,bgkd->bgrqd",
                                p.astype(jnp.bfloat16),
                                vj.astype(jnp.bfloat16),
                                preferred_element_type=_F32)
            else:
                pv = jnp.einsum("bgrqk,bgkd->bgrqd", p, vj.astype(_F32))
            acc = acc * alpha + pv
            return (m_n, l_n, acc), None

        (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                          jnp.arange(n_kb))
        blk = (acc / jnp.maximum(l_f, 1e-30)).astype(q.dtype)
        outs.append(blk)

    og = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return og.reshape(b, hq, tq, dv)


def decode_attention(q, k, v, kv_len, lo=None):
    """q (B,Hq,1,hd) against cache k/v (B,Hkv,S,hd); kv_len masks unfilled.

    ``lo`` (optional) masks cache slots below it — the sliding-window
    bound when a windowed layer keeps the full-length cache."""
    b, hq, _, hd = q.shape
    _, hkv, s, _ = k.shape
    dv = v.shape[-1]
    rep = hq // hkv
    qg = q.reshape(b, hkv, rep, hd)
    logits = jnp.einsum("bgrd,bgkd->bgrk", qg.astype(_F32),
                        k.astype(_F32)) * (hd ** -0.5)
    pos = jnp.arange(s)[None, None, None]
    mask = pos < kv_len
    if lo is not None:
        mask &= pos >= lo
    logits = jnp.where(mask, logits, _NEG)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bgrk,bgkd->bgrd", p, v.astype(_F32))
    return o.reshape(b, hq, 1, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# standard GQA block
# ---------------------------------------------------------------------------

def attn_init(rng, cfg, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(rng, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, nq * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, nkv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, nkv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (nq * hd, d)) * (nq * hd) ** -0.5
               ).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention(params, x, cfg, *, positions, causal=True, window=0,
              cache=None, cache_len=None, kv_source=None):
    """Full attention block. Returns (out, new_cache | None).

    cache: dict(k (B,Hkv,S,hd), v, len()) for decode; when given and
    x has T==1, appends and attends over the cache.
    kv_source: encoder output for cross-attention (no cache logic here —
    prefill computes cross KV once and stores it in the cache).
    """
    from repro.models.layers import rms_norm, rotary
    b, t, d = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads

    q = (x @ params["wq"]).reshape(b, t, nq, hd).transpose(0, 2, 1, 3)
    src = x if kv_source is None else kv_source
    ts = src.shape[1]
    k = (src @ params["wk"]).reshape(b, ts, nkv, hd).transpose(0, 2, 1, 3)
    v = (src @ params["wv"]).reshape(b, ts, nkv, hd).transpose(0, 2, 1, 3)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    if kv_source is None:  # self-attention: rotary on both
        q = rotary(q, positions[:, None, :], cfg.rope_theta)
        k = rotary(k, positions[:, None, :] if t == ts else
                   jnp.arange(ts)[None, None, :], cfg.rope_theta)

    new_cache = None
    if cache is not None and t == 1:
        # decode: append at the absolute position, or modulo the ring
        # size for window-capped caches (cfg.window_cache)
        pos = cache_len
        s_cache = cache["k"].shape[2]
        ring = window > 0 and s_cache <= window
        slot = pos % s_cache if ring else pos
        ck = _cache_append(cache["k"], k, slot)
        cv = _cache_append(cache["v"], v, slot)
        new_cache = {"k": ck, "v": cv}
        kv_len = jnp.minimum(pos + 1, s_cache) if ring else pos + 1
        lo = jnp.maximum(pos + 1 - window, 0) \
            if (window > 0 and not ring) else None
        out = decode_attention(q, ck, cv, kv_len, lo=lo)
    else:
        out = blockwise_attention(q, k, v, causal=causal, window=window,
                                  q_block=cfg.attn_q_block,
                                  k_block=cfg.attn_k_block,
                                  probs_bf16=cfg.attn_probs_bf16)
        if cache is not None:  # prefill into cache
            s = cache["k"].shape[2]
            if s < ts:
                # window-capped ring: keep the last s keys, stored at
                # row p % s so decode's ring append stays consistent
                shift = (ts - s) % s
                ck = jnp.roll(k[:, :, -s:], shift, axis=2)
                cv = jnp.roll(v[:, :, -s:], shift, axis=2)
            else:
                ck = jnp.pad(k, ((0, 0), (0, 0), (0, s - ts), (0, 0)))
                cv = jnp.pad(v, ((0, 0), (0, 0), (0, s - ts), (0, 0)))
            new_cache = {"k": ck.astype(cache["k"].dtype),
                         "v": cv.astype(cache["v"].dtype)}

    out = out.transpose(0, 2, 1, 3).reshape(b, t, nq * hd)
    return out @ params["wo"], new_cache


def _cache_append(buf, x, pos):
    """Append x (B,H,1,hd) at position pos (dynamic) in buf (B,H,S,hd)."""
    return jax.lax.dynamic_update_slice(
        buf, x.astype(buf.dtype), (0, 0, pos, 0))


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(rng, cfg, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(rng, 6)
    s = d ** -0.5
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": (jax.random.normal(ks[0], (d, m.q_lora_rank)) * s).astype(dtype),
        "w_uq": (jax.random.normal(ks[1], (m.q_lora_rank, h * qh))
                 * m.q_lora_rank ** -0.5).astype(dtype),
        "w_dkv": (jax.random.normal(ks[2], (d, m.kv_lora_rank)) * s).astype(dtype),
        "w_kr": (jax.random.normal(ks[3], (d, m.qk_rope_head_dim)) * s).astype(dtype),
        "w_ukv": (jax.random.normal(
            ks[4], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)))
            * m.kv_lora_rank ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[5], (h * m.v_head_dim, d))
               * (h * m.v_head_dim) ** -0.5).astype(dtype),
    }


def mla_attention(params, x, cfg, *, positions, cache=None, cache_len=None,
                  mesh=None, axes=None):
    """MLA with the compressed (c_kv, k_rope) cache. Returns (out, cache)."""
    from repro.models.layers import rotary
    m = cfg.mla
    b, t, d = x.shape
    h = cfg.n_heads
    nope, rope, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = ((x @ params["w_dq"]) @ params["w_uq"]).reshape(b, t, h, nope + rope)
    q = q.transpose(0, 2, 1, 3)                     # (B,H,T,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rotary(q_rope, positions[:, None, :], cfg.rope_theta)

    c_kv = x @ params["w_dkv"]                      # (B,T,r)
    k_rope = x @ params["w_kr"]                     # (B,T,rope) shared head
    k_rope = rotary(k_rope[:, None], positions[:, None, :],
                    cfg.rope_theta)[:, 0]

    new_cache = None
    if cache is not None and t == 1:
        pos = cache_len
        if cfg.mla_absorb and cfg.mla_cp_decode and mesh is not None:
            out, new_cache = mla_absorbed_decode_cp(
                params, cfg, q_nope, q_rope, c_kv[:, 0], k_rope[:, 0],
                cache, pos, mesh, axes)
            return out @ params["wo"], new_cache
        c_full = jax.lax.dynamic_update_slice(cache["c_kv"],
                                              c_kv.astype(cache["c_kv"].dtype),
                                              (0, pos, 0))
        r_full = jax.lax.dynamic_update_slice(cache["k_rope"],
                                              k_rope.astype(cache["k_rope"].dtype),
                                              (0, pos, 0))
        new_cache = {"c_kv": c_full, "k_rope": r_full}
        if cfg.mla_absorb:
            # DeepSeek weight absorption: attend in the LATENT space —
            # never re-expand K/V for the whole cache.  Per step:
            # O(B*H*S*(r+rope)) instead of O(B*S*r*H*(nope+v)), a ~2
            # orders-of-magnitude decode-compute cut at 32k
            # (EXPERIMENTS.md section Perf, deepseek decode cell).
            out = _mla_absorbed_decode(params, cfg, q_nope, q_rope,
                                       c_full, r_full, pos + 1)
            return out @ params["wo"], new_cache
        c_kv, k_rope = c_full, r_full
        s_len = c_kv.shape[1]
        kv_mask_len = pos + 1
    else:
        s_len = t
        kv_mask_len = None
        if cache is not None:
            s = cache["c_kv"].shape[1]
            new_cache = {
                "c_kv": jnp.pad(c_kv, ((0, 0), (0, s - t), (0, 0))
                                ).astype(cache["c_kv"].dtype),
                "k_rope": jnp.pad(k_rope, ((0, 0), (0, s - t), (0, 0))
                                  ).astype(cache["k_rope"].dtype)}

    kv = (c_kv @ params["w_ukv"]).reshape(b, s_len, h, nope + vdim)
    kv = kv.transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (b, h, s_len, rope))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    if kv_mask_len is not None:
        out = decode_attention(q_full, k, v, kv_mask_len)
    else:
        out = blockwise_attention(q_full, k, v, causal=True,
                                  q_block=cfg.attn_q_block,
                                  k_block=cfg.attn_k_block,
                                  probs_bf16=cfg.attn_probs_bf16)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, h * vdim)
    return out @ params["wo"], new_cache


def mla_absorbed_decode_cp(params, cfg, q_nope, q_rope, new_c, new_kr,
                           cache, pos, mesh, axes):
    """Context-parallel absorbed MLA decode: the compressed cache's
    SEQUENCE dim is sharded over the model axis; each rank attends its
    slice and a two-pass (flash-style) softmax combine merges partials:

      M = pmax(m_i);  l = psum(l_i * e^{m_i-M});  ctx = psum(ctx_i * ...)

    This is what makes a (128, 32k, 576) cache fit per-device HBM:
    18.4 GiB (data-sharded only, replicated over model) -> 1.15 GiB.
    Returns (out (B,1,H*vdim), new_cache).
    """
    m = cfg.mla
    b, h, _, nope = q_nope.shape
    r = m.kv_lora_rank
    vdim = m.v_head_dim
    w_full = params["w_ukv"].reshape(r, h, nope + vdim)
    w_uk, w_uv = w_full[:, :, :nope], w_full[:, :, nope:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0].astype(_F32),
                       w_uk.astype(_F32))                    # (B,H,r)
    qr = q_rope[:, :, 0].astype(_F32)                        # (B,H,rope)
    scale = (nope + m.qk_rope_head_dim) ** -0.5
    from jax.sharding import PartitionSpec as P

    def f(ql, qro, nc, nk, ckv, kr):
        rank = jax.lax.axis_index(axes.model)
        s_loc = ckv.shape[1]
        lpos = pos - rank * s_loc
        in_rng = (lpos >= 0) & (lpos < s_loc)
        lclip = jnp.clip(lpos, 0, s_loc - 1)
        upd_c = jax.lax.dynamic_update_slice(
            ckv, nc[:, None].astype(ckv.dtype), (0, lclip, 0))
        ckv = jnp.where(in_rng, upd_c, ckv)
        upd_k = jax.lax.dynamic_update_slice(
            kr, nk[:, None].astype(kr.dtype), (0, lclip, 0))
        kr = jnp.where(in_rng, upd_k, kr)

        cf = ckv.astype(_F32)
        s = jnp.einsum("bhr,bsr->bhs", ql, cf)
        s = s + jnp.einsum("bhp,bsp->bhs", qro, kr.astype(_F32))
        s = s * scale
        gpos = rank * s_loc + jnp.arange(s_loc)[None, None]
        s = jnp.where(gpos <= pos, s, _NEG)
        m_i = s.max(axis=-1)                                  # (B,H)
        e = jnp.exp(s - m_i[..., None])
        e = jnp.where(gpos <= pos, e, 0.0)
        l_i = e.sum(axis=-1)
        ctx_i = jnp.einsum("bhs,bsr->bhr", e, cf)
        m_g = jax.lax.pmax(m_i, axes.model)
        w = jnp.exp(m_i - m_g)
        l_g = jax.lax.psum(l_i * w, axes.model)
        ctx = jax.lax.psum(ctx_i * w[..., None], axes.model)
        ctx = ctx / jnp.maximum(l_g, 1e-30)[..., None]
        return ctx, ckv, kr

    d = axes.data
    bdim = q_lat.shape[0]
    n_data = 1
    for a in d:
        n_data *= mesh.shape[a]
    lead = d if bdim % n_data == 0 else None
    ctx, ckv2, kr2 = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(lead, None, None), P(lead, None, None),
                  P(lead, None), P(lead, None),
                  P(lead, axes.model, None), P(lead, axes.model, None)),
        out_specs=(P(lead, None, None),
                   P(lead, axes.model, None), P(lead, axes.model, None)),
    )(q_lat, qr, new_c, new_kr, cache["c_kv"], cache["k_rope"])
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(_F32))
    return (out.reshape(b, 1, h * vdim).astype(q_nope.dtype),
            {"c_kv": ckv2, "k_rope": kr2})


def _mla_absorbed_decode(params, cfg, q_nope, q_rope, c_kv, k_rope, kv_len):
    """Latent-space MLA decode (weight absorption).

    q_nope (B,H,1,nope), q_rope (B,H,1,rope); cache c_kv (B,S,r),
    k_rope (B,S,rope).  Scores: q_nope^T (W_uk c) = (W_uk^T q_nope)^T c,
    so queries are projected DOWN once and the cache is used as-is; the
    context is likewise accumulated in latent space and expanded once.
    Returns (B, 1, H*vdim).
    """
    m = cfg.mla
    b, h, _, nope = q_nope.shape
    r = m.kv_lora_rank
    vdim = m.v_head_dim
    w_full = params["w_ukv"].reshape(r, h, nope + vdim)
    w_uk = w_full[:, :, :nope]
    w_uv = w_full[:, :, nope:]

    cf = c_kv.astype(_F32)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0].astype(_F32),
                       w_uk.astype(_F32))                    # (B,H,r)
    scores = jnp.einsum("bhr,bsr->bhs", q_lat, cf)
    scores = scores + jnp.einsum("bhp,bsp->bhs",
                                 q_rope[:, :, 0].astype(_F32),
                                 k_rope.astype(_F32))
    scores = scores * ((nope + m.qk_rope_head_dim) ** -0.5)
    s = c_kv.shape[1]
    mask = jnp.arange(s)[None, None] < kv_len
    scores = jnp.where(mask, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs, cf)              # (B,H,r)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(_F32))  # (B,H,v)
    return out.reshape(b, 1, h * vdim).astype(q_nope.dtype)
