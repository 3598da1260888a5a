"""Pallas TPU kernel: fused (flash) attention forward.

The LM framework's compute hot spot.  Online-softmax attention with
causal and sliding-window masking and GQA (q-head groups share a kv
head via the BlockSpec index map — no KV replication in memory).

Grid: (batch, q_heads, Tq/BQ, Tk/BK); the last dim is a reduction —
running max / normalizer / accumulator live in VMEM scratch and the
output tile is written on the final reduction step.

VMEM per step at defaults (BQ=BK=128, D=128, f32):
q,k,v tiles 3*128*128*4 = 192 KiB + acc 64 KiB — fine.

On CPU this runs in interpret mode for correctness only; the model
stack uses the XLA path by default (see models/attention.py) so that
dry-run cost analysis sees the attention FLOPs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

_F32 = jnp.float32
_NEG_INF = -1e30



def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, tq: int, tk: int):
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(_F32)          # (BQ, D)
    k = k_ref[0, 0].astype(_F32)          # (BK, D)
    v = v_ref[0, 0].astype(_F32)          # (BK, D)

    s = jnp.dot(q, k.T, preferred_element_type=_F32) * scale   # (BQ, BK)

    # global positions: queries are suffix-aligned to keys (decode support)
    iq = pl.program_id(2)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (tk - tq)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                   # (BQ, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)       # (BQ, 1)
    l_new = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jnp.dot(p, v, preferred_element_type=_F32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q (B,Hq,Tq,D), k/v (B,Hkv,Tk,D) -> (B,Hq,Tq,D).

    Oracle: ref.flash_attention_ref (suffix-aligned causal + window).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    rep = hq // hkv
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    pad_q = (-tq) % bq
    pad_k = (-tk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    tqp, tkp = tq + pad_q, tk + pad_k

    # padded key positions must never win the mask: suffix alignment uses
    # the ORIGINAL tq/tk so padded keys (kpos >= tk) are masked by causal;
    # for non-causal pure-window we extend the window mask below.
    grid = (b, hq, tqp // bq, tkp // bk)
    kern = functools.partial(
        _flash_kernel, scale=1.0 / (d ** 0.5),
        causal=causal, window=(window if window > 0 else (tk if not causal else 0)),
        bq=bq, bk=bk, tq=tq, tk=tk)

    out = pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, rep=rep: (b_, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, tqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), _F32),    # running max
            pltpu.VMEM((bq, 1), _F32),    # running normalizer
            pltpu.VMEM((bq, d), _F32),    # output accumulator
        ],
    )(q, k, v)
    return out[:, :, :tq]
