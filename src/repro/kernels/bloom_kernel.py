"""Pallas TPU kernel: blocked Bloom filter hashing + membership.

Fuses the per-item pipeline of paper section 5.4.2 into one VPU pass:
murmur-finalizer double hashing (k bit positions), expansion to a 64-bit
block word, and the membership test against the (pre-gathered) filter
word.  Everything is shift/xor/mul/or lanes — ideal VPU code; the grid
tiles the item batch.

The owner-side OR-scatter (and the segmented OR-scan that makes batch
insertion atomic) stays outside the kernel: it is a data-dependent
scatter that the exchange engine already organizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

_U32 = jnp.uint32
# plain ints: Pallas kernels cannot capture module-level array constants
_C1 = 0x85EBCA6B
_C2 = 0xC2B2AE35
_PHI = 0x9E3779B9


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * _U32(_C1)
    h = h ^ (h >> 13)
    h = h * _U32(_C2)
    h = h ^ (h >> 16)
    return h


def _hash_lanes(lanes, seed, num_lanes):
    init = (seed * _PHI + num_lanes) & 0xFFFFFFFF
    h = jnp.full(lanes.shape[1:], _U32(init), _U32)
    for i in range(num_lanes):
        h = (h ^ _fmix32(lanes[i:i + 1])) * _U32(_C1) + _U32(i + 1)
    return _fmix32(h)


# Tiles are lane-major, (L, TM): items run along the vector lanes, so the
# two words of a 64-bit block (and a few key lanes) are not padded out to
# 128 lanes.  The wrappers transpose at the boundary.

def _words_kernel(lanes_ref, words_ref, *, k: int, num_lanes: int):
    lanes = lanes_ref[...]                       # (L, TM)
    h1 = _hash_lanes(lanes, 1, num_lanes)        # (1, TM)
    h2 = _hash_lanes(lanes, 2, num_lanes) | _U32(1)
    lo = jnp.zeros(h1.shape, _U32)
    hi = jnp.zeros(h1.shape, _U32)
    for i in range(k):
        bit = (h1 + _U32(i) * h2) % _U32(64)
        lo = lo | jnp.where(bit < 32, _U32(1) << (bit % 32), _U32(0))
        hi = hi | jnp.where(bit >= 32, _U32(1) << (bit % 32), _U32(0))
    words_ref[...] = jnp.concatenate([lo, hi], axis=0)


def hash_words(lanes: jax.Array, k: int, tile: int = 1024) -> jax.Array:
    """(M, L) u32 item lanes -> (M, 2) u32 64-bit block words (k bits)."""
    m, num_lanes = lanes.shape
    pad = (-m) % tile
    if pad:
        lanes = jnp.pad(lanes, ((0, pad), (0, 0)))
    mp = lanes.shape[0]
    kern = functools.partial(_words_kernel, k=k, num_lanes=num_lanes)
    words = pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((num_lanes, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((2, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((2, mp), _U32),
    )(lanes.T)
    return words.T[:m]


def _member_kernel(prior_ref, words_ref, valid_ref, out_ref):
    prior = prior_ref[...]                       # (2, TM)
    words = words_ref[...]
    hit = (prior & words) == words
    ok = hit[0:1] & hit[1:2]                     # (1, TM)
    out_ref[...] = (ok & (valid_ref[...] == 1)).astype(_U32)


def membership(prior: jax.Array, words: jax.Array, valid: jax.Array,
               tile: int = 1024) -> jax.Array:
    """already_present = all k bits of ``words`` set in ``prior``."""
    m = prior.shape[0]
    valid = valid.astype(_U32)
    pad = (-m) % tile
    if pad:
        prior = jnp.pad(prior, ((0, pad), (0, 0)))
        words = jnp.pad(words, ((0, pad), (0, 0)), constant_values=1)
        valid = jnp.pad(valid, (0, pad))
    mp = prior.shape[0]
    lane_major = pl.BlockSpec((2, tile), lambda i: (0, i))
    row = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pallas_call(
        _member_kernel,
        grid=(mp // tile,),
        in_specs=[lane_major, lane_major, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, mp), _U32),
    )(prior.T, words.T, valid[None, :])
    return out[0, :m] == 1
