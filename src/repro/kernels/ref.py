"""Pure-jnp oracles for every Pallas kernel in this package.

These are the *semantic ground truth*: deliberately simple (sequential
``fori_loop`` where ordering matters), obviously correct, and used by the
test suite to validate both the vectorized jnp implementations in
``ops.py`` and the Pallas kernels (run in interpret mode on CPU).

Hash-table layout (blocked open addressing, DESIGN.md section 2):
  tkeys  (nb, Lk, B) u32   stored key lanes, lane-major per block
  tvals  (nb, Lv, B) u32   stored value lanes, lane-major per block
  status (nb, B)     u32   0=FREE, 1=RESERVED, 2=READY (paper's 2-bit state)

A block's lane ``l`` is one B-wide row, so on TPU the table's minor axis
is the B = 128 slots of a block and its HBM layout carries no padding.

A key hashes to a block; probing is vectorized across the block's B slots.
Cross-block overflow is handled by the container via bounded rehash
attempts (quadratic in the attempt number), not inside the kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_U32 = jnp.uint32
FREE, RESERVED, READY = _U32(0), _U32(1), _U32(2)
STATE_MASK = _U32(3)   # low 2 bits = bucket state; high 30 bits = read flags


def bucket_state(status):
    return status & STATE_MASK

MODE_SET, MODE_ADD, MODE_KEEP = 0, 1, 2


# --------------------------------------------------------------------------
# blocked hash probe
# --------------------------------------------------------------------------

def hash_probe_insert_ref(tkeys, tvals, status, qblock, qkeys, qvals, qvalid,
                          mode: int = MODE_SET):
    """Sequential-semantics blocked insert oracle.

    Items are inserted one at a time in batch order: matching READY slot
    updates the value (set / add / keep); otherwise the first FREE slot
    in the block is claimed; a full block fails the item.

    Returns (tkeys, tvals, status, success(M,) bool).
    """
    m = qblock.shape[0]

    def body(i, carry):
        tk, tv, st, ok = carry
        b = qblock[i]
        key = qkeys[i]
        blk_keys = tk[b]          # (Lk, B)
        blk_stat = st[b]          # (B,)
        match = (blk_keys == key[:, None]).all(axis=0) & (bucket_state(blk_stat) == READY)
        has_match = match.any()
        match_slot = jnp.argmax(match)
        free = bucket_state(blk_stat) == FREE
        has_free = free.any()
        free_slot = jnp.argmax(free)
        slot = jnp.where(has_match, match_slot, free_slot)
        can = qvalid[i] & (has_match | has_free)

        old_val = tv[b, :, slot]
        if mode == MODE_SET:
            new_val = qvals[i]
        elif mode == MODE_ADD:
            new_val = jnp.where(has_match, old_val + qvals[i], qvals[i])
        else:  # MODE_KEEP: first writer wins
            new_val = jnp.where(has_match, old_val, qvals[i])

        tk = tk.at[b, :, slot].set(jnp.where(can, key, tk[b, :, slot]))
        tv = tv.at[b, :, slot].set(jnp.where(can, new_val, old_val))
        old_st = st[b, slot]
        st = st.at[b, slot].set(jnp.where(can, (old_st & ~STATE_MASK) | READY, old_st))
        ok = ok.at[i].set(can)
        return tk, tv, st, ok

    ok0 = jnp.zeros((m,), bool)
    tkeys, tvals, status, ok = jax.lax.fori_loop(
        0, m, body, (tkeys, tvals, status, ok0))
    return tkeys, tvals, status, ok


def hash_probe_find_ref(tkeys, tvals, status, qblock, qkeys, qvalid):
    """Blocked find oracle: (found(M,), values(M, Lv))."""
    blk_keys = tkeys[qblock]                  # (M, Lk, B)
    blk_stat = status[qblock]                 # (M, B)
    match = (blk_keys == qkeys[:, :, None]).all(axis=1) & (bucket_state(blk_stat) == READY)
    found = match.any(axis=1) & qvalid
    slot = jnp.argmax(match, axis=1)
    vals = tvals[qblock, :, slot]             # (M, Lv)
    return found, jnp.where(found[:, None], vals, jnp.zeros_like(vals))


# --------------------------------------------------------------------------
# blocked Bloom filter
# --------------------------------------------------------------------------

def bloom_words_ref(hashes: jax.Array, k: int) -> jax.Array:
    """Expand (M, k) u32 hashes (each in [0,64)) into 64-bit block words
    represented as (M, 2) u32 [lo, hi]."""
    bits = hashes.astype(_U32)
    lo = jnp.where(bits < 32, _U32(1) << (bits % 32), _U32(0))
    hi = jnp.where(bits >= 32, _U32(1) << (bits % 32), _U32(0))
    word_lo = jnp.bitwise_or.reduce(lo, axis=1)
    word_hi = jnp.bitwise_or.reduce(hi, axis=1)
    return jnp.stack([word_lo, word_hi], axis=1)


def bloom_insert_ref(filter_words, qblock, qwords, qvalid):
    """Sequential-semantics blocked Bloom insert oracle.

    filter_words: (nblocks, 2) u32.  Returns (filter_words,
    already_present(M,)): item i is "already present" iff all of its bits
    were set before *its own* insertion (earlier batch items count —
    first-inserter-wins atomicity, paper section 5.4.2).
    """
    m = qblock.shape[0]

    def body(i, carry):
        fw, present = carry
        b = qblock[i]
        w = qwords[i]
        cur = fw[b]
        already = ((cur & w) == w).all() & qvalid[i]
        fw = fw.at[b].set(jnp.where(qvalid[i], cur | w, cur))
        present = present.at[i].set(already)
        return fw, present

    present0 = jnp.zeros((m,), bool)
    return jax.lax.fori_loop(0, m, body, (filter_words, present0))


def bloom_find_ref(filter_words, qblock, qwords, qvalid):
    cur = filter_words[qblock]                        # (M, 2)
    return ((cur & qwords) == qwords).all(axis=1) & qvalid


# --------------------------------------------------------------------------
# binning histogram (ISx)
# --------------------------------------------------------------------------

def bin_histogram_ref(bins: jax.Array, nbins: int, valid=None) -> jax.Array:
    """Per-bin counts; the oracle for the one-hot-matmul Pallas kernel."""
    w = jnp.ones_like(bins, dtype=jnp.int32) if valid is None else valid.astype(jnp.int32)
    return jnp.zeros((nbins,), jnp.int32).at[bins].add(w)


def bin_offsets_ref(bins: jax.Array, nbins: int, valid=None):
    """Sequential oracle for exchange send-buffer construction.

    Returns ``(counts (nbins,), offsets (N,))`` where ``offsets[i]`` is
    the number of *valid* items ``j < i`` with ``bins[j] == bins[i]`` —
    the stable position-within-destination each item claims in the
    per-destination send bucket.  Offsets of invalid items are
    unspecified (callers mask them).
    """
    n = bins.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    bins = bins.astype(jnp.int32)

    def body(i, carry):
        counts, offs = carry
        b = jnp.clip(bins[i], 0, nbins - 1)
        offs = offs.at[i].set(counts[b])
        counts = jnp.where(valid[i], counts.at[b].add(1), counts)
        return counts, offs

    counts0 = jnp.zeros((nbins,), jnp.int32)
    offs0 = jnp.zeros((n,), jnp.int32)
    return jax.lax.fori_loop(0, n, body, (counts0, offs0))


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0):
    """Plain softmax attention oracle.

    q: (B, Hq, Tq, D), k/v: (B, Hkv, Tk, D); GQA by head repetition.
    ``window`` > 0 limits attention to the last ``window`` keys (sliding).
    """
    bq, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    qi = jnp.arange(tq)[:, None] + (tk - tq)   # align to suffix (decode)
    ki = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= ki <= qi
    if window > 0:
        mask &= ki > qi - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
