"""Public kernel API with implementation dispatch.

Every op comes in up to three implementations:

  impl="oracle"  sequential-semantics pure-jnp oracle (ref.py)
  impl="jnp"     vectorized pure-jnp (sort + segment ops) — the CPU
                 production path and the second correctness witness
  impl="pallas"  the Pallas TPU kernel (interpreted off the TPU)

``impl="auto"`` picks "pallas" on TPU and "jnp" elsewhere.  Containers
call through this module only.

One declared per-platform exception (DESIGN.md section 1.10): the
one-pass wire kernels ``binning.pack_rows``/``place_rows`` hold the whole
(total,) wire as one VMEM block and scatter into it, and Mosaic has no
scatter, so on TPU :data:`XLA_WIRE_OPS` lower "pallas" to the
``ragged_slots`` kernel plus XLA's scatter (``scatter_rows``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import kernels as _kernels
from repro.kernels import ref as _ref
from repro.kernels.ref import (FREE, READY, STATE_MASK, bucket_state,  # noqa: F401
                               MODE_SET, MODE_ADD, MODE_KEEP)

_U32 = jnp.uint32
_I32 = jnp.int32


#: ops whose "pallas" impl lowers to ragged_slots + XLA scatter on TPU
XLA_WIRE_OPS = ("pack_rows", "place_rows")


def default_impl() -> str:
    return "pallas" if _kernels.platform() == "tpu" else "jnp"


def _resolve(impl: str) -> str:
    return default_impl() if impl == "auto" else impl


def _wire_kernel(impl: str) -> bool:
    """Whether a wire op in :data:`XLA_WIRE_OPS` runs its one-pass kernel."""
    return impl == "pallas" and _kernels.platform() != "tpu"


def lowering(op: str, impl: str = "auto") -> str:
    """What ``op`` runs as on this platform for ``impl`` (after "auto"),
    as ``chip_smoke.py`` prints it; DESIGN.md section 1.10 lists it."""
    impl = _resolve(impl)
    if op in XLA_WIRE_OPS and impl == "pallas" and not _wire_kernel(impl):
        return {"pack_rows": "pallas ragged_slots + xla scatter",
                "place_rows": "xla scatter"}[op]
    if op == "bloom_find":
        return "jnp"
    if op == "bloom_insert" and impl == "pallas":
        return "pallas membership + xla or-scatter"
    return impl


# --------------------------------------------------------------------------
# segmented scan helpers
# --------------------------------------------------------------------------

def seg_exclusive_or_scan(words: jax.Array, seg_start: jax.Array) -> jax.Array:
    """Exclusive segmented bitwise-OR scan over rows (segments contiguous).

    words: (M, L) u32; seg_start: (M,) bool marking segment heads.
    Row i receives the OR of earlier rows in its segment (0 at heads).

    Doubling scan (Hillis-Steele): after the step of stride s, row i holds
    the OR over rows (i - 2s, i] of its segment, and ``head`` whether that
    window reaches a segment head.  log2(M) shifted elementwise passes;
    unlike ``lax.associative_scan`` it compiles quickly on TPU at large M.
    """
    m = words.shape[0]
    acc, head = words, seg_start
    s = 1
    while s < m:
        prev = jnp.concatenate([jnp.zeros_like(acc[:s]), acc[:-s]], axis=0)
        prev_head = jnp.concatenate([jnp.zeros_like(head[:s]), head[:-s]])
        acc = jnp.where(head[:, None], acc, acc | prev)
        head = head | prev_head
        s *= 2
    # exclusive = inclusive shifted down by one, zeroed at segment heads
    shifted = jnp.concatenate([jnp.zeros_like(words[:1]), acc[:-1]], axis=0)
    return jnp.where(seg_start[:, None], jnp.zeros_like(words), shifted)


def _lexsort_items(qblock, qkeys, qvalid, nb):
    """Stable order grouping items by (block, key lanes); invalid last."""
    b = jnp.where(qvalid, qblock.astype(_I32), nb)
    keys = [qkeys[:, i] for i in range(qkeys.shape[1] - 1, -1, -1)] + [b]
    order = jnp.lexsort(keys)
    return order, b[order]


# --------------------------------------------------------------------------
# blocked hash table: bulk insert
# --------------------------------------------------------------------------

def bulk_insert(tkeys, tvals, status, qblock, qkeys, qvals, qvalid,
                mode: int = MODE_SET, impl: str = "auto"):
    """Insert a batch into the blocked table; see ref.hash_probe_insert_ref.

    Vectorized semantics match the sequential oracle for any batch,
    including duplicate keys (SET keeps the last duplicate's value, ADD
    accumulates, KEEP keeps the first).
    Returns (tkeys, tvals, status, success(M,)).
    """
    impl = _resolve(impl)
    if impl == "oracle":
        return _ref.hash_probe_insert_ref(tkeys, tvals, status, qblock,
                                          qkeys, qvals, qvalid, mode)
    if impl == "pallas":
        from repro.kernels import hash_probe
        return hash_probe.insert(tkeys, tvals, status, qblock, qkeys,
                                 qvals, qvalid, mode)

    nb, lk, bsz = tkeys.shape
    m = qblock.shape[0]
    lv = qvals.shape[1]

    order, sb = _lexsort_items(qblock, qkeys, qvalid, nb)
    sk = qkeys[order]
    sv = qvals[order]
    svalid = qvalid[order]
    idx = jnp.arange(m, dtype=_I32)

    prev_same = jnp.concatenate([
        jnp.zeros((1,), bool),
        (sb[1:] == sb[:-1]) & (sk[1:] == sk[:-1]).all(axis=1)])
    is_leader = svalid & ~prev_same
    group_id = jnp.cumsum(is_leader.astype(_I32)) - 1          # (M,)
    group_id = jnp.maximum(group_id, 0)

    # combine duplicate values per group, honoring batch order
    if mode == MODE_ADD:
        gval = jnp.zeros((m, lv), _U32).at[group_id].add(
            jnp.where(svalid[:, None], sv, 0))
    elif mode == MODE_SET:   # last duplicate wins
        last_pos = jnp.full((m,), -1, _I32).at[group_id].max(
            jnp.where(svalid, idx, -1))
        gval = sv[jnp.maximum(last_pos, 0)]
    else:                     # MODE_KEEP: first duplicate (== leader row)
        gval = jnp.zeros((m, lv), _U32).at[group_id].add(
            jnp.where((is_leader & svalid)[:, None], sv, 0))
    leader_val = gval[group_id]   # value each leader should write

    # probe each leader's block
    blk_keys = tkeys[sb % nb]                                   # (M, Lk, B)
    blk_stat = status[sb % nb]                                  # (M, B)
    match = (blk_keys == sk[:, :, None]).all(axis=1) & (bucket_state(blk_stat) == READY)
    found = match.any(axis=1) & is_leader
    mslot = jnp.argmax(match, axis=1).astype(_I32)

    # free-slot ranking per block
    free_mask = bucket_state(status) == FREE                                  # (nb, B)
    free_order = jnp.argsort(~free_mask, axis=1).astype(_I32)   # free first
    nfree = free_mask.sum(axis=1).astype(_I32)                  # (nb,)

    new_leader = is_leader & ~found
    # Rank each new leader within its block by ORIGINAL batch position, so
    # free slots are claimed in the same order the sequential oracle claims
    # them (this fixes which items fail when a block overflows).
    orig_idx = order.astype(_I32)
    ord2 = jnp.lexsort((jnp.where(new_leader, orig_idx, m),
                        jnp.where(new_leader, sb, nb)))
    nl2 = new_leader[ord2]
    sb2 = jnp.where(nl2, sb[ord2], nb)
    blk_change2 = jnp.concatenate([jnp.ones((1,), bool), sb2[1:] != sb2[:-1]])
    seg2 = jnp.cumsum(blk_change2.astype(_I32)) - 1
    incl2 = jnp.cumsum(nl2.astype(_I32))
    ex2 = incl2 - nl2.astype(_I32)
    base2 = jnp.zeros((m,), _I32).at[seg2].add(jnp.where(blk_change2, ex2, 0))
    r2 = ex2 - base2[seg2]
    r = jnp.zeros((m,), _I32).at[ord2].set(r2)                  # (M,)

    sb_c = jnp.clip(sb, 0, nb - 1)
    has_room = r < nfree[sb_c]
    slot_new = free_order[sb_c, jnp.clip(r, 0, bsz - 1)]
    slot = jnp.where(found, mslot, slot_new)
    ok_leader = is_leader & (found | (new_leader & has_room))

    # value to store
    old_val = tvals[sb_c, :, slot]                              # (M, Lv)
    if mode == MODE_ADD:
        store_val = jnp.where(found[:, None], old_val + leader_val, leader_val)
    elif mode == MODE_KEEP:
        store_val = jnp.where(found[:, None], old_val, leader_val)
    else:
        store_val = leader_val

    wb = jnp.where(ok_leader, sb_c, nb)    # drop sentinel
    tkeys = tkeys.at[wb, :, slot].set(sk, mode="drop")
    tvals = tvals.at[wb, :, slot].set(store_val, mode="drop")
    old_st = status[sb_c, slot]
    status = status.at[wb, slot].set((old_st & ~STATE_MASK) | READY,
                                     mode="drop")

    # per-item success = its group leader's success
    succ_g = jnp.zeros((m,), _I32).at[group_id].add(
        (ok_leader & is_leader).astype(_I32))
    succ_sorted = (succ_g[group_id] > 0) & svalid
    success = jnp.zeros((m,), bool).at[order].set(succ_sorted)
    return tkeys, tvals, status, success


def bulk_find(tkeys, tvals, status, qblock, qkeys, qvalid, impl: str = "auto"):
    """Batch find; returns (found(M,), values(M,Lv))."""
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import hash_probe
        return hash_probe.find(tkeys, tvals, status, qblock, qkeys, qvalid)
    return _ref.hash_probe_find_ref(tkeys, tvals, status, qblock, qkeys, qvalid)


def bulk_find_arrivals(tkeys, tvals, status, seg, valid, impl: str = "auto"):
    """Batch find off the contiguous (M, 1+Lk) arrival segment.

    ``seg`` is an exchange owner view — local block in lane 0, key lanes
    after — consumed as-is (DESIGN.md section 1.10): the Pallas path
    bins the combined segment with ONE scatter and splits columns
    in-kernel, so no intermediate lane matrices cross HBM.  The jnp and
    oracle paths slice the columns and run :func:`bulk_find` — the
    fallback/oracle, bit-identical by construction.
    """
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import hash_probe
        return hash_probe.find_arrivals(tkeys, tvals, status, seg, valid)
    lk = tkeys.shape[1]
    qblock = jnp.where(valid, seg[:, 0].astype(_I32), 0)
    return bulk_find(tkeys, tvals, status, qblock, seg[:, 1:1 + lk], valid,
                     impl=impl)


def bulk_insert_arrivals(tkeys, tvals, status, seg, valid,
                         mode: int = MODE_SET, impl: str = "auto"):
    """Batch insert off the contiguous (M, 1+Lk+Lv) arrival segment.

    Arrival-buffer twin of :func:`bulk_insert` (see
    :func:`bulk_find_arrivals` for the layout and the HBM argument).
    Returns (tkeys, tvals, status, success(M,)).
    """
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import hash_probe
        return hash_probe.insert_arrivals(tkeys, tvals, status, seg, valid,
                                          mode)
    lk = tkeys.shape[1]
    qblock = jnp.where(valid, seg[:, 0].astype(_I32), 0)
    return bulk_insert(tkeys, tvals, status, qblock, seg[:, 1:1 + lk],
                       seg[:, 1 + lk:], valid, mode, impl=impl)


# --------------------------------------------------------------------------
# blocked Bloom filter
# --------------------------------------------------------------------------

def bloom_insert(filter_words, qblock, qwords, qvalid, impl: str = "auto"):
    """Batch blocked-Bloom insert with first-inserter-wins atomicity.

    Returns (filter_words, already_present(M,)).
    """
    impl = _resolve(impl)
    if impl == "oracle":
        return _ref.bloom_insert_ref(filter_words, qblock, qwords, qvalid)

    nb = filter_words.shape[0]
    m = qblock.shape[0]
    b = jnp.where(qvalid, qblock.astype(_I32), nb)
    order = jnp.argsort(b, stable=True)
    sb = b[order]
    sw = qwords[order]
    svalid = qvalid[order]

    seg_start = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
    ex_or = seg_exclusive_or_scan(jnp.where(svalid[:, None], sw, 0), seg_start)

    sb_c = jnp.clip(sb, 0, nb - 1)
    prior = filter_words[sb_c] | ex_or
    already = ((prior & sw) == sw).all(axis=1) & svalid

    # inclusive OR per segment lands on the segment's last row
    incl_or = ex_or | jnp.where(svalid[:, None], sw, 0)
    is_last = jnp.concatenate([sb[1:] != sb[:-1], jnp.ones((1,), bool)])
    wb = jnp.where(is_last & (sb < nb), sb_c, nb)
    new_words = filter_words[sb_c] | incl_or
    if impl == "pallas":
        from repro.kernels import bloom_kernel
        already = bloom_kernel.membership(prior, sw, svalid)
    filter_words = filter_words.at[wb].set(new_words, mode="drop")

    out = jnp.zeros((m,), bool).at[order].set(already)
    return filter_words, out


def bloom_find(filter_words, qblock, qwords, qvalid, impl: str = "auto"):
    return _ref.bloom_find_ref(filter_words, qblock, qwords, qvalid)


# --------------------------------------------------------------------------
# binning histogram + exchange send-buffer construction
# --------------------------------------------------------------------------

def bin_histogram(bins, nbins: int, valid=None, impl: str = "auto"):
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import binning
        return binning.histogram(bins, nbins, valid)
    return _ref.bin_histogram_ref(bins, nbins, valid)


def bin_offsets(bins, nbins: int, valid=None, impl: str = "auto"):
    """Per-destination counts + stable within-destination offsets.

    The exchange engine's send-buffer construction: item i's slot is
    ``bins[i] * capacity + offsets[i]``.  Returns ``(counts (nbins,),
    offsets (N,))``; offsets of invalid items are unspecified.
    """
    impl = _resolve(impl)
    if impl == "oracle":
        return _ref.bin_offsets_ref(bins, nbins, valid)
    if impl == "pallas":
        from repro.kernels import binning
        return binning.bin_offsets(bins, nbins, valid)

    # vectorized jnp path: one stable argsort, offsets scattered back
    n = bins.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    b = jnp.where(valid, bins.astype(_I32), nbins)   # invalid -> bucket NB
    counts_full = jnp.zeros((nbins + 1,), _I32).at[b].add(1)
    start = jnp.concatenate([jnp.zeros((1,), _I32),
                             jnp.cumsum(counts_full)[:-1].astype(_I32)])
    order = jnp.argsort(b, stable=True)
    pos_sorted = jnp.arange(n, dtype=_I32) - start[b[order]]
    offsets = jnp.zeros((n,), _I32).at[order].set(pos_sorted)
    return counts_full[:nbins], offsets


def multi_bin_offsets(bins, flow, nbins: int, nflows: int, valid=None,
                      impl: str = "auto"):
    """Segmented multi-flow slot assignment (the ExchangePlan hot path).

    One binning pass over the concatenation of all flows of a plan:
    items are ranked within their composite ``(dest, flow)`` bucket
    (destination-major) so the fused send buffer can place flow ``f``'s
    items for destination ``d`` at
    ``d * sum(caps) + flow_offset[f] + offsets``.  Returns
    ``(counts (nbins, nflows), offsets (N,))``; per-flow capacity
    masking is the caller's (drops are ``offsets >= cap[flow]``).

    Lowers to ONE :func:`bin_offsets` pass over the composite key
    ``dest * nflows + flow`` (destination-major), so every impl —
    oracle, jnp, and the Pallas kernel — serves multi-flow plans
    through its existing single-key path.
    """
    comp = bins.astype(_I32) * nflows + flow.astype(_I32)
    counts, offs = bin_offsets(comp, nbins * nflows, valid, impl=impl)
    return counts.reshape(nbins, nflows), offs


def ragged_slots(bins, flow, offsets, valid, rnd: int, word_off, row_words,
                 caps, rounds, wtot: int, sentinel: int, impl: str = "auto"):
    """Ragged fused-wire word slots for retry round ``rnd``.

    The ExchangePlan send buffer is a flat u32 word vector per
    destination (DESIGN.md section 1.5): flow ``f``'s segment starts at
    ``word_off[f]`` and its rows are exactly ``row_words[f] = L_f + 1``
    words wide — no cross-flow padding.  This op turns the ONE
    :func:`multi_bin_offsets` pass's within-bucket ranks into per-item
    word slots for one launch: item i starts at ``bins[i]*wtot +
    word_off[flow[i]] + (offsets[i] - rnd*caps[flow[i]]) *
    row_words[flow[i]]`` iff its rank falls in round ``rnd``'s capacity
    window ``[rnd*C_f, (rnd+1)*C_f)`` and ``rounds[flow[i]] > rnd``;
    all other items get ``sentinel`` (an index past the buffer, dropped
    by the scatter).  Retry rounds therefore reuse the same offsets
    with a different ``rnd`` — never a second binning pass.
    """
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import binning
        return binning.ragged_slots(bins, flow, offsets, valid, rnd,
                                    word_off, row_words, caps, rounds,
                                    wtot, sentinel)
    f = flow.astype(_I32)
    off_r = offsets.astype(_I32) - rnd * caps[f]
    in_r = valid & (rounds[f] > rnd) & (off_r >= 0) & (off_r < caps[f])
    return jnp.where(in_r,
                     bins.astype(_I32) * wtot + word_off[f]
                     + off_r * row_words[f],
                     sentinel).astype(_I32)


def stage_slots(bins, flow, offsets, valid, word_off, row_words, caps,
                live, wtot: int, sentinel: int, impl: str = "auto"):
    """Per-stage ragged word slots for a transport hop (DESIGN.md §1.7).

    The hierarchical transport re-bins items per hop — by destination
    *column* at the source, by destination *row* at the relay — and
    packs each hop's wire with the same ragged offset-table math as the
    fused plan wire.  This is :func:`ragged_slots` with no retry-round
    window: item i of flow ``f`` gets word ``bins[i]*wtot + word_off[f]
    + offsets[i]*row_words[f]`` iff it is valid, its stage rank is
    below the stage capacity ``caps[f]``, and ``live[f]`` marks the
    flow as riding this hop; everything else gets ``sentinel``.  Both
    the jnp path and the Pallas kernel are the existing ``ragged_slots``
    lowerings (round 0, per-flow "rounds" = the live mask), so the hop
    adds zero new kernel surface and still no argsort.
    """
    return ragged_slots(bins, flow, offsets, valid, 0, word_off, row_words,
                        caps, live, wtot, sentinel, impl=impl)


def pack_rows(rows, bins, flow, offsets, valid, rnd: int, word_off,
              row_words, caps, rounds, wtot: int, total: int,
              impl: str = "auto"):
    """Fused ragged wire pack: slots + row scatter in one pass.

    ``rows`` is the (N, wmax) right-padded u32 row matrix over all flows
    in item order (flow ``f`` uses lanes ``[0, row_words[f])``); returns
    the flat ``(total,)`` u32 send buffer for retry round ``rnd``.  The
    jnp path is the declared fallback/oracle — :func:`ragged_slots`
    followed by ``object_container.scatter_rows`` (the two-pass XLA
    lowering, DESIGN.md section 1.10); off the TPU the Pallas path
    writes the wire exactly once (``kernels/binning.pack_rows``), and on
    TPU it is the ``ragged_slots`` kernel plus that XLA scatter.
    """
    impl = _resolve(impl)
    if _wire_kernel(impl):
        from repro.kernels import binning
        return binning.pack_rows(rows, bins, flow, offsets, valid, rnd,
                                 word_off, row_words, caps, rounds,
                                 wtot, total)
    from repro.core.object_container import scatter_rows
    slots = ragged_slots(bins, flow, offsets, valid, rnd, word_off,
                         row_words, caps, rounds, wtot, total, impl=impl)
    return scatter_rows(jnp.zeros((total,), _U32), slots, rows,
                        widths=row_words[flow.astype(_I32)])


def place_rows(dst, slots, rows, impl: str = "auto"):
    """Scatter fixed-width (N, W) rows into ``dst`` at word ``slots``.

    Rows with ``slots[i] >= dst.size`` drop.  jnp path is
    ``object_container.scatter_rows`` (the fallback/oracle); off the
    TPU the Pallas path folds the scatter into one kernel pass, and on
    TPU it is that XLA scatter (:data:`XLA_WIRE_OPS`).
    """
    if _wire_kernel(_resolve(impl)):
        from repro.kernels import binning
        return binning.place_rows(dst, slots, rows)
    from repro.core.object_container import scatter_rows
    return scatter_rows(dst, slots, rows)


# --------------------------------------------------------------------------
# wire integrity: per-row mixing hash
# --------------------------------------------------------------------------

def mix_rows(rows: jax.Array, impl: str = "auto") -> jax.Array:
    """Per-row u32 mixing hash of a lane matrix (wire checksums).

    ``rows`` is (N, L) u32 (the exchange wire's payload + meta lanes);
    returns (N,) u32.  Lane ``l`` is weighted by the odd multiplier
    ``0x9E3779B1 * (2l + 1)`` (mod 2^32), the weighted sum is finished
    with the murmur3 fmix32 avalanche — all in wrapping u32 arithmetic,
    bit-identical across impls and platforms so sender and owner sides
    of an integrity-checked exchange (DESIGN.md section 1.8) agree.
    An all-zero row hashes to 0 (fmix32(0) == 0), so summing hashes
    over a wire window skips empty slots for free.
    """
    impl = _resolve(impl)
    if rows.ndim == 1:
        rows = rows[:, None]
    if impl == "pallas":
        from repro.kernels import binning
        return binning.row_mix(rows)
    rows = rows.astype(_U32)
    lanes = rows.shape[1]
    mult = (_U32(0x9E3779B1)
            * (jnp.arange(lanes, dtype=_U32) * _U32(2) + _U32(1)))
    h = jnp.sum(rows * mult[None, :], axis=1, dtype=_U32)
    h = h ^ (h >> 16)
    h = h * _U32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _U32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    impl = _resolve(impl)
    if impl == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention(q, k, v, causal=causal, window=window)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
