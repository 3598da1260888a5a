"""Pallas TPU kernel: destination histogram for the exchange engine / ISx.

The distribution stage of ISx (paper section 9.1) bins every key to a
destination bucket.  On TPU the per-tile histogram is a one-hot
contraction — an (1, TM) x (TM, NB) matmul that runs on the MXU — with
partial histograms accumulated across grid steps in the output block
(all grid steps map to the same output tile; step 0 initializes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

_I32 = jnp.int32
_F32 = jnp.float32
_U32 = jnp.uint32
_BF16 = jnp.bfloat16
_LANES = 128


def _hist_kernel(bins_ref, valid_ref, out_ref, *, nbins: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(_I32)            # (TM,)
    valid = valid_ref[...].astype(_F32)          # (TM,)
    onehot = (bins[:, None] ==
              jax.lax.broadcasted_iota(_I32, (bins.shape[0], nbins), 1))
    # (1, TM) @ (TM, NB) on the MXU
    part = jnp.dot(valid[None, :], onehot.astype(_F32),
                   preferred_element_type=_F32)[0]
    out_ref[...] = out_ref[...] + part.astype(_I32)


def _offsets_kernel(bins_ref, valid_ref, counts_ref, off_ref, *, nbins: int):
    """Histogram -> per-tile prefix -> per-item slot offset.

    Grid steps run in order on TPU, so ``counts_ref`` (all steps map to
    the same output tile) doubles as the running cross-tile prefix: at
    step t it holds the per-bin counts of tiles [0, t), which is exactly
    the base offset every item of tile t adds to its within-tile rank.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    bins = bins_ref[...].astype(_I32)                      # (TM,)
    valid = valid_ref[...].astype(_I32)                    # (TM,)
    tm = bins.shape[0]
    onehot = ((bins[:, None] ==
               jax.lax.broadcasted_iota(_I32, (tm, nbins), 1))
              .astype(_I32) * valid[:, None])              # (TM, NB)
    # stable within-tile rank: exclusive cumsum down each bin column.
    # Mosaic has no cumsum, so each sub-block of SB rows takes its
    # prefix as a strictly-lower-triangular ones matmul on the MXU (0/1
    # operands are exact in bf16, counts exact in the f32 accumulator),
    # plus the running column count of the sub-blocks before it.
    sb = math.gcd(tm, 128)
    tri = (jax.lax.broadcasted_iota(_I32, (sb, sb), 0)
           > jax.lax.broadcasted_iota(_I32, (sb, sb), 1)).astype(_BF16)
    run = jnp.zeros((1, nbins), _I32)
    parts = []
    for s in range(tm // sb):
        blk = onehot[s * sb:(s + 1) * sb]
        parts.append(jnp.dot(tri, blk.astype(_BF16),
                             preferred_element_type=_F32).astype(_I32) + run)
        run = run + blk.sum(axis=0, keepdims=True)
    within = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    base = counts_ref[...]                                 # tiles [0, i)
    off_ref[...] = ((within + base[None, :]) * onehot).sum(axis=1)
    # fold this tile's histogram into the running counts on the MXU
    part = jnp.dot(jnp.ones((1, tm), _F32), onehot.astype(_F32),
                   preferred_element_type=_F32)[0]
    counts_ref[...] = base + part.astype(_I32)


def bin_offsets(bins: jax.Array, nbins: int, valid: jax.Array | None = None,
                tile: int = 2048):
    """Exchange send-buffer construction; oracle: ref.bin_offsets_ref.

    Returns ``(counts (nbins,), offsets (N,))`` — per-destination valid
    counts and each item's stable position within its destination bucket.
    Replaces the argsort+gather hot path: the caller scatters payload
    rows straight to ``dest * capacity + offsets``.  The ExchangePlan
    scheduler's segmented multi-flow slot assignment
    (``kernels/ops.py::multi_bin_offsets``) feeds this same kernel
    composite ``dest * nflows + flow`` bins, so one launch bins every
    flow of a fused round.
    """
    m = bins.shape[0]
    if valid is None:
        valid = jnp.ones((m,), bool)
    pad = (-m) % tile
    if pad:
        bins = jnp.pad(bins, (0, pad), constant_values=nbins)
        valid = jnp.pad(valid, (0, pad))
    mp = bins.shape[0]
    kern = functools.partial(_offsets_kernel, nbins=nbins)
    counts, offs = pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((nbins,), lambda i: (0,)),
                   pl.BlockSpec((tile,), lambda i: (i,))],
        out_shape=(jax.ShapeDtypeStruct((nbins,), _I32),
                   jax.ShapeDtypeStruct((mp,), _I32)),
    )(bins.astype(_I32), valid)
    return counts, offs[:m]


def _ragged_slots_kernel(woff_ref, roww_ref, caps_ref, rounds_ref,
                         bins_ref, flow_ref, off_ref, valid_ref,
                         slot_ref, *, nflows: int, rnd: int, wtot: int,
                         sentinel: int):
    """Per-item ragged word slot off the ONE binning pass.

    Items are a lane-dense (rows, 128) tile; the flow tables (word
    offset, row words, capacity, rounds) sit in SMEM and each item picks
    its flow's entries by a select per flow (nflows is tiny).  Then the
    retry-round window ``[rnd*C_f, (rnd+1)*C_f)`` masks which items ride
    this launch — the §1.6 mask and the §1.5 ragged layout fused into
    one elementwise pass, with no second binning.
    """
    bins = bins_ref[...]
    flow = flow_ref[...]
    off = off_ref[...]
    valid = valid_ref[...] != 0
    woff_i = roww_i = cap_i = rnds_i = jnp.zeros_like(bins)
    for f in range(nflows):
        at = flow == f
        woff_i = jnp.where(at, woff_ref[f], woff_i)
        roww_i = jnp.where(at, roww_ref[f], roww_i)
        cap_i = jnp.where(at, caps_ref[f], cap_i)
        rnds_i = jnp.where(at, rounds_ref[f], rnds_i)
    off_r = off - rnd * cap_i
    in_r = valid & (rnds_i > rnd) & (off_r >= 0) & (off_r < cap_i)
    slot_ref[...] = jnp.where(in_r, bins * wtot + woff_i + off_r * roww_i,
                              sentinel)


def ragged_slots(bins: jax.Array, flow: jax.Array, offsets: jax.Array,
                 valid: jax.Array, rnd: int, word_off: jax.Array,
                 row_words: jax.Array, caps: jax.Array, rounds: jax.Array,
                 wtot: int, sentinel: int, tile: int = 2048) -> jax.Array:
    """Ragged send-buffer word slots for retry round ``rnd``.

    Item ``i`` of flow ``f = flow[i]`` with within-(dest, flow)-bucket
    rank ``offsets[i]`` (from :func:`bin_offsets`) starts at word
    ``bins[i]*wtot + word_off[f] + (offsets[i] - rnd*caps[f]) *
    row_words[f]`` of the flat fused wire iff its rank falls in round
    ``rnd``'s capacity window and the flow is still retrying; every
    other item gets ``sentinel`` (a drop index past the buffer).
    Oracle: the pure-jnp gather in ``kernels/ops.py::ragged_slots``.
    """
    m = bins.shape[0]
    nflows = word_off.shape[0]
    pad = (-m) % tile
    rows = (m + pad) // _LANES

    def dense(x):
        return jnp.pad(x.astype(_I32), (0, pad)).reshape(rows, _LANES)

    kern = functools.partial(_ragged_slots_kernel, nflows=nflows,
                             rnd=rnd, wtot=wtot, sentinel=sentinel)
    table = pl.BlockSpec(memory_space=pltpu.SMEM)
    items = pl.BlockSpec((tile // _LANES, _LANES), lambda i: (i, 0))
    slots = pallas_call(
        kern,
        grid=(rows * _LANES // tile,),
        in_specs=[table] * 4 + [items] * 4,
        out_specs=items,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), _I32),
    )(word_off.astype(_I32), row_words.astype(_I32), caps.astype(_I32),
      rounds.astype(_I32), dense(bins), dense(flow), dense(offsets),
      dense(valid))
    return slots.reshape(-1)[:m]


def _pack_rows_kernel(rows_ref, bins_ref, flow_ref, off_ref, valid_ref,
                      woff_ref, roww_ref, caps_ref, rounds_ref,
                      out_ref, *, nflows: int, rnd: int, wtot: int,
                      total: int, wmax: int):
    """Slot computation + row scatter fused: one pass writes the wire.

    Same slot math as :func:`_ragged_slots_kernel`, but instead of
    emitting the slot vector for an XLA ``.at[].set`` to consume (one
    extra HBM round trip over the rows), each tile scatters its rows
    straight into the flat send buffer held in the output block.  All
    grid steps map the same (total,) block; step 0 zero-fills.  Lanes at
    or past a row's flow width, rows outside the round window, and
    sentinel rows all index past ``total`` and drop.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = bins_ref[...].astype(_I32)
    flow = flow_ref[...].astype(_I32)
    off = off_ref[...].astype(_I32)
    valid = valid_ref[...]
    tm = bins.shape[0]
    oh = (flow[:, None] ==
          jax.lax.broadcasted_iota(_I32, (tm, nflows), 1)).astype(_I32)

    def sel(tbl_ref):
        return (oh * tbl_ref[...][None, :]).sum(axis=1)

    woff_i, roww_i = sel(woff_ref), sel(roww_ref)
    cap_i, rnds_i = sel(caps_ref), sel(rounds_ref)
    off_r = off - rnd * cap_i
    in_r = valid & (rnds_i > rnd) & (off_r >= 0) & (off_r < cap_i)
    slot = jnp.where(in_r, bins * wtot + woff_i + off_r * roww_i, total)
    lane = jax.lax.broadcasted_iota(_I32, (tm, wmax), 1)
    idx = jnp.where((lane < roww_i[:, None]) & in_r[:, None],
                    slot[:, None] + lane, total)
    buf = out_ref[...]
    out_ref[...] = buf.at[idx.reshape(-1)].set(
        rows_ref[...].astype(_U32).reshape(-1), mode="drop")


def pack_rows(rows: jax.Array, bins: jax.Array, flow: jax.Array,
              offsets: jax.Array, valid: jax.Array, rnd: int,
              word_off: jax.Array, row_words: jax.Array, caps: jax.Array,
              rounds: jax.Array, wtot: int, total: int,
              tile: int = 2048) -> jax.Array:
    """Fused ragged wire pack: one kernel, one HBM write of the buffer.

    ``rows`` is the (N, wmax) right-padded u32 row matrix (flow ``f``
    uses its first ``row_words[f]`` lanes); the result is the flat
    ``(total,)`` u32 send buffer that :func:`ragged_slots` +
    ``object_container.scatter_rows`` would produce in two passes.
    Oracle: the jnp path of ``kernels/ops.py::pack_rows``.
    """
    m, wmax = rows.shape
    nflows = word_off.shape[0]
    pad = (-m) % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        bins = jnp.pad(bins, (0, pad))
        flow = jnp.pad(flow, (0, pad))
        offsets = jnp.pad(offsets, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    mp = bins.shape[0]
    kern = functools.partial(_pack_rows_kernel, nflows=nflows, rnd=rnd,
                             wtot=wtot, total=total, wmax=wmax)
    full = lambda i: (0,)
    return pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((tile, wmax), lambda i: (i, 0)),
                  pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((nflows,), full),
                  pl.BlockSpec((nflows,), full),
                  pl.BlockSpec((nflows,), full),
                  pl.BlockSpec((nflows,), full)],
        out_specs=pl.BlockSpec((total,), full),
        out_shape=jax.ShapeDtypeStruct((total,), _U32),
    )(rows.astype(_U32), bins.astype(_I32), flow.astype(_I32),
      offsets.astype(_I32), valid, word_off.astype(_I32),
      row_words.astype(_I32), caps.astype(_I32), rounds.astype(_I32))


def _place_rows_kernel(dst_ref, slot_ref, rows_ref, out_ref, *,
                       total: int, w: int):
    """Scatter fixed-width rows at precomputed word slots, in-kernel.

    The output block starts as a copy of ``dst`` (step 0) and each tile
    folds its rows in; a slot at or past ``total`` drops its row.  Used
    where the wire slots are analytic (dense replies, owner-side
    assembly by hop/slot lane) so even those writes stay off XLA's
    scatter path.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = dst_ref[...]

    slot = slot_ref[...].astype(_I32)
    tm = slot.shape[0]
    lane = jax.lax.broadcasted_iota(_I32, (tm, w), 1)
    idx = jnp.where(slot[:, None] < total, slot[:, None] + lane, total)
    buf = out_ref[...]
    out_ref[...] = buf.at[idx.reshape(-1)].set(
        rows_ref[...].astype(_U32).reshape(-1), mode="drop")


def place_rows(dst: jax.Array, slots: jax.Array, rows: jax.Array,
               tile: int = 2048) -> jax.Array:
    """In-kernel ``scatter_rows``: pack (N, W) rows into ``dst`` words.

    Bit-identical to ``object_container.scatter_rows(dst, slots, rows)``
    (the jnp oracle) including the drop-on-sentinel contract; rows whose
    slot is ``>= dst.size`` are dropped whole.
    """
    m, w = rows.shape
    total = dst.shape[0]
    pad = (-m) % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        slots = jnp.pad(slots, (0, pad), constant_values=total)
    mp = slots.shape[0]
    kern = functools.partial(_place_rows_kernel, total=total, w=w)
    full = lambda i: (0,)
    return pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((total,), full),
                  pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((total,), full),
        out_shape=jax.ShapeDtypeStruct((total,), _U32),
    )(dst.astype(_U32), slots.astype(_I32), rows.astype(_U32))


def _row_mix_kernel(rows_ref, out_ref, *, lanes: int):
    """Per-row wire-checksum hash: weighted lane sum + fmix32 avalanche.

    The tile is lane-major, (L, TM): rows run along the vector lanes, so
    a narrow row matrix is not padded out to 128 lanes.  All arithmetic
    wraps mod 2^32 so the kernel is bit-identical to the jnp lowering in
    ``kernels/ops.py::mix_rows`` (the sender and owner sides of an
    integrity-checked exchange must agree exactly); Mosaic reduces only
    signed integers, so the lane sum runs on the i32 bit pattern, which
    wraps identically.
    """
    rows = rows_ref[...].astype(_U32)                    # (L, TM)
    mult = (_U32(0x9E3779B1)
            * (jax.lax.broadcasted_iota(_U32, (lanes, 1), 0) * _U32(2)
               + _U32(1)))
    h = jax.lax.bitcast_convert_type(
        jnp.sum(jax.lax.bitcast_convert_type(rows * mult, _I32), axis=0,
                keepdims=True), _U32)                    # (1, TM)
    h = h ^ (h >> 16)
    h = h * _U32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _U32(0xC2B2AE35)
    h = h ^ (h >> 16)
    out_ref[...] = h


def row_mix(rows: jax.Array, tile: int = 2048) -> jax.Array:
    """Per-row u32 hash of a lane matrix; oracle: ops.mix_rows jnp path."""
    m, lanes = rows.shape
    pad = (-m) % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    mp = rows.shape[0]
    kern = functools.partial(_row_mix_kernel, lanes=lanes)
    out = pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((lanes, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), _U32),
    )(rows.astype(_U32).T)
    return out[0, :m]


def histogram(bins: jax.Array, nbins: int, valid: jax.Array | None = None,
              tile: int = 2048) -> jax.Array:
    """Count items per destination bin; oracle: ref.bin_histogram_ref."""
    m = bins.shape[0]
    if valid is None:
        valid = jnp.ones((m,), bool)
    pad = (-m) % tile
    if pad:
        bins = jnp.pad(bins, (0, pad))
        valid = jnp.pad(valid, (0, pad))
    mp = bins.shape[0]
    kern = functools.partial(_hist_kernel, nbins=nbins)
    return pallas_call(
        kern,
        grid=(mp // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((nbins,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((nbins,), _I32),
    )(bins.astype(_I32), valid)
