"""Pallas TPU kernel: blocked open-addressing hash probe (insert + find).

TPU adaptation of the paper's hash bucket probing (DESIGN.md section 2).
The table is an array of blocks of B buckets, stored lane-major: a
block's key lane ``l`` is one B-wide vector row (``tkeys[b, l, :]``), so
a query compares against all B slots of its block in one vector op and
the table's HBM layout is dense (B = 128 fills the vector lanes).
Queries are pre-binned per block on the host side (the same machinery as
the exchange engine), so the kernel's addressing is entirely tile-local:

  grid         (nb / TB,)                    one step per tile of blocks
  table tile   (TB, Lk, B) / (TB, Lv, B)     keys / values
  status tile  (TB, B)
  query tile   (TB, Lq * Q)                  binned queries: lane l of
                                             query j in column l*Q + j

Both ops iterate the Q binned queries of each block in order (for
insert, the deterministic arrival order — the ownership-serialized
analogue of the paper's CAS loop) while staying vectorized across the
TB blocks of the tile and the B slots of each block.  Mosaic has no
scatter, gather, cumsum or dynamic slice of a loaded value, so every
step is selects and lane reductions: column ``c`` of the query tile is a
one-hot select plus lane sum, the first matching (or free) slot is the
minimum of a masked lane iota, and a slot's value is a one-hot select
plus lane sum.  Mosaic reduces only signed integers; the sums run on the
i32 bit pattern of u32 lanes, exact because exactly one term is nonzero.

VMEM per tile at TB=64, B=128, Lk=Lv=2 (u32, second-minor padded to 8):
2 * 64*8*128*4 B (keys + values) + 64*128*4 B (status) ~= 544 KiB per
buffer, well inside the scoped VMEM limit with in/out double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import costs
from repro.kernels import pallas_call
from repro.kernels.ref import MODE_SET, MODE_ADD, hash_probe_find_ref

# kernel-local constants (plain ints: Pallas kernels cannot capture arrays)
_FREE, _READY, _MASK = 0, 2, 3

_U32 = jnp.uint32
_I32 = jnp.int32


# --------------------------------------------------------------------------
# binning: group queries per local block (host side, shared by both ops)
# --------------------------------------------------------------------------

def bin_queries(qblock, qvalid, nb: int, q_cap: int):
    """Compute per-block slots for each query.

    Returns (bin_slot(M,) flat index into (nb, q_cap), overflow(M,) bool).
    Stable order within a block == original batch order.
    """
    with costs.scope("probe.bin"):
        m = qblock.shape[0]
        b = jnp.where(qvalid, qblock.astype(_I32), nb)
        order = jnp.argsort(b, stable=True)
        sortb = b[order]
        # rank within the block = sorted position - the block's first sorted
        # position (a binary search, not a prefix sum over all nb blocks)
        first = jnp.searchsorted(sortb, sortb, side="left").astype(_I32)
        pos = jnp.arange(m, dtype=_I32) - first
        pos_orig = jnp.zeros((m,), _I32).at[order].set(pos)
        overflow = qvalid & (pos_orig >= q_cap)
        ok = qvalid & ~overflow
        slot = jnp.where(ok, qblock.astype(_I32) * q_cap + pos_orig,
                         nb * q_cap)
        return slot, overflow


def _bin_rows(rows, slot, nb: int, q_cap: int):
    """Scatter (M, L) u32 rows into the (nb, L*q_cap) query tiles.

    Lane ``l`` of the query in bin slot ``b*q_cap + j`` lands in row
    ``b``, column ``l*q_cap + j``; rows with the drop slot vanish.
    """
    with costs.scope("probe.bin"):
        lanes = rows.shape[1]
        width = lanes * q_cap
        blk, pos = slot // q_cap, slot % q_cap
        # (L, M) lane-major indices: no (M, L) index array padded on TPU
        col = jnp.arange(lanes, dtype=_I32)[:, None] * q_cap + pos[None, :]
        idx = jnp.where((slot < nb * q_cap)[None, :],
                        blk[None, :] * width + col, nb * width)
        out = jnp.zeros((nb * width,), _U32).at[idx.reshape(-1)].set(
            rows.astype(_U32).T.reshape(-1), mode="drop")
        return out.reshape(nb, width)


def default_q_cap(m: int, nb: int) -> int:
    """Static per-block query capacity; generous for skewed batches."""
    avg = -(-m // max(nb, 1))
    return int(min(m, max(16, 8 * avg)))


def _tile_blocks(nb: int) -> int:
    """Blocks per grid step: a multiple of 8 (the sublane tiling of the
    status and query tiles) that divides ``nb``, else the whole table."""
    for tb in (64, 32, 16, 8):
        if nb % tb == 0:
            return tb
    return nb


# --------------------------------------------------------------------------
# in-kernel helpers
# --------------------------------------------------------------------------

def _as_i32(x):
    return jax.lax.bitcast_convert_type(x, _I32)


def _as_u32(x):
    return jax.lax.bitcast_convert_type(x, _U32)


def _lane_pick(x_i32, mask):
    """(T, 1) sum of the masked lanes of an i32 tile (one lane set)."""
    return jnp.sum(jnp.where(mask, x_i32, 0), axis=1, keepdims=True)


def _first_lane(mask, lane, width: int):
    """(T, 1) index of each row's first set lane; ``width`` when none."""
    return jnp.min(jnp.where(mask, lane, width), axis=1, keepdims=True)


# --------------------------------------------------------------------------
# insert kernel
# --------------------------------------------------------------------------

def _insert_kernel(tk_ref, tv_ref, st_ref, q_ref,
                   otk_ref, otv_ref, ost_ref, ok_ref, *, mode: int,
                   q_cap: int, lk: int, lv: int):
    """Ownership-serialized insert of one tile's binned queries.

    Query columns: key lanes, value lanes, validity (``lk + lv + 1``
    lanes of ``q_cap`` columns each).  The tile is read once; the query
    loop carries only what it wrote — a mask of written slots and their
    key and value lanes, all starting at zero — and the tile is stored
    once.  (A carry that starts as loaded data would be typed apart from
    the loop body under ``check_vma`` when the kernel is interpreted.)
    """
    tk0 = [tk_ref[:, l, :] for l in range(lk)]
    tv0 = [tv_ref[:, l, :] for l in range(lv)]
    st0 = st_ref[...]
    ready_st = (st0 & _U32(~_MASK & 0xFFFFFFFF)) | _U32(_READY)
    q = _as_i32(q_ref[...])                       # (TB, Lq*Q)
    tb, bsz = st0.shape
    qlane = jax.lax.broadcasted_iota(_I32, q.shape, 1)
    slot_lane = jax.lax.broadcasted_iota(_I32, (tb, bsz), 1)
    ok_lane = jax.lax.broadcasted_iota(_I32, (tb, q_cap), 1)

    def column(c):
        return _lane_pick(q, qlane == c)          # (TB, 1) i32

    def body(j, carry):
        wrote, wtk, wtv, ok = carry
        written = wrote == 1
        tks = [jnp.where(written, wtk[l], tk0[l]) for l in range(lk)]
        tvs = [jnp.where(written, wtv[l], tv0[l]) for l in range(lv)]
        key = [_as_u32(column(l * q_cap + j)) for l in range(lk)]
        val = [_as_u32(column((lk + l) * q_cap + j)) for l in range(lv)]
        vld = column((lk + lv) * q_cap + j) == 1
        state = jnp.where(written, ready_st, st0) & _U32(_MASK)
        match = state == _U32(_READY)
        for l in range(lk):
            match = match & (tks[l] == key[l])
        mslot = _first_lane(match, slot_lane, bsz)
        fslot = _first_lane(state == _U32(_FREE), slot_lane, bsz)
        has_match = mslot < bsz
        slot = jnp.where(has_match, mslot, fslot)
        can = vld & (slot < bsz)
        at = slot_lane == slot                    # (TB, B)
        put = at & can
        new_tv = []
        for l in range(lv):
            if mode == MODE_SET:
                new = val[l]
            else:
                old = _as_u32(_lane_pick(_as_i32(tvs[l]), at))
                new = jnp.where(has_match,
                                old + val[l] if mode == MODE_ADD else old,
                                val[l])
            new_tv.append(jnp.where(put, new, tvs[l]))
        return (jnp.where(put, 1, wrote),
                [jnp.where(put, key[l], tks[l]) for l in range(lk)], new_tv,
                jnp.where(ok_lane == j, can.astype(_I32), ok))

    zeros = jnp.zeros((tb, bsz), _U32)
    wrote, wtk, wtv, ok = jax.lax.fori_loop(
        0, q_cap, body, (jnp.zeros((tb, bsz), _I32), [zeros] * lk,
                         [zeros] * lv, jnp.zeros((tb, q_cap), _I32)))
    written = wrote == 1
    for l in range(lk):
        otk_ref[:, l, :] = jnp.where(written, wtk[l], tk0[l])
    for l in range(lv):
        otv_ref[:, l, :] = jnp.where(written, wtv[l], tv0[l])
    ost_ref[...] = jnp.where(written, ready_st, st0)
    ok_ref[...] = ok


def insert_binned(tkeys, tvals, status, qbins, mode: int, q_cap: int,
                  tile_blocks: int | None = None):
    """The insert kernel over binned query tiles.

    ``qbins`` is (nb, (Lk+Lv+1)*q_cap) u32 as :func:`_bin_rows` lays it
    out (key lanes, value lanes, validity).  Returns the updated table
    and the (nb, q_cap) i32 per-bin success flags.
    """
    nb, lk, bsz = tkeys.shape
    lv = tvals.shape[1]
    tb = tile_blocks or _tile_blocks(nb)
    width = qbins.shape[1]
    kern = functools.partial(_insert_kernel, mode=mode, q_cap=q_cap,
                             lk=lk, lv=lv)
    return pallas_call(
        kern,
        grid=(nb // tb,),
        in_specs=[
            pl.BlockSpec((tb, lk, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, lv, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, bsz), lambda i: (i, 0)),
            pl.BlockSpec((tb, width), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, lk, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, lv, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, bsz), lambda i: (i, 0)),
            pl.BlockSpec((tb, q_cap), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, lk, bsz), _U32),
            jax.ShapeDtypeStruct((nb, lv, bsz), _U32),
            jax.ShapeDtypeStruct((nb, bsz), _U32),
            jax.ShapeDtypeStruct((nb, q_cap), _I32),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2},
    )(tkeys.astype(_U32), tvals.astype(_U32), status.astype(_U32), qbins)


def _insert_rows(tkeys, tvals, status, qblock, rows, valid, mode: int,
                 q_cap: int | None, tile_blocks: int | None):
    """Bin (M, Lk+Lv) key|value rows per block and run the insert kernel."""
    with costs.scope("probe.insert"):
        nb = tkeys.shape[0]
        m = qblock.shape[0]
        q_cap = q_cap or default_q_cap(m, nb)
        slot, overflow = bin_queries(qblock, valid, nb, q_cap)
        comb = jnp.concatenate([rows.astype(_U32),
                                valid.astype(_U32)[:, None]], axis=1)
        otk, otv, ost, okbins = insert_binned(
            tkeys, tvals, status, _bin_rows(comb, slot, nb, q_cap), mode,
            q_cap, tile_blocks)
        flat_ok = okbins.reshape(-1)
        take = jnp.minimum(slot, nb * q_cap - 1)
        success = jnp.where(slot < nb * q_cap, flat_ok[take] == 1, False)
        return otk, otv, ost, success & ~overflow & valid


def insert(tkeys, tvals, status, qblock, qkeys, qvals, qvalid,
           mode: int = MODE_SET, q_cap: int | None = None,
           tile_blocks: int | None = None):
    """Pallas bulk insert; semantics == ref.hash_probe_insert_ref.

    Items that overflow a block's static query capacity fail (success
    False) exactly like a full block — callers already retry those.
    """
    rows = jnp.concatenate([qkeys.astype(_U32), qvals.astype(_U32)], axis=1)
    return _insert_rows(tkeys, tvals, status, qblock, rows, qvalid, mode,
                        q_cap, tile_blocks)


def insert_arrivals(tkeys, tvals, status, seg, valid,
                    mode: int = MODE_SET, q_cap: int | None = None,
                    tile_blocks: int | None = None):
    """Bulk insert consuming the contiguous arrival segment directly.

    ``seg`` is the exchange wire's (M, 1+Lk+Lv) owner view — local
    block, key lanes, value lanes — exactly as sliced off the arrival
    buffer.  Semantics == :func:`insert` on the sliced columns; the key
    and value lanes are binned together, by one scatter.
    """
    lk, lv = tkeys.shape[1], tvals.shape[1]
    qblock = jnp.where(valid, seg[:, 0].astype(_I32), 0)
    return _insert_rows(tkeys, tvals, status, qblock, seg[:, 1:1 + lk + lv],
                        valid, mode, q_cap, tile_blocks)


# --------------------------------------------------------------------------
# find kernel
# --------------------------------------------------------------------------

def _find_kernel(tk_ref, tv_ref, st_ref, q_ref, out_ref, *, q_cap: int,
                 lk: int, lv: int):
    """Probe one tile's binned queries (key lanes + validity columns).

    The output tile holds, per block, the value lanes of each query in
    columns ``l*Q + j`` and its found flag in column ``lv*Q + j``.
    """
    q = _as_i32(q_ref[...])                       # (TB, (Lk+1)*Q)
    st = st_ref[...]
    tb, bsz = st.shape
    ready = (st & _U32(_MASK)) == _U32(_READY)
    keys = [tk_ref[:, l, :] for l in range(lk)]
    vals = [_as_i32(tv_ref[:, l, :]) for l in range(lv)]
    qlane = jax.lax.broadcasted_iota(_I32, q.shape, 1)
    slot_lane = jax.lax.broadcasted_iota(_I32, (tb, bsz), 1)
    out_lane = jax.lax.broadcasted_iota(_I32, out_ref.shape, 1)

    def body(j, acc):
        match = ready
        for l in range(lk):
            key = _as_u32(_lane_pick(q, qlane == l * q_cap + j))
            match = match & (keys[l] == key)
        vld = _lane_pick(q, qlane == lk * q_cap + j) == 1
        mslot = _first_lane(match, slot_lane, bsz)
        found = vld & (mslot < bsz)
        at = (slot_lane == mslot) & found
        for l in range(lv):
            acc = jnp.where(out_lane == l * q_cap + j,
                            _lane_pick(vals[l], at), acc)
        return jnp.where(out_lane == lv * q_cap + j, found.astype(_I32), acc)

    out_ref[...] = _as_u32(jax.lax.fori_loop(
        0, q_cap, body, jnp.zeros(out_ref.shape, _I32)))


def find_binned(tkeys, tvals, status, qbins, q_cap: int,
                tile_blocks: int | None = None):
    """The find kernel over binned query tiles.

    ``qbins`` is (nb, (Lk+1)*q_cap) u32 (key lanes, validity); returns
    (nb, (Lv+1)*q_cap) u32: value lanes, then the found flags.
    """
    nb, lk, bsz = tkeys.shape
    lv = tvals.shape[1]
    tb = tile_blocks or _tile_blocks(nb)
    width = qbins.shape[1]
    owidth = (lv + 1) * q_cap
    kern = functools.partial(_find_kernel, q_cap=q_cap, lk=lk, lv=lv)
    return pallas_call(
        kern,
        grid=(nb // tb,),
        in_specs=[
            pl.BlockSpec((tb, lk, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, lv, bsz), lambda i: (i, 0, 0)),
            pl.BlockSpec((tb, bsz), lambda i: (i, 0)),
            pl.BlockSpec((tb, width), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, owidth), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, owidth), _U32),
    )(tkeys.astype(_U32), tvals.astype(_U32), status.astype(_U32), qbins)


def _find_keys(tkeys, tvals, status, qblock, qkeys, valid,
               q_cap: int | None, tile_blocks: int | None):
    """Bin (M, Lk) key rows per block and run the find kernel."""
    with costs.scope("probe.find"):
        nb = tkeys.shape[0]
        lv = tvals.shape[1]
        m = qblock.shape[0]
        q_cap = q_cap or default_q_cap(m, nb)
        slot, overflow = bin_queries(qblock, valid, nb, q_cap)
        comb = jnp.concatenate([qkeys.astype(_U32),
                                (valid & ~overflow).astype(_U32)[:, None]],
                               axis=1)
        res = find_binned(tkeys, tvals, status,
                          _bin_rows(comb, slot, nb, q_cap), q_cap,
                          tile_blocks)

        in_range = slot < nb * q_cap
        blk = jnp.minimum(slot // q_cap, nb - 1)
        pos = slot % q_cap
        cols = jnp.arange(lv + 1, dtype=_I32)[:, None] * q_cap + pos[None, :]
        got = res.reshape(-1)[blk[None, :] * ((lv + 1) * q_cap) + cols]
        # got: (Lv+1, M)
        found = in_range & (got[lv] == 1) & valid & ~overflow
        vals = jnp.where(found[:, None], got[:lv].T, 0)

        # overflow queries take the direct jnp probe (rare, bounded); it runs
        # only when some query overflowed its block's bin
        def probe_overflow(_):
            return hash_probe_find_ref(tkeys, tvals, status,
                                       jnp.clip(qblock, 0, nb - 1), qkeys,
                                       overflow)

        def none(_):
            return jnp.zeros_like(found), jnp.zeros_like(vals)

        f2, v2 = jax.lax.cond(overflow.any(), probe_overflow, none, None)
        return found | f2, jnp.where(f2[:, None], v2, vals)


def find(tkeys, tvals, status, qblock, qkeys, qvalid,
         q_cap: int | None = None, tile_blocks: int | None = None):
    """Pallas bulk find; semantics == ref.hash_probe_find_ref."""
    return _find_keys(tkeys, tvals, status, qblock, qkeys, qvalid, q_cap,
                      tile_blocks)


def find_arrivals(tkeys, tvals, status, seg, valid,
                  q_cap: int | None = None, tile_blocks: int | None = None):
    """Bulk find consuming the contiguous arrival segment directly.

    ``seg`` is the wire's (M, 1+Lk) owner view (local block + key
    lanes); results are bit-identical to :func:`find` on the sliced
    columns.
    """
    lk = tkeys.shape[1]
    qblock = jnp.where(valid, seg[:, 0].astype(_I32), 0)
    return _find_keys(tkeys, tvals, status, qblock, seg[:, 1:1 + lk], valid,
                      q_cap, tile_blocks)
