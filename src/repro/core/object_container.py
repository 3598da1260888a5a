"""BCL ObjectContainers (paper section 6): transparent, low-overhead
serialization of complex element types into distributed memory.

The C++ original stores elements as fixed-size byte-copyable containers,
using compile-time type introspection to (a) skip serialization entirely
for trivially-copyable types ("copy elision") and (b) spill variable-
length serializations behind a global pointer (``BCL::serial_ptr``).

The JAX port stores elements as fixed-width **u32 lane matrices**
``(N, L)`` — the unit every container and the exchange engine moves.
Trace-time dtype introspection plays the role of C++ template
introspection:

  * a single 32-bit array packs via one ``bitcast_convert_type`` — a
    layout no-op for XLA, i.e. genuine copy elision;
  * a struct (dict of fields) packs each field to u32 lanes and
    concatenates; widths are static so everything unrolls;
  * variable-length payloads pack as a 3-lane ``SerialPtr`` record
    (rank, offset, length) pointing into a heap container
    (``repro.containers.heap``), mirroring ``BCL::serial_ptr``.

Users with custom types subclass :class:`Packer` — the analogue of
injecting a serialization struct into the BCL namespace.
"""

from __future__ import annotations

import abc
from typing import Any

import jax
import jax.numpy as jnp
from jax import ShapeDtypeStruct

_U32 = jnp.uint32


def _lanes_for_dtype(dtype) -> int:
    """u32 lanes needed per scalar of ``dtype``."""
    size = jnp.dtype(dtype).itemsize
    if size <= 4:
        return 1
    if size == 8:
        return 2
    raise TypeError(f"unsupported element dtype {dtype}")


def _to_u32(x: jax.Array) -> jax.Array:
    """Bitcast any <=32-bit array (N,) or (N, d) to u32 lanes (N, d')."""
    if x.ndim == 1:
        x = x[:, None]
    dt = x.dtype
    if dt == jnp.uint32:
        return x
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, _U32)
    if dt.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(_U32)
    if dt.itemsize == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(_U32)
    raise TypeError(f"unsupported dtype {dt}")


def _from_u32(lanes: jax.Array, dtype, inner: int) -> jax.Array:
    """Invert :func:`_to_u32` back to ``dtype`` with trailing dim ``inner``."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 4:
        out = jax.lax.bitcast_convert_type(lanes, dt)
    elif dt.itemsize == 2:
        out = jax.lax.bitcast_convert_type(lanes.astype(jnp.uint16), dt)
    elif dt.itemsize == 1:
        out = jax.lax.bitcast_convert_type(lanes.astype(jnp.uint8), dt)
    else:
        raise TypeError(f"unsupported dtype {dt}")
    if inner == 0:
        return out[:, 0]
    return out


def ragged_offsets(widths) -> tuple[list[int], int]:
    """Word offsets of back-to-back ragged segments.

    The exchange engine's fused wire is a flat u32 word buffer per
    destination in which flow ``f`` owns a contiguous segment of
    ``C_f * widths[f]`` words (DESIGN.md section 1.5) — the serialized
    analogue of this module's lane matrices, with no cross-flow padding.
    Returns ``(starts, total)`` where ``starts[f]`` is the first word of
    segment ``f`` and ``total`` is the words per destination block.
    Within a segment the rows are lane-planar: lane ``k`` of rows
    ``[0, C_f)`` fills words ``[k*C_f, (k+1)*C_f)``.  Packing goes
    through :func:`scatter_rows` with lane stride ``C_f``; unpacking is
    :func:`rows_from_words`, a reshape that moves only major dimensions,
    never a gather.
    """
    starts, off = [], 0
    for w in widths:
        starts.append(off)
        off += int(w)
    return starts, off


def scatter_rows(flat: jax.Array, base: jax.Array, rows: jax.Array,
                 stride=1, widths: jax.Array | None = None) -> jax.Array:
    """Pack (N, W) u32 rows into a flat word buffer, one lane per plane.

    Lane ``k`` of row ``i`` lands at word ``base[i] + k * stride``, with
    ``stride`` one int or one per row (the lane stride of the row's
    segment, its capacity on the wire); a sentinel ``base[i] >=
    flat.size`` drops the row.  This is the ragged wire's serializer and
    the declared fallback/oracle for the fused Pallas wire
    (``kernels/ops.pack_rows`` — DESIGN.md section 1.10): the hot path
    packs in-kernel, this XLA scatter stays as the jnp reference.

    With ``widths`` (per-row word counts <= W), lanes past ``widths[i]``
    are dropped — one rectangular call packs right-padded rows of mixed
    flow widths bit-identically to per-flow calls on disjoint slots.
    """
    # word indices are built lane-major, (W, N): with the N rows on the
    # minor axis no index or update array is padded out to the vector
    # width on TPU, as an (N, W) one with a few lanes would be
    size = flat.shape[0]
    lane = jnp.arange(rows.shape[1], dtype=base.dtype)[:, None]
    keep = base[None, :] < size
    if widths is not None:
        keep = keep & (lane < widths[None, :].astype(base.dtype))
    step = jnp.reshape(jnp.asarray(stride, base.dtype), (1, -1))
    idx = jnp.where(keep, base[None, :] + lane * step, size)
    return flat.at[idx.reshape(-1)].set(rows.astype(_U32).T.reshape(-1),
                                        mode="drop")


def rows_from_words(words: jax.Array, roww: int) -> jax.Array:
    """View lane-planar segments as an (N, roww) u32 matrix.

    The inverse of :func:`scatter_rows` over dense slots: along its last
    axis ``words`` holds ``roww`` planes of ``C`` words each, lane ``k``
    of rows ``[0, C)`` in plane ``k``; the leading axes (if any) are
    blocks of such segments.  Row ``b*C + j`` of the result is row ``j``
    of block ``b``.  Only major dimensions move — ``(B, roww, C) ->
    (roww, B*C)`` and the final transpose — so on TPU this is a layout
    copy at most, never a gather, and no row-major ``(N, roww)`` array
    with a few lanes (padded to the 128-lane vector) need be formed.
    """
    planes = words.reshape(-1, roww, words.shape[-1] // roww)
    return jnp.swapaxes(planes, 0, 1).reshape(roww, -1).T


def words_from_rows(rows: jax.Array, blocks: int) -> jax.Array:
    """Lay (blocks*C, roww) rows out as ``blocks`` lane-planar segments,
    ``(blocks, roww*C)``: the inverse of :func:`rows_from_words`."""
    roww = rows.shape[1]
    planes = rows.T.reshape(roww, blocks, -1)
    return jnp.swapaxes(planes, 0, 1).reshape(blocks, -1)


class Packer(abc.ABC):
    """Serialize a record pytree <-> a fixed-width u32 lane matrix."""

    #: static number of u32 lanes per element
    lanes: int

    @abc.abstractmethod
    def pack(self, value: Any) -> jax.Array:
        """(pytree of (N,...) arrays) -> (N, lanes) u32."""

    @abc.abstractmethod
    def unpack(self, mat: jax.Array) -> Any:
        """(N, lanes) u32 -> pytree of (N, ...) arrays."""

    def example(self, n: int) -> Any:
        """Zero-filled example value with batch size n (testing aid)."""
        return self.unpack(jnp.zeros((n, self.lanes), _U32))


class IdentityPacker(Packer):
    """Copy-elision fast path: a single 32-bit field, packed by bitcast.

    Mirrors ``BCL::identity_serialize<T>``: XLA lowers the bitcast to a
    view change, so no copy is materialized.
    """

    def __init__(self, dtype, inner: int = 0):
        self.dtype = jnp.dtype(dtype)
        self.inner = inner  # 0 => scalar field (N,), else (N, inner)
        if self.dtype.itemsize != 4:
            raise TypeError("IdentityPacker requires a 32-bit dtype")
        self.lanes = max(inner, 1)

    def pack(self, value: jax.Array) -> jax.Array:
        return _to_u32(value)

    def unpack(self, mat: jax.Array) -> jax.Array:
        return _from_u32(mat, self.dtype, self.inner)


class StructPacker(Packer):
    """Fixed-size struct: dict of named fields, each <=32-bit scalar/vector."""

    def __init__(self, fields: dict[str, ShapeDtypeStruct]):
        # fields: name -> ShapeDtypeStruct with shape () or (inner,) per element
        self.fields = dict(sorted(fields.items()))
        self.layout: list[tuple[str, Any, int, int]] = []  # name,dtype,inner,lanes
        off = 0
        for name, sds in self.fields.items():
            if len(sds.shape) > 1:
                raise TypeError(f"field {name}: per-element shape must be scalar/vector")
            inner = sds.shape[0] if sds.shape else 0
            width = max(inner, 1) * _lanes_for_dtype(sds.dtype)
            if jnp.dtype(sds.dtype).itemsize == 8:
                raise TypeError(
                    f"field {name}: 64-bit fields unsupported without x64; "
                    "split into two u32 fields")
            self.layout.append((name, sds.dtype, inner, width))
            off += width
        self.lanes = off

    def pack(self, value: dict[str, jax.Array]) -> jax.Array:
        cols = []
        for name, _dtype, _inner, _width in self.layout:
            cols.append(_to_u32(value[name]))
        return jnp.concatenate(cols, axis=1)

    def unpack(self, mat: jax.Array) -> dict[str, jax.Array]:
        out = {}
        off = 0
        for name, dtype, inner, width in self.layout:
            out[name] = _from_u32(mat[:, off:off + width], dtype, inner)
            off += width
        return out


class SerialPtrPacker(Packer):
    """Variable-length indirection record: (rank, offset, length).

    The payload bytes live in a heap container; this record is what gets
    stored inside hash tables / queues — the ``BCL::serial_ptr`` path.
    """

    lanes = 3

    def pack(self, value: dict[str, jax.Array]) -> jax.Array:
        return jnp.stack(
            [value["rank"].astype(_U32), value["offset"].astype(_U32),
             value["length"].astype(_U32)], axis=1)

    def unpack(self, mat: jax.Array) -> dict[str, jax.Array]:
        return {"rank": mat[:, 0].astype(jnp.int32),
                "offset": mat[:, 1].astype(jnp.int32),
                "length": mat[:, 2].astype(jnp.int32)}


def packer_for(spec: Any) -> Packer:
    """Trace-time type introspection: pick the cheapest packer for ``spec``.

    ``spec`` is a ShapeDtypeStruct (single field), a dict of them
    (struct), an int (u32 vector of that many lanes), or an existing
    Packer (passed through, the "user-injected serializer" path).
    """
    if isinstance(spec, Packer):
        return spec
    if isinstance(spec, int):
        return IdentityPacker(_U32, inner=spec if spec > 1 else 0)
    if isinstance(spec, ShapeDtypeStruct):
        inner = spec.shape[0] if spec.shape else 0
        if jnp.dtype(spec.dtype).itemsize == 4:
            return IdentityPacker(spec.dtype, inner)
        return StructPacker({"value": spec})
    if isinstance(spec, dict):
        return StructPacker(spec)
    if isinstance(spec, jax.Array) or hasattr(spec, "dtype"):
        inner = spec.shape[1] if spec.ndim > 1 else 0
        return packer_for(ShapeDtypeStruct((inner,) if inner else (), spec.dtype))
    raise TypeError(f"cannot derive a Packer for {spec!r}")


def u64_from_u32_pair(hi: jax.Array, lo: jax.Array) -> dict[str, jax.Array]:
    """Convenience for 64-bit keys stored as two u32 lanes."""
    return {"hi": hi.astype(_U32), "lo": lo.astype(_U32)}
