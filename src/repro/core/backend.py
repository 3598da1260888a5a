"""BCL backends, JAX edition.

The paper's BCL Core runs over four communication backends (MPI one-sided,
OpenSHMEM, GASNet-EX, UPC++), each implementing a small primitive set:
init / barrier / read / write / CAS / broadcast / reduce.  Container code
is written once against that primitive set.

The JAX port keeps the exact same structure with three backends that are
*lowering strategies* rather than wire protocols:

  SerialBackend   nprocs == 1, collectives are identities.  The reference
                  semantics; used by oracles, single-device tests, and any
                  container running on an unsharded axis.

  SpmdBackend     per-device code inside ``jax.shard_map`` over a named
                  mesh axis.  Collectives lower to real ICI collectives
                  (all-to-all / all-gather / psum / ppermute).  This is the
                  production path.

  GspmdBackend    global-array semantics: the same primitive set expressed
                  as shape transforms + sharding constraints, letting the
                  XLA SPMD partitioner choose the collective schedule.
                  (Used by the model stack, where the compiler's schedule
                  is usually the right one.)

Container code takes a ``Backend`` and never mentions the lowering —
exactly the paper's "pick whichever backend is most optimized for your
system" portability story.
"""

from __future__ import annotations

import abc
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class Backend(abc.ABC):
    """Primitive set every BCL backend must implement (paper section 8)."""

    #: mesh axis name(s) this backend communicates over ("" for serial)
    axis: str | tuple[str, ...]

    @abc.abstractmethod
    def nprocs(self) -> int:
        """Static number of ranks on the communication axis."""

    @abc.abstractmethod
    def rank(self) -> jax.Array:
        """Traced index of the calling rank (i32 scalar)."""

    @abc.abstractmethod
    def all_to_all(self, x: jax.Array,
                   groups: Sequence[Sequence[int]] | None = None) -> jax.Array:
        """Tiled all-to-all over axis 0.

        ``x`` has shape (nprocs * C, ...): rows [d*C:(d+1)*C] are sent to
        rank d; the result's rows [s*C:(s+1)*C] were received from rank s.
        Identity when nprocs == 1.

        ``groups`` restricts the collective to a *sub-axis*: a static
        partition of [0, nprocs) into equal-size groups (e.g. the rows or
        columns of a Pr x Pc virtual factorization of the rank axis —
        DESIGN.md section 1.7).  Then ``x`` has shape (G * C, ...) with G
        the group size: block j goes to the j-th member of my group, and
        the result's block j came from that member.  This is the paper's
        "hierarchical team" primitive (DASH-style) expressed over one
        flat communication axis.
        """

    @abc.abstractmethod
    def all_gather(self, x: jax.Array) -> jax.Array:
        """Gather ``x`` from every rank, stacked on a new leading axis."""

    @abc.abstractmethod
    def psum(self, x: jax.Array) -> jax.Array:
        """Sum-reduce across ranks (broadcast result)."""

    @abc.abstractmethod
    def pmax(self, x: jax.Array) -> jax.Array:
        """Max-reduce across ranks (broadcast result)."""

    @abc.abstractmethod
    def ppermute(self, x: jax.Array, perm: Sequence[tuple[int, int]]) -> jax.Array:
        """Point-to-point permutation (the collective closest to RDMA put)."""

    def barrier(self) -> None:
        """Memory fence + barrier.

        SPMD program order already sequences collectives, so this is a
        semantic no-op kept for program structure (and cost accounting).
        """
        return None

    def broadcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Broadcast ``x`` from ``root`` to all ranks."""
        if self.nprocs() == 1:
            return x
        return self.all_gather(x)[root]

    # -- derived helpers -------------------------------------------------

    def exclusive_rank_offsets(self, count: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Prefix-sum slot reservation: the TPU analogue of fetch-and-add.

        Every rank contributes ``count`` items to a shared sequence.  RDMA
        BCL reserves slots with an atomic fetch-and-add on the owner;
        here the reservation is an exclusive prefix sum over ranks —
        associative, contention-free, and deterministic.

        Returns ``(my_offset, total)``.
        """
        counts = self.all_gather(count)          # (nprocs,)
        csum = jnp.cumsum(counts)
        my = self.rank()
        my_offset = jnp.where(my == 0, 0, csum[jnp.maximum(my - 1, 0)])
        return my_offset.astype(jnp.int32), csum[-1].astype(jnp.int32)


class SerialBackend(Backend):
    """Single-rank backend: the reference semantics."""

    axis = ""

    def nprocs(self) -> int:
        return 1

    def rank(self) -> jax.Array:
        return jnp.int32(0)

    def all_to_all(self, x: jax.Array, groups=None) -> jax.Array:
        return x

    def all_gather(self, x: jax.Array) -> jax.Array:
        return x[None]

    def psum(self, x: jax.Array) -> jax.Array:
        return x

    def pmax(self, x: jax.Array) -> jax.Array:
        return x

    def ppermute(self, x, perm):
        return x


class SpmdBackend(Backend):
    """Per-device backend for code running inside ``jax.shard_map``.

    ``axis`` may be a single mesh axis name or a tuple of names; a tuple
    communicates over the flattened product axis (used when a container is
    sharded over the whole mesh, e.g. ``("data", "model")``).
    """

    def __init__(self, axis: str | tuple[str, ...], axis_size: int | None = None):
        self.axis = axis
        # axis size must be static; read it from the ambient mesh if not given.
        if axis_size is None:
            axis_size = jax.lax.axis_size(axis)
        self._nprocs = int(axis_size)

    def nprocs(self) -> int:
        return self._nprocs

    def rank(self) -> jax.Array:
        return jax.lax.axis_index(self.axis).astype(jnp.int32)

    def all_to_all(self, x: jax.Array, groups=None) -> jax.Array:
        if self._nprocs == 1:
            return x
        if groups is not None:
            groups = [list(g) for g in groups]
            if all(len(g) == 1 for g in groups):
                return x          # single-member groups: identity
            return jax.lax.all_to_all(x, self.axis, split_axis=0,
                                      concat_axis=0, tiled=True,
                                      axis_index_groups=groups)
        return jax.lax.all_to_all(x, self.axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    def all_gather(self, x: jax.Array) -> jax.Array:
        if self._nprocs == 1:
            return x[None]
        return jax.lax.all_gather(x, self.axis)

    def psum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.axis)

    def pmax(self, x: jax.Array) -> jax.Array:
        return jax.lax.pmax(x, self.axis)

    def ppermute(self, x, perm):
        return jax.lax.ppermute(x, self.axis, perm)


def get_backend(axis: str | tuple[str, ...] | None = None,
                axis_size: int | None = None) -> Backend:
    """Backend factory: serial when ``axis`` is None, SPMD otherwise."""
    if axis is None or axis == "":
        return SerialBackend()
    return SpmdBackend(axis, axis_size=axis_size)


def spec_for(backend: Backend, *rest: str | None) -> P:
    """PartitionSpec that shards axis 0 over the backend's comm axis."""
    if isinstance(backend, SerialBackend):
        return P(*((None,) + rest))
    return P(*((backend.axis,) + rest))
