"""BCL::BloomFilter — distributed *blocked* Bloom filter (paper 5.4.2).

A value hashes to one 64-bit block; k bit positions inside that block
come from double hashing.  Insertion is a single owner-side RMW on one
64-bit word (the paper's single fetch-and-or AMO), and it atomically
returns whether the value was already present — including among
duplicates within the same batch, where exactly the first inserter (in
deterministic arrival order) observes "not present".  This is the
property the paper shows a flat distributed Bloom filter cannot provide.

Cost model (paper Table 2): insert = A, find = R.

``insert_find`` fuses an insert batch and a membership-query batch into
one ExchangePlan round trip (DESIGN.md section 1.5) — the dedup
pipeline's contamination-check pattern; ``Promise.FINE`` recovers the
sequential schedule.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.backend import Backend
from repro.core.exchange import ExchangePlan, PendingResult
from repro.core.hashing import double_hash, hash_lanes
from repro.core.object_container import Packer, packer_for
from repro.core.promises import Promise, fine_grained, validate
from repro.kernels import ops as kops
from repro.kernels.ref import bloom_words_ref

_U32 = jnp.uint32
_I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class BloomSpec:
    nblocks_global: int
    nblocks_local: int
    k: int
    packer: Packer
    impl: str = "auto"


class BloomState(NamedTuple):
    words: jax.Array   # (nb_local, 2) u32 — one 64-bit block per row


def bloom_create(backend: Backend, nbits: int, value_spec,
                 k: int = 4, impl: str = "auto") -> tuple[BloomSpec, BloomState]:
    packer = packer_for(value_spec)
    nprocs = backend.nprocs()
    nb_global = max(1, -(-nbits // 64))
    nb_global = -(-nb_global // nprocs) * nprocs
    nb_local = nb_global // nprocs
    spec = BloomSpec(nb_global, nb_local, k, packer, impl)
    return spec, BloomState(jnp.zeros((nb_local, 2), _U32))


def _words_of(spec: BloomSpec, items, valid):
    """Pack items into the wire body ``[local block | 2 bit-words]``."""
    lanes = spec.packer.pack(items)
    n = lanes.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    gblock = (hash_lanes(lanes, seed=11)
              % _U32(spec.nblocks_global)).astype(_I32)
    owner = gblock // spec.nblocks_local
    lblock = gblock % spec.nblocks_local
    words = bloom_words_ref(double_hash(lanes, spec.k, 64), spec.k)
    body = jnp.concatenate([lblock.astype(_U32)[:, None], words], axis=1)
    return n, body, owner, valid


def _route_words(backend: Backend, spec: BloomSpec, items, valid, capacity,
                 op_name: str, max_rounds: int = 1, transport=None):
    """Single-flow plan shipping ``[lblock | bit-words]`` rows; the
    1-word answer reply rides the committed plan's inverse permutation
    (through the chosen transport)."""
    n, body, owner, valid = _words_of(spec, items, valid)
    plan = ExchangePlan(name=op_name)
    h = plan.add(body, owner, capacity, reply_lanes=1, valid=valid,
                 op_name=op_name)
    c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                    transport=transport)
    res = c.view(h)
    rb = jnp.where(res.valid, res.payload[:, 0].astype(_I32), 0)
    rw = res.payload[:, 1:3]
    return n, c, h, res, rb, rw


def insert(backend: Backend, spec: BloomSpec, state: BloomState,
           items, capacity: int, valid: jax.Array | None = None,
           max_rounds: int = 1, transport=None):
    """Atomic insert; returns (state, already_present(N,)).

    ``already_present[i]`` is True iff every one of item i's k bits was
    set before item i's own insertion — first-inserter-wins across the
    whole machine and within the batch (paper's atomicity invariant).
    """
    with costs.scope("bloom.insert"):
        n, c, h, res, rb, rw = _route_words(
            backend, spec, items, valid, capacity, "bloom.insert",
            max_rounds=max_rounds, transport=transport)
        words, already = kops.bloom_insert(state.words, rb, rw, res.valid,
                                           impl=spec.impl)
        c.set_reply(h, already.astype(_U32))
        back, _ = c.finish(backend)[h]
        costs.record("bloom.insert", costs.Cost(A=1))
        return BloomState(words), back[:, 0] == 1


def find(backend: Backend, spec: BloomSpec, state: BloomState,
         items, capacity: int, valid: jax.Array | None = None,
         max_rounds: int = 1, transport=None):
    """Membership query; returns present(N,). Cost R."""
    with costs.scope("bloom.find"):
        n, c, h, res, rb, rw = _route_words(
            backend, spec, items, valid, capacity, "bloom.find",
            max_rounds=max_rounds, transport=transport)
        present = kops.bloom_find(state.words, rb, rw, res.valid,
                                  impl=spec.impl)
        c.set_reply(h, present.astype(_U32))
        back, _ = c.finish(backend)[h]
        costs.record("bloom.find", costs.Cost(R=n))
        return back[:, 0] == 1


def insert_find(backend: Backend, spec: BloomSpec, state: BloomState,
                ins_items, find_items, capacity_ins: int, capacity_find: int,
                ins_valid: jax.Array | None = None,
                find_valid: jax.Array | None = None,
                promise: Promise = Promise.NONE,
                max_rounds: int = 1,
                transport=None,
                async_: bool = False):
    """Fused insert + membership query sharing ONE exchange round trip.

    The insert is serialized before the find, so the query observes this
    batch's insertions (exactly the ``Promise.FINE`` sequential order).
    Both ops' flows ride one ExchangePlan: 2 collectives where the FINE
    schedule costs 4, at the exact sum of the standalone ops' wire
    bytes (ragged segments, DESIGN.md section 1.5 — the 1-bit answers
    ride 1-word reply rows).  Returns
    ``(state, already_present, present)``.

    ``async_=True`` issues the plan split-phase (DESIGN.md section 1.9)
    and instead returns a :class:`~repro.core.PendingResult` whose
    ``finish()`` yields the same triple.
    """
    with costs.scope("bloom.insert_find"):
        validate(promise)
        if fine_grained(promise):
            def _fine():
                st, already = insert(backend, spec, state, ins_items,
                                     capacity_ins, valid=ins_valid,
                                     max_rounds=max_rounds,
                                     transport=transport)
                present = find(backend, spec, st, find_items, capacity_find,
                               valid=find_valid, max_rounds=max_rounds,
                               transport=transport)
                return st, already, present
            # split-phase FINE stays the sequential oracle: run eagerly
            return PendingResult(lambda s=_fine(): s) if async_ else _fine()

        ni, body_i, owner_i, ins_valid = _words_of(spec, ins_items,
                                                   ins_valid)
        nf, body_f, owner_f, find_valid = _words_of(spec, find_items,
                                                    find_valid)
        plan = ExchangePlan(name="bloom.insert_find")
        hi = plan.add(body_i, owner_i, capacity_ins, reply_lanes=1,
                      valid=ins_valid, op_name="bloom.insert")
        hf = plan.add(body_f, owner_f, capacity_find, reply_lanes=1,
                      valid=find_valid, op_name="bloom.find")
        if async_:
            pend = plan.commit_async(backend, impl=spec.impl,
                                     max_rounds=max_rounds,
                                     transport=transport)

            def complete():
                # the completion tail is traced at finish(), outside the
                # scope above: it takes the op's name again
                with costs.scope("bloom.insert_find"):
                    return _insert_find_complete(
                        backend, spec, state, pend.finish(backend), hi, hf,
                        nf)
            return PendingResult(complete)
        c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                        transport=transport)
        return _insert_find_complete(backend, spec, state, c, hi, hf, nf)


def _insert_find_complete(backend, spec, state, c, hi, hf, nf):
    """Owner-side work + reply round of :func:`insert_find` (both the
    synchronous and the split-phase path complete through here)."""
    vi, vf = c.view(hi), c.view(hf)

    rb_i = jnp.where(vi.valid, vi.payload[:, 0].astype(_I32), 0)
    words, already = kops.bloom_insert(state.words, rb_i, vi.payload[:, 1:3],
                                       vi.valid, impl=spec.impl)
    rb_f = jnp.where(vf.valid, vf.payload[:, 0].astype(_I32), 0)
    present = kops.bloom_find(words, rb_f, vf.payload[:, 1:3], vf.valid,
                              impl=spec.impl)
    c.set_reply(hi, already.astype(_U32))
    c.set_reply(hf, present.astype(_U32))
    outs = c.finish(backend)
    bi, _ = outs[hi]
    bf, _ = outs[hf]
    costs.record("bloom.insert", costs.Cost(A=1))
    costs.record("bloom.find", costs.Cost(R=nf))
    return BloomState(words), bi[:, 0] == 1, bf[:, 0] == 1


def fill_fraction(backend: Backend, state: BloomState) -> jax.Array:
    """Fraction of set bits (diagnostic for false-positive estimation)."""
    pop = jax.lax.population_count(state.words).sum()
    tot = backend.psum(pop)
    nbits = backend.psum(jnp.int32(state.words.size * 32))
    return tot / nbits
