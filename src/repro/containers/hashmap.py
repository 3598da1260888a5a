"""BCL::HashMap — the distributed hash table (paper section 5.1).

Layout: a logically contiguous array of *blocks* of B buckets,
distributed block-wise across ranks (DESIGN.md: "blocked open
addressing").  A key hashes to a block; probing compares the key against
all B slots of the block at once (vectorized; the Pallas kernel's tile).
When a block fills, the container rehashes the failed items to a new
block — quadratic in the attempt number — with a bounded number of
attempts, mirroring the paper's quadratic probing plus its "insertion
may fail when full" semantics.

Concurrency promises select the schedule (paper Table 3):
  (a) find|insert   fully atomic   insert 2A + W     find 2A + R
  (b) local         local insert   l
  (c) find|insert   fully atomic find
  (d) find          phase-local find: one read, no AMOs     R

Promises also pick the *collective* schedule (DESIGN.md section 1.5):
the default 2-attempt find issues both probes as two flows of one
ExchangePlan (2 collectives), and ``find_insert`` fuses a find batch
and an insert batch into one plan under the
``ConProm.HashMap.find_insert`` promise; ``Promise.FINE`` at any
callsite forces the sequential one-op-per-round oracle.

"Atomic" ops execute the paper's flag dance (reserve CAS / read-bit
fetch-or + fetch-and) as real owner-side RMW passes over the status
word, so their extra cost is measurable; promise-relaxed ops skip it.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.backend import Backend
from repro.core.exchange import ExchangePlan, PendingResult
from repro.core.hashing import hash_lanes
from repro.core.object_container import Packer, packer_for
from repro.core.promises import (Promise, find_only, fine_grained,
                                 fully_atomic_hashmap, local_only, validate)
from repro.kernels import ops as kops

_U32 = jnp.uint32
_I32 = jnp.int32

# a "read bit" in the upper 30 bits of the status word (paper 5.1.2)
_READ_BIT = jnp.uint32(1 << 7)


@dataclasses.dataclass(frozen=True)
class HashMapSpec:
    nblocks_global: int
    nblocks_local: int
    block_size: int
    key_packer: Packer
    val_packer: Packer
    impl: str = "auto"   # kernel dispatch: auto|jnp|pallas|oracle

    @property
    def capacity(self) -> int:
        return self.nblocks_global * self.block_size


class HashMapState(NamedTuple):
    tkeys: jax.Array    # (nb_local, Lk, B) u32, lane-major per block
    tvals: jax.Array    # (nb_local, Lv, B) u32
    status: jax.Array   # (nb_local, B) u32


def hashmap_create(backend: Backend, capacity: int, key_spec, val_spec,
                   block_size: int = 128,
                   impl: str = "auto") -> tuple[HashMapSpec, HashMapState]:
    """Collective constructor (paper 5.1.1): fixed size, fixed K/V types."""
    with costs.scope("hashmap.create"):
        kp, vp = packer_for(key_spec), packer_for(val_spec)
        nprocs = backend.nprocs()
        nb_global = max(1, -(-capacity // block_size))
        nb_global = -(-nb_global // nprocs) * nprocs       # round up to P
        nb_local = nb_global // nprocs
        spec = HashMapSpec(nb_global, nb_local, block_size, kp, vp, impl)
        state = HashMapState(
            jnp.zeros((nb_local, kp.lanes, block_size), _U32),
            jnp.zeros((nb_local, vp.lanes, block_size), _U32),
            jnp.zeros((nb_local, block_size), _U32))
        return spec, state


def _block_of(spec: HashMapSpec, key_lanes: jax.Array,
              attempt: int) -> jax.Array:
    """Global block index; attempts rehash quadratically (paper 5.1)."""
    h1 = hash_lanes(key_lanes, seed=1)
    if attempt == 0:
        g = h1
    else:
        h2 = hash_lanes(key_lanes, seed=3) | _U32(1)
        g = h1 + jnp.uint32(attempt * attempt) * h2
    return (g % jnp.uint32(spec.nblocks_global)).astype(_I32)


def _owner_local(spec: HashMapSpec, gblock: jax.Array):
    return gblock // spec.nblocks_local, gblock % spec.nblocks_local


def insert(backend: Backend, spec: HashMapSpec, state: HashMapState,
           keys, vals, capacity: int,
           promise: Promise = Promise.FIND | Promise.INSERT,
           valid: jax.Array | None = None,
           mode: int = kops.MODE_SET,
           attempts: int = 2,
           return_success: bool = True,
           max_rounds: int = 1,
           transport=None,
           dead_ranks=None,
           integrity: bool = False):
    """Insert a batch of (key, value) pairs.

    Returns (state, success(N,) | None).  With ``promise=local`` the keys
    must hash to this rank's own blocks (cost l, no collectives) — the
    HashMapBuffer flush path (paper Table 3b).  ``max_rounds`` adds
    carryover retry rounds to each exchange, absorbing skewed key
    distributions (hot blocks) without inflating ``capacity``.

    ``dead_ranks``/``integrity`` forward to :meth:`ExchangePlan.commit`
    (DESIGN.md section 1.8).  Items owned by a dead rank are masked at
    admission and simply stay unsuccessful (``success`` False) — a
    multi-``attempts`` insert retries them against their rehash block,
    which may land on a live rank.  With ``integrity=True`` a
    checksum-failed arrival never acks, so the requester sees it as
    unsuccessful and the attempt loop re-sends it.
    """
    with costs.scope("hashmap.insert"):
        validate(promise)
        klanes = spec.key_packer.pack(keys)
        vlanes = spec.val_packer.pack(vals)
        n = klanes.shape[0]
        if valid is None:
            valid = jnp.ones((n,), bool)

        if local_only(promise):
            gblock = _block_of(spec, klanes, 0)
            _, lblock = _owner_local(spec, gblock)
            tk, tv, st, ok = kops.bulk_insert(
                state.tkeys, state.tvals, state.status, lblock, klanes, vlanes,
                valid, mode, impl=spec.impl)
            costs.record("hashmap.insert", costs.Cost(local=n))
            return HashMapState(tk, tv, st), ok

        atomic = fully_atomic_hashmap(promise)
        pending = valid
        success = jnp.zeros((n,), bool)
        new_state = state
        # success replies ride the plan's inverse permutation (through the
        # chosen transport); a fire-and-forget insert declares no reply
        rl = 1 if (return_success or attempts > 1) else 0
        for a in range(max(1, attempts)):
            gblock = _block_of(spec, klanes, a)
            owner, lblock = _owner_local(spec, gblock)
            body = jnp.concatenate(
                [lblock.astype(_U32)[:, None], klanes, vlanes], axis=1)
            plan = ExchangePlan(name="hashmap.insert")
            h = plan.add(body, owner, capacity, reply_lanes=rl, valid=pending,
                         op_name="hashmap.insert")
            c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                            transport=transport, dead_ranks=dead_ranks,
                            integrity=integrity)
            res = c.view(h)

            tk, tv, st = new_state
            if atomic:
                # paper 5.1.3: CAS free->reserved ... XOR ->ready.  The state
                # machine is owner-serialized here, but we execute the reserve
                # pass so its traffic is real: a net-zero RMW on the status
                # word of every touched block.
                rb = jnp.where(res.valid, res.payload[:, 0].astype(_I32), 0)
                st = st.at[rb].add(_READ_BIT, mode="drop")
                st = st.at[rb].add(_U32(0) - _READ_BIT, mode="drop")
            # the arrival segment feeds the probe directly (DESIGN.md §1.10)
            tk, tv, st, ok_here = kops.bulk_insert_arrivals(
                tk, tv, st, res.payload, res.valid, mode, impl=spec.impl)
            new_state = HashMapState(tk, tv, st)

            if rl:
                c.set_reply(h, ok_here.astype(_U32))
                back, _ = c.finish(backend)[h]
                ok_src = (back[:, 0] == 1) & pending
                success = success | ok_src
                pending = pending & ~ok_src
            else:
                break
        costs.record("hashmap.insert",
                     costs.Cost(A=2 if atomic else 1, W=n))
        return new_state, (success if (return_success or attempts > 1)
                           else None)


def _find_speculative(backend: Backend, spec: HashMapSpec,
                      state: HashMapState, klanes, capacity: int,
                      valid, atomic: bool, max_rounds: int = 1,
                      transport=None, dead_ranks=None,
                      integrity: bool = False):
    """Dual-attempt find in ONE round trip (2 collectives, not 4).

    Both probe attempts are two *flows* of one :class:`ExchangePlan`:
    each key is registered against its attempt-0 AND attempt-1 owners,
    the plan fuses both flows into a single request all-to-all, and the
    replies share a single inverse all-to-all.  The requester prefers
    the attempt-0 answer, which makes the result bit-identical to the
    sequential attempt loop whenever the per-flow capacity admits every
    request (zero drops — the operating regime callers are expected to
    size for).  Under capacity overflow both schedules degrade to
    best-effort on *different* probe subsets: here each attempt flow
    drops independently at capacity C per (src, dst, flow) segment.
    Halves the collective rounds of the default 2-attempt find at the
    price of one speculative lookup per key — the paper's aggregation
    trade (latency for bandwidth, section 4.2) applied to the probe
    path itself.
    """
    n = klanes.shape[0]
    owner0, lb0 = _owner_local(spec, _block_of(spec, klanes, 0))
    owner1, lb1 = _owner_local(spec, _block_of(spec, klanes, 1))
    rl = spec.val_packer.lanes + 1
    plan = ExchangePlan(name="hashmap.find")
    h0 = plan.add(jnp.concatenate([lb0.astype(_U32)[:, None], klanes], axis=1),
                  owner0, capacity, reply_lanes=rl, valid=valid,
                  op_name="hashmap.find")
    h1 = plan.add(jnp.concatenate([lb1.astype(_U32)[:, None], klanes], axis=1),
                  owner1, capacity, reply_lanes=rl, valid=valid,
                  op_name="hashmap.find")
    c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                    transport=transport, dead_ranks=dead_ranks,
                    integrity=integrity)
    v0, v1 = c.view(h0), c.view(h1)

    seg = jnp.concatenate([v0.payload, v1.payload])
    rvalid = jnp.concatenate([v0.valid, v1.valid])
    tk, tv, st = state
    if atomic:
        rb = jnp.where(rvalid, seg[:, 0].astype(_I32), 0)
        st = st.at[rb].add(_READ_BIT, mode="drop")
    found_here, vlanes = kops.bulk_find_arrivals(tk, tv, st, seg, rvalid,
                                                 impl=spec.impl)
    if atomic:
        st = st.at[rb].add(_U32(0) - _READ_BIT, mode="drop")
        state = HashMapState(tk, tv, st)
    body_back = jnp.concatenate(
        [vlanes, found_here.astype(_U32)[:, None]], axis=1)
    m = v0.payload.shape[0]
    c.set_reply(h0, body_back[:m])
    c.set_reply(h1, body_back[m:])
    outs = c.finish(backend)
    b0, _ = outs[h0]
    b1, _ = outs[h1]
    got0 = (b0[:, -1] == 1) & valid
    got1 = (b1[:, -1] == 1) & valid
    found = got0 | got1
    vals = jnp.where(got0[:, None], b0[:, :-1], b1[:, :-1])
    vals = jnp.where(found[:, None], vals, 0)
    costs.record("hashmap.find",
                 costs.Cost(A=2 if atomic else 0, R=n))
    return state, spec.val_packer.unpack(vals), found


def find(backend: Backend, spec: HashMapSpec, state: HashMapState,
         keys, capacity: int,
         promise: Promise = Promise.FIND | Promise.INSERT,
         valid: jax.Array | None = None,
         attempts: int = 2,
         speculative: bool = True,
         max_rounds: int = 1,
         transport=None,
         dead_ranks=None,
         integrity: bool = False):
    """Find a batch of keys. Returns (state, values, found(N,)).

    State is returned because the fully-atomic path's read-bit dance
    writes (net-zero) to the status array, exactly like the paper's
    fetch-and-or / fetch-and-and pair.

    With ``speculative`` (the default) a 2-attempt find issues both
    probe attempts as two flows of one ExchangePlan — 2 collectives
    instead of 4 — with identical results to the sequential attempt loop
    (``speculative=False``, the oracle schedule) as long as ``capacity``
    admits every request.  When requests overflow capacity (drops are
    counted, never silent) the two schedules probe different best-effort
    subsets; found keys always carry correct values either way.
    ``Promise.FINE`` in the promise forces the sequential schedule.
    """
    with costs.scope("hashmap.find"):
        validate(promise)
        if fine_grained(promise):
            speculative = False
        klanes = spec.key_packer.pack(keys)
        n = klanes.shape[0]
        if valid is None:
            valid = jnp.ones((n,), bool)

        if local_only(promise):
            gblock = _block_of(spec, klanes, 0)
            _, lblock = _owner_local(spec, gblock)
            found, vlanes = kops.bulk_find(state.tkeys, state.tvals,
                                           state.status, lblock, klanes,
                                           valid, impl=spec.impl)
            costs.record("hashmap.find", costs.Cost(local=n))
            return state, spec.val_packer.unpack(vlanes), found

        atomic = not find_only(promise)
        if speculative and attempts == 2:
            return _find_speculative(backend, spec, state, klanes, capacity,
                                     valid, atomic, max_rounds=max_rounds,
                                     transport=transport,
                                     dead_ranks=dead_ranks,
                                     integrity=integrity)
        pending = valid
        found_all = jnp.zeros((n,), bool)
        vals_all = jnp.zeros((n, spec.val_packer.lanes), _U32)
        for a in range(max(1, attempts)):
            gblock = _block_of(spec, klanes, a)
            owner, lblock = _owner_local(spec, gblock)
            body = jnp.concatenate([lblock.astype(_U32)[:, None], klanes],
                                   axis=1)
            plan = ExchangePlan(name="hashmap.find")
            h = plan.add(body, owner, capacity,
                         reply_lanes=spec.val_packer.lanes + 1, valid=pending,
                         op_name="hashmap.find")
            c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                            transport=transport, dead_ranks=dead_ranks,
                            integrity=integrity)
            res = c.view(h)
            tk, tv, st = state
            if atomic:
                # fetch-and-or a read bit, read, fetch-and-and it away
                rb = jnp.where(res.valid, res.payload[:, 0].astype(_I32), 0)
                st = st.at[rb].add(_READ_BIT, mode="drop")
            found_here, vlanes = kops.bulk_find_arrivals(
                tk, tv, st, res.payload, res.valid, impl=spec.impl)
            if atomic:
                st = st.at[rb].add(_U32(0) - _READ_BIT, mode="drop")
                state = HashMapState(tk, tv, st)
            c.set_reply(h, jnp.concatenate(
                [vlanes, found_here.astype(_U32)[:, None]], axis=1))
            back, _ = c.finish(backend)[h]
            got = (back[:, -1] == 1) & pending
            vals_all = jnp.where(got[:, None], back[:, :-1], vals_all)
            found_all = found_all | got
            pending = pending & ~got
            if attempts == 1:
                break
        costs.record("hashmap.find",
                     costs.Cost(A=2 if atomic else 0, R=n))
        return state, spec.val_packer.unpack(vals_all), found_all


def find_insert(backend: Backend, spec: HashMapSpec, state: HashMapState,
                find_keys, ins_keys, ins_vals, capacity: int,
                promise: Promise = Promise.FIND | Promise.INSERT,
                find_valid: jax.Array | None = None,
                ins_valid: jax.Array | None = None,
                mode: int = kops.MODE_SET,
                max_rounds: int = 1,
                transport=None,
                dead_ranks=None,
                integrity: bool = False,
                async_: bool = False):
    """Fused find + insert sharing ONE exchange round trip.

    Under ``ConProm.HashMap.find_insert`` the two batches are promised
    concurrent, so the runtime may serialize them however it likes; this
    schedule serializes find-before-insert (finds observe the table as
    it was before this batch's insertions) and fuses both ops' flows
    into one ExchangePlan: **2 collectives** per round trip where the
    ``Promise.FINE`` sequential schedule costs **4**, at EXACTLY the
    sum of the two ops' standalone wire bytes — the ragged layout
    (DESIGN.md section 1.5) keeps the narrower find rows and the 1-word
    insert-ok replies at their own widths (both pinned in
    tests/test_wire_format.py).  Both probes use attempt 0; callers
    needing rehash attempts issue the ops separately.

    Returns ``(state, values, found, ins_ok)`` — find results aligned
    with ``find_keys``, insert successes aligned with ``ins_keys``.

    ``async_=True`` issues the plan split-phase (DESIGN.md section 1.9)
    and instead returns a :class:`~repro.core.PendingResult` whose
    ``finish()`` yields the same 4-tuple: the request wire is in flight
    when the call returns, and everything the caller traces before
    ``finish()`` overlaps with it.
    """
    with costs.scope("hashmap.find_insert"):
        validate(promise)
        # per-op atomicity gates mirror the standalone ops exactly, so the
        # FINE oracle and the fused schedule agree on the A counts and the
        # status-word traffic for ANY promise, not just find_insert
        find_atomic = not find_only(promise)
        ins_atomic = fully_atomic_hashmap(promise)
        if fine_grained(promise) and not async_:
            state, vals, found = find(backend, spec, state, find_keys,
                                      capacity, promise=promise,
                                      valid=find_valid, attempts=1,
                                      max_rounds=max_rounds,
                                      transport=transport,
                                      dead_ranks=dead_ranks,
                                      integrity=integrity)
            state, ok = insert(backend, spec, state, ins_keys, ins_vals,
                               capacity,
                               promise=promise, valid=ins_valid, mode=mode,
                               attempts=1, return_success=True,
                               max_rounds=max_rounds, transport=transport,
                               dead_ranks=dead_ranks, integrity=integrity)
            return state, vals, found, ok
        if fine_grained(promise):
            # split-phase FINE stays the sequential oracle: commit eagerly,
            # hand completion back through the same future type
            sync = find_insert(backend, spec, state, find_keys, ins_keys,
                               ins_vals, capacity, promise=promise,
                               find_valid=find_valid, ins_valid=ins_valid,
                               mode=mode, max_rounds=max_rounds,
                               transport=transport, dead_ranks=dead_ranks,
                               integrity=integrity)
            return PendingResult(lambda: sync)

        kf = spec.key_packer.pack(find_keys)
        ki = spec.key_packer.pack(ins_keys)
        vi = spec.val_packer.pack(ins_vals)
        nf, ni = kf.shape[0], ki.shape[0]
        lk = spec.key_packer.lanes
        if find_valid is None:
            find_valid = jnp.ones((nf,), bool)
        if ins_valid is None:
            ins_valid = jnp.ones((ni,), bool)
        owner_f, lb_f = _owner_local(spec, _block_of(spec, kf, 0))
        owner_i, lb_i = _owner_local(spec, _block_of(spec, ki, 0))

        plan = ExchangePlan(name="hashmap.find_insert")
        hf = plan.add(jnp.concatenate([lb_f.astype(_U32)[:, None], kf],
                                      axis=1),
                      owner_f, capacity, reply_lanes=spec.val_packer.lanes + 1,
                      valid=find_valid, op_name="hashmap.find")
        hi = plan.add(jnp.concatenate([lb_i.astype(_U32)[:, None], ki, vi],
                                      axis=1),
                      owner_i, capacity, reply_lanes=1,
                      valid=ins_valid, op_name="hashmap.insert")
        if async_:
            pend = plan.commit_async(backend, impl=spec.impl,
                                     max_rounds=max_rounds,
                                     transport=transport,
                                     dead_ranks=dead_ranks,
                                     integrity=integrity)

            def complete():
                # the completion tail is traced at finish(), outside the
                # scope above: it takes the op's name again
                with costs.scope("hashmap.find_insert"):
                    return _find_insert_complete(
                        backend, spec, state, pend.finish(backend), hf, hi,
                        lk, find_valid, ins_valid, mode, find_atomic,
                        ins_atomic, nf, ni)
            return PendingResult(complete)
        c = plan.commit(backend, impl=spec.impl, max_rounds=max_rounds,
                        transport=transport, dead_ranks=dead_ranks,
                        integrity=integrity)
        return _find_insert_complete(backend, spec, state, c, hf, hi, lk,
                                     find_valid, ins_valid, mode,
                                     find_atomic, ins_atomic, nf, ni)


def _find_insert_complete(backend, spec, state, c, hf, hi, lk,
                          find_valid, ins_valid, mode,
                          find_atomic, ins_atomic, nf, ni):
    """Owner-side work + reply round of :func:`find_insert` (both the
    synchronous and the split-phase path complete through here)."""
    vf, vw = c.view(hf), c.view(hi)

    # find against the pre-insert table (the chosen serialization); both
    # owner-side probes consume their arrival segments directly
    tk, tv, st = state
    if find_atomic:
        rb_f = jnp.where(vf.valid, vf.payload[:, 0].astype(_I32), 0)
        st = st.at[rb_f].add(_READ_BIT, mode="drop")
    found_here, vlanes = kops.bulk_find_arrivals(tk, tv, st, vf.payload,
                                                 vf.valid, impl=spec.impl)
    if find_atomic:
        st = st.at[rb_f].add(_U32(0) - _READ_BIT, mode="drop")

    # insert (same reserve dance as the standalone op)
    if ins_atomic:
        rb_i = jnp.where(vw.valid, vw.payload[:, 0].astype(_I32), 0)
        st = st.at[rb_i].add(_READ_BIT, mode="drop")
        st = st.at[rb_i].add(_U32(0) - _READ_BIT, mode="drop")
    tk, tv, st, ok_here = kops.bulk_insert_arrivals(tk, tv, st, vw.payload,
                                                    vw.valid, mode,
                                                    impl=spec.impl)
    state = HashMapState(tk, tv, st)

    c.set_reply(hf, jnp.concatenate(
        [vlanes, found_here.astype(_U32)[:, None]], axis=1))
    c.set_reply(hi, ok_here.astype(_U32))
    outs = c.finish(backend)
    bf, _ = outs[hf]
    bi, _ = outs[hi]
    found = (bf[:, -1] == 1) & find_valid
    vals = jnp.where(found[:, None], bf[:, :-1], 0)
    ok = (bi[:, 0] == 1) & ins_valid
    costs.record("hashmap.find",
                 costs.Cost(A=2 if find_atomic else 0, R=nf))
    costs.record("hashmap.insert",
                 costs.Cost(A=2 if ins_atomic else 1, W=ni))
    return state, spec.val_packer.unpack(vals), found, ok


def count_ready(backend: Backend, state: HashMapState) -> jax.Array:
    """Global number of occupied buckets."""
    from repro.kernels.ref import READY, bucket_state
    return backend.psum((bucket_state(state.status) == READY).sum())


def local_entries(spec: HashMapSpec, state: HashMapState):
    """This rank's (keys, vals, occupied) — flattened local view."""
    from repro.kernels.ref import READY, bucket_state
    nb, b = state.status.shape
    occ = (bucket_state(state.status) == READY).reshape(-1)
    keys = spec.key_packer.unpack(
        state.tkeys.transpose(0, 2, 1).reshape(nb * b, -1))
    vals = spec.val_packer.unpack(
        state.tvals.transpose(0, 2, 1).reshape(nb * b, -1))
    return keys, vals, occ


def export_state(spec: HashMapSpec, state: HashMapState) -> dict:
    """This rank's table shard as a checkpointable pytree (plain dict).

    The dict rides ``checkpoint.save_checkpoint`` unchanged; after a
    rank loss a survivor restores the dead rank's shard with
    :func:`restore_state` and re-inserts its live entries
    (``local_entries`` of the restored shard) through an ordinary
    ``insert`` — the re-injection path of DESIGN.md section 1.8.
    """
    return {"tkeys": state.tkeys, "tvals": state.tvals,
            "status": state.status}


def restore_state(spec: HashMapSpec, exported: dict) -> HashMapState:
    """Rebuild a HashMapState shard from :func:`export_state` output."""
    tk = jnp.asarray(exported["tkeys"], _U32)
    want = (spec.nblocks_local, spec.key_packer.lanes, spec.block_size)
    if tk.shape != want:
        raise ValueError(
            f"hashmap.restore_state: tkeys shape {tk.shape} does not "
            f"match spec {want}")
    return HashMapState(tk, jnp.asarray(exported["tvals"], _U32),
                        jnp.asarray(exported["status"], _U32))


def resize(backend: Backend, spec: HashMapSpec, state: HashMapState,
           new_capacity: int, capacity_per_pair: int):
    """Collective resize (paper 5.1.5): rebuild and re-insert all entries."""
    backend.barrier()
    new_spec, new_state = hashmap_create(
        backend, new_capacity,
        spec.key_packer, spec.val_packer, spec.block_size, spec.impl)
    keys, vals, occ = local_entries(spec, state)
    new_state, _ = insert(backend, new_spec, new_state, keys, vals,
                          capacity_per_pair, valid=occ,
                          promise=Promise.INSERT, attempts=3)
    costs.record("hashmap.resize",
                 costs.Cost(B=1, W=int(occ.shape[0])))
    return new_spec, new_state
