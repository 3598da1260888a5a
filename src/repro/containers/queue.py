"""BCL queues (paper section 5.2): FastQueue and CircularQueue.

Both are *hosted* ring buffers: every rank hosts one ring, and any rank
may push to / pop from any ring (a single-host queue is the special case
where all traffic targets one rank; the "many" pattern of the paper's
microbenchmarks is the general case).

RDMA BCL reserves ring slots with remote fetch-and-add.  Here the
reservation is owner-side: routed items arrive in a deterministic order
(source rank, then source position), and an exclusive prefix sum over
the arrivals assigns disjoint slots — associative fetch-and-add.

Remote ops lower through the ExchangePlan scheduler (DESIGN.md
section 1.5): ``push``/``pop`` are eager single-flow plans, and
``push_pop`` — the ``ConProm.CircularQueue.push_pop`` promise made
operational — fuses both ops' flows into one collective round trip
(``Promise.FINE`` recovers the sequential schedule).

Cost model (paper Table 2):
  FastQueue      push = A + nW     pop = A + nR
  CircularQueue  push = 2A + nW    pop = 2A + nR   (extra AMO maintains
                 the ready cursors that make concurrent push/pop safe)
  local_nonatomic_pop = l           resize = B + l   migrate = B + nW
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.backend import Backend
from repro.core.exchange import ExchangePlan, PendingResult, route
from repro.core.object_container import Packer, packer_for
from repro.core.promises import (Promise, fine_grained, fully_atomic_queue,
                                 validate)

_U32 = jnp.uint32
_I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class QueueSpec:
    capacity: int          # ring capacity per host rank
    packer: Packer
    circular: bool = False  # CircularQueue: maintains ready cursors

    @property
    def lanes(self) -> int:
        return self.packer.lanes


class QueueState(NamedTuple):
    data: jax.Array        # (capacity, L) u32
    head: jax.Array        # (1,) i32 — monotone pop cursor
    tail: jax.Array        # (1,) i32 — monotone push cursor
    tail_ready: jax.Array  # (1,) i32 — CircularQueue publish cursor
    head_ready: jax.Array  # (1,) i32


def queue_create(backend: Backend, capacity: int, value_spec,
                 circular: bool = False) -> tuple[QueueSpec, QueueState]:
    with costs.scope("queue.create"):
        packer = packer_for(value_spec)
        spec = QueueSpec(capacity, packer, circular)
        z = lambda: jnp.zeros((1,), _I32)
        state = QueueState(jnp.zeros((capacity, packer.lanes), _U32),
                           z(), z(), z(), z())
        return spec, state


def size(state: QueueState) -> jax.Array:
    return (state.tail - state.head)[0]


def _amo_count(spec: QueueSpec, promise: Promise) -> int:
    """AMOs per op per the paper's Tables 2/4."""
    if promise & Promise.LOCAL:
        return 0
    return 2 if spec.circular else 1


def push(backend: Backend, spec: QueueSpec, state: QueueState,
         values, dest: jax.Array, capacity: int,
         valid: jax.Array | None = None,
         promise: Promise = Promise.PUSH,
         max_rounds: int = 1,
         overflow: str = "drop",
         transport=None,
         dead_ranks=None,
         integrity: bool = False,
         impl: str = "auto"):
    """Push each value to the ring hosted on ``dest[i]``.

    Returns (state, pushed_here, dropped):
      pushed_here  items this rank's ring accepted
      dropped      global count rejected (route overflow or ring full)

    ``max_rounds=R`` retries wire overflow with carryover rounds — an
    all-to-one or zipf-skewed destination pattern keeps every item as
    long as the hottest (src,dst) pair stays under R*capacity.

    ``overflow="carry"`` closes the LAST loss path — ring-full rejects
    (DESIGN.md section 1.6).  The push then declares a 1-lane reply
    carrying the owner's per-arrival acceptance bit back over the
    inverse all-to-all, and the return value grows to
    ``(state, pushed_here, dropped=0, carry)``: ``carry`` marks, in the
    ORIGINAL batch, every valid item that either never shipped (wire
    overflow beyond all retry rounds) or shipped and was refused by a
    full ring.  The caller re-injects exactly those rows next cycle —
    nothing is dropped, at the price of the reply collective a
    fire-and-forget push normally skips.  A LOCAL push honors the same
    4-tuple contract straight from its local accept mask, with zero
    collectives.

    ``dead_ranks``/``integrity``/``impl`` pass straight to
    :meth:`ExchangePlan.commit` (DESIGN.md sections 1.8/1.10): items bound for
    a dead rank are masked at admission (reappearing in ``carry`` so a
    caller can re-target them), and with ``integrity=True`` arrivals
    whose wire segment fails its checksum are invalidated — under
    ``overflow="carry"`` such items never receive an accept ack, so the
    carry mask re-injects them and a retry heals transient corruption.
    """
    with costs.scope("queue.push"):
        validate(promise)
        if overflow not in ("drop", "carry"):
            raise ValueError(
                f'queue.push overflow must be "drop" or "carry", '
                f"got {overflow!r}")
        lanes = spec.packer.pack(values)
        n = lanes.shape[0]
        if valid is None:
            valid = jnp.ones((n,), bool)

        if promise & Promise.LOCAL:
            # local push: no collectives, CPU-only ring append (paper 4c);
            # carry needs no reply wire here — the accept mask IS local
            costs.record("queue.push", costs.Cost(local=n))
            state, pushed, full_drop, accept = _append(spec, state, lanes,
                                                       valid)
            if overflow == "carry":
                return state, pushed, jnp.int32(0), valid & ~accept
            return state, pushed, full_drop

        if overflow == "carry":
            plan = ExchangePlan(name="queue.push")
            h = plan.add(lanes, dest, capacity, reply_lanes=1, valid=valid,
                         op_name="queue.push")
            c = plan.commit(backend, impl=impl, max_rounds=max_rounds,
                            transport=transport, dead_ranks=dead_ranks,
                            integrity=integrity)
            res = c.view(h)
            state, pushed, _, accept = _append(spec, state, res.payload,
                                               res.valid)
            c.set_reply(h, accept.astype(_U32))
            out, answered = c.finish(backend)[h]
            a = _amo_count(spec, promise)
            costs.record("queue.push", costs.Cost(A=a, W=n))
            landed = answered & (out[:, 0] == 1) & valid
            return state, pushed, jnp.int32(0), valid & ~landed

        res = route(backend, lanes, dest, capacity, valid=valid,
                    op_name="queue.push", impl=impl, max_rounds=max_rounds,
                    transport=transport, dead_ranks=dead_ranks,
                    integrity=integrity)
        state, pushed, full_drop, _ = _append(spec, state, res.payload,
                                              res.valid)
        a = _amo_count(spec, promise)
        costs.record("queue.push", costs.Cost(A=a, W=n))
        dropped = res.dropped + backend.psum(full_drop)
        return state, pushed, dropped


def _append(spec: QueueSpec, state: QueueState, rows: jax.Array,
            valid: jax.Array):
    """Owner-side ring append in deterministic arrival order.

    Returns ``(state, n_accepted, n_rejected, accept)``; ``accept`` is
    the per-arrival acceptance mask in wire order — exactly the rows a
    reply-side carry (``push(overflow="carry")``) reports back so
    ring-full rejects are re-injected instead of lost.
    """
    pos = jnp.cumsum(valid.astype(_I32)) - valid.astype(_I32)  # exclusive
    total = valid.sum().astype(_I32)
    used = (state.tail - state.head)[0]
    room = jnp.maximum(spec.capacity - used, 0)
    accept = valid & (pos < room)
    n_acc = jnp.minimum(total, room)
    slot = jnp.where(accept, (state.tail[0] + pos) % spec.capacity,
                     spec.capacity)
    data = state.data.at[slot].set(rows, mode="drop")
    tail = state.tail + n_acc
    tail_ready = tail if spec.circular else state.tail_ready
    new = QueueState(data, state.head, tail, tail_ready, state.head_ready)
    return new, n_acc, (total - n_acc), accept


def _grant(spec: QueueSpec, state: QueueState, req_valid: jax.Array,
           promise: Promise):
    """Owner-side pop grant in deterministic arrival order (FAA analogue).

    Returns ``(new_state, body)`` where ``body`` rows are
    ``[value lanes | granted flag]`` aligned with the request arrivals.
    """
    arrival = jnp.cumsum(req_valid.astype(_I32)) - req_valid.astype(_I32)
    limit = state.tail[0] - state.head[0]
    if spec.circular and fully_atomic_queue(promise):
        limit = state.tail_ready[0] - state.head[0]
    grant = req_valid & (arrival < limit)
    idx = jnp.where(grant, (state.head[0] + arrival) % spec.capacity, 0)
    rows = jnp.where(grant[:, None], state.data[idx], 0)
    n_grant = jnp.minimum(req_valid.sum().astype(_I32), limit)
    head = state.head + n_grant
    head_ready = head if spec.circular else state.head_ready
    new = QueueState(state.data, head, state.tail, state.tail_ready,
                     head_ready)
    body = jnp.concatenate([rows, grant.astype(_U32)[:, None]], axis=1)
    return new, body


def _src_ranks(src: jax.Array | int, n: int) -> jax.Array:
    if isinstance(src, int):
        return jnp.full((n,), src, _I32)
    if src.ndim == 0:
        return jnp.broadcast_to(src, (n,)).astype(_I32)
    return src.astype(_I32)


def pop(backend: Backend, spec: QueueSpec, state: QueueState,
        n: int, src: jax.Array | int,
        promise: Promise = Promise.POP,
        max_rounds: int = 1,
        transport=None,
        dead_ranks=None,
        integrity: bool = False,
        impl: str = "auto"):
    """Pop up to ``n`` items from the ring hosted on rank ``src``.

    Every rank issues its own request; the owner grants ranges in
    deterministic requester order (the FAA analogue).  Returns
    (state, values, got_mask).
    """
    with costs.scope("queue.pop"):
        validate(promise)
        src = _src_ranks(src, n)

        if promise & Promise.LOCAL:
            return local_nonatomic_pop(spec, state, n)

        # unit requests: one row per wanted item (per-(src,dst) capacity = n);
        # a single-flow plan so the grant reply rides the transport's exact
        # inverse hop sequence (dense: the one inverse all-to-all)
        plan = ExchangePlan(name="queue.pop")
        h = plan.add(jnp.zeros((n, 1), _U32), src, n,
                     reply_lanes=spec.lanes + 1, op_name="queue.pop")
        c = plan.commit(backend, impl=impl, max_rounds=max_rounds,
                        transport=transport, dead_ranks=dead_ranks,
                        integrity=integrity)
        req = c.view(h)
        new, body = _grant(spec, state, req.valid, promise)
        c.set_reply(h, body)
        out, _ = c.finish(backend)[h]
        got = out[:, -1] == 1
        values = spec.packer.unpack(out[:, :-1])
        a = _amo_count(spec, promise)
        costs.record("queue.pop", costs.Cost(A=a, R=n))
        return new, values, got


def push_pop(backend: Backend, spec: QueueSpec, state: QueueState,
             values, dest: jax.Array, capacity: int,
             n: int, src: jax.Array | int,
             valid: jax.Array | None = None,
             promise: Promise = Promise.PUSH | Promise.POP,
             max_rounds: int = 1,
             overflow: str = "drop",
             transport=None,
             dead_ranks=None,
             integrity: bool = False,
             async_: bool = False,
             impl: str = "auto"):
    """Fused push + pop sharing ONE exchange round trip.

    Under ``ConProm.CircularQueue.push_pop`` the two ops are promised
    concurrent, so the runtime may serialize them; this schedule applies
    the push before granting the pop (items pushed this round are
    poppable this round) and fuses both ops' flows into one
    ExchangePlan: 2 collectives where the ``Promise.FINE`` sequential
    schedule costs 3 (push has no reply).  The ragged wire (DESIGN.md
    section 1.5) keeps the pop's unit requests at 2 u32 words per row
    no matter how wide the pushed values are — fusing costs exactly the
    two ops' standalone bytes.  Returns
    ``(state, pushed, dropped, out_values, got)``.

    ``overflow="carry"`` gives the fused push the same ring-full
    backpressure as ``push(overflow="carry")`` (DESIGN.md section 1.6):
    the push flow declares a 1-lane reply carrying the owner's
    ``_append`` accept mask — it rides the pop's inverse all-to-all, so
    the carry costs ZERO extra collectives here — and the return grows
    to ``(state, pushed, dropped=0, out_values, got, carry)`` where
    ``carry`` marks every valid item that never shipped or was refused
    by a full ring.

    ``async_=True`` issues the plan split-phase (DESIGN.md section 1.9)
    and instead returns a :class:`~repro.core.PendingResult` whose
    ``finish()`` yields the same tuple — the request wire overlaps with
    whatever the caller traces before finishing.
    """
    with costs.scope("queue.push_pop"):
        validate(promise)
        if overflow not in ("drop", "carry"):
            raise ValueError(
                f'queue.push_pop overflow must be "drop" or "carry", '
                f"got {overflow!r}")
        if async_ and fine_grained(promise):
            # split-phase FINE stays the sequential oracle: run eagerly,
            # hand completion back through the same future type
            sync = push_pop(backend, spec, state, values, dest, capacity, n,
                            src, valid=valid, promise=promise,
                            max_rounds=max_rounds, overflow=overflow,
                            transport=transport, dead_ranks=dead_ranks,
                            integrity=integrity, impl=impl)
            return PendingResult(lambda: sync)
        if fine_grained(promise):
            if overflow == "carry":
                state, pushed, dropped, carry = push(
                    backend, spec, state, values, dest, capacity, valid=valid,
                    promise=promise, max_rounds=max_rounds, overflow="carry",
                    transport=transport, dead_ranks=dead_ranks,
                    integrity=integrity, impl=impl)
                state, out, got = pop(backend, spec, state, n, src,
                                      promise=promise, max_rounds=max_rounds,
                                      transport=transport,
                                      dead_ranks=dead_ranks,
                                      integrity=integrity, impl=impl)
                return state, pushed, dropped, out, got, carry
            state, pushed, dropped = push(backend, spec, state, values, dest,
                                          capacity, valid=valid,
                                          promise=promise,
                                          max_rounds=max_rounds,
                                          transport=transport,
                                          dead_ranks=dead_ranks,
                                          integrity=integrity, impl=impl)
            state, out, got = pop(backend, spec, state, n, src,
                                  promise=promise, max_rounds=max_rounds,
                                  transport=transport,
                                  dead_ranks=dead_ranks, integrity=integrity,
                                  impl=impl)
            return state, pushed, dropped, out, got

        lanes = spec.packer.pack(values)
        nv = lanes.shape[0]
        if valid is None:
            valid = jnp.ones((nv,), bool)
        src = _src_ranks(src, n)
        carrying = overflow == "carry"

        plan = ExchangePlan(name="queue.push_pop")
        hp = plan.add(lanes, dest, capacity, valid=valid,
                      reply_lanes=1 if carrying else 0, op_name="queue.push")
        hq = plan.add(jnp.zeros((n, 1), _U32), src, n,
                      reply_lanes=spec.lanes + 1, op_name="queue.pop")
        if async_:
            pend = plan.commit_async(backend, impl=impl,
                                     max_rounds=max_rounds,
                                     transport=transport,
                                     dead_ranks=dead_ranks,
                                     integrity=integrity)

            def complete():
                # the completion tail is traced at finish(), outside the
                # scope above: it takes the op's name again
                with costs.scope("queue.push_pop"):
                    return _push_pop_complete(
                        backend, spec, state, pend.finish(backend), hp, hq,
                        valid, promise, carrying, nv, n)
            return PendingResult(complete)
        c = plan.commit(backend, impl=impl, max_rounds=max_rounds,
                        transport=transport, dead_ranks=dead_ranks,
                        integrity=integrity)
        return _push_pop_complete(backend, spec, state, c, hp, hq, valid,
                                  promise, carrying, nv, n)


def _push_pop_complete(backend, spec, state, c, hp, hq, valid, promise,
                       carrying, nv, n):
    """Owner-side work + reply round of :func:`push_pop` (both the
    synchronous and the split-phase path complete through here)."""
    vp, vq = c.view(hp), c.view(hq)

    state, pushed, full_drop, accept = _append(spec, state, vp.payload,
                                               vp.valid)
    state, body = _grant(spec, state, vq.valid, promise)
    if carrying:
        c.set_reply(hp, accept.astype(_U32))
    c.set_reply(hq, body)
    outs = c.finish(backend)
    out, _ = outs[hq]
    got = out[:, -1] == 1
    out_values = spec.packer.unpack(out[:, :-1])
    a = _amo_count(spec, promise)
    costs.record("queue.push", costs.Cost(A=a, W=nv))
    costs.record("queue.pop", costs.Cost(A=a, R=n))
    if carrying:
        outp, answered = outs[hp]
        landed = answered & (outp[:, 0] == 1) & valid
        return (state, pushed, jnp.int32(0), out_values, got,
                valid & ~landed)
    dropped = vp.dropped + backend.psum(full_drop)
    return state, pushed, dropped, out_values, got


def local_nonatomic_pop(spec: QueueSpec, state: QueueState, n: int):
    """Pop n items from this rank's own ring; no collectives (paper 4f)."""
    avail = state.tail[0] - state.head[0]
    take = jnp.arange(n, dtype=_I32)
    got = take < avail
    idx = jnp.where(got, (state.head[0] + take) % spec.capacity, 0)
    rows = jnp.where(got[:, None], state.data[idx], 0)
    n_got = jnp.minimum(jnp.int32(n), avail)
    head = state.head + n_got
    head_ready = head if spec.circular else state.head_ready
    new = QueueState(state.data, head, state.tail, state.tail_ready,
                     head_ready)
    costs.record("queue.local_nonatomic_pop", costs.Cost(local=n))
    return new, spec.packer.unpack(rows), got


def local_drain(spec: QueueSpec, state: QueueState):
    """Read the whole local ring in FIFO order (the ``as_vector`` of the
    paper's Fig. 3); state unchanged.  Returns (rows, valid)."""
    with costs.scope("queue.drain"):
        take = jnp.arange(spec.capacity, dtype=_I32)
        avail = state.tail[0] - state.head[0]
        got = take < avail
        idx = (state.head[0] + take) % spec.capacity
        rows = jnp.where(got[:, None], state.data[idx], 0)
        return spec.packer.unpack(rows), got


def export_state(spec: QueueSpec, state: QueueState) -> dict:
    """This rank's ring as a checkpointable pytree (plain dict of arrays).

    The dict rides ``checkpoint.save_checkpoint`` unchanged; a survivor
    restores a dead rank's shard with :func:`restore_state` and
    re-injects its live rows (``local_drain`` of the restored state)
    through an ordinary ``push`` — the recovery path of DESIGN.md
    section 1.8.
    """
    return {"data": state.data, "head": state.head, "tail": state.tail,
            "tail_ready": state.tail_ready, "head_ready": state.head_ready}


def restore_state(spec: QueueSpec, exported: dict) -> QueueState:
    """Rebuild a QueueState from :func:`export_state` output."""
    data = jnp.asarray(exported["data"], _U32)
    if data.shape != (spec.capacity, spec.lanes):
        raise ValueError(
            f"queue.restore_state: data shape {data.shape} does not match "
            f"spec (capacity={spec.capacity}, lanes={spec.lanes})")
    as_i32 = lambda k: jnp.asarray(exported[k], _I32).reshape((1,))
    return QueueState(data, as_i32("head"), as_i32("tail"),
                      as_i32("tail_ready"), as_i32("head_ready"))


def resize(backend: Backend, spec: QueueSpec, state: QueueState,
           new_capacity: int) -> tuple[QueueSpec, QueueState]:
    """Collective resize (paper cost B + l)."""
    backend.barrier()
    rows, got = local_drain(spec, state)
    lanes = spec.packer.pack(rows)
    new_spec = dataclasses.replace(spec, capacity=new_capacity)
    m = jnp.minimum((state.tail - state.head)[0], new_capacity)
    take = jnp.arange(spec.capacity, dtype=_I32)
    data = jnp.zeros((new_capacity, spec.lanes), _U32)
    data = data.at[jnp.where(got & (take < m), take, new_capacity)].set(
        lanes, mode="drop")
    z = jnp.zeros((1,), _I32)
    tail = m[None]
    costs.record("queue.resize", costs.Cost(B=1, local=int(spec.capacity)))
    return new_spec, QueueState(data, z, tail,
                                tail if spec.circular else z, z)


def migrate(backend: Backend, spec: QueueSpec, state: QueueState,
            shift: int = 1) -> QueueState:
    """Collective migration: ring moves to (rank + shift) % P (B + nW)."""
    nprocs = backend.nprocs()
    if nprocs == 1:
        return state
    backend.barrier()
    perm = [(i, (i + shift) % nprocs) for i in range(nprocs)]
    moved = jax.tree_util.tree_map(lambda x: backend.ppermute(x, perm), state)
    costs.record("queue.migrate", costs.Cost(B=1, W=int(spec.capacity)))
    return moved
