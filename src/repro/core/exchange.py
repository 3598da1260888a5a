"""The many-to-many exchange engine — the heart of the TPU port.

Paper section 4.2 identifies "asynchronous many-to-many redistribution"
as the parallel pattern behind queues, buffered hash-table insertion, and
the ISx bucket sort.  On RDMA hardware BCL realizes it as: buffer locally
per destination -> fetch-and-add reserves remote slots -> RDMA put.

On TPU the same pattern is one fused collective program:

  1. bin items by destination rank          (histogram + per-tile prefix +
                                             slot scatter — a Pallas
                                             kernel, no argsort)
  2. reserve slots                          (exclusive prefix sums — the
                                             associative, contention-free
                                             analogue of fetch-and-add)
  3. pad each destination bucket to a
     static capacity C                      (SPMD shapes are static)
  4. one tiled all-to-all moves everything  (latency-bound -> bandwidth-
                                             bound, which is exactly the
                                             HashMapBuffer insight)
  5. unmask on the owner

Scheduling is two-phase (DESIGN.md section 1.5): callers register typed
*flows* on an :class:`ExchangePlan` (``plan.add(payload, dest, capacity,
reply_lanes, op_name)``), and ``plan.commit(backend)`` concatenates all
same-round flows lane-wise into ONE binning pass and ONE tiled
all-to-all, demultiplexing per-flow owner views; replies from every flow
share one inverse all-to-all (``plan.finish``).  This is the paper's
concurrency-promise story made operational: a promise names which ops
may run concurrently, and concurrent ops are exactly the ops whose
flows may share a collective round.  ``Promise.FINE`` on the plan
forces the sequential one-op-per-round schedule — the oracle every
fused path is tested against.

``route``/``reply`` remain as thin single-flow wrappers, so a container
op that has nothing to fuse with still compiles to the same program it
always did.

Wire format (DESIGN.md section 1): payloads are u32 lane matrices (see
object_container.py), and the fused wire is *ragged*: per destination
rank, the request buffer is a flat u32 word vector in which each flow
owns one contiguous segment of exactly ``C_f * (L_f + 1)`` words — rows
of flow f are ``L_f + 1`` words wide, the last word being the flow's
metadata lane (bit 31 the valid flag, low 31 bits the item's position
in its flow's batch).  No flow pays another flow's width: a plan's
request bytes equal the SUM of its flows' single-flow ``route()``
bytes, which is what makes fusion unconditionally profitable.  Reply
segments are likewise exactly ``R_f`` words per row and zero metadata:
the owner's receive layout is the exact image of the requesters' send
layout under the all-to-all, so writing replies into segment-order
rows and running one more all-to-all is an *inverse permutation* that
lands every reply back in the requester's original send slot.  The
requester resolves slots to batch positions from purely local state
captured at commit time; no binning, no argsort, and no src_pos lane
in the reply direction.

The *physical* movement behind commit/finish is pluggable (DESIGN.md
section 1.7): the plan computes the logical exchange — the ONE binning
pass, admission, ragged layout, send maps — and hands movement to a
:class:`repro.core.transport.Transport`.  ``DenseTransport`` (the
default) is the one-shot tiled all-to-all described above;
``HierarchicalTransport`` factors the rank axis ``P = Pr x Pc`` and
moves everything in two sqrt(P)-peer stages with a relay re-binning
hop, bit-identical to dense whenever its stage capacities admit the
dense-admitted traffic.  Containers thread a ``transport=`` knob;
``None`` keeps the dense program byte-for-byte.

Shapes and capacities are static; what happens beyond a flow's capacity
is governed by the plan's ``overflow`` policy (DESIGN.md section 1.6).
RDMA BCL retries a failed fetch-and-add; the static-shape analogue is
*carryover retry rounds*: ``commit(max_rounds=R)`` ships, in round
``r``, exactly the items whose within-(dest, flow)-bucket rank from the
SINGLE binning pass falls in ``[r*C_f, (r+1)*C_f)`` — the retry rounds
are pure extra all-to-alls whose masks are derived from the offsets
already computed, with no second binning pass.  Owner views concatenate
the rounds to an effective capacity ``R*C_f`` (row ``s*(R*C_f) + o``
holds rank-``o`` arrivals from rank ``s`` — bit-identical to a single
round at capacity ``R*C_f``), the reply stays ONE inverse all-to-all
(just ``R`` times wider), and ``dropped`` counts only items whose rank
is ``>= R*C_f``.  Residual overflow is then dropped-and-counted
(``overflow="drop"``), raised on eagerly (``"raise-in-test"``), or
handed back to the caller as a re-injection mask
(``"carry"``/:meth:`CommittedPlan.leftover` — the HashMapBuffer flush
path re-stages leftovers exactly like the paper's failed-insert
re-insertion loop).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.backend import Backend
from repro.core.promises import Promise, fine_grained, validate
from repro.core.transport import (DENSE, FlowWire, RequestArgs, Transport,
                                  _DenseCtx, make_transport)
from repro.kernels import ops as kops

_U32 = jnp.uint32
_I32 = jnp.int32

# metadata lane: bit 31 = valid, bits 0..30 = src_pos
_VALID_BIT = jnp.uint32(1 << 31)
_POS_MASK = jnp.uint32((1 << 31) - 1)

#: salt added to every wire checksum word so an intact-but-empty window
#: (stored = SALT + 0) is distinguishable from a zeroed/lost segment
#: (stored = 0, checksum row's own meta lane also zeroed)
_CK_SALT = jnp.uint32(0x9E3779B9)

#: legal ``overflow=`` policies (DESIGN.md section 1.6)
OVERFLOW_POLICIES = ("drop", "raise-in-test", "carry")


class ExchangeOverflowError(RuntimeError):
    """Raised by ``overflow="raise-in-test"`` when a flow drops items.

    Only raised when drop counts are concrete (eager execution — the
    test/debug regime the policy is named for); under ``jit`` tracing
    the counts are tracers and the policy degrades to ``"drop"``.
    """


class RouteResult(NamedTuple):
    """Owner-side view of a routed flow (+ requester-local slot map).

    payload   (P*C, L) u32 — rows [s*C:(s+1)*C] arrived from rank s
    valid     (P*C,) bool  — which rows hold real items
    src_rank  (P*C,) i32   — originating rank (derived from slot position)
    src_pos   (P*C,) i32   — item's index in the sender's original batch
    dropped   () i32       — items dropped for capacity overflow (global)
    capacity  int          — static EFFECTIVE per-(src,dst) capacity: the
                             flow's declared C times the plan's
                             ``max_rounds`` (retry rounds concatenate)
    send_item (P*C,) i32   — requester-local: original batch index this
                             rank placed in each of its own send slots,
                             in flow-local coordinates (sentinel N when
                             the slot was empty); identical whether the
                             flow was routed eagerly or as a segment of
                             a fused plan
    send_occ  (P*C,) bool  — requester-local send-slot occupancy; the
                             reply path's ``answered`` comes from here,
                             not from the wire
    lost      () i32       — items shipped but NOT surviving arrival
                             (global): wire windows whose integrity
                             check failed, plus anything a faulty or
                             under-provisioned transport lost in
                             flight.  Always 0 unless the plan was
                             committed with ``integrity=True``
                             (DESIGN.md section 1.8); such items are
                             healed by the caller's ack-driven carry
                             path, never silently consumed
    """

    payload: jax.Array
    valid: jax.Array
    src_rank: jax.Array
    src_pos: jax.Array
    dropped: jax.Array
    capacity: int
    send_item: jax.Array
    send_occ: jax.Array
    lost: jax.Array | int = 0


@dataclasses.dataclass
class _Flow:
    """One registered flow of an ExchangePlan (trace-time record)."""

    payload: jax.Array        # (N, L) u32
    dest: jax.Array           # (N,) i32
    capacity: int             # per-(src,dst) slot count C_f
    valid: jax.Array          # (N,) bool
    op_name: str
    reply_lanes: int          # 0 = fire-and-forget (no reply expected)
    max_rounds: int | None = None   # per-flow override; None = plan-wide

    @property
    def n(self) -> int:
        return self.payload.shape[0]

    @property
    def lanes(self) -> int:
        return self.payload.shape[1]


def _flow_rounds(f: _Flow, plan_rounds: int) -> int:
    """Effective retry rounds for one flow.

    The flow-level ``max_rounds`` (if set) overrides the plan-wide
    knob, and the result is clamped to ``ceil(N_f / C_f)``: no
    (dest, flow) bucket can ever hold more than the flow's N items, so
    rounds past that bound could never ship anything new — an
    exact-capacity flow (queue.pop's unit requests, MoE's stats flow)
    stays at ONE launch no matter what the plan requests, instead of
    paying R-fold wire for nothing.
    """
    r = plan_rounds if f.max_rounds is None else f.max_rounds
    return max(1, min(int(r), -(-f.n // f.capacity)))


class ExchangePlan:
    """Two-phase scheduler fusing concurrent container ops' collectives.

    Usage::

        plan = ExchangePlan(name="hashmap.find_insert")
        h_f = plan.add(find_body, owners_f, cap, reply_lanes=Lv + 1,
                       op_name="hashmap.find")
        h_i = plan.add(ins_body, owners_i, cap, reply_lanes=1,
                       op_name="hashmap.insert")
        c = plan.commit(backend)          # ONE all-to-all for all flows
        ... owner-side work on c.view(h_f), c.view(h_i) ...
        c.set_reply(h_f, find_replies)
        c.set_reply(h_i, ok_bits)
        outs = c.finish(backend)          # ONE inverse all-to-all
        find_out, find_answered = outs[h_f]

    Cost attribution (DESIGN.md section 1.5): each flow is charged the
    EXACT bytes of its own ragged wire segment — ``P * C_f * (L_f+1) * 4``
    out, ``P * C_f * R_f * 4`` back, identical to a single-flow
    ``route``/``reply`` — under its ``op_name``; the single physical
    collective and its round are charged once, under ``name`` (default:
    the first flow's op).

    A plan constructed with ``promise=Promise.FINE`` lowers to the
    sequential one-op-per-round schedule instead (one ``route`` and one
    ``reply`` per flow) — the semantic oracle for the fused schedule.
    """

    def __init__(self, promise: Promise = Promise.NONE,
                 name: str | None = None):
        validate(promise)
        self.promise = promise
        self.name = name
        self._flows: list[_Flow] = []
        self._committed = False

    def add(self, payload: jax.Array, dest: jax.Array, capacity: int,
            reply_lanes: int = 0, valid: jax.Array | None = None,
            op_name: str = "flow", max_rounds: int | None = None) -> int:
        """Register a flow; returns its handle (index into the plan).

        Shape/capacity mistakes are caught HERE, named after the flow's
        ``op_name`` — not three layers down as an opaque concatenate or
        reshape error inside the fused lowering.  ``max_rounds``
        overrides the plan-wide retry-round knob for THIS flow (e.g. an
        exactly-sized flow declares 1 so it never rides retry launches);
        either way the effective count clamps to ``ceil(N / capacity)``.
        """
        if self._committed:
            raise ValueError(
                "add() after commit(): the round's flows are already on "
                "the wire; build a new ExchangePlan for the next round")
        if payload.ndim not in (1, 2):
            raise ValueError(
                f"flow '{op_name}': payload must be (N,) or (N, L) u32 "
                f"lanes, got ndim={payload.ndim}")
        if payload.ndim == 1:
            payload = payload[:, None]
        payload = payload.astype(_U32)
        n = payload.shape[0]
        if dest.ndim != 1 or dest.shape[0] != n:
            raise ValueError(
                f"flow '{op_name}': dest must be ({n},) to match the "
                f"payload's {n} rows, got shape {tuple(dest.shape)}")
        if int(capacity) <= 0:
            raise ValueError(
                f"flow '{op_name}': capacity must be a positive static "
                f"per-(src,dst) slot count, got {capacity}")
        if int(reply_lanes) < 0:
            raise ValueError(
                f"flow '{op_name}': reply_lanes must be >= 0, "
                f"got {reply_lanes}")
        if valid is None:
            valid = jnp.ones((n,), bool)
        elif valid.ndim != 1 or valid.shape[0] != n:
            raise ValueError(
                f"flow '{op_name}': valid must be ({n},) bool to match "
                f"the payload's {n} rows, got shape {tuple(valid.shape)}")
        if max_rounds is not None and int(max_rounds) < 1:
            raise ValueError(
                f"flow '{op_name}': max_rounds must be >= 1, "
                f"got {max_rounds}")
        self._flows.append(_Flow(payload, dest.astype(_I32), int(capacity),
                                 valid, op_name, int(reply_lanes),
                                 None if max_rounds is None
                                 else int(max_rounds)))
        return len(self._flows) - 1

    def commit(self, backend: Backend, impl: str = "auto",
               max_rounds: int = 1,
               overflow: str = "drop",
               transport: Transport | str | None = None,
               dead_ranks: tuple[int, ...] | None = None,
               integrity: bool = False) -> "CommittedPlan":
        """Issue the request round: one fused all-to-all for all flows.

        ``max_rounds=R`` adds R-1 carryover retry rounds: retry round r
        re-ships the items whose within-bucket rank from the single
        binning pass falls in ``[r*C_f, (r+1)*C_f)``, so owner views see
        an effective capacity of ``R*C_f`` per flow and only rank
        ``>= R*C_f`` counts as dropped.  ``overflow`` picks the residual
        policy: ``"drop"`` (count only), ``"raise-in-test"`` (raise
        :class:`ExchangeOverflowError` when counts are concrete), or
        ``"carry"`` (leftovers stay available via
        :meth:`CommittedPlan.leftover` for caller re-injection).
        ``transport`` picks the physical collective layer (DESIGN.md
        section 1.7): ``None``/``"dense"`` is the one-shot tiled
        all-to-all, ``"hier"`` the two-stage Pr x Pc exchange; a
        :class:`~repro.core.transport.Transport` instance passes
        through.  The logical semantics — admission, owner layout,
        drops, send maps — are transport-independent.

        Degraded operation (DESIGN.md section 1.8): ``dead_ranks`` is a
        static tuple of ranks known to be down; traffic addressed to
        them is masked at admission and handed back as carry-compatible
        leftovers (:meth:`CommittedPlan.unreachable`) instead of being
        shipped into the void, with ``unreachable``/``lost_bytes``
        observables recorded in :mod:`repro.core.costs`.
        ``integrity=True`` appends a synthetic checksum flow to the
        wire (one u32 word per (dest, round, flow) window, riding the
        same launches); windows whose checksum fails verification on
        arrival are invalidated wholesale and surfaced as the per-flow
        ``lost`` count on the views, so corruption feeds the caller's
        ack/carry retry path instead of poisoning owner state.  Both
        default off, leaving the wire byte-identical to a plain commit.
        """
        with costs.scope("exchange.commit"):
            dead, transport = self._precommit(backend, max_rounds, overflow,
                                              dead_ranks, transport)
            if fine_grained(self.promise):
                return self._commit_fine(backend, impl, int(max_rounds),
                                         overflow, transport, dead, integrity)
            st = self._stage_fused(backend, impl, int(max_rounds), overflow,
                                   transport, dead, integrity)
            with costs.scope("transport.request"):
                segments, extra_drop, tctx = transport.request(backend,
                                                               st.args)
            return self._finalize_fused(backend, st, segments, extra_drop,
                                        tctx, transport)

    def commit_async(self, backend: Backend, impl: str = "auto",
                     max_rounds: int = 1,
                     overflow: str = "drop",
                     transport: Transport | str | None = None,
                     dead_ranks: tuple[int, ...] | None = None,
                     integrity: bool = False) -> "PendingPlan":
        """Split-phase :meth:`commit`: start the wire, defer completion.

        Issues the request's collectives through the transport's
        ``request_start`` and returns a :class:`PendingPlan`; the caller
        traces independent compute in the window before calling
        ``finish()``, which completes the transport wait and yields the
        same :class:`CommittedPlan` a synchronous commit would have —
        bit-identical views, drops, and send maps (DESIGN.md §1.9).
        Retry rounds are double-buffered for free: every round's launch
        is issued at start, so round ``r+1``'s all-to-all is already in
        flight while round ``r``'s arrivals are processed at the wait.

        Cost attribution: the launches record their normal
        collectives/hops/bytes exactly once, at the wait (where the
        owner segments materialize); the start additionally records
        ``overlap_launches`` — the count of collectives whose completion
        was deferred — under the plan op, so logs show HOW MUCH of the
        wire ran split-phase without double-charging any hop.

        The ``Promise.FINE`` oracle stays sequential: under a FINE
        promise the plan commits eagerly (no overlap window, no
        ``overlap_launches``) and the returned PendingPlan is already
        complete — ``finish()`` just unwraps it.
        """
        with costs.scope("exchange.commit"):
            dead, transport = self._precommit(backend, max_rounds, overflow,
                                              dead_ranks, transport)
            if fine_grained(self.promise):
                return PendingPlan(self, committed=self._commit_fine(
                    backend, impl, int(max_rounds), overflow, transport,
                    dead, integrity))
            st = self._stage_fused(backend, impl, int(max_rounds), overflow,
                                   transport, dead, integrity)
            with costs.scope("transport.request"):
                handle = transport.request_start(backend, st.args)
            return PendingPlan(self, staged=st, handle=handle,
                               transport=transport)

    def _precommit(self, backend: Backend, max_rounds, overflow,
                   dead_ranks, transport):
        """Shared commit/commit_async validation + one-shot latch."""
        if not self._flows:
            raise ValueError("commit() on an empty ExchangePlan")
        if self._committed:
            # a silent second commit would launch a duplicate collective
            # and double-record every cost pin
            raise ValueError("ExchangePlan already committed")
        if int(max_rounds) < 1:
            raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
        if overflow not in OVERFLOW_POLICIES:
            raise ValueError(
                f"overflow must be one of {OVERFLOW_POLICIES}, "
                f"got {overflow!r}")
        dead = tuple(sorted({int(d) for d in (dead_ranks or ())}))
        for d in dead:
            if not 0 <= d < backend.nprocs():
                raise ValueError(
                    f"dead_ranks names rank {d}, outside the "
                    f"{backend.nprocs()}-rank axis")
        self._committed = True
        return dead, make_transport(transport)

    def _commit_fine(self, backend: Backend, impl: str, max_rounds: int,
                     overflow: str, transport: Transport,
                     dead: tuple[int, ...],
                     integrity: bool) -> "CommittedPlan":
        # sequential oracle: one single-flow plan per flow, in
        # registration order; the sub-plans carry the replies so the
        # oracle exercises the SAME transport end to end
        subs = []
        for f in self._flows:
            p = ExchangePlan(name=f.op_name)
            p.add(f.payload, f.dest, f.capacity,
                  reply_lanes=f.reply_lanes, valid=f.valid,
                  op_name=f.op_name)
            subs.append(p.commit(
                backend, impl=impl,
                max_rounds=_flow_rounds(f, max_rounds),
                overflow=overflow, transport=transport,
                dead_ranks=dead, integrity=integrity))
        return CommittedPlan(self, [c.view(0) for c in subs],
                             sequential=True, subplans=subs,
                             dead_ranks=dead)

    # -- fused lowering ---------------------------------------------------

    def _stage_fused(self, backend: Backend, impl: str,
                     max_rounds: int = 1,
                     overflow: str = "drop",
                     transport: Transport = DENSE,
                     dead_ranks: tuple[int, ...] = (),
                     integrity: bool = False) -> "_StagedCommit":
        """Everything that happens BEFORE the wire moves: the one binning
        pass, admission, wire bodies, send maps, and the RequestArgs the
        transport ships.  Shared verbatim by the synchronous commit and
        commit_async, which is what makes the two bit-identical."""
        flows = self._flows
        nprocs = backend.nprocs()
        nflows = len(flows)
        rounds = int(max_rounds)   # validated by commit(), the sole entry
        caps = [f.capacity for f in flows]
        # per-flow effective retry rounds: flow override else plan-wide,
        # clamped to ceil(N_f/C_f) — exactly-sized flows never pay for
        # retry launches their buckets cannot use
        rounds_f = [_flow_rounds(f, rounds) for f in flows]
        # ragged wire: flow f's rows are exactly L_f + 1 words (payload
        # lanes + its own metadata lane) — no cross-flow padding
        roww = [f.lanes + 1 for f in flows]

        dest_all = jnp.concatenate([f.dest for f in flows])
        valid_all = jnp.concatenate([f.valid for f in flows])
        flow_id = jnp.concatenate([
            jnp.full((f.n,), fi, _I32) for fi, f in enumerate(flows)])

        # degraded commit (DESIGN.md section 1.8): traffic toward dead
        # ranks is masked BEFORE admission, so such items never take a
        # send slot — they keep their flow-level validity and surface as
        # carry-compatible leftovers / unreachable() rows instead of
        # shipping into the void (or counting as capacity drops)
        if dead_ranks:
            alive = jnp.ones_like(valid_all)
            for d in dead_ranks:
                alive = alive & (dest_all != d)
            valid_all = valid_all & alive

        # ONE binning pass for every flow AND every retry round:
        # composite (dest, flow) buckets.  Retry round r ships exactly
        # the items with within-bucket rank in [r*C_f, (r+1)*C_f) — a
        # pure mask over these same offsets, never a second pass.  The
        # "exchange.bin" entry is how tests pin that invariant (per-hop
        # re-binning passes inside a transport record their own).
        costs.record("exchange.bin",
                     costs.Cost(local=int(dest_all.shape[0])))
        with costs.scope("exchange.bin"):
            counts, offsets = kops.multi_bin_offsets(
                dest_all, flow_id, nprocs, nflows, valid_all, impl=impl)
        caps_arr = jnp.asarray(caps, _I32)
        rounds_arr = jnp.asarray(rounds_f, _I32)
        eff_arr = caps_arr * rounds_arr                # effective R_f*C_f
        ok = valid_all & (offsets < eff_arr[flow_id])

        # wire bodies and requester-local slot maps are built ONCE and
        # are TRANSPORT-INDEPENDENT: admission comes from the one
        # binning pass, so every transport ships the same items to the
        # same dense owner slots
        bodies = []
        send_items, send_occs = [], []
        row0 = 0
        for fi, f in enumerate(flows):
            meta = jnp.where(f.valid,
                             _VALID_BIT | jnp.arange(f.n, dtype=_U32), 0)
            bodies.append(jnp.concatenate([f.payload, meta[:, None]],
                                          axis=1))

            # requester-local inverse slot maps in FLOW-local coordinates
            # (d*(R*C_f) + within-bucket rank): identical to the eager
            # layout at capacity R*C_f, so the reply path — fused segment
            # slice or standalone ``reply()`` — resolves slots the same
            # way either way
            cap_e = rounds_f[fi] * f.capacity
            okf = ok[row0:row0 + f.n]
            sl_f = jnp.where(okf,
                             f.dest * cap_e + offsets[row0:row0 + f.n],
                             nprocs * cap_e).astype(_I32)
            # 1-lane in-kernel scatters (kops.place_rows): commit traces
            # zero standalone XLA scatter ops (DESIGN.md section 1.10);
            # values are < 2**31 so the u32 round trip is exact
            send_items.append(kops.place_rows(
                jnp.full((nprocs * cap_e,), f.n, _U32), sl_f,
                jnp.arange(f.n, dtype=_U32)[:, None],
                impl=impl).astype(_I32))
            send_occs.append(kops.place_rows(
                jnp.zeros((nprocs * cap_e,), _U32), sl_f,
                jnp.ones((f.n, 1), _U32), impl=impl) != 0)
            row0 += f.n

        # physical movement: the transport owns the launches, the wire
        # words, and their cost attribution (DESIGN.md section 1.7)
        plan_op = self.name or flows[0].op_name
        specs = [FlowWire(caps[fi], rounds_f[fi], roww[fi],
                          flows[fi].reply_lanes, flows[fi].n,
                          flows[fi].op_name)
                 for fi in range(nflows)]

        if dead_ranks:
            # static degraded-commit observables: how many destinations
            # were masked and the worst-case wire bytes their buckets
            # would have carried (per requesting rank)
            lb = sum(len(dead_ranks) * rounds_f[fi] * caps[fi]
                     * roww[fi] * 4 for fi in range(nflows))
            costs.record(plan_op, costs.Cost(unreachable=len(dead_ranks),
                                             lost_bytes=lb))

        send_dest, send_flow = dest_all, flow_id
        send_off, send_valid = offsets, valid_all
        ck_rmax = 0
        if integrity:
            # synthetic checksum flow (DESIGN.md section 1.8): ONE u32
            # checksum word (+ meta lane) certifying each (dest, round,
            # flow) wire window, riding the SAME launches as the data.
            # Row d*R*F + r*F + f has the analytic within-bucket rank
            # r*F + f at capacity F, so the flow needs no second binning
            # pass; the stored word is SALT + sum of the window's row
            # hashes (u32 wraparound), which the owner recomputes from
            # the arrival segment.
            ck_rmax = max(rounds_f)
            ck_vals = []
            row0 = 0
            for fi, f in enumerate(flows):
                h = kops.mix_rows(bodies[fi], impl=impl)
                rf, cf = rounds_f[fi], caps[fi]
                okf = ok[row0:row0 + f.n]
                seg = jnp.where(
                    okf, f.dest * rf + offsets[row0:row0 + f.n] // cf,
                    nprocs * rf).astype(_I32)
                sums = jax.ops.segment_sum(
                    h, seg, num_segments=nprocs * rf + 1)[:-1] \
                    .reshape(nprocs, rf).astype(_U32)
                if rf < ck_rmax:
                    sums = jnp.pad(sums, ((0, 0), (0, ck_rmax - rf)))
                ck_vals.append(sums)
                row0 += f.n
            ck_lane = (_CK_SALT + jnp.stack(ck_vals, axis=2)).reshape(-1)
            n_ck = nprocs * ck_rmax * nflows
            ck_meta = _VALID_BIT | jnp.arange(n_ck, dtype=_U32)
            bodies.append(jnp.stack([ck_lane, ck_meta], axis=1))
            specs.append(FlowWire(nflows, ck_rmax, 2, 0, n_ck,
                                  "exchange.integrity"))
            ar = jnp.arange(n_ck, dtype=_I32)
            send_dest = jnp.concatenate(
                [dest_all, ar // (ck_rmax * nflows)])
            send_flow = jnp.concatenate(
                [flow_id, jnp.full((n_ck,), nflows, _I32)])
            send_off = jnp.concatenate([offsets, ar % (ck_rmax * nflows)])
            send_valid = jnp.concatenate(
                [valid_all, jnp.ones((n_ck,), bool)])

        return _StagedCommit(
            args=RequestArgs(specs, bodies, send_dest, send_flow,
                             send_off, send_valid, plan_op, impl),
            rounds_f=rounds_f, counts=counts, eff_arr=eff_arr, ok=ok,
            send_items=send_items, send_occs=send_occs,
            overflow=overflow, dead_ranks=dead_ranks,
            integrity=integrity, ck_rmax=ck_rmax, impl=impl)

    def _finalize_fused(self, backend: Backend, st: "_StagedCommit",
                        segments, extra_drop, tctx,
                        transport: Transport) -> "CommittedPlan":
        """Everything that happens AFTER the wire lands: integrity
        verification, overflow accounting, owner views."""
        flows = self._flows
        nprocs = backend.nprocs()
        nflows = len(flows)
        rounds_f, ok, integrity = st.rounds_f, st.ok, st.integrity
        caps = [f.capacity for f in flows]
        impl, ck_rmax = st.impl, st.ck_rmax

        # one psum covers every flow's overflow accounting; only rank
        # >= R_f*C_f is a drop — earlier overflow was carried to a retry.
        # A transport with explicitly undersized stage capacities may
        # drop admitted items too; those counts arrive psum'ed.
        over = jnp.maximum(st.counts - st.eff_arr[None, :], 0).sum(0)  # (F,)
        lost = None
        good_by_flow: list[jax.Array] = []
        if integrity:
            # owner-side verification: recompute each (src, round)
            # window's hash sum from the arrival segment and compare to
            # the stored checksum word.  A failed window (corrupt word,
            # zeroed segment, transport loss) invalidates ALL its
            # arrivals — corrupted items re-enter via the caller's
            # ack/carry retry path instead of being consumed.  The lost
            # count is global sent-minus-survived, folded into the same
            # psum as the overflow counts.
            ck_seg = segments[nflows]
            ck_ok3 = ((ck_seg[:, 1] & _VALID_BIT) != 0) \
                .reshape(nprocs, ck_rmax, nflows)
            ck_val3 = ck_seg[:, 0].reshape(nprocs, ck_rmax, nflows)
            sent, surv = [], []
            row0 = 0
            for fi, f in enumerate(flows):
                rf, cf = rounds_f[fi], caps[fi]
                comp = kops.mix_rows(segments[fi], impl=impl) \
                    .reshape(nprocs, rf, cf).sum(axis=2, dtype=_U32)
                good = (ck_ok3[:, :rf, fi]
                        & (ck_val3[:, :rf, fi] == _CK_SALT + comp))
                good_rows = jnp.repeat(good.reshape(-1), cf)
                good_by_flow.append(good_rows)
                sent.append(ok[row0:row0 + f.n].sum().astype(_I32))
                meta_f = segments[fi][:, f.lanes]
                alive = ((meta_f & _VALID_BIT) != 0) & good_rows
                surv.append(alive.sum().astype(_I32))
                row0 += f.n
            red = backend.psum(jnp.concatenate(
                [over, jnp.stack(sent), jnp.stack(surv)])).astype(_I32)
            dropped = red[:nflows]
            lost = jnp.maximum(red[nflows:2 * nflows]
                               - red[2 * nflows:], 0)
        else:
            dropped = backend.psum(over).astype(_I32)
        if extra_drop is not None:
            dropped = dropped + extra_drop[:nflows]

        views = []
        for fi, f in enumerate(flows):
            cap_e = rounds_f[fi] * f.capacity
            segment = segments[fi]
            pay = segment[:, :f.lanes]
            meta_r = segment[:, f.lanes]
            out_valid = (meta_r & _VALID_BIT) != 0
            if integrity:
                out_valid = out_valid & good_by_flow[fi]
            out_src_pos = (meta_r & _POS_MASK).astype(_I32)
            src_rank = jnp.repeat(jnp.arange(nprocs, dtype=_I32), cap_e)
            views.append(RouteResult(pay, out_valid, src_rank, out_src_pos,
                                     dropped[fi], cap_e,
                                     st.send_items[fi], st.send_occs[fi],
                                     lost[fi] if lost is not None
                                     else jnp.int32(0)))

        if st.overflow == "raise-in-test":
            _raise_on_drops(flows, dropped)

        return CommittedPlan(self, views, sequential=False,
                             transport=transport, tctx=tctx,
                             dead_ranks=st.dead_ranks)


@dataclasses.dataclass
class _StagedCommit:
    """Pre-wire state of a fused commit (shared by sync + async paths).

    ``args`` is what the transport ships; the rest is what
    ``_finalize_fused`` needs once the owner segments land.
    """

    args: RequestArgs
    rounds_f: list[int]
    counts: jax.Array
    eff_arr: jax.Array
    ok: jax.Array
    send_items: list[jax.Array]
    send_occs: list[jax.Array]
    overflow: str
    dead_ranks: tuple[int, ...]
    integrity: bool
    ck_rmax: int
    impl: str


class CommittedPlan:
    """Request round issued; owner-side views available, replies pending."""

    def __init__(self, plan: ExchangePlan, views: list[RouteResult],
                 sequential: bool, transport: Transport | None = None,
                 tctx=None, subplans: list["CommittedPlan"] | None = None,
                 dead_ranks: tuple[int, ...] = ()):
        self._plan = plan
        self._views = views
        self._sequential = sequential
        self._transport = transport        # physical layer (fused path)
        self._tctx = tctx                  # transport's reply context
        self._subplans = subplans or []    # FINE: one sub-plan per flow
        self._dead_ranks = tuple(dead_ranks or ())
        self._replies: dict[int, jax.Array] = {}
        self._finished = False

    def view(self, handle: int) -> RouteResult:
        """Owner-side view of one flow (same layout as eager ``route``)."""
        return self._views[handle]

    def reply_lanes(self, handle: int) -> int:
        """Reply words per row one flow declared at ``add`` (0 = none)."""
        return self._plan._flows[handle].reply_lanes

    def leftover(self, handle: int) -> tuple[jax.Array, jax.Array]:
        """Requester-side overflow carry for one flow.

        Returns ``(payload, mask)`` in the flow's ORIGINAL batch
        coordinates: ``mask[i]`` is True iff item i was valid but never
        shipped (its within-bucket rank fell beyond every round's
        capacity window).  The ``overflow="carry"`` contract: the caller
        re-injects exactly these rows next cycle — the static-shape
        analogue of re-inserting a failed fetch-and-add, which
        ``hashmap_buffer.flush`` uses to make spills lossless.  Derived
        from purely local state (the commit-time send maps), so it costs
        zero collectives and works on fused and FINE schedules alike.
        """
        f = self._plan._flows[handle]
        return f.payload, carry_mask(self._views[handle], f.valid)

    def unreachable(self, handle: int) -> tuple[jax.Array, jax.Array]:
        """Rows addressed to a dead rank (``commit(dead_ranks=...)``).

        Returns ``(payload, mask)`` in the flow's ORIGINAL batch
        coordinates, exactly like :meth:`leftover` — and every
        unreachable row is also IN that leftover mask, since masking at
        admission means it never took a send slot.  This narrower view
        lets recovery code separate "re-inject verbatim next cycle"
        (capacity overflow) from "re-route after the mesh heals" (the
        owner is gone; after ``elastic.plan_remesh`` re-homes the key
        space, these rows are re-inserted against the new owner map).
        Purely local state; zero collectives.
        """
        f = self._plan._flows[handle]
        mask = jnp.zeros((f.n,), bool)
        for d in self._dead_ranks:
            mask = mask | (f.dest == d)
        return f.payload, f.valid & mask

    def set_reply(self, handle: int, rows: jax.Array) -> None:
        """Stage per-request replies for one flow.

        ``rows`` is (P*C_f, reply_lanes) aligned with ``view(handle)``
        rows; lane count must match the flow's declared ``reply_lanes``.
        """
        f = self._plan._flows[handle]
        if rows.ndim == 1:
            rows = rows[:, None]
        if f.reply_lanes == 0:
            raise ValueError(
                f"flow {handle} ({f.op_name}) declared reply_lanes=0")
        if rows.shape[1] != f.reply_lanes:
            raise ValueError(
                f"flow {handle} ({f.op_name}) declared reply_lanes="
                f"{f.reply_lanes}, got {rows.shape[1]}")
        self._replies[handle] = rows.astype(_U32)

    def finish(self, backend: Backend) -> dict[int, tuple[jax.Array, jax.Array]]:
        """Issue the reply round: one fused inverse all-to-all.

        Returns ``{handle: (replies (N_f, reply_lanes), answered (N_f,))}``
        for every flow with ``reply_lanes > 0``; replies land aligned
        with each flow's *original* request batch.
        """
        with costs.scope("exchange.finish"):
            if self._finished:
                # callers must cache the returned dict; a second finish would
                # launch a duplicate collective and double-record costs
                raise ValueError("CommittedPlan already finished")
            flows = self._plan._flows
            replying = [fi for fi, f in enumerate(flows) if f.reply_lanes > 0]
            for fi in replying:
                if fi not in self._replies:
                    raise ValueError(
                        f"finish() before set_reply() for flow {fi} "
                        f"({flows[fi].op_name})")
            self._finished = True
            if not replying:
                return {}

            if self._sequential:
                # FINE oracle: each flow's reply is its own sub-plan finish,
                # through the same transport as its request
                outs = {}
                for fi in replying:
                    sub = self._subplans[fi]
                    sub.set_reply(0, self._replies[fi])
                    outs[fi] = sub.finish(backend)[0]
                return outs

            # owner replies in arrival order, masked to real arrivals; the
            # transport lands them back in the requesters' send slots
            staged = {fi: jnp.where(self._views[fi].valid[:, None],
                                    self._replies[fi], 0)
                      for fi in replying}
            with costs.scope("transport.reply"):
                slots = self._transport.reply(backend, self._tctx, staged)

            outs = {}
            for fi in replying:
                f = flows[fi]
                view = self._views[fi]
                seg = slots[fi]
                item = jnp.where(view.send_occ, view.send_item, f.n)
                out = jnp.zeros((f.n, f.reply_lanes), _U32).at[item].set(
                    seg, mode="drop")
                answered = jnp.zeros((f.n,), bool).at[item].set(
                    view.send_occ, mode="drop")
                outs[fi] = (out, answered)
            return outs


class PendingPlan:
    """Future returned by :meth:`ExchangePlan.commit_async`.

    The request's collectives are already in flight (traced into the
    program) when this object exists; ``finish(backend)`` completes the
    transport wait and returns the :class:`CommittedPlan` — bit-identical
    to what the synchronous commit would have produced.  Everything the
    caller traces between the two calls sits in the overlap window.
    """

    def __init__(self, plan: ExchangePlan,
                 committed: CommittedPlan | None = None,
                 staged: _StagedCommit | None = None,
                 handle=None, transport: Transport | None = None):
        self._plan = plan
        self._committed = committed        # FINE oracle: already complete
        self._staged = staged
        self._handle = handle
        self._transport = transport
        self._done = False

    def finish(self, backend: Backend) -> CommittedPlan:
        """Complete the wire; one-shot (a second wait would duplicate
        the transport's completion collectives and cost records)."""
        with costs.scope("exchange.commit"):
            if self._done:
                raise ValueError("PendingPlan already finished")
            self._done = True
            if self._committed is not None:
                return self._committed
            st = self._staged
            # the deferred launches' collectives/hops/bytes record exactly
            # once, inside request_wait; the start's only extra observable
            # is HOW MANY launches ran split-phase
            costs.record(st.args.plan_op,
                         costs.Cost(overlap_launches=self._handle.launched))
            with costs.scope("transport.request"):
                segments, extra_drop, tctx = self._transport.request_wait(
                    backend, self._handle)
            return self._plan._finalize_fused(backend, st, segments,
                                              extra_drop, tctx,
                                              self._transport)


class PendingResult:
    """Future for a container op issued split-phase (``async_=True``).

    Wraps the op's completion closure: the exchange wire is in flight,
    and ``finish()`` runs the owner-side work + reply round, returning
    exactly what the synchronous op would have returned.  One-shot.
    """

    def __init__(self, complete):
        self._complete = complete
        self._done = False

    def finish(self):
        if self._done:
            raise ValueError("PendingResult already finished")
        self._done = True
        out, self._complete = self._complete, None
        return out()


def carry_mask(req: RouteResult, valid: jax.Array) -> jax.Array:
    """Items of the ORIGINAL batch that were valid but never shipped.

    Requester-local: recovered from the route's commit-time send maps
    (an item shipped iff it owns a send slot), so it needs no extra
    collective.  ``route(..., capacity=C, max_rounds=R)`` marks exactly
    the items with within-bucket rank >= R*C — the rows an
    ``overflow="carry"`` caller re-injects next cycle.
    """
    n = valid.shape[0]
    shipped = jnp.zeros((n,), bool).at[
        jnp.where(req.send_occ, req.send_item, n)].set(
        jnp.ones_like(req.send_occ), mode="drop")
    return valid & ~shipped


def _raise_on_drops(flows: list[_Flow], dropped: jax.Array) -> None:
    """``overflow="raise-in-test"``: raise on any concrete drop count."""
    if isinstance(dropped, jax.core.Tracer):
        return          # traced: counts unknowable here; policy degrades
    for fi, f in enumerate(flows):
        if int(dropped[fi]) > 0:
            raise ExchangeOverflowError(
                f"flow '{f.op_name}' dropped {int(dropped[fi])} item(s) "
                f"for capacity overflow (capacity={f.capacity}); raise "
                f"capacity or max_rounds, or use overflow='carry'")


def route(backend: Backend,
          payload: jax.Array,
          dest: jax.Array,
          capacity: int,
          valid: jax.Array | None = None,
          op_name: str = "route",
          impl: str = "auto",
          max_rounds: int = 1,
          overflow: str = "drop",
          transport: Transport | str | None = None,
          dead_ranks: tuple[int, ...] | None = None,
          integrity: bool = False) -> RouteResult:
    """Send each row of ``payload`` to rank ``dest[i]``; return owner view.

    Thin eager wrapper: a single-flow :class:`ExchangePlan`, committed
    immediately.  Wire format, costs, and owner-view layout are exactly
    the fused engine's single-flow case.

    payload: (N, L) u32 (or (N,) — treated as one lane)
    dest:    (N,) i32 destination ranks in [0, nprocs)
    capacity: static per-(src,dst) slot count C
    valid:   (N,) bool mask (default all valid)
    impl:    kernel dispatch for send-buffer construction
             (kops.multi_bin_offsets)
    max_rounds: carryover retry rounds R — the result is bit-identical
             to a single round at capacity R*C (only the cost accounting
             differs: R all-to-all launches off ONE binning pass).
             Clamped to ceil(N/C), past which a round can't ship
             anything new
    overflow: residual policy beyond rank R*C — "drop" | "raise-in-test"
             | "carry" (pair with :func:`carry_mask` on the result)
    transport: physical collective layer ("dense" default; see
             DESIGN.md section 1.7).  Flows needing a reply through a
             non-dense transport should use an :class:`ExchangePlan`
             with ``reply_lanes`` declared — the standalone
             :func:`reply` is the dense inverse all-to-all only.
    dead_ranks / integrity: degraded-operation knobs, forwarded to
             :meth:`ExchangePlan.commit` (DESIGN.md section 1.8).
    """
    plan = ExchangePlan(name=op_name)
    h = plan.add(payload, dest, capacity, valid=valid, op_name=op_name)
    return plan.commit(backend, impl=impl, max_rounds=max_rounds,
                       overflow=overflow, transport=transport,
                       dead_ranks=dead_ranks, integrity=integrity).view(h)


def reply(backend: Backend,
          req: RouteResult,
          reply_payload: jax.Array,
          orig_n: int,
          op_name: str = "reply",
          transport: Transport | str | None = None
          ) -> tuple[jax.Array, jax.Array]:
    """Route per-request replies back to the requesters (single flow).

    ``reply_payload`` is (P*C, L) aligned with ``req.payload`` rows.
    Returns ``(replies, answered)`` where ``replies`` is (orig_n, L)
    aligned with the *original* request batch and ``answered`` marks rows
    that received a reply.

    This is a single inverse all-to-all: the owner's row s*C+j arrived
    from rank s's send slot d*C+j, and the tiled all-to-all maps row
    s*C+j straight back there — so replies written in arrival order need
    no binning, no metadata lanes, and no second slot reservation.  The
    requester resolves slots to batch positions with its local
    ``send_item`` map and knows ``answered`` from its own ``send_occ``.
    Flows of a multi-flow plan should reply through
    ``CommittedPlan.finish`` instead, which fuses every flow's replies
    into ONE such inverse permutation (calling ``reply`` on a fused view
    is semantically correct — the slot maps are flow-local — but launches
    an unfused collective per flow).

    ``transport`` must name the transport the request moved over, and
    only the dense inverse permutation is expressible from a bare
    :class:`RouteResult` — a view routed over a multi-hop transport
    carries per-launch relay state that only the committed plan holds,
    so a non-dense transport raises here and the caller must reply
    through ``finish`` (declare ``reply_lanes`` on the flow).
    """
    with costs.scope("exchange.finish"):
        tr = make_transport(transport)
        if tr.name != "dense":
            raise ValueError(
                f"reply({op_name!r}): the standalone reply is the dense "
                f"inverse permutation; a flow routed over transport "
                f"{tr.name!r} must declare reply_lanes and reply through "
                f"CommittedPlan.finish, which holds the transport's inverse "
                f"hop state")
        if reply_payload.ndim == 1:
            reply_payload = reply_payload[:, None]
        lanes = reply_payload.shape[1]

        # ride the transport's inverse permutation (one single-flow wire):
        # bit-identical to the pre-transport direct all-to-all, and keeps
        # every physical collective inside core/transport.py
        spec = FlowWire(req.capacity, 1, lanes + 1, lanes, orig_n, op_name)
        staged = {0: jnp.where(req.valid[:, None],
                               reply_payload.astype(_U32), 0)}
        with costs.scope("transport.reply"):
            back = tr.reply(backend, _DenseCtx([spec], op_name, "auto"),
                            staged)[0]

        # back[k] answers the item this rank placed in send slot k of the
        # original route call
        item = jnp.where(req.send_occ, req.send_item, orig_n)  # drop sentinel
        out = jnp.zeros((orig_n, lanes), _U32).at[item].set(back, mode="drop")
        answered = jnp.zeros((orig_n,), bool).at[item].set(
            req.send_occ, mode="drop")
        return out, answered


def suggest_rounds(loads, capacity: int, slack: float = 1.0,
                   limit: int = 16) -> int:
    """Heuristic ``max_rounds`` from an observed load trajectory.

    The retry-round analogue of :func:`exchange_capacity` (ROADMAP's
    adaptive-rounds item): given the per-step observed PEAK
    (dest, flow)-bucket loads of recent batches — e.g. ``max
    bucket count`` or ``max expert_load`` readings — pick the smallest
    R whose effective capacity ``R * capacity`` covers the hottest
    bucket seen, times ``slack``.  ``loads`` is a scalar or any
    iterable of scalars (ints, numpy, or concrete jax scalars); the
    result clamps to ``[1, limit]`` so a pathological trajectory cannot
    demand unbounded launches.  Callers with no trajectory yet pass the
    uniform expectation and get 1.
    """
    if capacity <= 0:
        raise ValueError(f"capacity must be positive, got {capacity}")
    try:
        peak = max((int(x) for x in loads), default=0)
    except TypeError:
        peak = int(loads)
    need = -(-int(peak * slack) // int(capacity)) if peak > 0 else 1
    return max(1, min(int(limit), need))


def exchange_capacity(n_per_rank: int, nprocs: int, slack: float = 1.25) -> int:
    """Heuristic static capacity for roughly-uniform traffic.

    Uniform traffic puts ~n/P items in each (src,dst) bucket; ``slack``
    absorbs skew.  Irregular apps (MoE dispatch!) pass explicit
    capacities derived from their own load model instead.
    """
    if nprocs == 1:
        return n_per_rank
    base = (n_per_rank + nprocs - 1) // nprocs
    return max(1, int(base * slack) + 1)
