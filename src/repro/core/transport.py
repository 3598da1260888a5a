"""Exchange transports: the physical collective layer (DESIGN.md §1.7).

The paper's portability story is a *separation*: containers are written
once against the BCL Core primitive set, and the physical data movement
is whichever backend is fastest for the machine (MPI, OpenSHMEM,
GASNet-EX, UPC++); DASH makes the same move with hierarchical teams
matched to the machine topology.  This module is that separation for
the TPU exchange engine: :mod:`repro.core.exchange` owns the *logical*
exchange — binning, ragged wire layout, carryover retry rounds,
overflow policy, requester-local send maps — and a :class:`Transport`
owns the *physical* request/reply movement.

Two transports ship:

  :class:`DenseTransport`      today's one-shot tiled all-to-all over the
                               full rank axis.  The oracle: container
                               results and the wire-format cost pins are
                               exactly the pre-transport engine's.

  :class:`HierarchicalTransport`  factors the rank axis ``P = Pr x Pc``
                               (a 2-D mesh or a virtual factorization of
                               one flat axis) and exchanges in two
                               stages: stage 1 bins items by destination
                               *column* and all-to-alls over the row
                               sub-axis; the relay re-bins by final rank
                               and stage 2 all-to-alls over the column
                               sub-axis.  Replies ride the exact inverse
                               two-hop permutation back to the original
                               send slots.  Each collective has only
                               sqrt(P)-ish peers and each hop's padded
                               capacity is sized to per-stage load, so
                               sparse/skewed destination sets stop
                               paying ``P``-wide padding.

Hierarchical wire format: each hop's row is the flow's dense row
(``L_f`` payload lanes + the meta lane) plus ONE hop lane packing
``rank << s | o`` where ``o`` is the item's within-(dest, flow)-bucket
rank from the ONE dense binning pass.  On the source->relay hop the
rank field is the final destination (the relay re-bins on it); the
relay rewrites it to the source rank (recovered positionally from the
stage-1 arrival block) so the owner can scatter each arrival straight
into the dense layout slot ``src * R*C_f + o`` — which is what makes
hierarchical results bit-identical to :class:`DenseTransport` whenever
the stage capacities admit every dense-admitted item (the default
sizing guarantees it).  The rank field takes the ``bits(P - 1)`` bits
the axis needs (:func:`hop_shift`), leaving ``s = 32 - bits(P - 1)``
bits for ``o``: more than any bucket rank of a wire whose ``P * R*C_f``
word slots fit the i32 indices.

Cost attribution (DESIGN.md §1.7): the hop that touches the requester
is charged under the flow's own ``op_name`` (request ``bytes_out``,
reply ``bytes_in``); the hop between relay and owner is charged under
``"<op_name>.relay"``; each physical launch records
``collectives/rounds/hops`` under the plan op (2 hops per hierarchical
launch, 1 per dense).  Per-hop re-binning passes record
``"exchange.bin"`` entries exactly like the main pass, so binning work
stays pinned.
"""

from __future__ import annotations

import abc
import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.backend import Backend
from repro.core.object_container import ragged_offsets, rows_from_words
from repro.kernels import ops as kops

_U32 = jnp.uint32
_I32 = jnp.int32

_VALID_BIT = jnp.uint32(1 << 31)


def hop_shift(nprocs: int) -> int:
    """Hop lane packing on a ``nprocs``-rank axis: bits ``[shift, 32)``
    hold a rank, bits ``[0, shift)`` a within-bucket rank — the rank
    field takes only the bits the axis needs."""
    return 32 - max(1, (nprocs - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class FlowWire:
    """Static wire description of one flow (from the ExchangePlan)."""

    capacity: int       # per-round per-(src,dst) slot count C_f
    rounds: int         # effective retry rounds R_f (already clamped)
    roww: int           # dense row words: payload lanes L_f + meta lane
    reply_lanes: int    # declared reply words per row (0 = no reply)
    n: int              # flow batch size N_f
    op_name: str

    @property
    def cap_e(self) -> int:
        """Effective capacity R_f * C_f (retry rounds concatenate)."""
        return self.rounds * self.capacity


@dataclasses.dataclass
class RequestArgs:
    """Everything a transport needs to move one committed plan's requests.

    The logical exchange state — the ONE ``multi_bin_offsets`` pass over
    composite (dest, flow) buckets — is computed by the plan and shared
    by every transport, so admission (which items ship, which drop) is
    transport-independent by construction.
    """

    specs: list[FlowWire]
    bodies: list[jax.Array]   # per flow (N_f, roww_f) u32, meta lane last
    dest: jax.Array           # (N,) i32 concatenated over flows
    flow_id: jax.Array        # (N,) i32
    offsets: jax.Array        # (N,) i32 within-(dest, flow) bucket ranks
    valid: jax.Array          # (N,) bool
    plan_op: str
    impl: str


@dataclasses.dataclass
class InFlight:
    """Handle for a split-phase request (DESIGN.md §1.9).

    ``request_start`` returns one; ``request_wait`` consumes it.  The
    window between the two calls is where callers place independent
    compute — every collective counted in ``launched`` is already in
    the traced program when start returns, so the scheduler can overlap
    it with whatever the caller traces before the wait.
    """

    launched: int   # collectives issued before start returned
    state: Any      # transport-private completion state


class Transport(abc.ABC):
    """Physical movement strategy for the exchange engine's collectives."""

    #: stable identifier ("dense" / "hier") used by config/benchmark knobs
    name: str

    def request_start(self, backend: Backend, args: RequestArgs) -> InFlight:
        """Issue the request's collectives; completion deferred to wait.

        Default: the synchronous one-shot — every launch is issued (and
        the owner segments fully materialized) before start returns, so
        :meth:`request_wait` just unwraps.  Dense keeps this default
        (its single hop leaves nothing to defer: start IS the oracle
        path); transports with dependent hops override both halves to
        leave later hops for the wait.
        """
        nrounds = max(s.rounds for s in args.specs)
        return InFlight(nrounds, self.request(backend, args))

    def request_wait(self, backend: Backend, handle: InFlight
                     ) -> tuple[list[jax.Array], jax.Array | None, Any]:
        """Complete a :meth:`request_start`; returns what request returns."""
        return handle.state

    @abc.abstractmethod
    def request(self, backend: Backend, args: RequestArgs
                ) -> tuple[list[jax.Array], jax.Array | None, Any]:
        """Move every flow's admitted items to their owners.

        Returns ``(segments, extra_dropped, ctx)``: per-flow owner-side
        segments ``(P * cap_e_f, roww_f)`` in the DENSE layout (row
        ``s * cap_e + o`` holds the rank-``o`` arrival from rank ``s``),
        an optional per-flow global count of transport-stage drops
        (``None`` when the transport can never drop beyond the dense
        admission), and an opaque context for :meth:`reply`.
        """

    @abc.abstractmethod
    def reply(self, backend: Backend, ctx: Any,
              staged: dict[int, jax.Array]) -> dict[int, jax.Array]:
        """Move owner replies back to the requesters' send slots.

        ``staged[fi]`` is ``(P * cap_e_f, R_f)`` aligned with the owner
        segment rows (already masked to valid arrivals); the result maps
        each flow to the same-shape array in the REQUESTER's dense
        send-slot layout (row ``d * cap_e + o`` answers the item this
        rank placed in that slot), which the plan resolves to batch
        positions with its local send maps.
        """


# ---------------------------------------------------------------------------
# dense: one-shot tiled all-to-all over the full rank axis
# ---------------------------------------------------------------------------

def _pad_rows(mats: list[jax.Array], wmax: int) -> jax.Array:
    """Right-pad per-flow row matrices to one (N, wmax) u32 matrix.

    The fused wire kernel (``kops.pack_rows``) takes all flows' rows in
    item order with each flow using its own first ``roww_f`` lanes; the
    pad lanes never reach the wire (the kernel masks ``lane < roww_f``).
    """
    return jnp.concatenate(
        [m if m.shape[1] == wmax else jnp.pad(m, ((0, 0), (0, wmax - m.shape[1])))
         for m in mats], axis=0).astype(_U32)


@dataclasses.dataclass
class _DenseCtx:
    specs: list[FlowWire]
    plan_op: str
    impl: str


class DenseTransport(Transport):
    """The pre-transport engine's movement, verbatim (the oracle).

    One ragged-word all-to-all per launch over all P ranks; retry round
    ``r`` is a narrower launch carrying the flows still retrying, masked
    off the ONE binning pass (DESIGN.md §1.6).  The reply is ONE inverse
    all-to-all whose tiled layout lands every reply in the requester's
    original send slot (§1.2).
    """

    name = "dense"

    def request(self, backend, args):
        specs = args.specs
        nprocs = backend.nprocs()
        nflows = len(specs)
        caps_arr = jnp.asarray([s.capacity for s in specs], _I32)
        rounds_arr = jnp.asarray([s.rounds for s in specs], _I32)
        roww_arr = jnp.asarray([s.roww for s in specs], _I32)
        nrounds = max(s.rounds for s in specs)

        # round r's all-to-all carries only the flows still retrying at
        # r, each in its own ragged word segment of this round's
        # (narrower) wire; the fused kernel turns the ONE binning pass's
        # ranks into word slots AND packs the rows in the same pass
        # (items outside the round's capacity window, and flows done
        # retrying, drop at the sentinel) — one HBM write of the wire
        # per launch (DESIGN.md §1.10)
        wmax = max(s.roww for s in specs)
        rows_all = _pad_rows(args.bodies, wmax)
        recvs, woffs_by_round = [], []
        for r in range(nrounds):
            live = [fi for fi in range(nflows) if specs[fi].rounds > r]
            starts, w_r = ragged_offsets(
                [specs[fi].capacity * specs[fi].roww for fi in live])
            woff_map = dict(zip(live, starts))
            woff_round = jnp.asarray(
                [woff_map.get(fi, 0) for fi in range(nflows)], _I32)
            send = kops.pack_rows(
                rows_all, args.dest, args.flow_id, args.offsets, args.valid,
                r, woff_round, roww_arr, caps_arr, rounds_arr, w_r,
                nprocs * w_r, impl=args.impl)
            recvs.append(backend.all_to_all(send).reshape(nprocs, w_r))
            woffs_by_round.append(woff_map)

        segments = []
        for fi, s in enumerate(specs):
            # rounds concatenate per source: owner row s*(R*C_f) + o holds
            # the rank-o arrival from rank s, exactly the single-round
            # layout at capacity R*C_f; the flow's word segment reads
            # back as its own (rows, L_f+1) rows
            parts = [recvs[r][:, woffs_by_round[r][fi]:
                              woffs_by_round[r][fi] + s.capacity * s.roww]
                     for r in range(s.rounds)]
            segments.append(rows_from_words(jnp.stack(parts, axis=1),
                                            s.roww))

        # cost attribution: per-flow wire segments are ragged, so each
        # flow's bytes are EXACT — its own capacity x its own row width,
        # equal to the single-flow route() cost; the physical collective,
        # its round, and its single hop once per launch, under the plan's
        # op name — retry launches land under "<op>.retry" so skew
        # tolerance is priced separately from the base round
        for s in specs:
            fb = nprocs * s.capacity * s.roww * 4
            costs.record(s.op_name, costs.Cost(bytes_moved=fb, bytes_out=fb))
            if s.rounds > 1:
                rb = fb * (s.rounds - 1)
                costs.record(f"{s.op_name}.retry",
                             costs.Cost(bytes_moved=rb, bytes_out=rb))
        costs.record(args.plan_op, costs.Cost(collectives=1, rounds=1,
                                              hops=1))
        for _ in range(nrounds - 1):
            costs.record(f"{args.plan_op}.retry",
                         costs.Cost(collectives=1, rounds=1, hops=1))
        return segments, None, _DenseCtx(specs, args.plan_op, args.impl)

    def reply(self, backend, ctx, staged):
        specs = ctx.specs
        nprocs = backend.nprocs()
        replying = sorted(staged)
        rls = {fi: staged[fi].shape[1] for fi in replying}
        # ragged reply wire: only replying flows get a word segment,
        # exactly R_f words per row, spanning the EFFECTIVE capacity so
        # the single inverse all-to-all answers every round's arrivals
        starts, wtot = ragged_offsets(
            [specs[fi].cap_e * rls[fi] for fi in replying])
        seg_off = dict(zip(replying, starts))

        send = jnp.zeros((nprocs * wtot,), _U32)
        for fi in replying:
            cap = specs[fi].cap_e
            rl = rls[fi]
            # owner arrival row s*C_f + j  ->  words
            # [s*wtot + seg_f + j*R_f, ... + R_f) — the flow's own ragged
            # segment, exactly R_f words per reply
            ar = jnp.arange(nprocs * cap, dtype=_I32)
            base = (ar // cap) * wtot + seg_off[fi] + (ar % cap) * rl
            send = kops.place_rows(send, base, staged[fi], impl=ctx.impl)

        back2 = backend.all_to_all(send).reshape(nprocs, wtot)

        # the inverse all-to-all lands flow f's replies in its own word
        # segment of each source block; slicing the segment recovers the
        # flow-local send-slot layout
        outs = {}
        for fi in replying:
            cap = specs[fi].cap_e
            rl = rls[fi]
            seg = back2[:, seg_off[fi]:seg_off[fi] + cap * rl]
            outs[fi] = rows_from_words(seg, rl)
            fb = nprocs * cap * rl * 4
            costs.record(specs[fi].op_name,
                         costs.Cost(bytes_moved=fb, bytes_in=fb))
        costs.record(ctx.plan_op, costs.Cost(collectives=1, rounds=1,
                                             hops=1))
        return outs


# ---------------------------------------------------------------------------
# hierarchical: two-stage exchange over a Pr x Pc factorization
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _HierRound:
    """Per-launch inverse-permutation state retained for the reply."""

    live: list[int]
    # source side, per flow: (stage-1 send row index (N_f,), dense
    # requester slot (N_f,)); sentinels past-the-end drop
    src: dict[int, tuple[jax.Array, jax.Array]]
    # relay side, per flow: stage-2 send row index per stage-1 arrival
    rel: dict[int, jax.Array]
    # owner side, per flow: dense owner slot per stage-2 arrival
    own: dict[int, jax.Array]


@dataclasses.dataclass
class _HierCtx:
    specs: list[FlowWire]
    plan_op: str
    impl: str
    pr: int
    pc: int
    c1: list[int]
    c2: list[int]
    row_groups: tuple
    col_groups: tuple
    rounds: list[_HierRound]


@dataclasses.dataclass
class _HierPre:
    """Launch-invariant state shared by every round's two stages."""

    args: RequestArgs
    pr: int
    pc: int
    row_groups: tuple
    col_groups: tuple
    myrow: jax.Array
    caps_arr: jax.Array
    rounds_arr: jax.Array
    w1: list[int]
    w1_arr: jax.Array
    c1: list[int]
    c2: list[int]
    c1_arr: jax.Array
    c2_arr: jax.Array
    nrounds: int
    destcol: jax.Array
    hop1: jax.Array
    rows1: jax.Array   # (N, max w1) right-padded stage-1 rows, hop lane last
    shift: int         # hop lane packing, :func:`hop_shift`


@dataclasses.dataclass
class _Stage1Out:
    """One round's source->relay hop, awaiting its relay->owner hop."""

    live: list[int]
    woff1_map: dict[int, int]
    recv1: jax.Array
    src: dict[int, tuple[jax.Array, jax.Array]]
    extra: jax.Array


@dataclasses.dataclass
class _RoundOut:
    """One completed round: inverse-permutation state + owner scatters."""

    rnd: _HierRound
    scatters: dict[int, tuple[jax.Array, jax.Array]]  # fi -> (dslot, rows)
    extra: jax.Array


class HierarchicalTransport(Transport):
    """Two-stage all-to-all over the factored rank axis ``P = Pr x Pc``.

    Rank ``r`` sits at mesh coordinate ``(r // Pc, r % Pc)``.  Stage 1
    bins each item by its destination's COLUMN and all-to-alls over the
    row sub-axis (Pc peers): item for rank ``(i', j')`` moves from
    ``(i, j)`` to the relay ``(i, j')``.  The relay re-bins arrivals by
    destination ROW and stage 2 all-to-alls over the column sub-axis
    (Pr peers), landing everything at ``(i', j')``.  Per-stage padded
    capacities are per *flow*:

      stage 1 (per (src, dest-column) bucket)  default min(Pr*C_f, N_f)
      stage 2 (per (relay, dest-rank) bucket)  default Pc*min(C_f, N_f)

    The defaults are the worst-case bounds of dense-admitted traffic,
    so results are bit-identical to :class:`DenseTransport` out of the
    box.  Callers with sparse/skewed destination knowledge size them
    down via ``stage_caps={op_name: (c1, c2)}`` — that is where the
    sqrt(P)-peers wire saving comes from — at the price of counted
    stage drops if the hint under-provisions (the dense admission's
    send maps still mark such items as shipped, so size stage caps to
    load, like ``capacity`` itself).

    ``pr``/``pc`` pin the factorization (e.g. to match a physical 2-D
    mesh); by default ``P`` is factored as close to square as possible.
    """

    name = "hier"

    def __init__(self, pr: int | None = None, pc: int | None = None,
                 stage_caps: dict[str, tuple[int, int]] | None = None):
        self.pr = pr
        self.pc = pc
        self.stage_caps = dict(stage_caps or {})

    def _factor(self, nprocs: int) -> tuple[int, int]:
        pr, pc = self.pr, self.pc
        if pr is None and pc is None:
            pr = int(math.isqrt(nprocs))
            while nprocs % pr:
                pr -= 1
        elif pr is None:
            pr = nprocs // int(pc)
        pr = int(pr)
        pc = nprocs // pr if pc is None else int(pc)
        if pr < 1 or pc < 1 or pr * pc != nprocs:
            raise ValueError(
                f"HierarchicalTransport: {pr} x {pc} does not factor the "
                f"{nprocs}-rank axis")
        return pr, pc

    def _stage_caps(self, s: FlowWire, pr: int, pc: int) -> tuple[int, int]:
        if s.op_name in self.stage_caps:
            c1, c2 = self.stage_caps[s.op_name]
            return int(c1), int(c2)
        # worst-case bounds of dense-admitted traffic in ONE launch: a
        # source ships <= min(C_f, N_f) to each of a column's Pr ranks;
        # a relay forwards <= min(C_f, N_f) per (row source, dest rank)
        return (min(pr * s.capacity, s.n), pc * min(s.capacity, s.n))

    def _pre(self, backend, args):
        """Validate, factor the axis, and derive launch-invariant state."""
        specs = args.specs
        nprocs = backend.nprocs()
        pr, pc = self._factor(nprocs)
        # every bucket rank fits the hop lane's low bits: one that needed
        # more than ``shift`` bits would put the wire's P * cap_e word
        # slots past the i32 indices first (P > 2**(31 - shift))
        shift = hop_shift(nprocs)
        row_groups = tuple(tuple(i * pc + j for j in range(pc))
                           for i in range(pr))
        col_groups = tuple(tuple(i * pc + j for i in range(pr))
                           for j in range(pc))
        myrow = backend.rank() // pc

        caps_arr = jnp.asarray([s.capacity for s in specs], _I32)
        rounds_arr = jnp.asarray([s.rounds for s in specs], _I32)
        w1 = [s.roww + 1 for s in specs]          # + hop lane
        w1_arr = jnp.asarray(w1, _I32)
        c1 = [self._stage_caps(s, pr, pc)[0] for s in specs]
        c2 = [self._stage_caps(s, pr, pc)[1] for s in specs]
        nrounds = max(s.rounds for s in specs)

        destcol = (args.dest % pc).astype(_I32)
        # hop lane, source->relay: final dest rank | dense bucket rank o
        hop1 = ((args.dest.astype(_U32) << shift)
                | (args.offsets.astype(_U32) & _U32((1 << shift) - 1)))
        # stage-1 rows (body + hop lane), launch-invariant: every round
        # packs a window of the same matrix through the fused kernel
        row0, w1max = 0, max(w1)
        mats = []
        for fi, s in enumerate(specs):
            mats.append(jnp.concatenate(
                [args.bodies[fi],
                 hop1[row0:row0 + s.n].astype(_U32)[:, None]], axis=1))
            row0 += s.n
        rows1 = _pad_rows(mats, w1max)
        return _HierPre(args, pr, pc, row_groups, col_groups, myrow,
                        caps_arr, rounds_arr, w1, w1_arr, c1, c2,
                        jnp.asarray(c1, _I32), jnp.asarray(c2, _I32),
                        nrounds, destcol, hop1, rows1, shift)

    def _stage1(self, backend, pre, r):
        """Round r's source->relay hop: bin by dest column, row a2a."""
        args, specs = pre.args, pre.args.specs
        nflows = len(specs)
        pc, w1, c1 = pre.pc, pre.w1, pre.c1
        live = [fi for fi in range(nflows) if specs[fi].rounds > r]
        live_arr = jnp.asarray(
            [1 if specs[fi].rounds > r else 0 for fi in range(nflows)],
            _I32)
        # this launch ships exactly the dense round-r window — the
        # same items DenseTransport's round r ships
        fl = args.flow_id
        in_round = (args.valid & (pre.rounds_arr[fl] > r)
                    & (args.offsets >= r * pre.caps_arr[fl])
                    & (args.offsets < (r + 1) * pre.caps_arr[fl]))

        costs.record("exchange.bin",
                     costs.Cost(local=int(args.dest.shape[0])))
        cnt1, off1 = kops.multi_bin_offsets(pre.destcol, fl, pc, nflows,
                                            in_round, impl=args.impl)
        starts1, w1r = ragged_offsets([c1[fi] * w1[fi] for fi in live])
        woff1_map = dict(zip(live, starts1))
        woff1 = jnp.asarray(
            [woff1_map.get(fi, 0) for fi in range(nflows)], _I32)
        # fused wire pack: the stage form is the round-0 window with the
        # per-flow live mask as "rounds" (kops.stage_slots's contract)
        send1 = kops.pack_rows(pre.rows1, pre.destcol, fl, off1, in_round,
                               0, woff1, pre.w1_arr, pre.c1_arr, live_arr,
                               w1r, pc * w1r, impl=args.impl)
        src_state = {}
        row0 = 0
        nprocs = backend.nprocs()
        for fi, s in enumerate(specs):
            sl = slice(row0, row0 + s.n)
            if s.rounds > r:
                ship1 = in_round[sl] & (off1[sl] < c1[fi])
                r1 = jnp.where(ship1, pre.destcol[sl] * c1[fi] + off1[sl],
                               pc * c1[fi]).astype(_I32)
                dslot = jnp.where(
                    ship1, args.dest[sl] * s.cap_e + args.offsets[sl],
                    nprocs * s.cap_e).astype(_I32)
                src_state[fi] = (r1, dslot)
            row0 += s.n
        extra = jnp.maximum(cnt1 - pre.c1_arr[None, :], 0).sum(0)
        recv1 = backend.all_to_all(send1, groups=pre.row_groups) \
            .reshape(pc, w1r)
        return _Stage1Out(live, woff1_map, recv1, src_state, extra)

    def _stage2(self, backend, pre, s1):
        """One round's relay re-bin + relay->owner hop + owner scatter."""
        args, specs = pre.args, pre.args.specs
        nflows = len(specs)
        pr, pc, w1, c1, c2 = pre.pr, pre.pc, pre.w1, pre.c1, pre.c2
        live, woff1_map, recv1 = s1.live, s1.woff1_map, s1.recv1
        nprocs = backend.nprocs()

        # ---- relay: recover source positionally, re-bin by row ----
        rel_bins, rel_flow, rel_valid, rel_rows = [], [], [], []
        for fi in live:
            s = specs[fi]
            seg = rows_from_words(
                recv1[:, woff1_map[fi]:woff1_map[fi] + c1[fi] * w1[fi]],
                w1[fi])
            meta = seg[:, s.roww - 1]
            hop = seg[:, s.roww]
            rv = (meta & _VALID_BIT) != 0
            dst = (hop >> pre.shift).astype(_I32)
            o = (hop & _U32((1 << pre.shift) - 1))
            # stage-1 arrival block index IS the source's column
            src_col = jnp.arange(pc * c1[fi], dtype=_I32) // c1[fi]
            src = (pre.myrow * pc + src_col).astype(_U32)
            hop2 = (src << pre.shift) | o
            rel_rows.append(jnp.concatenate(
                [seg[:, :s.roww], hop2[:, None]], axis=1))
            rel_bins.append(jnp.where(rv, dst // pc, 0))
            rel_flow.append(jnp.full((pc * c1[fi],), fi, _I32))
            rel_valid.append(rv)
        rbins = jnp.concatenate(rel_bins)
        rflow = jnp.concatenate(rel_flow)
        rvalid = jnp.concatenate(rel_valid)

        # ---- stage 2: bin by destination row, column all-to-all ----
        costs.record("exchange.bin",
                     costs.Cost(local=int(rbins.shape[0])))
        cnt2, off2 = kops.multi_bin_offsets(rbins, rflow, pr, nflows,
                                            rvalid, impl=args.impl)
        live_arr = jnp.asarray(
            [1 if fi in live else 0 for fi in range(nflows)], _I32)
        starts2, w2r = ragged_offsets([c2[fi] * w1[fi] for fi in live])
        woff2_map = dict(zip(live, starts2))
        woff2 = jnp.asarray(
            [woff2_map.get(fi, 0) for fi in range(nflows)], _I32)
        send2 = kops.pack_rows(
            _pad_rows(rel_rows, max(w1[fi] for fi in live)), rbins, rflow,
            off2, rvalid, 0, woff2, pre.w1_arr, pre.c2_arr, live_arr, w2r,
            pr * w2r, impl=args.impl)
        rel_state = {}
        m0 = 0
        for fi in live:
            mfi = pc * c1[fi]
            sl = slice(m0, m0 + mfi)
            ship2 = rvalid[sl] & (off2[sl] < c2[fi])
            rel_state[fi] = jnp.where(
                ship2, rbins[sl] * c2[fi] + off2[sl],
                pr * c2[fi]).astype(_I32)
            m0 += mfi
        extra = s1.extra + jnp.maximum(cnt2 - pre.c2_arr[None, :], 0).sum(0)
        recv2 = backend.all_to_all(send2, groups=pre.col_groups) \
            .reshape(pr, w2r)

        # ---- owner: recover dense slots for the scatter ----
        own_state = {}
        scatters = {}
        for fi in live:
            s = specs[fi]
            seg2 = rows_from_words(
                recv2[:, woff2_map[fi]:woff2_map[fi] + c2[fi] * w1[fi]],
                w1[fi])
            meta2 = seg2[:, s.roww - 1]
            hop2v = seg2[:, s.roww]
            v2 = (meta2 & _VALID_BIT) != 0
            src2 = (hop2v >> pre.shift).astype(_I32)
            o2 = (hop2v & _U32((1 << pre.shift) - 1)).astype(_I32)
            dslot = jnp.where(v2, src2 * s.cap_e + o2,
                              nprocs * s.cap_e).astype(_I32)
            scatters[fi] = (dslot, seg2[:, :s.roww])
            own_state[fi] = dslot
        return _RoundOut(_HierRound(live, s1.src, rel_state, own_state),
                         scatters, extra)

    def _assemble(self, backend, pre, rounds):
        """Fold completed rounds into owner segments + cost records."""
        args, specs = pre.args, pre.args.specs
        nflows = len(specs)
        pr, pc, w1, c1, c2 = pre.pr, pre.pc, pre.w1, pre.c1, pre.c2
        nprocs = backend.nprocs()

        seg_out = [jnp.zeros((nprocs * s.cap_e * s.roww,), _U32)
                   for s in specs]
        extra = jnp.zeros((nflows,), _I32)
        for out in rounds:
            for fi, (dslot, rows) in out.scatters.items():
                # dense-slot owner scatter through the in-kernel placer:
                # word slot = row slot * row width, sentinel rows (dslot
                # == P * cap_e) land exactly at the buffer size and drop
                seg_out[fi] = kops.place_rows(
                    seg_out[fi], dslot * specs[fi].roww, rows,
                    impl=args.impl)
            extra = extra + out.extra
        seg_out = [rows_from_words(w, s.roww) for w, s in zip(seg_out, specs)]

        # cost attribution: the requester-side hop under the flow's own
        # op (retry launches under "<op>.retry"); ALL relay->owner hop
        # bytes (every launch) under "<op>.relay"; each launch is 2
        # collectives / 2 dependent rounds / 2 hops under the plan op
        for fi, s in enumerate(specs):
            b1 = pc * c1[fi] * w1[fi] * 4
            b2 = pr * c2[fi] * w1[fi] * 4
            costs.record(s.op_name, costs.Cost(bytes_moved=b1, bytes_out=b1))
            if s.rounds > 1:
                rb = b1 * (s.rounds - 1)
                costs.record(f"{s.op_name}.retry",
                             costs.Cost(bytes_moved=rb, bytes_out=rb))
            rel = b2 * s.rounds
            costs.record(f"{s.op_name}.relay",
                         costs.Cost(bytes_moved=rel, bytes_out=rel))
        costs.record(args.plan_op, costs.Cost(collectives=2, rounds=2,
                                              hops=2))
        for _ in range(pre.nrounds - 1):
            costs.record(f"{args.plan_op}.retry",
                         costs.Cost(collectives=2, rounds=2, hops=2))

        dropped = backend.psum(extra).astype(_I32)
        ctx = _HierCtx(specs, args.plan_op, args.impl, pr, pc, c1, c2,
                       pre.row_groups, pre.col_groups,
                       [out.rnd for out in rounds])
        return seg_out, dropped, ctx

    def request(self, backend, args):
        # synchronous path: the stages interleave per round, exactly the
        # pre-split launch order [s1_r0, s2_r0, s1_r1, s2_r1, ...] — the
        # fault-injection launch numbering and every cost pin depend on
        # this ordering staying put
        pre = self._pre(backend, args)
        rounds = [self._stage2(backend, pre, self._stage1(backend, pre, r))
                  for r in range(pre.nrounds)]
        return self._assemble(backend, pre, rounds)

    def request_start(self, backend, args):
        # split-phase: issue EVERY round's source->relay hop up front
        # (the hops are mutually independent — each ships its own dense
        # round window), deferring relays, owner hops, and scatters to
        # the wait.  Launch order becomes [s1_r0 .. s1_rk, s2_r0 ..],
        # overlapping the two hops across the caller's window.
        pre = self._pre(backend, args)
        s1s = [self._stage1(backend, pre, r) for r in range(pre.nrounds)]
        return InFlight(pre.nrounds, (pre, s1s))

    def request_wait(self, backend, handle):
        pre, s1s = handle.state
        rounds = [self._stage2(backend, pre, s1) for s1 in s1s]
        return self._assemble(backend, pre, rounds)

    def reply(self, backend, ctx, staged):
        specs = ctx.specs
        nprocs = backend.nprocs()
        pr, pc, c1, c2 = ctx.pr, ctx.pc, ctx.c1, ctx.c2
        rls = {fi: staged[fi].shape[1] for fi in staged}

        # ---- inverse stage 2: owner -> relay, ONE collective covering
        # every launch (per-launch blocks concatenate along words) ----
        blocks2, layout = [], []
        for rnd in ctx.rounds:
            rf = [fi for fi in rnd.live if fi in staged]
            parts = []
            for fi in rf:
                s = specs[fi]
                dslot = rnd.own[fi]                    # (pr*c2,) sentinel
                in_r = dslot < nprocs * s.cap_e
                rows = jnp.where(
                    in_r[:, None],
                    staged[fi][jnp.minimum(dslot, nprocs * s.cap_e - 1)], 0)
                parts.append(rows.reshape(pr, c2[fi] * rls[fi]))
            layout.append(rf)
            blocks2.append(jnp.concatenate(parts, axis=1) if parts
                           else jnp.zeros((pr, 0), _U32))
        send2 = jnp.concatenate(blocks2, axis=1)
        wtot2 = send2.shape[1]
        back2 = backend.all_to_all(send2.reshape(-1), groups=ctx.col_groups) \
            .reshape(pr, wtot2)

        # ---- inverse stage 1: relay -> source, ONE collective ----
        blocks1 = []
        woff = 0
        for rnd, rf in zip(ctx.rounds, layout):
            parts = []
            for fi in rf:
                rl = rls[fi]
                rep2 = rows_from_words(back2[:, woff:woff + c2[fi] * rl],
                                       rl)
                woff += c2[fi] * rl
                r2 = rnd.rel[fi]                       # (pc*c1,) sentinel
                in_r = r2 < pr * c2[fi]
                rows = jnp.where(
                    in_r[:, None],
                    rep2[jnp.minimum(r2, pr * c2[fi] - 1)], 0)
                parts.append(rows.reshape(pc, c1[fi] * rl))
            blocks1.append(jnp.concatenate(parts, axis=1) if parts
                           else jnp.zeros((pc, 0), _U32))
        send1 = jnp.concatenate(blocks1, axis=1)
        wtot1 = send1.shape[1]
        back1 = backend.all_to_all(send1.reshape(-1), groups=ctx.row_groups) \
            .reshape(pc, wtot1)

        # ---- source: land replies in the dense send-slot layout ----
        outs = {fi: jnp.zeros((nprocs * specs[fi].cap_e * rls[fi],), _U32)
                for fi in staged}
        woff = 0
        for rnd, rf in zip(ctx.rounds, layout):
            for fi in rf:
                rl = rls[fi]
                rep1 = rows_from_words(back1[:, woff:woff + c1[fi] * rl],
                                       rl)
                woff += c1[fi] * rl
                r1, dslot = rnd.src[fi]
                in_r = r1 < pc * c1[fi]
                rows = jnp.where(
                    in_r[:, None],
                    rep1[jnp.minimum(r1, pc * c1[fi] - 1)], 0)
                outs[fi] = kops.place_rows(outs[fi], dslot * rl, rows,
                                           impl=ctx.impl)

        outs = {fi: rows_from_words(w, rls[fi]) for fi, w in outs.items()}
        for fi in sorted(staged):
            s = specs[fi]
            b1 = pc * c1[fi] * rls[fi] * 4 * s.rounds
            b2 = pr * c2[fi] * rls[fi] * 4 * s.rounds
            costs.record(s.op_name, costs.Cost(bytes_moved=b1, bytes_in=b1))
            costs.record(f"{s.op_name}.relay",
                         costs.Cost(bytes_moved=b2, bytes_in=b2))
        costs.record(ctx.plan_op, costs.Cost(collectives=2, rounds=2,
                                             hops=2))
        return outs


#: process-wide default transport: unchanged programs compile unchanged
DENSE = DenseTransport()


def make_transport(name: str | Transport | None,
                   pr: int | None = None,
                   pc: int | None = None) -> Transport:
    """Transport factory for config/benchmark knobs.

    ``None``/``"dense"`` return the shared :data:`DENSE` singleton;
    ``"hier"`` builds a :class:`HierarchicalTransport` (optionally with
    a pinned ``pr x pc`` factorization); an existing transport passes
    through — the "user-injected backend" path.
    """
    if name is None:
        return DENSE
    if isinstance(name, Transport):
        return name
    if name == "dense":
        return DENSE
    if name == "hier":
        return HierarchicalTransport(pr, pc)
    raise ValueError(f"unknown transport {name!r} (want 'dense' or 'hier')")
