"""Shared benchmark timing utilities."""

from __future__ import annotations

import time

import jax

from repro.core import costs


def time_fn(fn, *args, warmup: int = 2, iters: int = 5, **kw):
    """Median wall time per call (seconds) of a jit-compatible fn."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def trace_costs(fn, *args, **kw):
    """Cost observables of one call of ``fn`` (collectives, bytes, rounds).

    Costs are recorded at trace time, so this must run on a FRESH jit
    wrapper (an already-compiled fn records nothing).  Call it before
    ``time_fn``; the traced call doubles as warmup.
    """
    with costs.recording() as log:
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return log.total()


#: the one CSV schema every benchmark row follows (schema-checked by
#: tests/test_benchmarks_smoke.py).  ``hops`` counts physical exchange
#: stages (1 per dense launch, 2 per hierarchical launch) so the
#: ``--transport`` arms' extra stage shows up next to wall time.
HEADER = ("name,us_per_call,collectives,bytes_moved,rounds,"
          "rounds_per_op,retry_rounds,dropped,hops,"
          "lost_bytes,recovered,unreachable,overlap_launches,derived")


def resolve_wire(name: str):
    """Shared ``--wire {scatter,fused}`` plumbing: impl + row-name tag.

    Returns ``(impl, suffix)`` — the kernel-dispatch impl to thread into
    container calls ("jnp" keeps the documented scatter_rows fallback,
    "pallas" takes the one-kernel wire path) and the row-name suffix
    ("" for the backend default, so existing arms keep their names).
    """
    if name not in ("auto", "scatter", "fused"):
        raise ValueError(f"--wire takes scatter or fused, got {name!r}")
    impl = {"auto": "auto", "scatter": "jnp", "fused": "pallas"}[name]
    return impl, "" if name == "auto" else f"_{name}"


def resolve_transport(name: str):
    """Shared ``--transport {dense,hier}`` plumbing: transport + tag.

    Returns ``(transport, suffix)`` — the transport instance to thread
    into container calls and the row-name suffix ("" for dense, so the
    default arms keep their historical names).
    """
    from repro.core import make_transport
    return make_transport(name), "" if name == "dense" else f"_{name}"

#: the --skew arms' virtual peer count: ceil(wave / SKEW_PEERS) is the
#: uniform per-bucket expectation ("mean-load capacity")
SKEW_PEERS = 4


def skew_retry_rounds(loads, capacity: int) -> int:
    """The ``--skew`` retry arms' round pick (ROADMAP adaptive rounds).

    Feeds the observed per-wave peak bucket loads into
    ``exchange.suggest_rounds`` instead of hardcoding
    :data:`SKEW_PEERS`: the arm runs exactly as many carryover rounds
    as the hottest observed bucket needs at the given per-round
    capacity, so the losslessness pins hold by construction and the
    ``retry_rounds`` CSV column tracks the heuristic's actual pick.
    """
    from repro.core import suggest_rounds
    return suggest_rounds(loads, capacity, limit=2 * SKEW_PEERS)


def mean_load_cap(n: int) -> int:
    """Per-round wire capacity at the uniform per-peer expectation.

    Ceil division, so ``SKEW_PEERS`` retry rounds always cover ``n``
    exactly — the retry arms' losslessness pins depend on it.  Every
    benchmark's skew arm uses THIS definition, so drop/retry rows are
    comparable across micro and application workloads.
    """
    return max(1, -(-n // SKEW_PEERS))


def zipf_wave_mask(n_waves: int, wave: int, total: int, s: float = 1.2):
    """Shared --skew workload shape: valid masks (n_waves, wave) whose
    wave sizes follow ~ total/(w+1)^s (hot waves saturate at ``wave``),
    so early waves hammer the hot bucket far past mean-load capacity.
    One definition keeps the micro_hashmap and micro_queue skew arms
    comparable; callers normalize per-op timings by the mask's actual
    ``sum()``, not ``total``, because of the saturation."""
    import jax.numpy as jnp
    import numpy as np
    zw = np.array([1.0 / (w + 1) ** s for w in range(n_waves)])
    sizes = np.maximum((zw / zw.sum() * total).astype(int), 1)
    return jnp.asarray(np.arange(wave)[None, :] < sizes[:, None])


def bench_skew_arm(fn, tag: str, rounds: int, n_ops: int, results: dict,
                   *args, derived: str = "mean-load wire capacity"):
    """Shared ``--skew`` arm protocol: trace the cost observables on a
    fresh jit, time the arm, read its dropped count, and emit ONE
    schema-complete CSV row (retry_rounds + dropped columns filled).
    ``fn(*args)`` must return ``(_, dropped)``; timings and the drop
    count land in ``results[tag]`` / ``results[tag + "_dropped"]``.
    One definition keeps every benchmark's skew rows on the schema that
    tests/test_benchmarks_smoke.py pins.
    """
    # one call serves as cost trace, dropped-count read, AND warmup —
    # costs record at trace time, so this must be fn's first execution
    with costs.recording() as log:
        out = fn(*args)
        jax.block_until_ready(out)
    d = int(out[-1])
    t = time_fn(fn, *args, warmup=1, iters=3)
    results[tag] = t / n_ops * 1e6
    results[tag + "_dropped"] = d
    emit(tag, results[tag], derived, cost=log.total(), n_ops=n_ops,
         retry_rounds=rounds, dropped=d)


def emit(name: str, us_per_call: float, derived: str = "",
         cost=None, n_ops: int | None = None,
         retry_rounds: int | None = None, dropped: int | None = None,
         lost_bytes: int | None = None, recovered: int | None = None,
         unreachable: int | None = None):
    """CSV row following :data:`HEADER`.

    ``rounds_per_op`` (rounds amortized over ``n_ops`` data-structure
    ops) is the collective-count observable of the plan/commit fusion:
    fused schedules cut it without touching bytes, so BENCH trajectories
    show the aggregation win directly.  ``retry_rounds``/``dropped``
    track skew tolerance: the ``--skew`` arms report how many carryover
    rounds they ran and how many items still fell off the wire, so the
    perf trajectory covers skewed traffic, not just uniform.
    ``lost_bytes``/``recovered``/``unreachable`` are the ``--faults``
    arms' observables (DESIGN.md section 1.8): wire bytes invalidated by
    injected faults, items healed by the integrity+carry retry, and dead
    destination ranks masked by a degraded commit; cost rows default the
    lost_bytes/unreachable columns from the recorded Cost fields.
    ``overlap_launches`` is the ``--async`` arms' observable (DESIGN.md
    section 1.9): collective launches issued split-phase whose
    completion was deferred past an overlap window.
    """
    rr = "" if retry_rounds is None else str(retry_rounds)
    dr = "" if dropped is None else str(dropped)
    lb = "" if lost_bytes is None else str(lost_bytes)
    rc = "" if recovered is None else str(recovered)
    un = "" if unreachable is None else str(unreachable)
    if cost is None:
        print(f"{name},{us_per_call:.2f},,,,,{rr},{dr},,"
              f"{lb},{rc},{un},,{derived}")
        return
    if lost_bytes is None:
        lb = str(cost.lost_bytes)
    if unreachable is None:
        un = str(cost.unreachable)
    rpo = f"{cost.rounds / n_ops:.6f}" if n_ops else ""
    print(f"{name},{us_per_call:.2f},{cost.collectives},"
          f"{cost.bytes_moved},{cost.rounds},{rpo},{rr},{dr},"
          f"{cost.hops},{lb},{rc},{un},{cost.overlap_launches},"
          f"{derived}")
