"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,collectives,bytes_moved,rounds,derived`` CSV
rows (benchmarks/util.emit); modules that predate the cost columns leave
them empty.

  micro_hashmap   paper Fig. 9   (insert / insert_buffer / find variants)
  micro_queue     paper Fig. 10/11 (CircularQueue vs FastQueue, promises)
  isx             paper Fig. 5   (bucket sort, aggregation sweep)
  meraculous      paper Fig. 6/7 (contig-generation build + traversal)
  kmer            paper Fig. 8   (k-mer counting +/- Bloom filter)
  lm_step         framework-side step throughput (reduced configs)

``--smoke`` runs each benchmark at tiny sizes (seconds, not minutes) so
the tier-1 suite can exercise the full benchmark path and its cost
accounting; timings from a smoke run are not meaningful.

``--fused`` adds the plan/commit-fusion arms (fused vs Promise.FINE
schedules) to the modules that have them, so the rounds_per_op column
shows the collective-count reduction side by side with wall time.

``--skew zipf`` adds the skewed-traffic arms (drop-mode vs carryover
retry rounds at mean-load capacity) to the modules that have them; the
retry_rounds and dropped columns track skew tolerance over time.  The
retry arms pick their round count with ``exchange.suggest_rounds`` over
the observed wave loads.

``--transport {dense,hier}`` re-runs the exchange-layer arms over the
named physical transport (DESIGN.md section 1.7); hierarchical rows are
suffixed ``_hier`` and the ``hops`` column shows the two-stage launches.

``--faults`` adds the fault-injection arms (DESIGN.md section 1.8) to
the modules that have them: a seeded FaultSpec corrupts wire segments
under the integrity checksum, the carry retry heals the loss, and a
degraded commit masks a dead rank — the lost_bytes / recovered /
unreachable columns track the robustness observables over time.

``--async`` adds the split-phase arms (DESIGN.md section 1.9) to the
modules that have them: the same ops issued via commit_async, completed
via finish after an overlap window — the overlap_launches column counts
the deferred launches while every other cost column matches the sync
row (the charge-once-at-wait attribution rule).

``--wire {scatter,fused}`` pins the send-buffer construction path
(DESIGN.md section 1.10) on the modules that have wire arms: ``scatter``
forces the documented scatter_rows fallback (impl="jnp"), ``fused`` the
one-kernel Pallas pack (impl="pallas"); rows are suffixed ``_scatter`` /
``_fused`` and the hbm_passes column reports the traced call's
standalone scatter-op count — fewer on the fused path, same bytes and
collectives everywhere.
"""

from __future__ import annotations

import inspect
import sys


def main() -> None:
    from benchmarks import isx, kmer, lm_step, meraculous, micro_hashmap, \
        micro_queue
    from benchmarks.util import HEADER
    from repro.launch.compile_cache import enable_compile_cache
    mods = {
        "micro_hashmap": micro_hashmap,
        "micro_queue": micro_queue,
        "isx": isx,
        "meraculous": meraculous,
        "kmer": kmer,
        "lm_step": lm_step,
    }
    args = [a for a in sys.argv[1:]]
    smoke = "--smoke" in args
    fused = "--fused" in args
    faults = "--faults" in args
    async_ = "--async" in args
    skew = "none"
    if "--skew" in args:
        i = args.index("--skew")
        skew = args[i + 1] if i + 1 < len(args) else ""
        if skew not in ("zipf",):
            sys.exit(f"--skew takes a distribution name (zipf), "
                     f"got {skew!r}")
        del args[i:i + 2]
    transport = "dense"
    if "--transport" in args:
        i = args.index("--transport")
        transport = args[i + 1] if i + 1 < len(args) else ""
        if transport not in ("dense", "hier"):
            sys.exit(f"--transport takes dense or hier, got {transport!r}")
        del args[i:i + 2]
    wire = "auto"
    if "--wire" in args:
        i = args.index("--wire")
        wire = args[i + 1] if i + 1 < len(args) else ""
        if wire not in ("scatter", "fused"):
            sys.exit(f"--wire takes scatter or fused, got {wire!r}")
        del args[i:i + 2]
    args = [a for a in args if a not in ("--smoke", "--fused", "--faults", "--async")]
    only = args[0] if args else None
    enable_compile_cache()
    print(HEADER)
    failed = []
    for name, mod in mods.items():
        if only and name != only:
            continue
        params = inspect.signature(mod.run).parameters
        kw = {}
        if smoke and "smoke" in params:
            kw["smoke"] = True
        if fused and "fused" in params:
            kw["fused"] = True
        if skew != "none" and "skew" in params:
            kw["skew"] = skew
        if transport != "dense" and "transport" in params:
            kw["transport"] = transport
        if faults and "faults" in params:
            kw["faults"] = True
        if async_ and "async_" in params:
            kw["async_"] = True
        if wire != "auto" and "wire" in params:
            kw["wire"] = wire
        try:
            if smoke and "smoke" not in params:
                print(f"{name},SKIPPED,,,,,,,,,,,,,no smoke mode yet")
            elif transport != "dense" and "transport" not in params:
                print(f"{name},SKIPPED,,,,,,,,,,,,,no transport arm yet")
            elif wire != "auto" and "wire" not in params:
                print(f"{name},SKIPPED,,,,,,,,,,,,,no wire arm yet")
            else:
                mod.run(**kw)
        except Exception as e:  # keep the harness going; report the row
            print(f"{name},ERROR,,,,,,,,,,,,,{type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
