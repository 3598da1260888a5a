#!/usr/bin/env python3
"""Device time by the library's layer scopes.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

The library wraps each layer's work in ``costs.scope(op)``, a
``jax.named_scope("bcl." + op)`` under the op's cost-log name
(DESIGN.md section 1.11), so the ``op_name`` metadata of every compiled
instruction holds the layers it was traced in, outermost first:
``jit(step)/bcl.queue.push/bcl.exchange.commit/bcl.transport.request/
gather``.  The innermost ``bcl.`` entry is the op's layer.

  scopes(hlo_text)    {(module, instruction): innermost scope or None},
                      from the same ``op_name`` that ``hlo_names.labels``
                      reads; a fusion carries its root's ``op_name``, so
                      it is charged where its label comes from
  joined(scopes, labels)  names for ``trace_reduce.reduce_xplane``, so
                      that each device op of a trace reads
                      ``<scope>|<label>``; an op with no ``op_name``, or
                      none of the scope vocabulary (ops XLA makes itself,
                      the harness's own ops), is ``(unscoped)``
  by_scope(summary)   {scope: seconds}: summed self time of the leaf ops
                      of each scope inside the window, averaged over
                      chips; the values add up to the leaf-op total that
                      ``TraceSummary.top_ops`` shares out by label
  scope_seconds(summary, prefix)  the seconds of the scopes that start
                      with ``prefix`` (``bcl.transport.``)

Run as a script, it makes one traced run of a cell through ``run.py``
(the same set-up, window and check), reads the trace a second time by
scope, and prints on standard error ``bench: scopes {...}``, the layer
readings of ``LAYERS`` in ms per step, and the leaf-op totals of both
readings.  The result line on standard output is ``run.py``'s own.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

UNSCOPED = "(unscoped)"
SEP = "|"
_SCOPE = re.compile(r"(?:^|[/(])(bcl\.[\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

#: per-layer readings a traced run of each cell gives: the device self
#: time of the scopes starting with the prefix, in ms per step
LAYERS = {
    "isx.sort": {"transport_ms.isx": "bcl.transport.",
                 "exchange_ms.isx": "bcl.exchange.",
                 "queue_ms.isx": "bcl.queue."},
    "kmer.lookup": {"transport_ms.lookup": "bcl.transport.",
                    "exchange_ms.lookup": "bcl.exchange.",
                    "hashmap_ms.lookup": "bcl.hashmap.",
                    "probe_bin_ms.lookup": "bcl.probe.bin"},
}


def innermost(op_name: str | None) -> str | None:
    """The last ``bcl.`` entry of an ``op_name``, else None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def scopes(hlo_text: str) -> dict:
    """{(module, instruction): innermost bcl scope or None} for one
    compiled program, over the instructions ``hlo_names.labels`` names."""
    from bench import hlo_names

    module, out = None, {}
    for line in hlo_text.splitlines():
        m = hlo_names._MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = hlo_names._INSTR.match(line)
        if m:
            src = _OP_NAME.search(m.group(2))
            out[(module, m.group(1))] = innermost(src.group(1) if src
                                                  else None)
    return out


def joined(scope_map: dict, labels: dict) -> dict:
    """{(module, instruction): ``<scope>|<label>``} for ``reduce_xplane``."""
    return {k: f"{scope_map.get(k) or UNSCOPED}{SEP}{lab}"
            for k, lab in labels.items()}


def scope_of(name: str) -> str:
    """The scope part of an op named by :func:`joined`; an op the
    programs did not name is unscoped."""
    head = name.split(SEP, 1)[0]
    return head if SEP in name and head.startswith("bcl.") else UNSCOPED


def by_scope(summary) -> dict:
    """{scope: seconds} of leaf-op self time inside the window, averaged
    over chips, largest first."""
    tot: dict = {}
    for name, t in summary.top_ops(10 ** 9):
        s = scope_of(name)
        tot[s] = tot.get(s, 0.0) + t
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def scope_seconds(summary, prefix: str) -> float:
    """Seconds of the scopes that start with ``prefix``."""
    return sum(t for s, t in by_scope(summary).items()
               if s.startswith(prefix))


def layers(summary, cell: str, steps: int) -> dict:
    """{reading: ms per step} of :data:`LAYERS` for ``cell``; a reading
    whose scopes ran no op is left out."""
    out = {}
    for name, prefix in LAYERS.get(cell, {}).items():
        t = scope_seconds(summary, prefix)
        if t > 0 and steps:
            out[name] = t / steps * 1e3
    return out


def traced_by_scope(call):
    """Run ``call()``, a traced run of a cell through ``run.py``, and
    read its trace by scope too.  Returns ``(what call returned,
    {"scopes": summary by scope, "labels": run.py's summary} or {} when
    the run read no trace, the cells the run built)``."""
    from types import SimpleNamespace

    from bench import hlo_names, run, trace_reduce

    texts, cells, got = [], [], {}
    originals = (hlo_names.labels, trace_reduce.reduce_xplane,
                 run.load_config)
    labels_of, reduce_xplane, load_config = originals

    def labels(text):
        texts.append(text)
        return labels_of(text)

    def reduce(path, chips, labels=None, **kw):
        scope_map = {}
        for text in texts:
            scope_map.update(scopes(text))
        got["scopes"] = reduce_xplane(path, chips,
                                      joined(scope_map, labels or {}), **kw)
        got["labels"] = reduce_xplane(path, chips, labels, **kw)
        return got["labels"]

    def config(name):
        mod, cfg = load_config(name)

        def build(*a, **k):
            cells.append(mod.build(*a, **k))
            return cells[-1]
        return SimpleNamespace(build=build), cfg

    # the run is run.py's own; these wrappers only keep what it reads
    hlo_names.labels, trace_reduce.reduce_xplane = labels, reduce
    run.load_config = config
    try:
        out = call()
    finally:
        (hlo_names.labels, trace_reduce.reduce_xplane,
         run.load_config) = originals
    return out, got, cells


def report(cell: str, got: dict, steps: int) -> list:
    """The stderr lines of a traced run read by scope."""
    summary = got["scopes"]
    leaf = sum(t for _, t in got["labels"].top_ops(10 ** 9))
    split = by_scope(summary)
    return [f"bench: scopes {json.dumps(split)}",
            f"bench: layers {json.dumps(layers(summary, cell, steps))} "
            f"steps={steps} leaf_s={leaf!r} "
            f"scoped_plus_unscoped_s={sum(split.values())!r}",
            f"bench: scope_ops {json.dumps(summary.top_ops(25))}"]


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    rc, got, cells = traced_by_scope(lambda: run.main(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--trace", "1"]))
    if rc or "scopes" not in got:
        return rc or 1
    for line in report(args.workload, got,
                       cells[0].counters().get("steps", 0)):
        print(line, file=sys.stderr)
    return 0


if __name__ == "__main__":
    # import this directory as the package ``bench``, as run.py does
    BENCH = Path(__file__).resolve().parent
    sys.path[:] = [str(BENCH.parent / "src"), str(BENCH.parent)] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    from bench.scopes import main as _main

    raise SystemExit(_main())
