#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: the system under test is ``src/repro``
beside this directory.  Everything else is data found by name from
``BENCHMARK.json``:

  cell          an entry of ``workloads``: its configuration, traffic mix
                and chips
  configuration ``bench/configs/<config>.json`` (sizes, source, cuts) and
                ``bench/configs/<config>.py`` (program builder, seeded
                data, plain reference; ``build()`` returns the cell's
                driver)
  traffic       ``bench/traffic/<cell>.json``, read by ``bench/traffic.py``
  metric        ``bench/metrics/<metric>.py`` for each per-layer metric:
                ``read(ctx)`` returns its number, or None where the run
                has nothing to read; ``ctx`` holds ``trace`` (a
                ``trace_reduce.TraceSummary``), ``counters`` (the cell's),
                ``steps``, ``chips``, ``cfg``, ``traffic`` and ``peaks``

A run: set-up (JAX on the chip, the compile cache, seeded data, the
cell's own programs warmed up) is ``setup_s``, from process start to the
window.  The window then runs for ``--seconds``: a closed loop of steps,
or an open loop of seeded Poisson arrivals served in batches.  After it
the peak device memory is read, the program's state is pulled to the
host and compared with the plain reference, and the result is printed.
With ``--trace 1`` the window runs under the profiler and the line
carries the per-layer metrics from the trace; otherwise it carries the
end-to-end metrics.  The numbers compared are printed last, on standard
error and in the line under ``checks``.

The run refuses (exit 1, no result) when JAX finds no TPU, fewer chips
than the cell asks for, or Pallas would interpret its kernels; and
(exit 2) outside a checkout that holds ``src/repro``.
"""

import time

T0 = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
PERCENTILE = re.compile(r"_p(\d+(?:\.\d+)?)_ms$")


# --------------------------------------------------------------------------
# what BENCHMARK.json says about a cell
# --------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str):
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in bench["end_to_end"] if _listed(m, cell)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if m["moves"] in names and _listed(m, cell)]
    return e2e, per


def load_module(path: Path, name: str):
    """The module in ``path``, loaded once per process under ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str):
    """(module, sizes) of configuration ``name``."""
    with open(BENCH / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    return load_module(BENCH / "configs" / f"{name}.py",
                       f"bench.configs.{name}"), cfg


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

def span(name: str):
    """A host span in the profiler's trace (cheap when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def closed_loop(cell, seconds: float) -> dict:
    """Steps back to back until ``seconds`` have passed; the rate is all
    the work over all the time, to the end of the last step."""
    ops = steps = 0
    t0 = time.perf_counter()
    while True:
        with span("step"):
            ops += cell.step()
        steps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {"ops": ops, "elapsed": elapsed, "steps": steps,
            "ops_per_s": ops / elapsed}


def open_loop(cell, traffic: dict, seed: int, seconds: float,
              chips: int) -> dict:
    """Seeded Poisson arrivals over ``seconds``, each step taking every
    due arrival up to the batch limit.  Arrivals due in the window that
    are still waiting when it closes are served after it: their latency
    counts, their completion does not count toward the rate."""
    import numpy as np

    from bench import traffic as tr

    arrivals = tr.Arrivals(float(traffic["rate_per_chip"]) * chips,
                           tr.rng(seed, "arrivals"))
    limit = int(traffic["max_batch_per_chip"]) * chips
    lat, done_in_window, steps, backlog = [], 0, 0, None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if backlog is None and now >= seconds:
            backlog = arrivals.waiting(seconds)
        due = arrivals.take(min(now, seconds), limit)
        if due.size == 0:
            nxt = arrivals.next_due(seconds)
            if nxt is None:
                if now >= seconds:
                    break
                nxt = seconds
            with span("idle"):
                time.sleep(max(0.0, nxt - now))
            continue
        with span("step"):
            done = cell.serve(int(due.size)) - t0
        lat.append(done - due)
        if done <= seconds:
            done_in_window += int(due.size)
        steps += 1
    lat = np.concatenate(lat) if lat else np.zeros(0)
    return {"ops": done_in_window, "elapsed": seconds, "steps": steps,
            "ops_per_s": done_in_window / seconds, "latency_s": lat,
            "backlog_at_close": backlog or 0}


def end_to_end(metrics, win: dict, setup_s: float) -> dict:
    import numpy as np

    out = {}
    for m in metrics:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "ops_per_s":
            v = win["ops_per_s"]
        elif PERCENTILE.search(name) and "latency_s" in win:
            q = float(PERCENTILE.search(name).group(1))
            v = float(np.percentile(win["latency_s"], q)) * 1e3
        else:
            raise KeyError(f"the harness has no end-to-end metric {name!r}")
        out[name] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

_COMPILES = [0]


def _count_compiles(event: str, duration: float, **_) -> None:
    if "backend_compile" in event:
        _COMPILES[0] += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t0: float | None = None, require_chip: bool = True,
             overrides: dict | None = None, control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict, with the
    compared numbers under ``checks``.

    ``require_chip=False`` and ``overrides`` (``{"config": {...},
    "traffic": {...}}`` merged over the files) serve the CPU tests;
    ``control=True`` runs the cell's control in the program's place.
    """
    import jax

    from bench import device, hlo_names, roofline, trace_reduce
    from bench import traffic as tr

    t0 = time.time() if t0 is None else t0
    bench = load_benchmark()
    entry = {w["name"]: w for w in bench["workloads"]}[workload]
    chips = int(entry["chips"])
    devices = (device.require_chip(chips) if require_chip
               else jax.devices()[:chips])
    if len(devices) < chips:
        raise device.NoChip(f"the cell needs {chips} devices")
    mesh = device.make_mesh(devices)
    mod, cfg = load_config(entry["config"])
    traf = tr.load(entry["traffic"])
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    traf.update(overrides.get("traffic", {}))
    e2e, per = cell_metrics(bench, workload)

    cell = mod.build(mesh, cfg, traf, seed, span, control=control)
    with span("setup"):
        cell.setup()
    setup_s = time.time() - t0

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles = _COMPILES[0]
    try:
        with span("window"):
            if cell.loop == "open":
                win = open_loop(cell, traf, seed, seconds, chips)
            else:
                win = closed_loop(cell, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = _COMPILES[0] - compiles
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": chips,
           "memory_peak_bytes": device.memory_peak_bytes(devices)}
    t_check = time.time()
    checks = cell.check()
    print(f"bench: {workload} seed={seed} setup_s={setup_s:.3f} "
          f"steps={win['steps']} window_s={win['elapsed']:.3f} "
          f"compiles_in_window={compiles} "
          f"backlog_at_close={win.get('backlog_at_close', 0)} "
          f"check_s={time.time() - t_check:.3f} "
          f"detail={getattr(cell, 'detail', {})}", file=sys.stderr)

    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": int(cell.attempted), "failed": int(cell.failed)}
    if trace:
        labels = {}
        for fn, args in cell.window_programs:
            labels.update(hlo_names.labels(fn.lower(*args).compile()
                                           .as_text()))
        try:
            summary = trace_reduce.reduce_xplane(
                trace_reduce.find_xplane(log_dir), chips, labels)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = SimpleNamespace(trace=summary, counters=cell.counters(),
                              steps=win["steps"], chips=chips, cfg=cfg,
                              traffic=traf, peaks=roofline.peaks(dev["kind"])
                              if require_chip else None)
        metrics = {}
        for m in per:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench.metrics.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s()
        dev["window_s"] = summary.window_s
        result["metrics"] = metrics
        result["device"] = dev
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    else:
        result["metrics"] = end_to_end(e2e, win, setup_s)
        result["device"] = dev
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def configure_jax() -> None:
    """The persistent compilation cache at its fixed place in the
    checkout, and the compile counter."""
    # the path is part of the cache's key; the program's own cache
    # setting reads this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir() or not (ROOT / "BENCHMARK.json").is_file():
        print(f"bench: no src/repro or BENCHMARK.json in {ROOT}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    # import this directory as the package ``bench``, never its files
    # as top-level modules
    sys.path[:] = [str(SRC), str(ROOT)] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    configure_jax()
    from bench.device import NoChip

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=T0)
    except NoChip as e:
        print(f"bench: {e}; the benchmark runs only on the chip",
              file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
