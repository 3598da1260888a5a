#!/usr/bin/env python3
"""Run one cell on several seeds in one process: the program, or its
control in the program's place.

    python3 bench/seeds.py --workload <cell> --seconds <s> --seeds 1 2 3 [--control]

This is how the limits of the comparison that decides ``correct`` are
set: the program's readings over a dozen seeds or more give the lower
reading, the control's over three or more the upper one.  One process
reaches the chip once for all seeds.  Each seed prints one JSON line:
the seed, ``correct``, and every number compared.  The benchmark's own
runs (``bench/run.py``) never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    from bench import run
    from bench.device import NoChip

    run.configure_jax()
    for seed in args.seeds:
        try:
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             t0=time.time(), control=args.control)
        except NoChip as e:
            print(f"seeds: {e}", file=sys.stderr)
            return 1
        print(json.dumps({
            "seed": seed, "control": args.control, "correct": r["correct"],
            "checks": {k: c["value"] for k, c in r["checks"].items()},
            "metrics": {k: m["value"] for k, m in r["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
