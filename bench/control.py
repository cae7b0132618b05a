#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--control-seeds 3]

Runs the cell once per seed in this one process (a short window at the
cell's own size and load), and prints one JSON line per seed with the
numbers the program reads against the plain reference and, for the first
``--control-seeds`` seeds, the numbers the control reads (the reference
at the precision below the configuration's, and the reference's partition
of the domain under the per-axis box map, in the program's place, on the
same inputs).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    from bench import harness
    harness.use_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               control=i < args.control_seeds)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"], "device": out["device"],
            "program": {k: c["value"] for k, c in out["checks"].items()},
            "control": out.get("control_checks")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
