#!/usr/bin/env python3
"""Benchmark of the adaptive-FEM solver and its dynamic load balancer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips JAX finds: sets up,
measures for ``--seconds``, compares what the window produced with the
plain reference, and prints the result as one JSON line, last on standard
output; each number compared, with its limit, ends standard error.
``--trace 1`` profiles the window (one adaptive cycle, or a few seconds
of rebalances) and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell asks
for, it exits with status 1 and prints no result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    harness.use_compile_cache()
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 1
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
