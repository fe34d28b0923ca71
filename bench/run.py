#!/usr/bin/env python3
"""Run one benchmark cell on this machine's chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Loads the cell named in BENCHMARK.json, sets
up, measures for --seconds, checks the served tokens against the
reference, and prints one JSON object as the last line of standard output:
with --trace 0 the cell's end-to-end metrics, with --trace 1 its
per-layer metrics from a traced sub-window. Each number the check compares
is printed beside its limit as the last lines of standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero
before measuring and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(e, file=sys.stderr, flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
