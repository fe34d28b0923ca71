#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's and the
control's, over several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it runs the cell as bench/run.py does (set-up, lead-in, a
window of --seconds at the cell's own load, the reference on as many
finished requests as the check file's `requests`) and prints one JSON
line: the program's `correct` and
widest logit gap (logit_gap_max), and the control's, decided by the same
comparison with the control's tokens in place of the served ones: at each
served position, the token that the reference computed with float8
matmuls puts first. The limit in checks/<cell>.json lies above the
program's readings and below the control's. The benchmark's own runs do
not run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench import harness
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False,
                          control=True)
        ctl = out.get("control", {})
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "logit_gap_max": out["check"]["logit_gap_max"],
                          "control_correct": ctl.get("correct"),
                          "control_gap_max": ctl.get("check", {}).get(
                              "logit_gap_max"),
                          "metrics": out["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
