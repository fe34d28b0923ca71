#!/usr/bin/env python3
"""Find a configuration's KV pool size on the chip: the largest
`serving.num_blocks` at which a run of the cell goes through.

    python3 bench/poolsize.py --workload <cell> --seed <n> --seconds <s> --blocks <N> [<N> ...]

Tries each N, from the largest down, in a process of its own: a copy of
the benchmark whose configuration file holds that N runs the cell as
bench/run.py does (set-up with every packed-step shape the traffic
reaches, the lead-in, a window of --seconds, the check). It stops at the
first N that runs, and prints one JSON line per N tried: whether it ran,
the chip's peak and limit in bytes, and the end of the error where it did
not. Copy the number, less the margin the configuration file states,
into its serving.num_blocks by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
TRIAL_S = 600            # a trial that has not ended by then has failed


def trial(cell: str, config: str, n: int, seed: int, seconds: float) -> dict:
    with tempfile.TemporaryDirectory(prefix="bench-pool-") as tmp:
        tmp = Path(tmp)
        shutil.copytree(CHECKOUT / "bench", tmp / "bench",
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))
        shutil.copy(CHECKOUT / "BENCHMARK.json", tmp)
        os.symlink(CHECKOUT / "src", tmp / "src")
        path = tmp / "bench" / "configs" / f"{config}.json"
        c = json.loads(path.read_text())
        c["serving"]["num_blocks"] = n
        path.write_text(json.dumps(c))
        try:
            p = subprocess.run(
                [sys.executable, str(tmp / "bench" / "run.py"), "--workload",
                 cell, "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", "0"], capture_output=True, text=True,
                timeout=TRIAL_S)
        except subprocess.TimeoutExpired as e:
            return {"num_blocks": n, "ran": False, "rc": None,
                    "error": f"no end within {TRIAL_S} s: "
                             f"{(e.stderr or b'')[-1500:]!r}"}
    out = {"num_blocks": n, "ran": False, "rc": p.returncode}
    lines = p.stdout.strip().splitlines()
    limit = re.search(r"limit_gb=([0-9.]+)", p.stdout)
    if limit:
        out["bytes_limit"] = float(limit.group(1)) * 1e9
    if p.returncode == 0 and lines:
        res = json.loads(lines[-1])
        out.update(ran=True, correct=res["correct"],
                   memory_peak_bytes=res["device"]["memory_peak_bytes"])
    else:
        out["error"] = p.stderr[-1500:]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--blocks", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT)]
    from bench import registry
    config = registry.cell(registry.load_benchmark(), args.workload)["config"]
    for n in sorted(args.blocks, reverse=True):
        out = trial(args.workload, config, n, args.seed, args.seconds)
        print(json.dumps(out), flush=True)
        if out["ran"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
