"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines plus the per-table CSVs.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""
from __future__ import annotations

import argparse
import time


def _timed(name, fn, fast):
    t0 = time.perf_counter()
    result = fn(fast=fast)
    dt = (time.perf_counter() - t0) * 1e6
    print(f"{name},{dt:.0f},rows={len(result) if result else 0}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size runs (bert-small QAT etc.)")
    args, _ = ap.parse_known_args()
    fast = not args.full

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (fig2_fidelity, fig3_scaling, roofline_report,
                            serving_throughput, table1_accuracy,
                            table2_granularity, table3_throughput)

    print("name,us_per_call,derived")
    _timed("table3_throughput", table3_throughput.run, fast)
    _timed("serving_throughput", serving_throughput.run, fast)
    _timed("fig2_fidelity", fig2_fidelity.run, fast)
    _timed("fig3_scaling", fig3_scaling.run, fast)
    _timed("roofline_report", roofline_report.run, fast)
    _timed("table1_accuracy", table1_accuracy.run, fast)
    _timed("table2_granularity", table2_granularity.run, fast)


if __name__ == "__main__":
    main()
