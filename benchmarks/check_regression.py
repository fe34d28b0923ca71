"""Perf-regression gate over BENCH_serving.json.

Compares a candidate serving-benchmark result against the committed
reference (the BENCH_serving.json checked in by the last benchmark run) and
exits nonzero when any gated metric regresses past its tolerance band —
the reframe-style performance-test discipline: every metric carries a
DIRECTION (higher- or lower-is-better) and a RELATIVE tolerance, and only
moves in the bad direction beyond the band fail.

Two metric classes, two tolerance regimes:

* timing metrics (tok/s, latency percentiles) are noisy across boxes and
  under CI contention, so their bands are wide — a throughput row must LOSE
  more than half its reference rate to fail, a latency percentile must
  more than 2.5x. These catch order-of-magnitude breakage (a step that
  stopped batching, a sharing path that stopped hitting), not 10% drift.
* structural metrics (cache-byte ratios, padding efficiency, hit/skip
  rates, greedy exact-match) are deterministic given the code, so their
  bands are tight (10%). These are the real per-PR gate.

Ratios the benchmark computes between its own rows (packed vs lockstep,
sharing on vs off, int8 vs fp32 bytes) are gated in ratio form, so a
globally slow box — which scales both sides — cancels out.

    # gate a fresh fast run against the committed reference
    PYTHONPATH=src python -m benchmarks.check_regression

    # gate one existing result file against another
    PYTHONPATH=src python -m benchmarks.check_regression \
        --reference BENCH_serving.json --candidate fresh.json

CI runs this as the non-blocking `perf-regression` job (.github/workflows/
ci.yml); tests/test_check_regression.py pins the pass/fail semantics with
synthetically degraded snapshots.
"""
from __future__ import annotations

import argparse
import json
import sys

HIGHER, LOWER = "higher", "lower"

# relative tolerance in the BAD direction: a HIGHER metric fails when
# cand < ref * (1 - tol); a LOWER metric fails when cand > ref * (1 + tol)
TOL_THROUGHPUT = 0.50    # tok/s and tok/s-derived ratios: cross-box noise
TOL_LATENCY = 1.50       # latency percentiles: queueing amplifies noise
TOL_STRUCTURAL = 0.10    # deterministic counters/ratios: the tight gate


def _get(snap: dict, path: tuple):
    """Walk `path` through dicts and [(key, value)]-selected list rows;
    returns None when any hop is missing (sections are skippable)."""
    cur = snap
    for hop in path:
        if cur is None:
            return None
        if isinstance(hop, tuple):
            key, val = hop
            if not isinstance(cur, list):
                return None
            cur = next((r for r in cur if r.get(key) == val), None)
        else:
            if not isinstance(cur, dict):
                return None
            cur = cur.get(hop)
    return cur


def metric_specs(ref: dict) -> list:
    """(name, path, direction, tolerance) for every gated metric PRESENT in
    the reference — rows the reference lacks (e.g. a --engine-filtered run)
    are simply not gated, so partial references stay usable."""
    specs = []
    for row in ref.get("engines") or []:
        name = row["scheduler"]
        specs.append((f"engines[{name}].tok_per_s",
                      ("engines", ("scheduler", name), "tok_per_s"),
                      HIGHER, TOL_THROUGHPUT))
        if row.get("padding_efficiency") is not None:
            specs.append((f"engines[{name}].padding_efficiency",
                          ("engines", ("scheduler", name),
                           "padding_efficiency"),
                          HIGHER, TOL_STRUCTURAL))
    for layout in ("lockstep", "packed"):
        specs.append((f"prefill_heavy[{layout}].tok_per_s",
                      ("prefill_heavy", ("step_layout", layout),
                       "tok_per_s"),
                      HIGHER, TOL_THROUGHPUT))
        specs.append((f"prefill_heavy[{layout}].padding_efficiency",
                      ("prefill_heavy", ("step_layout", layout),
                       "padding_efficiency"),
                      HIGHER, TOL_STRUCTURAL))
    for variant in ("off", "on"):
        specs.append((f"prefix_sharing[{variant}].tok_per_s",
                      ("prefix_sharing", ("variant", variant), "tok_per_s"),
                      HIGHER, TOL_THROUGHPUT))
    specs += [
        ("prefix_sharing[on].prefix.hit_rate",
         ("prefix_sharing", ("variant", "on"), "prefix", "hit_rate"),
         HIGHER, TOL_STRUCTURAL),
        ("prefix_sharing[on].prefix.skip_rate",
         ("prefix_sharing", ("variant", "on"), "prefix", "skip_rate"),
         HIGHER, TOL_STRUCTURAL),
        # the decode-sharing acceptance ratio: on/off measured on one box,
        # so box speed cancels — gate it structurally-tight-ish but leave
        # headroom for the short runs' scheduler noise
        ("multi_turn[on].vs_off",
         ("multi_turn", ("variant", "on"), "vs_off"),
         HIGHER, 0.25),
        ("multi_turn[on].prefix.followup_skip_rate",
         ("multi_turn", ("variant", "on"), "prefix", "followup_skip_rate"),
         HIGHER, TOL_STRUCTURAL),
        # the speculative-decoding acceptance ratio: same one-box on/off
        # form as multi_turn.vs_off, same noise headroom
        ("speculative[on].vs_off",
         ("speculative", ("variant", "on"), "vs_off"),
         HIGHER, 0.25),
        # draft acceptance rate is deterministic given the seeded workload
        ("speculative[on].acceptance_rate",
         ("speculative", ("variant", "on"), "acceptance_rate"),
         HIGHER, TOL_STRUCTURAL),
        ("speculative[on].tok_per_s",
         ("speculative", ("variant", "on"), "tok_per_s"),
         HIGHER, TOL_THROUGHPUT),
        ("kv_int8[int8].kv_bytes_vs_fp32",
         ("kv_int8", ("kv_quant", "int8"), "kv_bytes_vs_fp32"),
         LOWER, TOL_STRUCTURAL),
        ("kv_int8[int8].greedy_exact_match",
         ("kv_int8", ("kv_quant", "int8"), "greedy_exact_match"),
         HIGHER, TOL_STRUCTURAL),
        # the pipelined-loop acceptance ratio: async/sync timed in
        # interleaved passes on one box, so box speed cancels — the async
        # loop must at least hold the sync rate; same noise headroom as
        # the other one-box ratios
        ("async_loop.vs_sync",
         ("async_loop", "vs_sync"), HIGHER, 0.25),
        ("async_loop[async].tok_per_s",
         ("async_loop", "async", "tok_per_s"), HIGHER, TOL_THROUGHPUT),
        # greedy parity async-on vs async-off is exact-or-fail (the
        # benchmark asserts it inline; this guards the recorded flag)
        ("async_loop.greedy_parity",
         ("async_loop", "greedy_parity"), HIGHER, 0.0),
        # host-visible device-stall share, async/sync: the fence moved
        # from every dispatch to one-step-late commit, and this ratio is
        # the profiler's evidence it stays that way (timing-derived, so
        # the wide band)
        ("async_loop.stall_share_vs_sync",
         ("async_loop", "stall_share_vs_sync"), LOWER, TOL_LATENCY),
        ("latency_slo.tok_per_s",
         ("latency_slo", "tok_per_s"), HIGHER, TOL_THROUGHPUT),
        ("latency_slo.phase_coverage",
         ("latency_slo", "phase_coverage"), HIGHER, TOL_STRUCTURAL),
        # overload section (serve/admission.py): resume parity is exact-or-
        # fail — a preempted request's greedy output must stay token-
        # identical to the uncontended run, so the band is ZERO
        ("overload.resume_token_parity",
         ("overload", "resume_token_parity"), HIGHER, 0.0),
        # the parity sub-run is fully seeded (no clocks), so its trie-riding
        # resume skip rate is deterministic — tight band
        ("overload.parity_reprefill_skip_rate",
         ("overload", "parity_reprefill_skip_rate"), HIGHER, TOL_STRUCTURAL),
        ("overload.tok_per_s",
         ("overload", "tok_per_s"), HIGHER, TOL_THROUGHPUT),
        # per-class fairness under 2x overload: the HIGH class's SLO-failure
        # rate (deadline miss + shed + rejected) must not blow up (failure
        # rates under deliberate overload are queueing-noise-sensitive, so
        # the band is the wide one)
        ("overload.per_class[2].slo_fail_rate",
         ("overload", "per_class", "2", "slo_fail_rate"),
         LOWER, TOL_LATENCY),
        # the HIGH class's TTFT p95 under overload (queue wait included):
        # the latency the priority machinery exists to protect
        ("overload.per_class[2].ttft_p95_ms",
         ("overload", "per_class", "2", "ttft_p95_ms"),
         LOWER, TOL_LATENCY),
    ]
    for m in ("ttft", "tpot", "e2e"):
        for q in ("p50", "p95", "p99"):
            specs.append((f"latency_slo.{m}.{q}",
                          ("latency_slo", m, q), LOWER, TOL_LATENCY))
    return [(name, path, d, tol) for name, path, d, tol in specs
            if _get(ref, path) is not None]


def compare(ref: dict, cand: dict) -> list:
    """Gate `cand` against `ref`; returns the list of regression strings
    (empty = pass). Metrics missing from the candidate ARE regressions —
    a section that silently stopped being produced must not pass the gate."""
    failures = []
    for name, path, direction, tol in metric_specs(ref):
        r = _get(ref, path)
        c = _get(cand, path)
        if c is None:
            failures.append(f"{name}: missing from candidate (ref {r:.4g})")
            continue
        if r == 0:
            continue                      # no band to scale; nothing to gate
        if direction == HIGHER:
            bound = r * (1 - tol)
            bad = c < bound
            word = "below"
        else:
            bound = r * (1 + tol)
            bad = c > bound
            word = "above"
        if bad:
            failures.append(
                f"{name}: {c:.4g} {word} tolerance bound {bound:.4g} "
                f"(ref {r:.4g}, tol {tol:+.0%} {direction}-is-better)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", default="BENCH_serving.json",
                    help="committed baseline to gate against")
    ap.add_argument("--candidate", default=None,
                    help="result file to check; default: run the fast "
                         "benchmark now and gate its output")
    args = ap.parse_args(argv)

    with open(args.reference) as f:
        ref = json.load(f)
    if args.candidate:
        with open(args.candidate) as f:
            cand = json.load(f)
    else:
        import tempfile

        from benchmarks import serving_throughput
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        with tempfile.NamedTemporaryFile(suffix=".json", mode="r") as tmp:
            serving_throughput.run(fast=True, json_path=tmp.name)
            cand = json.load(tmp)

    specs = metric_specs(ref)
    failures = compare(ref, cand)
    print(f"# perf-regression gate: {len(specs)} metrics vs "
          f"{args.reference}")
    if failures:
        for f_ in failures:
            print(f"REGRESSION  {f_}")
        print(f"# FAIL: {len(failures)}/{len(specs)} metrics regressed")
        return 1
    print("# PASS: no metric regressed past its tolerance band")
    return 0


if __name__ == "__main__":
    sys.exit(main())
