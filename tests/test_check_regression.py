"""Perf-regression gate semantics (benchmarks/check_regression.py): identity
compares pass, degradations past the tolerance band fail, improvements and
in-band noise pass, missing candidate sections fail, and the CLI exit codes
match. Runs entirely on synthetic snapshots — no benchmark execution."""
import copy
import json
import pathlib

from benchmarks.check_regression import compare, main, metric_specs


def _snapshot():
    """A miniature but schema-complete BENCH_serving.json."""
    dist = dict(count=24, mean=0.02, p50=0.01, p95=0.05, p99=0.08)
    return dict(
        benchmark="serving_throughput",
        engines=[
            dict(scheduler="wave", tok_per_s=100.0, padding_efficiency=None),
            dict(scheduler="paged+packed", tok_per_s=1600.0,
                 padding_efficiency=0.74),
        ],
        prefill_heavy=[
            dict(step_layout="packed", tok_per_s=1200.0,
                 padding_efficiency=0.80),
        ],
        prefix_sharing=[
            dict(variant="off", tok_per_s=850.0, prefix=None),
            dict(variant="on", tok_per_s=1700.0,
                 prefix=dict(hit_rate=1.0, skip_rate=0.87)),
        ],
        multi_turn=[
            dict(variant="off", tok_per_s=300.0, vs_off=1.0, prefix=None),
            dict(variant="on", tok_per_s=550.0, vs_off=1.8,
                 prefix=dict(followup_skip_rate=0.75)),
        ],
        kv_int8=[
            dict(kv_quant="none", tok_per_s=1800.0, kv_bytes_vs_fp32=1.0,
                 greedy_exact_match=1.0),
            dict(kv_quant="int8", tok_per_s=1750.0, kv_bytes_vs_fp32=0.25,
                 greedy_exact_match=0.87),
        ],
        async_loop={"sync": dict(loop="sync", tok_per_s=1500.0,
                                 device_stall_share=0.5),
                    "async": dict(loop="async", tok_per_s=1650.0,
                                  device_stall_share=0.3),
                    "vs_sync": 1.1, "stall_share_vs_sync": 0.6,
                    "greedy_parity": 1.0},
        latency_slo=dict(arrival_rate=8.0, tok_per_s=85.0,
                         phase_coverage=0.98, ttft=dict(dist),
                         tpot=dict(dist), e2e=dict(dist)),
        overload=dict(tok_per_s=900.0, resume_token_parity=1.0,
                      parity_reprefill_skip_rate=0.75,
                      per_class={"2": dict(slo_fail_rate=0.1,
                                           ttft_p95_ms=770.0)}),
    )


def test_specs_cover_every_section():
    names = [name for name, *_ in metric_specs(_snapshot())]
    for prefix in ("engines[", "prefill_heavy[", "prefix_sharing[",
                   "multi_turn[", "kv_int8[", "async_loop", "latency_slo.",
                   "overload."):
        assert any(n.startswith(prefix) for n in names), prefix
    # higher-is-better latency would be nonsense; spot-check directions
    spec = {name: (d, tol) for name, _, d, tol in metric_specs(_snapshot())}
    assert spec["latency_slo.ttft.p99"][0] == "lower"
    assert spec["engines[wave].tok_per_s"][0] == "higher"
    assert spec["kv_int8[int8].kv_bytes_vs_fp32"][0] == "lower"
    assert spec["async_loop.stall_share_vs_sync"][0] == "lower"
    assert spec["async_loop.greedy_parity"][1] == 0.0
    assert spec["overload.per_class[2].slo_fail_rate"][0] == "lower"
    # resume parity is exact-or-fail: zero tolerance band
    assert spec["overload.resume_token_parity"] == ("higher", 0.0)


def test_identity_passes():
    ref = _snapshot()
    assert compare(ref, copy.deepcopy(ref)) == []


def test_improvement_and_in_band_noise_pass():
    ref = _snapshot()
    cand = copy.deepcopy(ref)
    cand["engines"][1]["tok_per_s"] *= 2.0           # improvement
    cand["latency_slo"]["ttft"]["p99"] *= 0.5        # improvement (lower)
    cand["prefill_heavy"][0]["tok_per_s"] *= 0.7     # within the 0.5 band
    cand["latency_slo"]["e2e"]["p95"] *= 2.0         # within the 1.5 band
    assert compare(ref, cand) == []


def test_throughput_collapse_fails():
    ref = _snapshot()
    cand = copy.deepcopy(ref)
    cand["engines"][1]["tok_per_s"] = ref["engines"][1]["tok_per_s"] * 0.3
    fails = compare(ref, cand)
    assert len(fails) == 1
    assert "engines[paged+packed].tok_per_s" in fails[0]


def test_latency_blowup_fails():
    ref = _snapshot()
    cand = copy.deepcopy(ref)
    cand["latency_slo"]["ttft"]["p95"] = \
        ref["latency_slo"]["ttft"]["p95"] * 3.0
    fails = compare(ref, cand)
    assert len(fails) == 1 and "latency_slo.ttft.p95" in fails[0]


def test_structural_metrics_are_tight():
    ref = _snapshot()
    cand = copy.deepcopy(ref)
    # 20% drops: far inside the throughput band, outside the structural one
    cand["prefill_heavy"][0]["padding_efficiency"] *= 0.8
    cand["kv_int8"][1]["greedy_exact_match"] *= 0.8
    cand["kv_int8"][1]["kv_bytes_vs_fp32"] *= 1.2
    fails = compare(ref, cand)
    assert len(fails) == 3


def test_missing_candidate_section_fails():
    ref = _snapshot()
    cand = copy.deepcopy(ref)
    cand["latency_slo"] = None
    fails = compare(ref, cand)
    assert any("latency_slo.tok_per_s" in f and "missing" in f
               for f in fails)


def test_missing_reference_section_is_not_gated():
    """A partial reference (e.g. from an --engine-filtered run) gates only
    what it has — it must not fail candidates for sections IT lacks."""
    ref = _snapshot()
    ref["multi_turn"] = []
    cand = _snapshot()
    assert compare(ref, cand) == []
    assert not any(n.startswith("multi_turn")
                   for n, *_ in metric_specs(ref))


def test_cli_exit_codes(tmp_path):
    ref = tmp_path / "ref.json"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    ref.write_text(json.dumps(_snapshot()))
    good.write_text(json.dumps(_snapshot()))
    degraded = _snapshot()
    degraded["latency_slo"]["tok_per_s"] *= 0.2
    bad.write_text(json.dumps(degraded))
    assert main(["--reference", str(ref), "--candidate", str(good)]) == 0
    assert main(["--reference", str(ref), "--candidate", str(bad)]) == 1


def test_committed_reference_passes_against_itself():
    """The checked-in BENCH_serving.json must be self-consistent with the
    gate (guards against spec paths drifting from the benchmark schema)."""
    path = pathlib.Path(__file__).parent.parent / "BENCH_serving.json"
    ref = json.loads(path.read_text())
    specs = metric_specs(ref)
    assert len(specs) >= 20          # the gate actually covers the file
    assert compare(ref, copy.deepcopy(ref)) == []
