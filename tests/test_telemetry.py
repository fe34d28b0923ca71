"""Serving telemetry: percentile math vs numpy, request-lifecycle ordering
invariants, the step profiler's spans and self-time accounting, the
launcher's profiler trace, no device fence with telemetry off, telemetry
on/off greedy parity on all three engines, snapshot schema stability, phase
coverage, and the open-loop arrival driver."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.models import model as M
from repro.serve import (ContinuousEngine, MetricsRegistry, PagedEngine,
                         Request, ServeEngine, StepProfiler, Telemetry,
                         drive_open_loop, format_snapshot, percentile)

# the unified snapshot contract (telemetry.make_snapshot): every engine,
# every telemetry setting, exactly these keys. v2 added `robustness`
# (admission/preemption/deadline counters; None off the robust path)
SNAPSHOT_KEYS = {"schema_version", "engine", "latency", "phases", "kv_cache",
                 "occupancy", "prefix", "padding", "robustness"}
ROBUSTNESS_KEYS = {"preemptions", "exhaustion_events", "device_retries",
                   "cancelled", "shed", "rejected", "deadline_misses",
                   "reprefill", "per_class"}
LATENCY_KEYS = {"requests", "ttft", "tpot", "e2e", "queue_wait",
                "queue_wait_hist", "queue_depth_peak", "queue_depth_mean"}
DIST_KEYS = {"count", "mean", "p50", "p95", "p99"}
PHASES_KEYS = {"steps", "step_seconds", "coverage", "phases"}
# v3 added the ctx_positions / sampled_lanes work counters, v4 the state
# arena's, v5 grid_rows; v6 dropped pad_lanes_skipped
PADDING_KEYS = {"lanes_valid", "lanes_total", "efficiency",
                "ctx_positions", "sampled_lanes", "state_slots_moved",
                "state_slots_live", "state_bytes", "grid_rows"}
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def served(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(rng, n, lens=(5, 9, 13), max_new=6):
    return [Request(uid=i,
                    prompt=rng.integers(0, 256, int(rng.choice(lens))).astype(
                        np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _engines(params, cfg, telemetry):
    paged_cfg = cfg.replace(cache_layout="paged", prefix_sharing=True)
    return {
        "wave": ServeEngine(params, cfg, max_batch=4, max_len=64,
                            telemetry=telemetry),
        "continuous": ContinuousEngine(params, cfg, max_batch=4, max_len=64,
                                       telemetry=telemetry),
        "paged": PagedEngine(params, paged_cfg, max_batch=4, max_len=64,
                             block_size=8, telemetry=telemetry),
    }


# ------------------------------------------------------------ percentile --


def test_percentile_matches_numpy(rng):
    for n in (1, 2, 3, 7, 50, 101):
        xs = rng.normal(size=n).tolist()
        for q in (0, 1, 25, 50, 75, 95, 99, 100):
            assert percentile(xs, q) == pytest.approx(
                float(np.percentile(xs, q)), abs=1e-12)


def test_percentile_empty_is_none():
    assert percentile([], 50) is None


# ------------------------------------------------- lifecycle invariants --


class FakeClock:
    """Deterministic monotonic clock for registry/profiler unit tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.25
        return self.t


def test_registry_lifecycle_and_summary():
    reg = MetricsRegistry(clock=FakeClock())
    for uid in range(3):
        reg.on_submit(uid, prompt_len=10 + uid)
    assert reg.queue_depth == 3 and reg.queue_depth_peak == 3
    for uid in range(3):
        reg.on_admit(uid)
        reg.on_first_token(uid)
        reg.on_finish(uid, n_tokens=4)
    assert reg.queue_depth == 0
    s = reg.latency_summary()
    assert s["requests"] == 3
    assert set(s) == LATENCY_KEYS
    for m in ("ttft", "tpot", "e2e", "queue_wait"):
        assert set(s[m]) == DIST_KEYS
        assert s[m]["count"] == 3
        assert s[m]["p50"] >= 0 and s[m]["p99"] >= s[m]["p50"]
    h = s["queue_wait_hist"]
    assert sum(h["counts"]) == 3
    assert len(h["counts"]) == len(h["edges_ms"]) + 1


def test_registry_hooks_are_idempotent_and_order_safe():
    reg = MetricsRegistry(clock=FakeClock())
    reg.on_submit(0, 5)
    reg.on_admit(0)
    t_admit = reg.traces[0].admit_ts
    reg.on_admit(0)                       # duplicate admit: no double count
    assert reg.traces[0].admit_ts == t_admit and reg.queue_depth == 0
    reg.on_first_token(0)
    reg.on_finish(0, 3)
    reg.on_finish(0, 99)                  # duplicate finish: first wins
    assert len(reg.finished) == 1 and reg.finished[0].n_tokens == 3
    reg.on_admit(42)                      # unknown uid: ignored, no crash
    reg.on_finish(42, 1)
    assert len(reg.finished) == 1


def test_single_token_request_has_no_tpot():
    reg = MetricsRegistry(clock=FakeClock())
    reg.on_submit(0, 5)
    reg.on_admit(0)
    reg.on_first_token(0)
    reg.on_finish(0, n_tokens=1)
    t = reg.finished[0]
    assert t.tpot is None
    assert reg.latency_summary()["tpot"]["count"] == 0


@pytest.mark.parametrize("name", ["wave", "continuous", "paged"])
def test_engine_trace_ordering_invariants(served, rng, name):
    """submit <= admit <= first_token <= finish on every finished trace, and
    every derived latency is non-negative, driven by a REAL engine."""
    cfg, params = served
    tel = Telemetry(enabled=True)
    eng = _engines(params, cfg, None)[name]       # build others w/o tel
    eng = _engines(params, cfg, tel)[name]
    reqs = _requests(rng, 6)
    reqs[1].max_new_tokens = 1                    # finishes at first token
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(reqs)
    traces = tel.metrics.finished
    assert sorted(t.uid for t in traces) == sorted(r.uid for r in reqs)
    for t in traces:
        assert t.submit_ts <= t.admit_ts <= t.first_token_ts <= t.finish_ts
        assert t.queue_wait >= 0 and t.ttft >= 0 and t.e2e >= 0
        assert t.e2e >= t.ttft
        assert t.tpot is None or t.tpot >= 0
        assert t.n_tokens == len(
            next(r for r in done if r.uid == t.uid).out_tokens)


# -------------------------------------------------------- profiler spans --


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_nested_phase_is_charged_its_self_time():
    """register inside sample: each phase holds its self time, and the step
    is attributed once (outermost phases only), so coverage stays <= 1."""
    clock = _FakeClock()
    prof = StepProfiler(enabled=True, clock=clock)
    with prof.step():
        with prof.phase("schedule"):
            clock.t += 2.0
        with prof.phase("sample"):
            clock.t += 1.0
            with prof.phase("register"):
                clock.t += 3.0
            clock.t += 1.0
        clock.t += 1.0                       # unattributed
    s = prof.summary()
    assert s["steps"] == 1 and s["step_seconds"] == 8.0
    assert {n: p["seconds"] for n, p in s["phases"].items()} == {
        "schedule": 2.0, "sample": 2.0, "register": 3.0}
    assert s["coverage"] == 7.0 / 8.0


def test_disabled_profiler_records_nothing():
    """Disabled: step()/phase() hand back the bare profiler span (no timing
    wrapper, nothing accumulated), with the step spans still numbered."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    prof = StepProfiler(enabled=False)
    step0 = prof.step()
    assert type(step0) is StepTraceAnnotation
    assert type(prof.phase("device")) is TraceAnnotation
    with prof.step():
        with prof.phase("device"):
            pass
    assert prof.step_num == 2
    assert prof.step_count == 0 and prof.phase_seconds == {}
    assert prof.coverage is None


def test_trace_out_writes_profiler_trace(tmp_path):
    """`launch/serve.py --trace-out DIR` wraps the serving loop in a
    profiler session: DIR holds an .xplane.pb whose host spans include the
    engine's serve.step and serve.<phase> spans, with telemetry off."""
    from jax.profiler import ProfileData
    out = tmp_path / "trace"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "granite-3-2b", "--reduced", "--requests", "2", "--prompt-len", "8",
         "--new-tokens", "2", "--scheduler", "paged", "--max-batch", "2",
         "--block-size", "8",
         "--trace-out", str(out)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "telemetry snapshot" not in p.stdout      # no host accounting
    found = sorted(out.rglob("*.xplane.pb"))
    assert len(found) == 1
    names = {e.name for plane in ProfileData.from_file(str(found[0])).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")}
    assert {"serve.step", "serve.admit", "serve.schedule", "serve.device",
            "serve.sample"} <= names
    assert f"profiler trace in {out}" in p.stdout


@pytest.mark.parametrize("name", ["wave", "continuous", "paged",
                                  "paged_async"])
@pytest.mark.parametrize("enabled", [False, True])
def test_device_fence_only_with_telemetry(served, rng, monkeypatch, name,
                                          enabled):
    """The device phase's block_until_ready fence belongs to the host-clock
    accounting: with telemetry disabled no step calls it, so async dispatch
    keeps its overlap; enabled, every engine fences."""
    cfg, params = served
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)
    tel = Telemetry(enabled=enabled)
    engines = _engines(params, cfg, tel)
    if name == "paged_async":
        eng = PagedEngine(params, cfg.replace(cache_layout="paged"),
                          max_batch=4, max_len=64, block_size=8,
                          async_loop=True, telemetry=tel)
    else:
        eng = engines[name]
    for r in _requests(rng, 3):
        eng.submit(r)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    done = eng.run()
    assert len(done) == 3
    assert bool(calls) == enabled


def _count_step_inputs(eng):
    """Wrap every step function of `eng`; returns the running sums of the
    valid lanes and their causal contexts the steps were handed, and of
    the query rows of the attention grids they were steered onto."""
    seen = {"lanes": 0, "ctx": 0, "grid": 0}

    def wrap(attr, idx):
        fn = getattr(eng, attr)

        def counted(*a):
            ex = a[idx]
            valid = np.asarray(ex["slot_ids"]) >= 0
            seen["lanes"] += int(valid.sum())
            seen["ctx"] += int(np.asarray(ex["kv_len"])[valid].sum())
            seen["grid"] += ex["dec_kv_len"].size + (
                ex["chunk_q_pos"].size if "chunk_q_pos" in ex else 0)
            return fn(*a)
        setattr(eng, attr, counted)
    for attr, idx in (("_packed_fn", 5), ("_packed_spec_fn", 5),
                      ("_packed_async_fn", 7)):
        wrap(attr, idx)
    return seen


@pytest.mark.parametrize("layout", ["packed", "async", "speculative",
                                    "small_budget"])
def test_work_counters_match_step_inputs(served, rng, layout):
    """padding_stats()' ctx_positions is the sum of every valid lane's
    causal context the step functions are handed, sampled_lanes the lanes
    sampled and grid_rows the attention grids' query rows, on every step
    path, and at a token budget of max_batch lanes."""
    cfg, params = served
    kw = dict(max_batch=4, max_len=128, block_size=8)
    if layout == "speculative":
        kw.update(prefix_sharing=True, decode_sharing=True, speculative=True,
                  draft_len=4)
    if layout == "small_budget":
        kw.update(token_budget=4)
    eng = PagedEngine(params, cfg.replace(cache_layout="paged"),
                      async_loop=layout == "async", **kw)
    seen = _count_step_inputs(eng)
    done = []
    if layout == "speculative":
        # repeated turns over one history give the trie drafts to verify
        base = rng.integers(0, 256, 12).astype(np.int32)
        for turn in range(3):
            for s in range(2):
                eng.submit(Request(uid=2 * turn + s, prompt=base,
                                   max_new_tokens=8), session=f"s{s}")
            done.extend(eng.run())
        assert eng.drafted_tokens > 0
    else:
        for r in _requests(rng, 5):
            eng.submit(r)
        done = eng.run()
    pad = eng.padding_stats()
    assert pad["ctx_positions"] == seen["ctx"] > 0
    assert pad["lanes_valid"] == seen["lanes"]
    assert pad["grid_rows"] == seen["grid"] > 0
    tokens = sum(len(r.out_tokens) for r in done)
    if layout == "speculative":
        assert pad["sampled_lanes"] >= tokens
    else:
        assert pad["sampled_lanes"] == tokens


# ------------------------------------------------------- parity & schema --


def test_greedy_parity_telemetry_on_vs_off(served, rng):
    """Telemetry must be purely observational: token-identical greedy
    outputs with it on vs off, for all three engines."""
    cfg, params = served
    reqs = _requests(rng, 6)
    outs = {}
    for enabled in (False, True):
        engines = _engines(params, cfg, Telemetry(enabled=enabled))
        outs[enabled] = {}
        for name, eng in engines.items():
            work = copy.deepcopy(reqs)
            for r in work:
                eng.submit(r)
            outs[enabled][name] = {r.uid: r.out_tokens for r in eng.run()}
    assert outs[True] == outs[False]


@pytest.mark.parametrize("enabled", [False, True])
def test_snapshot_schema_stability(served, rng, enabled):
    """The snapshot key set is STABLE across engines and telemetry
    settings: sections an engine lacks are None, never absent."""
    cfg, params = served
    engines = _engines(params, cfg, Telemetry(enabled=enabled))
    for r in _requests(rng, 4):
        engines["paged"].submit(r)
    engines["paged"].run()
    for name, eng in engines.items():
        snap = eng.snapshot()
        assert set(snap) == SNAPSHOT_KEYS
        assert snap["schema_version"] == 6
        assert snap["engine"] == name
        assert snap["robustness"] is None    # none of these are robust
        assert set(snap["kv_cache"]) == {"cache_bytes_logical",
                                         "cache_bytes_padded"}
        if enabled:
            assert set(snap["latency"]) == LATENCY_KEYS
            assert set(snap["phases"]) == PHASES_KEYS
        else:
            assert snap["latency"] is None and snap["phases"] is None
        if name == "paged":
            assert snap["prefix"] is not None
            assert set(snap["padding"]) == PADDING_KEYS
        else:
            assert snap["prefix"] is None and snap["padding"] is None
        assert json.dumps(snap)           # JSON-serializable as-is
        assert format_snapshot(snap).startswith("telemetry snapshot")


def test_snapshot_robustness_section(served, rng):
    """A robust engine's snapshot carries the v2 `robustness` section with a
    stable key set (JSON-serializable, str per-class keys), populated from
    the run's admission/preemption counters."""
    from repro.serve import AdmissionConfig
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      admission=AdmissionConfig(preemption=True),
                      telemetry=Telemetry(enabled=True))
    for i, r in enumerate(_requests(rng, 4)):
        r.priority = i % 2
        eng.submit(r)
    eng.run()
    snap = eng.snapshot()
    rb = snap["robustness"]
    assert set(rb) == ROBUSTNESS_KEYS
    assert set(rb["deadline_misses"]) == {"ttft", "e2e", "total"}
    assert set(rb["reprefill"]) == {"tokens", "skipped", "skip_rate"}
    assert all(isinstance(k, str) for k in rb["per_class"])
    assert sum(pc["finished"] for pc in rb["per_class"].values()) == 4
    assert json.dumps(snap)


def _assert_no_nan(node, path="snap"):
    if isinstance(node, dict):
        for k, v in node.items():
            _assert_no_nan(v, f"{path}.{k}")
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            _assert_no_nan(v, f"{path}[{i}]")
    elif isinstance(node, float):
        assert node == node, f"NaN at {path}"


def test_snapshot_mid_run_never_crashes(served, rng):
    """A snapshot taken MID-FLIGHT (unfinished requests, zero finished, a
    speculative engine that has not drafted yet) must render and serialize:
    every empty distribution reports None (percentile([]) -> None), the
    draft acceptance_rate is None until something was drafted, and nothing
    anywhere is NaN — dashboards poll snapshot() on live engines."""
    cfg, params = served
    eng = PagedEngine(params, cfg.replace(cache_layout="paged",
                                          speculative=True),
                      max_batch=4, max_len=64, block_size=8,
                      prefix_sharing=True, decode_sharing=True,
                      telemetry=Telemetry(enabled=True))
    # before ANY work: no steps, no finished requests, empty trie
    for snap_point in range(3):
        snap = eng.snapshot()
        assert set(snap) == SNAPSHOT_KEYS
        for dist in ("ttft", "tpot", "e2e", "queue_wait"):
            if snap["latency"]["requests"] == 0:
                assert snap["latency"][dist]["count"] == 0
                assert snap["latency"][dist]["p50"] is None
        if snap["prefix"]["tokens_drafted"] == 0:
            assert snap["prefix"]["acceptance_rate"] is None
        _assert_no_nan(snap)
        assert json.dumps(snap)
        assert format_snapshot(snap).startswith("telemetry snapshot")
        if snap_point == 0:               # go mid-flight: some steps, no
            for r in _requests(rng, 3, max_new=24):   # finishes yet
                eng.submit(r)
            eng.step()
            eng.step()
        elif snap_point == 1:
            eng.run()                     # drained: finished requests exist


@pytest.mark.parametrize("name", ["wave", "continuous", "paged"])
def test_phase_coverage_gate(served, rng, name):
    """>= 90% of measured step wall time must be attributed to named phases
    — the acceptance gate that keeps new per-step host work from hiding
    outside the breakdown."""
    cfg, params = served
    tel = Telemetry(enabled=True)
    eng = _engines(params, cfg, tel)[name]
    for r in _requests(rng, 6):
        eng.submit(r)
    eng.run()
    snap = eng.snapshot()
    assert snap["phases"]["steps"] > 0
    assert snap["phases"]["coverage"] >= 0.9


# -------------------------------------------------------------- open loop --


def test_drive_open_loop_validates_inputs(served):
    cfg, params = served
    eng = _engines(params, cfg, None)["continuous"]
    reqs = _requests(np.random.default_rng(0), 3)
    with pytest.raises(ValueError, match="arrivals"):
        drive_open_loop(eng, reqs, [0.0, 0.1])
    with pytest.raises(ValueError, match="sorted"):
        drive_open_loop(eng, reqs, [0.2, 0.1, 0.3])


@pytest.mark.parametrize("name", ["continuous", "paged"])
def test_drive_open_loop_serves_everything(served, rng, name):
    """Arrival-driven serving finishes every request, matches batch-drain
    greedy outputs (arrival timing must not change what is generated), and
    records positive queue waits in the traces."""
    cfg, params = served
    reqs = _requests(rng, 6)
    ref_eng = _engines(params, cfg, None)[name]
    ref_work = copy.deepcopy(reqs)
    for r in ref_work:
        ref_eng.submit(r)
    ref = {r.uid: r.out_tokens for r in ref_eng.run()}

    tel = Telemetry(enabled=True)
    eng = _engines(params, cfg, tel)[name]
    arrivals = np.cumsum(rng.exponential(0.005, len(reqs)))
    done = drive_open_loop(eng, copy.deepcopy(reqs), arrivals)
    assert {r.uid: r.out_tokens for r in done} == ref
    assert not eng.busy
    s = tel.metrics.latency_summary()
    assert s["requests"] == len(reqs)
    assert s["ttft"]["count"] == len(reqs)
    assert s["queue_wait"]["p50"] >= 0


# -------------------------------------------------- serving-clock unity --


def _noop_sleep(_):
    pass


@pytest.mark.parametrize("name", ["continuous", "paged"])
def test_deadline_and_telemetry_share_one_clock(served, name):
    """Regression: admission deadlines used time.monotonic while telemetry
    used time.perf_counter — two timebases for one SLA. Injecting a fake
    clock into Telemetry alone must now drive BOTH: the deadline expires on
    the fake timebase (it never would on a real one here), the miss counter
    bumps, and the dropped request's trace agrees with the miss on the
    same clock."""
    from repro.serve import AdmissionConfig
    cfg, params = served
    clock = FakeClock()
    tel = Telemetry(enabled=True, clock=clock)
    kw = dict(max_batch=2, max_len=64, telemetry=tel,
              admission=AdmissionConfig())          # note: no clock override
    if name == "paged":
        eng = PagedEngine(params, cfg.replace(cache_layout="paged"),
                          block_size=8, **kw)
    else:
        eng = ContinuousEngine(params, cfg, **kw)
    rng = np.random.default_rng(0)
    req = Request(uid=0, prompt=rng.integers(0, 256, 6).astype(np.int32),
                  max_new_tokens=32, deadline_e2e=1.5)
    eng.submit(req)
    guard = 0
    while eng.busy and guard < 200:
        eng.step()
        guard += 1
    assert req.failed and req.fail_reason == "deadline_e2e"
    assert eng.robust_counters.deadline_miss_e2e == 1
    trace = tel.metrics.traces[0]
    # the trace's submit anchor and the expiry decision read ONE timebase:
    # the request's age on the fake clock genuinely exceeds its deadline
    assert clock.t - trace.submit_ts > 1.5


def test_explicit_admission_clock_still_wins(served):
    """Back-compat: an explicitly injected AdmissionConfig.clock overrides
    the engine's serving clock — a frozen admission clock means deadlines
    never expire even while telemetry time races ahead."""
    from repro.serve import AdmissionConfig
    cfg, params = served
    tel = Telemetry(enabled=True, clock=FakeClock())
    eng = ContinuousEngine(params, cfg, max_batch=2, max_len=64,
                           telemetry=tel,
                           admission=AdmissionConfig(clock=lambda: 0.0))
    rng = np.random.default_rng(0)
    req = Request(uid=0, prompt=rng.integers(0, 256, 6).astype(np.int32),
                  max_new_tokens=4, deadline_e2e=0.5)
    eng.submit(req)
    eng.run()
    assert req.done and not req.failed
    assert eng.robust_counters.deadline_miss_e2e == 0


@pytest.mark.parametrize("name", ["continuous", "paged"])
def test_drive_open_loop_stamps_intended_arrivals(served, name):
    """Regression: queue wait / TTFT were measured from the post-step
    submit() call, silently absorbing step-granularity jitter. The driver
    now stamps each request's INTENDED arrival (t0 + offset) and the
    engines anchor the telemetry trace there — so consecutive submit
    timestamps reproduce the arrival offsets exactly, fake-clock ticks
    between arrivals notwithstanding."""
    cfg, params = served
    clock = FakeClock()
    tel = Telemetry(enabled=True, clock=clock)
    eng = _engines(params, cfg, tel)[name]
    rng = np.random.default_rng(1)
    reqs = _requests(rng, 4, max_new=3)
    arrivals = np.array([0.0, 2.0, 7.0, 9.0])
    done = drive_open_loop(eng, reqs, arrivals, clock=clock,
                           sleep=_noop_sleep)
    assert len(done) == len(reqs)
    subs = [tel.metrics.traces[r.uid].submit_ts for r in reqs]
    gaps = np.diff(subs)
    assert np.allclose(gaps, np.diff(arrivals)), (
        f"submit timestamps {subs} do not reproduce arrival offsets "
        f"{list(arrivals)}")
    # queue wait can only begin at arrival: no admit precedes its submit
    for r in reqs:
        t = tel.metrics.traces[r.uid]
        assert t.admit_ts is None or t.admit_ts >= t.submit_ts
