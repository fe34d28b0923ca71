"""Whole runs of the harness on the CPU at a tiny size: the comparison
that decides `correct` passes the program, fails the float8 control, and
fails the timed path broken underneath; a traced run reads the per-layer
metrics; without a TPU the command exits non-zero with no result."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, registry

# the tiny model's readings on the CPU: program 0 to 0.003, float8
# control 0.16 to 0.25 (seeds 1-4)
TINY_LIMIT = 0.05
SEED = 2 ** 31 + 11
REQUESTS = {"closed": 8, "open": 5}    # each check file's `requests`


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A benchmark root with a tiny configuration (granite's block at
    d_model 64, 2 layers, vocab 256) and two small mixes."""
    root = tmp_path_factory.mktemp("bench")
    for d in ("metrics", "archs"):
        shutil.copytree(registry.ROOT / d, root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(registry.ROOT / "peaks.json", root)
    for d in ("configs", "traffic", "checks"):
        (root / d).mkdir()
    c = registry.config("granite-3-2b")
    c["name"] = "tiny"
    c["model"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, d_ff=128, vocab_size=256)
    c["serving"].update(max_batch=4, max_len=128, block_size=8,
                        num_blocks=80, prefix_sharing=True)
    (root / "configs" / "tiny.json").write_text(json.dumps(c))
    lengths = {"prompt": {"dist": "uniform", "min": 8, "max": 40},
               "output": {"dist": "uniform", "min": 4, "max": 12}}
    (root / "traffic" / "closed.json").write_text(json.dumps(dict(
        loop="closed", clients=4, per_client=400, lead_in_s=0.5, **lengths)))
    (root / "traffic" / "open.json").write_text(json.dumps(dict(
        loop="open", rate_rps=20, lead_in_s=0.5, **lengths,
        shared={"groups": 2, "context": {"dist": "uniform", "min": 16,
                                         "max": 30},
                "zipf": 1.1, "warm": True})))
    bench = registry.load_benchmark()
    bench["workloads"] = [
        {"name": f"tiny.{m}", "config": "tiny", "traffic": m, "chips": 1,
         "why": "test"} for m in ("closed", "open")]
    bench["per_layer"].append({        # a reader no listed cell reads yet
        "name": "sched.prefix_skip_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p50_ms"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    for m in REQUESTS:
        (root / "checks" / f"tiny.{m}.json").write_text(json.dumps(
            {"requests": REQUESTS[m],
             "logit_gap_max": {"limit": TINY_LIMIT}}))
    return root, bench


def _run(tiny, cell="tiny.closed", trace=False, **kw):
    root, bench = tiny
    return harness.run(cell, SEED, 2.0, trace, root=root, bench=bench,
                       require_tpu=False, **kw)


def test_program_passes_and_the_control_fails(tiny):
    out = _run(tiny, control=True)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["check"]["logit_gap_max"]["value"] <= TINY_LIMIT
    # the control's tokens in place of the served ones, through the same
    # decision
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["check"]["logit_gap_max"]["value"] > TINY_LIMIT
    assert ctl["check"]["logit_gap_max"]["limit"] == TINY_LIMIT
    assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "ttft_p50_ms", "setup_s"}
    assert list(out)[-1] == "check"


def test_an_altered_token_fails(tiny, monkeypatch):
    import repro.serve.paged as paged
    real = paged.sample_tokens

    def altered(*a, **k):           # every slot's token, every step
        return (np.array(real(*a, **k)) + 1) % 256
    monkeypatch.setattr(paged, "sample_tokens", altered)
    out = _run(tiny)
    assert not out["correct"]
    assert out["check"]["logit_gap_max"]["value"] > TINY_LIMIT


def test_a_kv_write_left_out_fails(tiny, monkeypatch):
    import repro.models.attention as attn
    monkeypatch.setattr(attn, "_paged_scatter", lambda pool, *a: pool)
    out = _run(tiny)
    assert not out["correct"]


def test_traced_run_reads_the_layer_metrics(tiny):
    out = _run(tiny, cell="tiny.open", trace=True)
    assert out["correct"]
    m = out["metrics"]
    assert 0 < m["sched.lane_efficiency"]["value"] <= 100
    assert "output_tokens_per_s" not in m
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_open_loop_prefix_sharing_passes_and_the_control_fails(
        tiny, monkeypatch):
    """Shared contexts filled at set-up, arrivals on a schedule: the trie
    skips part of every prompt, the served tokens pass, the control's fail,
    and the reference checks the check file's number of requests."""
    from bench import reference as R
    real, shapes = R.logit_stats, []

    def spy(c, seed, sequences, **kw):
        shapes.append((len(sequences), kw["n"]))
        return real(c, seed, sequences, **kw)
    monkeypatch.setattr(R, "logit_stats", spy)
    out = _run(tiny, cell="tiny.open", trace=True, control=True)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["control"]["correct"]
    assert out["control"]["check"]["logit_gap_max"]["value"] > TINY_LIMIT
    assert 0 < out["metrics"]["sched.prefix_skip_share"]["value"] < 100
    # the program's check, then the control's tokens and their gaps
    assert shapes == [(REQUESTS["open"], REQUESTS["open"])] * 3


def test_sample_takes_the_longest_and_n_from_the_seed():
    import types
    recs = [types.SimpleNamespace(
        finished=i != 3, req=types.SimpleNamespace(
            prompt=[0] * (10 + i), out_tokens=[1] * (i % 4)))
        for i in range(12)]
    done = [r for r in recs if r.finished and r.req.out_tokens]
    for n in (1, 4, 24):
        got = harness.sample(recs, SEED, n)
        assert len(got) == min(n, len(done))
        assert got[0] is recs[11]                   # the longest first
        assert all(r in done for r in got)
        assert len({id(r) for r in got}) == len(got)
        assert got == harness.sample(recs, SEED, n)
    assert harness.sample(recs, SEED, 4) != harness.sample(recs, SEED + 1, 4)


def test_without_a_tpu_the_command_exits_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(registry.ROOT / "run.py"), "--workload",
         "granite-3-2b.rag-4k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_pool_sizes_that_do_not_run_are_reported(capsys, monkeypatch):
    from bench import poolsize
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert poolsize.main(["--workload", "granite-3-2b.rag-4k", "--seed", "1",
                          "--seconds", "1", "--blocks", "64", "128"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    # largest first; without a TPU none runs, so each is tried
    assert [x["num_blocks"] for x in lines] == [128, 64]
    assert all(not x["ran"] and "no TPU" in x["error"] for x in lines)
