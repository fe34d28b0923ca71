"""Cells, configurations, mixes and metrics are found by file name, so a
later change adds them as new files and edits none."""
import json
import shutil

import pytest

from bench import readings, registry
from bench.traffic import generator as G


def test_benchmark_names_resolve_to_files():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        c = registry.config(w["config"])
        assert c["name"] == w["config"]
        registry.mix(w["traffic"])
        assert registry.check(w["name"])["logit_gap_max"]["limit"] > 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.reader(m["name"]))
    for c in bench["configs"]:
        assert registry.config(c["name"])["source"] == c["source"]


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(registry.ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    c = registry.config("granite-3-2b")
    c["name"] = "granite-3-2b-wide"
    (root / "configs" / "granite-3-2b-wide.json").write_text(json.dumps(c))
    mix = dict(registry.mix("rag-4k"), loop="open", rate_rps=1.5)
    (root / "traffic" / "repo-slow.json").write_text(json.dumps(mix))
    (root / "checks" / "granite-3-2b-wide.repo-slow.json").write_text(
        json.dumps({"logit_gap_max": {"limit": 0.5}}))
    (root / "metrics" / "requests_due.py").write_text(
        "def read(ctx):\n    return len(ctx.run.due_in_window())\n")
    bench = registry.load_benchmark()
    bench["workloads"].append({"name": "granite-3-2b-wide.repo-slow",
                               "config": "granite-3-2b-wide",
                               "traffic": "repo-slow", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "requests_due", "unit": "requests",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["granite-3-2b-wide.repo-slow"]})
    w = registry.cell(bench, "granite-3-2b-wide.repo-slow")
    assert registry.config(w["config"], root)["name"] == "granite-3-2b-wide"
    assert registry.mix(w["traffic"], root)["rate_rps"] == 1.5
    assert registry.check(w["name"], root)["logit_gap_max"]["limit"] == 0.5
    names = [m["name"] for m in registry.metrics_for(bench, w["name"],
                                                     "end_to_end")]
    assert "requests_due" in names and "itl_p95_ms" in names
    layer = [m["name"] for m in registry.metrics_for(bench, w["name"],
                                                     "per_layer")]
    assert "device.idle_share" not in layer   # it lists its cells
    assert "requests_due" not in [m["name"] for m in registry.metrics_for(
        bench, "granite-3-2b.rag-4k", "end_to_end")]

    class Run:
        def due_in_window(self):
            return [1, 2, 3]
    ctx = readings.Context(run=Run(), config=c, mix=mix, setup_s=1.0, end=0)
    assert registry.reader("requests_due", root)(ctx) == 3
    tr = G.generate(registry.mix("repo-slow", root), 100, 1, 10)
    assert len(tr.items) == G.request_count(mix, 10) == 1 + int(
        1.5 * (mix["lead_in_s"] + 10) + 0.999)
    after = {p: p.read_bytes() for p in before}
    assert after == before          # no file that was there changed


def test_a_missing_file_names_what_is_missing(tmp_path):
    with pytest.raises(FileNotFoundError, match="no-such"):
        registry.config("no-such", tmp_path)
    with pytest.raises(FileNotFoundError, match="no-such"):
        registry.reader("no-such", tmp_path)
    with pytest.raises(KeyError, match="no-such"):
        registry.cell(registry.load_benchmark(), "no-such")
