"""Everything the benchmark runs is found by name: a cell in
BENCHMARK.json names its configuration (`configs/<config>.json`), its
traffic mix (`traffic/<traffic>.json`) and its correctness limits
(`checks/<cell>.json`); each metric BENCHMARK.json lists is read by
`metrics/<metric>.py`, whose `read(ctx)` returns a number or None.
Adding a cell, a configuration, a mix or a metric adds files and entries,
and edits none."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def load_benchmark(path=None) -> dict:
    return json.loads(Path(path or ROOT.parent / "BENCHMARK.json").read_text())


def _json(root, sub, name) -> dict:
    path = Path(root) / sub / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {sub} file for {name!r}: {path}")
    return json.loads(path.read_text())


def config(name, root=ROOT) -> dict:
    return _json(root, "configs", name)


def mix(name, root=ROOT) -> dict:
    return _json(root, "traffic", name)


def check(cell, root=ROOT) -> dict:
    return _json(root, "checks", cell)


def reader(metric, root=ROOT):
    """`read` of metrics/<metric>.py (the file name may hold dots)."""
    path = Path(root) / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, name: str, kind: str) -> list:
    """The cell's metrics of `kind` ("end_to_end" or "per_layer"): those
    with no `workloads` list, and those whose list names the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or name in m["workloads"]]
