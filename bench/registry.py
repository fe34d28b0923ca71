"""Everything the benchmark runs is found by name: a cell in
BENCHMARK.json names its configuration (`configs/<config>.json`), its
traffic mix (`traffic/<traffic>.json`) and its correctness limits
(`checks/<cell>.json`); a configuration names its architecture
(`archs/<arch>.py`: the program's ModelConfig, the weights' leaves, the
FLOPs a step requires and the reference forward); each metric
BENCHMARK.json lists is read by `metrics/<metric>.py`, whose `read(ctx)`
returns a number or None. Adding a cell, a configuration, an
architecture, a mix or a metric adds files and entries, and edits none."""
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
    c = _json(root, "configs", name)
    if "arch" not in c:
        raise KeyError(f"configuration {name!r} names no \"arch\": add "
                       f"\"arch\": \"<name>\" for archs/<name>.py")
    return c


def mix(name, root=ROOT) -> dict:
    return _json(root, "traffic", name)


def check(cell, root=ROOT) -> dict:
    """The cell's limits, and `requests`: how many finished requests the
    reference checks."""
    chk = _json(root, "checks", cell)
    if "requests" not in chk:
        raise KeyError(f"check file of {cell!r} names no \"requests\": the "
                       f"finished requests the reference checks")
    return chk


def _module(root, sub, name, what):
    """The module <sub>/<name>.py (the file name may hold dots)."""
    path = Path(root) / sub / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {what} {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric, root=ROOT):
    """`read` of metrics/<metric>.py."""
    return _module(root, "metrics", metric, "reader for metric").read


def arch(name, root=ROOT):
    """The architecture module archs/<name>.py."""
    return _module(root, "archs", name, "architecture")


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
