"""Reduction of a profiler trace to device busy time, idle gaps and the
host spans open during them.

`load` turns the `.xplane.pb` that `jax.profiler` writes into plain
tuples; every other function works on those, so tests pin them on a
small synthetic trace. Device planes are named `/device:<KIND>:<n>`
(the CPU's own plane, `/host:CPU`, holds the host threads and the
harness's spans). A device op is an event on a device plane's "XLA Ops"
line; a program execution is one on its "XLA Modules" line.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: list         # (name, start_ns, end_ns) device ops, all devices
    modules: list     # (name, start_ns, end_ns) program executions
    spans: list       # (name, start_ns, end_ns) harness spans on the host
    devices: int      # device planes seen


def load(path) -> Trace:
    """Read one `.xplane.pb` (or the newest under a profiler directory)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(str(path))
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            n = len(ops)
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ops if line.name == OPS_LINE else modules
                    dst.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices += len(ops) > n         # planes that ran device ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans, devices=devices)


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window(tr: Trace):
    """The traced window: from the first harness span's start to the last
    one's end (the harness opens and closes the trace between spans)."""
    if not tr.spans:
        raise ValueError("trace holds no harness span")
    return (min(s for _, s, _ in tr.spans), max(e for _, _, e in tr.spans))


def busy_ns(tr: Trace) -> float:
    """Union of device op intervals inside the window, averaged over the
    devices (one device here; ops of several overlap in the union)."""
    lo, hi = window(tr)
    busy = clip(union((s, e) for _, s, e in tr.ops), lo, hi)
    return sum(e - s for s, e in busy) / max(tr.devices, 1)


def idle_share(tr: Trace) -> float:
    lo, hi = window(tr)
    return 1.0 - busy_ns(tr) / (hi - lo)


def idle_gaps(tr: Trace):
    """Each gap in the device's busy union inside the window, as (start,
    end, name of the harness span covering most of it)."""
    lo, hi = window(tr)
    busy = clip(union((s, e) for _, s, e in tr.ops), lo, hi)
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s, _covering_span(tr.spans, t, s)))
        t = max(t, e)
    return gaps


def _covering_span(spans, s, e):
    """The harness span that overlaps [s, e) most (the shorter one on a
    tie); "none" if the host was in no harness span then."""
    best, rank = "none", None
    for name, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0 and (rank is None or (ov, a - b) > rank):
            best, rank = name, (ov, a - b)
    return best


def step_gaps_ns(tr: Trace, is_step):
    """Device-idle time between consecutive step programs (modules for
    which is_step(name) holds) while the engine had work: gaps that
    overlap a `bench.wait_arrival` span (the engine idle, waiting for a
    request) are left out. Each gap's idle time excludes other device ops
    inside it."""
    steps = sorted((s, e) for n, s, e in tr.modules if is_step(n))
    waits = [(s, e) for n, s, e in tr.spans if n == "bench.wait_arrival"]
    busy = union((s, e) for _, s, e in tr.ops)
    out = []
    for (_, e0), (s1, _) in zip(steps, steps[1:]):
        if s1 <= e0 or any(a < s1 and b > e0 for a, b in waits):
            continue
        inside = sum(b - a for a, b in clip(busy, e0, s1))
        out.append((s1 - e0) - inside)
    return out


def short_name(hlo: str, width: int = 120) -> str:
    """An op's HLO text without layouts, cut to `width` characters."""
    out, depth = [], 0
    for ch in hlo:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            out.append(ch)
    return "".join(out).lstrip("%")[:width]


def self_times(ops):
    """(name, start, end, self ns) for each op: its time less the time of
    the ops nested inside it (a loop holds the ops of its body)."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def top_ops(tr: Trace, n: int = 10):
    """[name, seconds] of the device ops with the most self time in the
    window, by their HLO text without layouts."""
    lo, hi = window(tr)
    tot = defaultdict(float)
    for name, s, e, own in self_times(tr.ops):
        if s >= lo and e <= hi:
            tot[short_name(name)] += own / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(tr: Trace, n: int = 10):
    """[span name, seconds] of the longest idle gaps, longest first."""
    g = sorted(idle_gaps(tr), key=lambda x: -(x[1] - x[0]))[:n]
    return [[name, (e - s) / 1e9] for s, e, name in g]
