"""The program's own marks in a profiler trace: the serving engine's
`serve.*` spans on the host, and the named scope of each device op.

`repro.serve.telemetry.StepProfiler` writes a `serve.step` span around each
engine step and a `serve.<phase>` span around each phase of its body
(`admit`, `schedule`, `alloc_cow`, `device`, `sample`, `register`,
`rollback`); the model names its parts with `jax.named_scope` (`attn.proj`,
`attn.kv_write`, `attn.gather`, `attn.core`, `mlp`, `head`), which reaches a
device op through its HLO `metadata={op_name=...}`. The TPU's op events
carry no op_name, so an op's scope is looked up by instruction name and
result type in the compiled text of the program it ran in.

`load_serve_spans` reads an `.xplane.pb`; every other function works on
plain tuples and `bench.trace.Trace`, so tests pin them on small synthetic
traces. The reductions here are the same gaps and the same busy time as
`bench.trace`'s: the gap split partitions `step_gaps_ns` exactly.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

from bench import trace as T

SERVE_PREFIX = "serve."
SCOPES = ("attn.proj", "attn.kv_write", "attn.gather", "attn.core", "mlp",
          "head")
UNSCOPED = "unscoped"
# the host's innermost span during a device-idle stretch between steps
PREPARE = ("serve.admit", "serve.schedule", "serve.alloc_cow",
           "serve.device")
SAMPLE = ("serve.sample", "serve.register")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def load_serve_spans(path) -> list:
    """(name, start_ns, end_ns) of every `serve.*` host span in one
    `.xplane.pb` (or the newest under a profiler directory)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name.startswith(SERVE_PREFIX))
    return out


def scope_of(op_name: str) -> str:
    """The innermost model scope on an op_name path, else "unscoped"."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def hlo_scopes(hlo_text: str) -> dict:
    """instr_key() -> scope, from a compiled program's HLO text. An
    instruction with no op_name (a copy or bitcast the compiler inserted
    to change a layout) takes the scope of the value it reads: that of its
    first operand that has one, followed back through such instructions."""
    own, reads, types = {}, {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            own[m.group(1)] = scope_of(n.group(1)) if n else None
            reads[m.group(1)] = _OPERAND.findall(line[m.end():])
            types[m.group(1)] = result_type(line[m.end():])
    out = {}

    def resolve(name, seen):
        if name in out:
            return out[name]
        sc = own[name]
        if sc is None:
            sc = UNSCOPED
            for x in reads[name]:
                if x in own and x not in seen:
                    up = resolve(x, seen | {name})
                    if up != UNSCOPED:
                        sc = up
                        break
        out[name] = sc
        return sc
    for name in own:
        resolve(name, frozenset())
    return {(name, types[name]): sc for name, sc in out.items()}


def result_type(rhs: str) -> str:
    """The result type that opens an instruction's right-hand side, without
    layouts: ` (f32[8]{0}, s32[]) fusion(...)` -> `(f32[8], s32[])`."""
    out, depth, brace = [], 0, 0
    for ch in rhs.lstrip():
        if ch in "{}":
            brace += 1 if ch == "{" else -1
            continue
        if brace:
            continue
        if ch == " " and depth == 0:
            break
        depth += (ch == "(") - (ch == ")")
        out.append(ch)
    return "".join(out)


def instr_key(op: str) -> tuple:
    """`%fusion.185 = bf16[16,8]{1,0} fusion(...)` -> ("fusion.185",
    "bf16[16,8]"): the name and result type by which a trace's op event
    finds its instruction. Programs of one model code at other widths
    reuse the names, with other shapes."""
    name, rhs = op.split("=", 1)
    return name.strip().lstrip("%"), result_type(rhs)


def hlo_module_name(hlo_text: str) -> str:
    """`HloModule jit__packed, is_scheduled=true, ...` -> `jit__packed`."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def op_scopes(tr: T.Trace, programs: list) -> list:
    """The scope of each op of `tr.ops`, in order. `programs` holds a
    (module name, hlo_scopes() table) pair per compiled program that may
    have run. The executions of one program (one XLA Modules event name,
    `jit__packed(<id>)`) take the table, among those of its module name,
    that knows the most of their instructions (instr_key()). Where tables
    tie and scope those instructions differently, this raises ValueError
    rather than guess. An op outside every module, or in a module no table
    knows, is unscoped."""
    mods = sorted(tr.modules, key=lambda m: m[1])
    starts = [s for _, s, _ in mods]
    owner = []
    for _, s, e in tr.ops:
        i = bisect.bisect_right(starts, s) - 1
        owner.append(mods[i][0] if i >= 0 and e <= mods[i][2] else None)
    names = defaultdict(set)
    for (op, _, _), m in zip(tr.ops, owner):
        if m is not None:
            names[m].add(instr_key(op))
    table = {}
    for m, ns in names.items():
        cands = [t for n, t in programs if n == m.split("(")[0]]
        best = max((len(ns & t.keys()) for t in cands), default=0)
        tops = [t for t in cands if best and len(ns & t.keys()) == best]
        if len({tuple(t.get(x) for x in sorted(ns)) for t in tops}) > 1:
            raise ValueError(f"{m}: {len(tops)} compiled programs know as "
                             "many of its ops and scope them differently")
        table[m] = tops[0] if tops else {}
    return [table[m].get(instr_key(op), UNSCOPED) if m else UNSCOPED
            for (op, _, _), m in zip(tr.ops, owner)]


def scope_seconds(tr: T.Trace, scopes: list) -> dict:
    """Device self time by scope in the window, seconds; ops nested in a
    loop count once, in their own scope (as in bench.trace.top_ops)."""
    lo, hi = T.window(tr)
    of = {}
    for (name, s, e), sc in zip(tr.ops, scopes):
        of[(name, s, e)] = sc
    tot = defaultdict(float)
    for name, s, e, own in T.self_times(tr.ops):
        if s >= lo and e <= hi:
            tot[of[(name, s, e)]] += own / 1e9
    return dict(sorted(tot.items(), key=lambda kv: -kv[1]))


def innermost(spans, lo, hi):
    """[lo, hi) cut into (start, end, name) pieces by the innermost span
    covering each (spans of one thread nest: the latest-starting one, the
    earliest-ending of those), and None where no span covers it."""
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for name, s, e in spans:
            if s <= a and e >= b and (best is None or (s, -e) > best[1]):
                best = (name, (s, -e))
        name = best[0] if best else None
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_pieces(busy, lo, hi):
    """[lo, hi) less the busy union, as (start, end) pieces."""
    out, t = [], lo
    for s, e in T.clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def gap_split_ns(tr: T.Trace, serve_spans, is_step) -> dict | None:
    """The gaps `bench.trace.step_gaps_ns` measures, each cut by the host's
    innermost `serve.*` span: "prepare" (admit, schedule, alloc_cow,
    device), "sample" (sample, register) and "other" (the harness's client
    work, a bare serve.step, or no span). Returns each part's mean per gap
    in ns and the gap count; the three means sum to the mean gap."""
    steps = sorted((s, e) for n, s, e in tr.modules if is_step(n))
    waits = [(s, e) for n, s, e in tr.spans if n == "bench.wait_arrival"]
    busy = T.union((s, e) for _, s, e in tr.ops)
    parts = {"prepare": 0, "sample": 0, "other": 0}
    n = 0
    for (_, e0), (s1, _) in zip(steps, steps[1:]):
        if s1 <= e0 or any(a < s1 and b > e0 for a, b in waits):
            continue
        n += 1
        for a, b in idle_pieces(busy, e0, s1):
            for x, y, name in innermost(serve_spans, a, b):
                key = ("prepare" if name in PREPARE else
                       "sample" if name in SAMPLE else "other")
                parts[key] += y - x
    if not n:
        return None
    return {"gaps": n, **{k: v / n for k, v in parts.items()}}


def named_gaps(tr: T.Trace, serve_spans, n: int = 10):
    """[span name, seconds] of the longest device-idle gaps in the window,
    longest first. A gap is named by the innermost `serve.*` span among
    those that cover over half of it, else by the harness span
    bench.trace.top_gaps names it by."""
    out = []
    for s, e, bench_name in T.idle_gaps(tr):
        best, name = None, bench_name
        for sp, a, b in serve_spans:
            if 2 * (min(b, e) - max(a, s)) > e - s and (
                    best is None or (a, -b) > best):
                best, name = (a, -b), sp
        out.append([name, (e - s) / 1e9])
    return sorted(out, key=lambda g: -g[1])[:n]
