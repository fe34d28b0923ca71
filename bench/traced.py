#!/usr/bin/env python3
"""A traced window of one benchmark cell, read through the program's own
spans and scopes: where the host was in each device-idle gap, which part
of the model the device time went to, and what happened in the longest
steps.

    python3 bench/traced.py --workload <cell> --seed <n> --seconds <s> \
        [--window-s W]

From the root of a checkout, on the chip. Sets up as bench/run.py does,
drives the cell's traffic through a window of --seconds, and traces one
sub-window of W seconds (default 4) from 1 s into it. One profiler session
per process: on a TPU v5e, a process that opened a second session read no
harness span from its first. It prints one JSON line:

- `host_gap_ms` (the `loop.host_gap_ms` reading) and its split by the
  host's innermost `serve.*` span (bench/spans.py);
- device seconds by model scope, the unscoped share and its largest ops
  (`scope_error` instead where the window's programs cannot be told
  apart);
- the idle gaps named by `serve.*` span;
- every step over LONG_STEP_S (0.5 s): its `serve.*` spans, and the
  device's programs and idle time inside it;
- the model FLOPs of the sub-window's steps counted from the step inputs
  (as bench/harness.py does) and from the engine's padding_stats()
  counters, side by side.

The end-to-end metrics of such a run are not the benchmark's: tracing
slows the host.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
LONG_STEP_S = 0.5   # a stall: a rag-4k step takes 0.33 s on a TPU v5e


def _is_step(name):
    return "packed" in name


def signature(args):
    import jax
    leaves, tree = jax.tree.flatten(args)
    return str(tree), tuple((tuple(x.shape), str(x.dtype)) for x in leaves)


def compiled_scopes(fn, args):
    """(module name, instruction -> scope table) of the program `fn` runs
    for `args`, compiled afresh: the persistent compilation cache's key
    leaves out op_name metadata, so a program it holds carries the op_names
    of whichever code compiled it first, such as a commit without the
    model's scopes. The device ran the cached program, whose instruction
    names a fresh compile repeats. Clears JAX's in-memory caches."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench import spans as S
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          args)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()            # else `fn` hands back its loaded program
    try:
        text = fn.lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return S.hlo_module_name(text), S.hlo_scopes(text)


def long_steps(tr, serve_spans):
    """Each bench.step of at least LONG_STEP_S: the seconds of each innermost
    serve.* span in it, and the device's programs and idle stretches in it
    (name or "idle", offset from the step's start and length, seconds)."""
    from bench import spans as S
    from bench import trace as T
    out = []
    busy = T.union((s, e) for _, s, e in tr.ops)
    for name, s, e in tr.spans:
        if name != "bench.step" or (e - s) / 1e9 < LONG_STEP_S:
            continue
        host = defaultdict(float)
        for a, b, sp in S.innermost(serve_spans, s, e):
            host[sp or "none"] += (b - a) / 1e9
        idle = S.idle_pieces(busy, s, e)
        timeline = sorted(
            [[m, (max(a, s) - s) / 1e9, (min(b, e) - max(a, s)) / 1e9]
             for m, a, b in tr.modules if b > s and a < e]
            + [["idle", (a - s) / 1e9, (b - a) / 1e9] for a, b in idle
               if b - a > 1e6], key=lambda x: x[1])
        longest = max(idle, key=lambda p: p[1] - p[0], default=None)
        out.append({
            "step_s": (e - s) / 1e9,
            "host_s": dict(sorted(host.items(), key=lambda kv: -kv[1])),
            "timeline": timeline,
            "device_idle_s": sum(b - a for a, b in idle) / 1e9,
            "longest_idle_s": ((longest[1] - longest[0]) / 1e9
                               if longest else 0.0),
            "longest_idle_host": (
                max(((sp or "none", b - a) for a, b, sp in
                     S.innermost(serve_spans, *longest)),
                    key=lambda x: x[1])[0] if longest else None)})
    return out


def by_scope(tr, programs) -> dict:
    """Device seconds and shares of busy time by model scope, and the
    largest unscoped ops; a `scope_error` instead where the trace's
    programs cannot be told apart (bench.spans.op_scopes)."""
    from bench import spans as S
    from bench import trace as T
    try:
        scopes = S.op_scopes(tr, programs)
    except ValueError as e:
        return {"scope_error": str(e)}
    secs = S.scope_seconds(tr, scopes)
    busy = T.busy_ns(tr) / 1e9
    lo, hi = T.window(tr)
    of = {(n, s, e): sc for (n, s, e), sc in zip(tr.ops, scopes)}
    unscoped = defaultdict(float)
    for n, s, e, own in T.self_times(tr.ops):
        if s >= lo and e <= hi and of[(n, s, e)] == S.UNSCOPED:
            unscoped[T.short_name(n)] += own / 1e9
    return {"scope_s": secs,
            "scope_share": ({k: 100 * v / busy for k, v in secs.items()}
                            if busy > 0 else None),
            "unscoped_top": sorted(([k, v] for k, v in unscoped.items()),
                                   key=lambda kv: -kv[1])[:10]}


def counter_flops(arch, c, d) -> float | None:
    """The model FLOPs of the counters' deltas `d`: `lanes_valid` tokens
    whose causal contexts sum to `ctx_positions`, and `sampled_lanes`
    positions through the head. The counters keep only the sum, so one
    lane carries it: right where a token's FLOPs grow linearly with its
    context."""
    if "ctx_positions" not in d:
        return None
    contexts = np.zeros(d["lanes_valid"], np.int64)
    contexts[:1] = d["ctx_positions"]
    return arch.step_flops(c, contexts, d["sampled_lanes"])


def read_window(c, arch, tdir, calls, fn, counters, tokens) -> dict:
    """Everything the module docstring lists, from the trace in tdir, the
    step inputs `calls` handed to `fn`, the engine counters at the window's
    two ends and the tokens its steps returned; `arch` is the
    configuration's architecture module."""
    from bench import harness
    from bench import spans as S
    from bench import trace as T
    tr = T.load(tdir)
    sp = S.load_serve_spans(tdir)
    tables = {}
    for args in calls:
        sig = signature(args)
        if sig not in tables:
            tables[sig] = compiled_scopes(fn, args)
    lo, hi = T.window(tr)
    gaps = T.step_gaps_ns(tr, _is_step)
    split = S.gap_split_ns(tr, sp, _is_step)
    d = {k: counters[1][k] - counters[0][k] for k in counters[0]}
    contexts = harness.step_contexts([a[5] for a in calls])
    wrapper = (sum(arch.step_flops(c, kv, 0) for kv in contexts)
               + tokens * arch.head_flops(c)) if contexts else None
    from_counters = counter_flops(arch, c, d)
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": T.busy_ns(tr) / 1e9,
        "idle_share": T.idle_share(tr), "steps": len(calls),
        "programs": len(tables),
        "modules": dict(Counter(n for n, _, _ in tr.modules)),
        "host_gap_ms": sum(gaps) / len(gaps) / 1e6 if gaps else None,
        "gap_split_ms": ({k: (v / 1e6 if k != "gaps" else v)
                          for k, v in split.items()} if split else None),
        **by_scope(tr, list(tables.values())),
        "idle_gaps": S.named_gaps(tr, sp),
        "long_steps": long_steps(tr, sp),
        "max_step_s": max(((e - s) / 1e9 for n, s, e in tr.spans
                           if n == "bench.step"), default=None),
        "flops_wrapper": wrapper, "flops_counters": from_counters,
        "counters": d}


def run(cell: str, seed: int, seconds: float, *, window_s: float = 4.0,
        root=None, bench=None,
        require_tpu: bool = True, t_start: float | None = None) -> dict:
    """Set up the cell, drive its traffic, trace one sub-window of
    window_s seconds opening TRACE_AT_S into the window; returns
    read_window()'s dict."""
    import jax.profiler as P

    from bench import drivers, harness, registry
    from bench.traffic import generator as G
    from repro.serve import Request
    su = harness.setup(cell, seed, root=root or registry.ROOT, bench=bench,
                       require_tpu=require_tpu, t_start=t_start)
    c, eng = su.config, su.eng
    traffic = G.generate(su.mix, c["model"]["vocab_size"], seed, seconds)
    harness.fill(eng, traffic.warm)

    fn = eng._packed_fn
    tdir = tempfile.mkdtemp(prefix="bench-traced-")
    w = {"on": False, "calls": []}

    def recorded(*a):
        if w["on"]:                  # a reference only: read after stop
            w["calls"].append(a)
        return fn(*a)
    eng._packed_fn = recorded

    def start():
        w["c0"], w["t0"] = eng.padding_stats(), time.perf_counter()
        opts = P.ProfileOptions()
        opts.python_tracer_level = 0
        P.start_trace(tdir, profiler_options=opts)
        w["on"] = True

    def stop():
        w["on"], w["t1"] = False, time.perf_counter()
        P.stop_trace()
        w["c1"] = eng.padding_stats()

    at = harness.TRACE_AT_S
    make_req = lambda it: Request(uid=it.uid, prompt=it.prompt,
                                  max_new_tokens=it.max_new, temperature=0.0)
    drive = (drivers.drive_open if traffic.loop == "open"
             else drivers.drive_closed)
    kw = {} if traffic.loop == "open" else {"clients": traffic.clients}
    res = drive(eng, traffic.items, make_req, lead_in_s=traffic.lead_in_s,
                seconds=seconds, drain_s=0.0, span=P.TraceAnnotation,
                marks=[(at, start), (at + window_s, stop)], **kw)
    eng._packed_fn = fn
    tokens = sum(n for s, e, n in res.steps if w["t0"] <= s and e <= w["t1"])
    out = read_window(c, su.arch, tdir, w["calls"], fn, (w["c0"], w["c1"]),
                      tokens)
    shutil.rmtree(tdir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--window-s", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench import harness
    try:
        harness.find_devices(1)
    except harness.NoAccelerator as e:
        print(e, file=sys.stderr, flush=True)
        return 3
    out = run(args.workload, args.seed, args.seconds,
              window_s=args.window_s, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
