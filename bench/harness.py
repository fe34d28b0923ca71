"""One run of one cell: set up, warm up, drive the traffic through the
window, read the metrics, check the served tokens against the reference.

Set-up makes the weights on the device from the seed in one jitted call,
as the configuration's architecture module (archs/<arch>.py) lays them
out, builds the cell's `PagedEngine`, compiles (or loads from the persistent
cache) every packed-step shape the cell's traffic reaches, fills the
prefix cache where the mix asks for it, then runs the lead-in traffic.
`setup_s` runs from the start of the process to the window's opening.

With `trace`, a sub-window of a few seconds inside the window is traced
with `jax.profiler`; the harness's spans (`bench.step`,
`bench.wait_arrival`, `bench.client`) go into the same trace, and the
engine counters are read at its two ends. The model FLOPs its steps
require are counted from each step's packed inputs: the harness keeps a
reference to the `kv_len` and `slot_ids` arrays the engine hands its
`_packed_fn`, and reads them only once the sub-window has closed, so no
transfer from the device enters a step. Where the program stops passing
them so, the count is left out and `mfu.*` reads nothing.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import drivers, readings, registry
from bench import model as BM
from bench import trace as T
from bench.traffic import generator as G

TRACE_AT_S = 1.0         # traced sub-window: opens this long into the window
TRACE_S = 4.0            # and lasts this long (less in a shorter window)
DRAIN_S = 60.0           # most seconds stepped after the window closes


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def use_compile_cache(checkout: Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, for every program."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def find_devices(chips: int, require_tpu: bool = True):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"bench: no accelerator: {e}")
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"bench: no TPU: JAX's first device is "
                            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, JAX "
                            f"found {len(devs)}")
    return devs


class CompileCount:
    """Backend compilations, from JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def ladder(sv: dict):
    """The (step width, attention-grid width) pairs of the engine's packed
    step, and for each a batch of fresh prompts whose first step lands on
    it: n prompts of p tokens each (p <= one slot's chunk cap, 4 blocks).
    The engine's ladders: widths max_batch, budget/4, budget/2, budget for
    budget = max_batch * block_size; grid widths 1 and 1..4 blocks."""
    b, bs = sv["max_batch"], sv["block_size"]
    budget = b * bs
    widths = sorted({b, max(budget // 4, b), max(budget // 2, b), budget})
    out = [(b, 1, b, 1)]                      # every slot one token
    for i, w in enumerate(widths):
        prev = widths[i - 1] if i else 0
        for wb in range(bs, 4 * bs + 1, bs):
            p = min(wb, w)
            n = min(b, w // p)
            if n * p > prev and p > wb - bs:
                out.append((w, wb, n, p))
    return out


def warm_up(eng, sv: dict, vocab: int, rng):
    """One engine step on every ladder pair (compiling it, or loading it
    from the cache), and, with prefix sharing, one copy-on-write."""
    from repro.serve import Request
    uid = -1
    for _, _, n, p in ladder(sv):
        for _ in range(n):
            eng.submit(Request(uid=uid, prompt=rng.integers(
                0, vocab, p).astype(np.int32), max_new_tokens=1))
            uid -= 1
        while eng.busy:
            eng.step()
    if sv["prefix_sharing"]:
        prompt = rng.integers(0, vocab, 2 * sv["block_size"]).astype(np.int32)
        for _ in range(2):                    # the second one matches whole
            eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=1))
            uid -= 1
            while eng.busy:
                eng.step()
        eng.clear_prefix_cache()


def fill(eng, prompts):
    """Send each prompt once (set-up's cache fill), one token each."""
    from repro.serve import Request
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=-10_000 - i, prompt=p, max_new_tokens=1))
    while eng.busy:
        eng.step()


def mix_max_len(mix: dict) -> int:
    ctx = mix.get("shared", {}).get("context", {}).get("max", 0)
    return ctx + mix["prompt"]["max"] + mix["output"]["max"]


def sample(records, seed: int, n: int):
    """n finished requests for the reference (the cell's check file's
    `requests`): the longest, and the rest drawn from the seed."""
    done = [r for r in records if r.finished and r.req.out_tokens]
    if not done:
        return []
    total = lambda r: len(r.req.prompt) + len(r.req.out_tokens)
    longest = max(range(len(done)), key=lambda i: total(done[i]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed, 1])
    pick = [longest] + list(rng.permutation(rest)[:n - 1])
    return [done[i] for i in pick]


def _seqs(recs):
    return [(np.asarray(r.req.prompt), np.asarray(r.req.out_tokens))
            for r in recs]


def logit_gaps(c: dict, seed: int, recs, tokens=None, **shape):
    """Per served token (or per token of `tokens`, one array a request, in
    its place), how far its logit lies below the reference's best at that
    position. `shape` is logit_stats's t, k, n and root."""
    from bench import reference as R
    stats = R.logit_stats(c, seed, _seqs(recs), tokens=tokens, **shape)
    return np.concatenate([mx - at for mx, at, _ in stats])


def control_tokens(c: dict, seed: int, recs, **shape):
    """The control's tokens: at each served position, the one that the
    reference computed with float8 matmuls puts first."""
    from bench import reference as R
    low = R.logit_stats(c, seed, _seqs(recs), precision="fp8", **shape)
    return [am for _, _, am in low]


def decide(gap, failed: int, limit: float):
    """`correct`, and each number it compared beside its limit."""
    check = {"logit_gap_max": {"value": gap, "limit": limit},
             "failed_due": {"value": failed, "limit": 0}}
    return gap is not None and gap <= limit and failed == 0, check


def step_contexts(recorded) -> list | None:
    """Each valid lane's causal context, per recorded step input; None
    where an input does not hold the `kv_len` and `slot_ids` it should."""
    out = []
    for ex in recorded:
        if not (isinstance(ex, dict) and "kv_len" in ex and "slot_ids" in ex):
            return None
        sid = np.asarray(ex["slot_ids"])
        out.append(np.asarray(ex["kv_len"])[sid >= 0])
    return out


def setup(cell_name: str, seed: int, *, root: Path = registry.ROOT,
          bench: dict | None = None, require_tpu: bool = True,
          t_start: float | None = None):
    """Everything before the traffic: the cell's files, the device check,
    the weights, the engine and its warm-up. Returns a namespace."""
    import types
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench if bench is not None else registry.load_benchmark()
    w = registry.cell(bench, cell_name)
    c = registry.config(w["config"], root)
    arch = registry.arch(c["arch"], root)
    peaks = json.loads((Path(root) / "peaks.json").read_text())

    import jax
    devs = find_devices(w["chips"], require_tpu)
    dev = devs[0]
    if require_tpu:
        use_compile_cache(Path(root).resolve().parent)
        if dev.device_kind not in peaks:
            raise ValueError(f"no peaks for device kind {dev.device_kind!r} "
                             f"in peaks.json")
    from repro.serve import PagedEngine
    compiles = CompileCount()
    parts = {"import_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    cfg = arch.model_config(c)
    params = BM.make_params(c, seed, cfg, arch)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t

    sv = c["serving"]
    t = time.perf_counter()
    eng = PagedEngine(params, cfg, max_batch=sv["max_batch"],
                      max_len=sv["max_len"], block_size=sv["block_size"],
                      num_blocks=sv["num_blocks"],
                      prefix_sharing=sv["prefix_sharing"],
                      decode_sharing=False, packed=sv["packed"],
                      speculative=sv["speculative"],
                      async_loop=sv["async_loop"], admission=None)
    warm_up(eng, sv, c["model"]["vocab_size"],
            np.random.default_rng([seed, 2]))
    parts["warmup_s"] = time.perf_counter() - t
    parts["compile_s"] = compiles.seconds
    stats = dev.memory_stats() or {}
    parts["live_gb"] = stats.get("bytes_in_use", 0) / 1e9
    parts["limit_gb"] = stats.get("bytes_limit", 0) / 1e9
    return types.SimpleNamespace(
        t_start=t_start, bench=bench, cell=w, config=c, arch=arch,
        mix=registry.mix(w["traffic"], root),
        check=registry.check(cell_name, root), peaks=peaks, devs=devs,
        dev=dev, cfg=cfg, params=params, eng=eng, parts=parts,
        compiles=compiles)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = registry.ROOT, bench: dict | None = None,
        require_tpu: bool = True, t_start: float | None = None,
        control: bool = False) -> dict:
    """One run; returns the result line's object. `control` adds, under
    "control", the same decision taken with the control's tokens in place
    of the served ones (bench/control.py)."""
    su = setup(cell_name, seed, root=root, bench=bench,
               require_tpu=require_tpu, t_start=t_start)
    c, arch, mix, chk, peaks = su.config, su.arch, su.mix, su.check, su.peaks
    dev, devs, eng, parts, compiles = (su.dev, su.devs, su.eng, su.parts,
                                       su.compiles)
    sv = c["serving"]
    e2e = registry.metrics_for(su.bench, cell_name, "end_to_end")
    layer = registry.metrics_for(su.bench, cell_name, "per_layer")
    t_start = su.t_start
    from repro.serve import Request

    vocab = c["model"]["vocab_size"]
    traffic = G.generate(mix, vocab, seed, seconds)
    t = time.perf_counter()
    fill(eng, traffic.warm)
    parts["fill_s"] = time.perf_counter() - t

    # traced runs: spans, counters and step inputs over a sub-window
    span, marks, tr_state = drivers._nospan, [], {}
    steps_seen = []
    if trace:
        import jax.profiler as P
        span = P.TraceAnnotation
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        step_fn = eng._packed_fn

        def recorded(*args):
            if tr_state.get("on"):      # a reference only: read after stop
                steps_seen.append(args[5] if len(args) > 5 else None)
            return step_fn(*args)

        eng._packed_fn = recorded

        def counters():
            return dict(eng.padding_stats(), **eng.prefix_stats())

        def start():
            tr_state["start"] = counters()
            tr_state["t0"] = time.perf_counter()
            opts = P.ProfileOptions()
            opts.python_tracer_level = 0        # host spans, no Python calls
            P.start_trace(tdir, profiler_options=opts)
            tr_state["on"] = True

        def stop():
            tr_state["on"] = False
            tr_state["t1"] = time.perf_counter()
            P.stop_trace()
            tr_state["end"] = counters()

        sub = min(TRACE_S, max(seconds - TRACE_AT_S - 0.5, 0.5))
        marks = [(TRACE_AT_S, start), (TRACE_AT_S + sub, stop)]

    make_req = lambda it: Request(uid=it.uid, prompt=it.prompt,
                                  max_new_tokens=it.max_new, temperature=0.0)
    setup_box = {}
    opened = lambda: setup_box.setdefault("setup_s",
                                          time.perf_counter() - t_start)
    kw = dict(lead_in_s=traffic.lead_in_s, seconds=seconds, drain_s=DRAIN_S,
              span=span, on_open=opened, marks=marks)
    compiles_before = compiles.n
    if traffic.loop == "open":
        res = drivers.drive_open(eng, traffic.items, make_req, **kw)
    else:
        res = drivers.drive_closed(eng, traffic.items, make_req,
                                   clients=traffic.clients, **kw)
    end = time.perf_counter()
    setup_s = setup_box["setup_s"]
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    tr = None
    if trace:
        tr = T.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    flops = None
    contexts = step_contexts(steps_seen) if trace else None
    if contexts:
        n_tok = sum(n for s, e, n in res.steps
                    if tr_state["t0"] <= s and e <= tr_state["t1"])
        flops = sum(arch.step_flops(c, kv, 0) for kv in contexts) \
            + n_tok * arch.head_flops(c)
    del steps_seen
    ctx = readings.Context(
        run=res, config=c, mix=mix, setup_s=setup_s, end=end, trace=tr,
        counters=({"start": tr_state["start"], "end": tr_state["end"]}
                  if trace else None),
        flops=flops, peak=peaks.get(dev.device_kind))

    due = res.due_in_window()
    failed = sum(1 for r in due if r.failed or not r.stamps)
    late = sorted(res.late_s)
    log(f"[setup] " + " ".join(f"{k}={v:.3f}" for k, v in parts.items())
        + f" lead_in_s={traffic.lead_in_s} setup_s={setup_s:.3f}")
    log(f"[window] seconds={seconds} due={len(due)} failed={failed} "
        f"finished={sum(r.finished for r in res.records)} "
        f"steps={len(res.steps)} drain_s={res.drain_s:.3f} "
        f"compiles_in_window={compiles.n - compiles_before} "
        f"generator_late_p50_ms="
        f"{1e3 * late[len(late) // 2] if late else 0:.3f} "
        f"generator_late_max_ms={1e3 * late[-1] if late else 0:.3f}")

    metrics = {}
    for m in (layer if trace else e2e):
        v = registry.reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak_bytes}
    out = {}
    if trace:
        device["busy_s"] = T.busy_ns(tr) / 1e9
        lo, hi = T.window(tr)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": T.top_ops(tr),
                            "idle_gaps": T.top_gaps(tr)}

    # the check: the served tokens of finished requests against the
    # reference, once the program's state is freed
    recs = sample(res.records, seed, chk["requests"])
    del eng, su, res, ctx, traffic
    gc.collect()
    t = time.perf_counter()
    lim = chk["logit_gap_max"]["limit"]
    gap = None
    tt = 256
    while tt < min(mix_max_len(mix), sv["max_len"]):
        tt *= 2
    shape = dict(t=tt, k=mix["output"]["max"], n=chk["requests"],
                 root=root)
    if recs:
        gap = float(logit_gaps(c, seed, recs, **shape).max())
    log(f"[check] requests={len(recs)} tokens="
        f"{sum(len(r.req.out_tokens) for r in recs)} distinct_tokens="
        f"{sum(len(set(r.req.out_tokens)) for r in recs)} "
        f"reference_s={time.perf_counter() - t:.3f}")
    correct, check = decide(gap, failed, lim)
    if control and recs:
        tokens = control_tokens(c, seed, recs, **shape)
        ctl_gap = float(logit_gaps(c, seed, recs, tokens, **shape).max())
        ctl_correct, ctl_check = decide(ctl_gap, failed, lim)
        out["control"] = {"correct": ctl_correct, "check": ctl_check}
    for name, v in check.items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": len(due), "failed": failed,
            "metrics": metrics, "device": device, **out, "check": check}
