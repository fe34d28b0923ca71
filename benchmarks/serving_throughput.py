"""Serving throughput: wave lockstep vs slot-based continuous batching vs
paged-KV chunked prefill (lockstep AND packed token steps), plus paged
prompt-prefix sharing.

A mixed prompt/output-length workload (the online-serving regime): prompt
lengths and output budgets drawn from skewed distributions, so the wave
scheduler fragments into small same-length waves and each wave is held
hostage by its slowest member, while the continuous/paged engines back-fill
freed slots every step. Reported tokens/sec is generated tokens over wall
clock, after a warm-up pass that covers every jit shape (prefill buckets or
chunk widths + decode) for each engine, so compile time is excluded.

Every row also records PADDING EFFICIENCY (valid token-lanes / padded
token-lanes over the timed steps): the paged lockstep chunk step pads every
decode-riding slot to (block_size,) lanes, and the packed token step
(serve/paged.py packed mode) removes that structurally — the third,
prefill-heavy workload (long prompts, short outputs, so decode-riding waste
dominates chunk steps) runs paged lockstep vs packed head-to-head and is the
acceptance gate for the packing win.

A shared-system-prompt workload (every request opens with the same 48-token
prefix — the chatbot/few-shot regime) runs the paged engine with prefix
sharing off vs on and records prefix hit-rate, prefill tokens skipped, COW
copies, and cache bytes.

A MULTI-TURN chat workload (sessions of several turns, each turn a fresh
user message on top of the stored history) runs the paged engine with
decode-block sharing off vs on: off re-prefills the whole conversation —
prompt AND previously generated replies — every turn, on prefix-matches the
cached blocks (decode-origin ones included) and prefills only the new
message. Records tok/s, decode-block hit counts, and follow-up-turn
skip rates; the on/off tok/s ratio is the acceptance gate for the
decode-sharing win (>= 1.5x).

A SPECULATIVE-DECODING workload (multi-turn sessions on a DECODE-HEAVY
geometry — short user messages, long replies — because drafting can only
win back decode steps, and the long greedy replies are the self-repeating
regime the draft sources can predict) runs the paged+packed engine with
trie-driven speculative decoding off vs on: on drafts up to K tokens per
decode step from the trie (n-gram prompt-lookup fallback when the trie
path runs dry) and verifies them all in ONE packed step. The off/on pair
is timed in INTERLEAVED passes (off, on, off, on, ...; best pass per
side) because box-speed drift between two sequential runs is the same
order as the effect. Records tok/s, the on/off ratio (the acceptance gate
for the speculative win, >= 1.5x), drafted/accepted/rejected counts and
the acceptance rate — and asserts the greedy outputs token-identical
across off/on with block sharing both on and off (speculation must never
change what greedy decoding emits).

An INT8 KV workload (the mixed workload again, fp32 pool vs int8 pool with
per-block per-kv-head scales at identical geometry) runs paged+packed under
kv_quant off vs on and records tok/s, pool bytes, the padded-byte ratio
(acceptance gate: int8 <= 0.35x fp32 — payload shrinks 4x, scales add a
few KB) and the greedy exact-match rate of the int8 outputs against the
fp32 outputs (the drift the per-block requant path actually costs).

A LATENCY-SLO workload (open-loop): seeded Poisson arrivals at
--arrival-rate req/s drive the paged engine (packed steps, prefix sharing
on) through the step-at-a-time API via telemetry.drive_open_loop — arrivals
never wait for the system, so admission queueing lands in TTFT. Records
TTFT/TPOT/E2E/queue-wait p50/p95/p99, queue-depth peak/mean, and the
step-phase coverage, as the `latency_slo` section of BENCH_serving.json;
benchmarks/check_regression.py gates fresh runs against those committed
numbers.

An OVERLOAD workload (open-loop again, but HOSTILE): arrivals at ~2x the
engine's measured closed-loop capacity, an UNDERSIZED block pool (half the
slot-arena equivalent), three priority classes with per-request E2E
deadlines, a bounded queue with shed-lowest-priority backpressure, and
priority preemption on (serve/admission.py). Records per-class
deadline-miss and SLO-failure rates (miss + shed + rejected) and TTFT p95;
the SLO-failure ordering is the fairness signal — the high class must fail
at most as often as the low class (asserted) — and preemption /
exhaustion / shed counts, and the re-prefill skip rate of resumed
requests. A second, contention-only sub-run (no deadlines, no bound)
forces real preemptions by arrival order and asserts every preempted
request's greedy output TOKEN-IDENTICAL to an uncontended run of the same
requests — `resume_token_parity`, gated at zero tolerance.

Cache bytes are reported as cache_bytes_logical AND cache_bytes_padded:
with the decode kernel active the arena is lane-padded (head_dim -> 128),
so the raw allocation is up to 4x the logical cache — reporting both keeps
kernel and non-kernel rows comparable.

Machine-readable output: every run writes BENCH_serving.json (override with
--json) with tok/s, cache bytes, mean batch occupancy and padding efficiency
per engine — plus the prefix-sharing and prefill-heavy rows — so the perf
trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.serving_throughput \
        --engine wave --engine paged --json out.json
"""
from __future__ import annotations

import argparse
import copy
import json
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.serve import (AdmissionConfig, ContinuousEngine, PagedEngine,
                         Request, RobustnessCounters, ServeEngine, Telemetry,
                         drive_open_loop, kv_cache_byte_stats, percentile)

VOCAB = 512
MAX_BATCH = 8
MAX_LEN = 128
BLOCK_SIZE = 16
SYSTEM_PROMPT_LEN = 48               # shared prefix of the prefix workload
# multi-turn chat workload geometry: user-message length is a non-multiple
# of BLOCK_SIZE and replies cross block boundaries mid-decode, so the trie
# caches genuine decode-origin blocks (not just re-registered prompt ones)
MT_SESSIONS = 6
MT_TURNS = 6
MT_USER_LEN = 40
MT_REPLY = 12
MT_MAX_LEN = 384                     # holds a full 6-turn history per slot
SPEC_TURNS = 3                       # speculative section: decode-heavy chat —
SPEC_USER_LEN = 16                   # short messages, long replies (drafting
SPEC_REPLY = 64                      # only wins back DECODE steps, and long
SPEC_MAX_LEN = 384                   # greedy replies are the loopy regime)
DEFAULT_JSON = "BENCH_serving.json"


def _cfg():
    return ModelConfig(
        name="serve-bench", family="dense", num_layers=4, d_model=128,
        num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=VOCAB,
        vocab_pad_multiple=1, attention_prob="hccs", hccs_mode="i16_div",
        attention_impl="dense")


def _workload(rng, n):
    """Skewed mixed-length traffic: mostly short prompts/outputs, a long tail."""
    reqs = []
    for i in range(n):
        plen = int(rng.choice([6, 10, 14, 22, 30, 46],
                              p=[.3, .25, .2, .1, .1, .05]))
        out = int(rng.choice([4, 8, 16, 32], p=[.35, .3, .2, .15]))
        reqs.append(Request(uid=i,
                            prompt=rng.integers(0, VOCAB, plen).astype(np.int32),
                            max_new_tokens=out))
    return reqs


def _prefix_workload(rng, n):
    """Shared-system-prompt traffic: every request opens with the same
    48-token prefix (3 full KV blocks) followed by a short unique tail —
    the regime prefix sharing targets (chatbots, few-shot headers)."""
    system = rng.integers(0, VOCAB, SYSTEM_PROMPT_LEN).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, VOCAB,
                            int(rng.choice([4, 8, 12, 20]))).astype(np.int32)
        out = int(rng.choice([4, 8, 16], p=[.4, .35, .25]))
        reqs.append(Request(uid=i, prompt=np.concatenate([system, tail]),
                            max_new_tokens=out))
    return reqs


def _overload_workload(rng, n, classes=3):
    """Tiered overload traffic (the regime priority preemption exists for):
    the BATCH tier (class 0) runs long generations that pin pool blocks for
    most of the run, the INTERACTIVE top tier is short and
    latency-sensitive, the middle tier sits between. Short interactive
    arrivals landing on a pool full of long batch work is what forces the
    reservation gate to preempt rather than queue."""
    reqs = []
    for i in range(n):
        c = i % classes
        if c == 0:
            plen = int(rng.choice([22, 30, 46]))
            out = int(rng.choice([32, 48]))
        elif c == classes - 1:
            plen = int(rng.choice([6, 10, 14]))
            out = int(rng.choice([4, 8]))
        else:
            plen = int(rng.choice([10, 14, 22]))
            out = int(rng.choice([8, 16]))
        reqs.append(Request(uid=i, priority=c,
                            prompt=rng.integers(0, VOCAB,
                                                plen).astype(np.int32),
                            max_new_tokens=out))
    return reqs


def _prefill_heavy_workload(rng, n):
    """Long prompts, short-to-moderate outputs: most steps are chunk steps
    where the decode-riding slots dominate the padded lanes — the regime the
    packed token step targets (lockstep burns block_size lanes per rider)."""
    reqs = []
    for i in range(n):
        plen = int(rng.choice([40, 56, 72, 88], p=[.35, .3, .2, .15]))
        out = int(rng.choice([8, 16, 24], p=[.4, .35, .25]))
        reqs.append(Request(uid=i,
                            prompt=rng.integers(0, VOCAB, plen).astype(np.int32),
                            max_new_tokens=out))
    return reqs


def _multi_turn_traffic(rng, turns=MT_TURNS, user_len=MT_USER_LEN):
    """Chat sessions: per session, `turns` fresh user messages. Every turn
    rides on the engine-stored history, so turn k's effective prompt is the
    whole conversation so far plus this message."""
    return [[rng.integers(0, VOCAB, user_len).astype(np.int32)
             for _ in range(turns)] for _ in range(MT_SESSIONS)]


def _serve_turns(eng, traffic, tag, reply=MT_REPLY):
    """Drive one round of every session per turn through the session API
    (all sessions' turn-k requests batch together); returns the finished
    requests."""
    done = []
    for turn in range(len(traffic[0])):
        for s, msgs in enumerate(traffic):
            eng.submit(Request(uid=turn * len(traffic) + s,
                               prompt=msgs[turn].copy(),
                               max_new_tokens=reply),
                       session=f"{tag}{s}")
        done.extend(eng.run())
    return done


def _serve_multi_turn(make_engine, warm_traffic, traffic, passes: int = 3):
    """Warm-up + timed multi-turn serve on the SAME engine instance (the jit
    cache lives on it). The warm-up drives identical turn structure under
    throwaway session ids; each timed pass then starts from a cold prefix
    cache and fresh sessions, so it measures the steady-state multi-turn
    regime, compile excluded. Reports the BEST of `passes` identical passes:
    the multi-turn runs are short and the on/off ratio is an acceptance
    gate, so a single pass is too exposed to scheduler noise on a shared
    box — the minimum is the least-contended measurement of the same
    deterministic work."""
    eng = make_engine()
    _serve_turns(eng, warm_traffic, "warm")
    for s in range(len(warm_traffic)):
        eng.end_session(f"warm{s}")
    best = None
    for p in range(passes):
        if eng.prefix_sharing:
            eng.clear_prefix_cache()
        row, done = _timed(eng,
                           lambda: _serve_turns(eng, traffic, f"chat{p}-"))
        for s in range(len(traffic)):
            eng.end_session(f"chat{p}-{s}")
        if best is None or row["seconds"] < best["seconds"]:
            best = row
    return best


def _engine_factories(cfg, params):
    mk = dict(max_batch=MAX_BATCH, max_len=MAX_LEN)
    # "paged" is the lockstep (B, block_size)/(B, 1) baseline; "paged+packed"
    # flattens each step to a ragged token batch (the library default)
    return {
        "wave": lambda: ServeEngine(params, cfg, **mk),
        "continuous": lambda: ContinuousEngine(params, cfg, **mk),
        "continuous+kernel": lambda: ContinuousEngine(
            params, cfg.replace(decode_kernel="fused"), **mk),
        "paged": lambda: PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                                     packed=False, **mk),
        "paged+packed": lambda: PagedEngine(params, cfg,
                                            block_size=BLOCK_SIZE,
                                            packed=True, **mk),
        "paged+kernel": lambda: PagedEngine(
            params, cfg.replace(decode_kernel="fused"),
            block_size=BLOCK_SIZE, packed=False, **mk),
        "paged+packed+kernel": lambda: PagedEngine(
            params, cfg.replace(decode_kernel="fused"),
            block_size=BLOCK_SIZE, packed=True, **mk),
    }


# interpret-mode kernel emulation is slow on CPU; the packed+kernel row is
# opt-in via --engine so the default sweep stays fast
DEFAULT_ENGINES = ["wave", "continuous", "continuous+kernel", "paged",
                   "paged+packed", "paged+kernel"]


def _cache_byte_stats(eng):
    cache = getattr(eng, "_cache", None)
    if cache is None:
        # the wave engine allocates a fresh (max_batch, max_len) slot cache
        # per wave rather than holding one; measure that reservation
        cache = M.init_cache(eng.cfg, eng.max_batch, eng.max_len,
                             eng.cache_dtype)
    # paged pools pass max_len=None: their rows axis is block_size, unpadded
    max_len = None if isinstance(eng, PagedEngine) else eng.max_len
    return kv_cache_byte_stats(cache, eng.cfg, max_len)


def _prefix_delta(eng, p0):
    """Prefix-sharing counters over a timed segment: the engine counters are
    cumulative, so subtract the pre-segment snapshot (the warm-up populates
    the prefix cache — this is the steady-state rate) and rebuild the
    rates."""
    p1 = eng.prefix_stats()
    d = {k: p1[k] - p0[k]
         for k in ("lookups", "hits", "prompt_hits", "decode_hits",
                   "prefill_tokens", "prefill_tokens_skipped",
                   "prompt_tokens_skipped", "decode_tokens_skipped",
                   "followup_prefill_tokens", "followup_tokens_skipped",
                   "cow_copies", "evictions", "pad_lanes_skipped",
                   "spec_steps", "spec_rollbacks", "tokens_drafted",
                   "tokens_accepted", "tokens_rejected")}
    d["hit_rate"] = d["hits"] / max(d["lookups"], 1)
    d["skip_rate"] = (d["prefill_tokens_skipped"]
                      / max(d["prefill_tokens"], 1))
    d["followup_skip_rate"] = (d["followup_tokens_skipped"]
                               / max(d["followup_prefill_tokens"], 1))
    d["acceptance_rate"] = (d["tokens_accepted"] / d["tokens_drafted"]
                            if d["tokens_drafted"] else None)
    return d


def _timed(eng, serve_fn):
    """Time ONE serving segment on an already-warm engine and report the
    row schema every workload section shares: counter DELTAS past the
    warm-up (mean occupancy, padding efficiency, prefix-sharing rates —
    the engine counters are cumulative), tokens/seconds, cache bytes, and
    the engine's unified telemetry snapshot (latency/phases are None unless
    the engine was built with telemetry on). serve_fn drives the engine and
    returns the finished requests; returns (row, finished)."""
    s0 = getattr(eng, "occupancy_sum", 0.0)
    n0 = getattr(eng, "occupancy_steps", 0)
    lv0 = getattr(eng, "lanes_valid", 0)
    lt0 = getattr(eng, "lanes_total", 0)
    ps0 = getattr(eng, "pad_lanes_skipped", 0)
    p0 = eng.prefix_stats() if getattr(eng, "prefix_sharing", False) else None
    t0 = time.perf_counter()
    done = serve_fn()
    dt = time.perf_counter() - t0
    # mean live fraction over the TIMED steps only (delta past the warm-up)
    n = getattr(eng, "occupancy_steps", 0) - n0
    occ = (getattr(eng, "occupancy_sum", 0.0) - s0) / n if n else None
    # per-step padding efficiency (valid token-lanes / padded token-lanes)
    # over the timed steps; None for engines without lane telemetry
    lt = getattr(eng, "lanes_total", 0) - lt0
    pad_eff = ((getattr(eng, "lanes_valid", 0) - lv0) / lt) if lt else None
    row = dict(tokens=sum(len(r.out_tokens) for r in done), seconds=dt,
               **_cache_byte_stats(eng), occupancy=occ,
               padding_efficiency=pad_eff,
               pad_lanes_skipped=(getattr(eng, "pad_lanes_skipped", 0) - ps0
                                  if lt else None),
               prefix=None if p0 is None else _prefix_delta(eng, p0),
               snapshot=eng.snapshot())
    return row, done


def _serve(make_engine, warmup, reqs, warmup_passes: int = 1,
           keep_outputs: bool = False):
    """Warm and time the SAME engine instance: the jitted closures live on
    the instance, so a throwaway warm-up engine would discard its compile
    cache and the timed run would re-trace every shape.

    warmup_passes > 1 is for engines whose STATE changes the step shapes:
    with prefix sharing, the first pass runs against a cold prefix cache
    (full-length chunk steps) while the timed run is all-hit (short tail
    chunks) — the second pass covers the warm-cache shapes."""
    eng = make_engine()
    for _ in range(warmup_passes):
        for r in copy.deepcopy(warmup):
            eng.submit(r)
        eng.run()
    work = copy.deepcopy(reqs)
    for r in work:
        eng.submit(r)
    row, done = _timed(eng, eng.run)
    if keep_outputs:
        # per-request greedy outputs, for cross-engine exact-match rates
        row["outputs"] = {r.uid: [int(t) for t in r.out_tokens]
                          for r in done}
    return row


def run(fast: bool = True, engines: list | None = None,
        json_path: str = DEFAULT_JSON, arrival_rate: float = 8.0):
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n = 24 if fast else 96
    reqs = _workload(rng, n)
    # warm-up must cover every jit shape the timed run hits: same workload
    # distribution (prefill buckets / chunk widths + decode) drawn once more
    warmup = _workload(np.random.default_rng(0), n)

    factories = _engine_factories(cfg, params)
    names = engines or DEFAULT_ENGINES

    out = []
    print("\n# serving throughput: scheduler, tokens, s, tok/s, vs_first, "
          "cache_MB(logical/padded), occupancy, pad_eff")
    base_tps = None
    for name in names:
        row = _serve(factories[name], warmup, reqs)
        tps = row["tokens"] / row["seconds"]
        if base_tps is None:
            base_tps = tps
        occ = "-" if row["occupancy"] is None else "%.2f" % row["occupancy"]
        eff = ("-" if row["padding_efficiency"] is None
               else "%.2f" % row["padding_efficiency"])
        print("serving,%s,%d,%.2f,%.1f,%.2fx,%.2f/%.2f,%s,%s" % (
            name, row["tokens"], row["seconds"], tps, tps / base_tps,
            row["cache_bytes_logical"] / 2**20,
            row["cache_bytes_padded"] / 2**20, occ, eff))
        out.append(dict(scheduler=name, tok_per_s=tps,
                        vs_first=tps / base_tps, **row))

    # prefill-heavy workload: paged lockstep vs packed token steps — the
    # acceptance gate for the packing win (tok/s AND padding efficiency)
    packed_out = []
    if engines is None or any(e.startswith("paged") for e in names):
        # 2x the request count: the packed-vs-lockstep delta is the
        # acceptance gate, so the timed region gets extra length to keep
        # scheduler noise well below the effect size
        hreqs = _prefill_heavy_workload(np.random.default_rng(3), 2 * n)
        hwarm = _prefill_heavy_workload(np.random.default_rng(3), 2 * n)
        # full pool so packing, not admission gating, is what differs
        nblk = MAX_BATCH * (MAX_LEN // BLOCK_SIZE) + 1
        print("\n# prefill-heavy (paged, long prompts): step_layout, tokens, "
              "s, tok/s, pad_eff, pad_lanes_skipped")
        for packed in (False, True):
            row = _serve(
                lambda: PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                                    max_batch=MAX_BATCH, max_len=MAX_LEN,
                                    num_blocks=nblk, packed=packed),
                hwarm, hreqs)
            tps = row["tokens"] / row["seconds"]
            print("prefill_heavy,%s,%d,%.2f,%.1f,%.2f,%d" % (
                "packed" if packed else "lockstep", row["tokens"],
                row["seconds"], tps, row["padding_efficiency"],
                row["pad_lanes_skipped"]))
            packed_out.append(dict(step_layout="packed" if packed
                                   else "lockstep", tok_per_s=tps, **row))

    # shared-system-prompt workload: paged engine, prefix sharing off vs on
    # (skipped when --engine filters to non-paged rows only)
    prefix_out = []
    if engines is None or any(e.startswith("paged") for e in names):
        preqs = _prefix_workload(np.random.default_rng(7), n)
        pwarm = _prefix_workload(np.random.default_rng(7), n)
        print("\n# prefix sharing (paged, shared-system-prompt workload): "
              "variant, tokens, s, tok/s, hit_rate, skip_rate, cow, cache_MB")
        for sharing in (False, True):
            row = _serve(
                lambda: PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                                    max_batch=MAX_BATCH, max_len=MAX_LEN,
                                    prefix_sharing=sharing),
                pwarm, preqs, warmup_passes=2)
            tps = row["tokens"] / row["seconds"]
            p = row["prefix"]
            print("prefix,%s,%d,%.2f,%.1f,%s,%s,%s,%.2f" % (
                "on" if sharing else "off", row["tokens"], row["seconds"],
                tps,
                "-" if p is None else "%.2f" % p["hit_rate"],
                "-" if p is None else "%.2f" % p["skip_rate"],
                "-" if p is None else p["cow_copies"],
                row["cache_bytes_logical"] / 2**20))
            prefix_out.append(dict(variant="on" if sharing else "off",
                                   tok_per_s=tps, **row))

    # multi-turn chat workload: paged engine + session API, decode-block
    # sharing off vs on — off re-prefills the whole conversation every turn,
    # on serves it from cached prompt+decode blocks. The on/off tok/s ratio
    # is the acceptance gate for the decode-sharing win.
    mt_out = []
    if engines is None or any(e.startswith("paged") for e in names):
        traffic = _multi_turn_traffic(np.random.default_rng(11))
        mwarm = _multi_turn_traffic(np.random.default_rng(13))
        nblk = MAX_BATCH * (MT_MAX_LEN // BLOCK_SIZE) + 1
        print("\n# multi-turn chat (paged, %d sessions x %d turns): "
              "decode_sharing, tokens, s, tok/s, vs_off, decode_hits, "
              "followup_skip" % (MT_SESSIONS, MT_TURNS))
        for sharing in (False, True):
            row = _serve_multi_turn(
                lambda: PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                                    max_batch=MAX_BATCH, max_len=MT_MAX_LEN,
                                    num_blocks=nblk, prefix_sharing=sharing,
                                    decode_sharing=sharing),
                mwarm, traffic)
            tps = row["tokens"] / row["seconds"]
            row["vs_off"] = tps / mt_out[0]["tok_per_s"] if mt_out else 1.0
            p = row["prefix"]
            print("multi_turn,%s,%d,%.2f,%.1f,%.2fx,%s,%s" % (
                "on" if sharing else "off", row["tokens"], row["seconds"],
                tps, row["vs_off"],
                "-" if p is None else p["decode_hits"],
                "-" if p is None else "%.2f" % p["followup_skip_rate"]))
            mt_out.append(dict(variant="on" if sharing else "off",
                               tok_per_s=tps, **row))

    # trie-driven speculative decoding: multi-turn sessions on the decode-
    # heavy geometry (drafting only wins back DECODE steps — the default
    # multi-turn geometry's 12-token replies never leave prefill-dominated
    # territory), paged+packed engine with block sharing on, speculative off
    # vs on. The pair is timed in INTERLEAVED passes (off, on, off, on; best
    # pass per side) so box-speed drift between runs cancels out of the
    # vs_off ratio — the acceptance gate for the speculative win. The greedy
    # outputs are asserted token-identical across off/on — with sharing BOTH
    # on and off (the off pair is untimed: it exists to prove the n-gram
    # fallback path alone also never changes what greedy decoding emits).
    spec_out = []
    if engines is None or any(e.startswith("paged") for e in names):
        straffic = _multi_turn_traffic(np.random.default_rng(31),
                                       turns=SPEC_TURNS,
                                       user_len=SPEC_USER_LEN)
        swarm = _multi_turn_traffic(np.random.default_rng(37),
                                    turns=SPEC_TURNS,
                                    user_len=SPEC_USER_LEN)
        nblk = MAX_BATCH * (SPEC_MAX_LEN // BLOCK_SIZE) + 1
        print("\n# speculative decoding (paged+packed+sharing, %d sessions "
              "x %d turns, %d-token replies): variant, tokens, s, tok/s, "
              "vs_off, drafted, accepted, acceptance"
              % (MT_SESSIONS, SPEC_TURNS, SPEC_REPLY))
        for sharing in (True, False):
            engs, best, outs = {}, {}, {}
            for spec in (False, True):
                eng = PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                                  max_batch=MAX_BATCH, max_len=SPEC_MAX_LEN,
                                  num_blocks=nblk, prefix_sharing=sharing,
                                  decode_sharing=sharing, packed=True,
                                  speculative=spec)
                _serve_turns(eng, swarm, f"w{int(spec)}-", reply=SPEC_REPLY)
                for s in range(len(swarm)):
                    eng.end_session(f"w{int(spec)}-{s}")
                engs[spec] = eng
            for p in range(3 if sharing else 1):
                for spec in (False, True):
                    eng = engs[spec]
                    if eng.prefix_sharing:
                        eng.clear_prefix_cache()
                    tag = f"chat{p}{int(spec)}-"
                    row, done = _timed(
                        eng, lambda: _serve_turns(eng, straffic, tag,
                                                  reply=SPEC_REPLY))
                    for s in range(len(straffic)):
                        eng.end_session(f"{tag}{s}")
                    # passes run identical deterministic work, so the first
                    # pass's greedy outputs stand for the run
                    outs.setdefault(spec, {r.uid: [int(t) for t in
                                                   r.out_tokens]
                                           for r in done})
                    if (best.get(spec) is None
                            or row["seconds"] < best[spec]["seconds"]):
                        best[spec] = row
            assert outs[False] == outs[True], (
                "speculative decoding changed greedy outputs "
                f"(sharing {'on' if sharing else 'off'})")
            if not sharing:
                continue    # untimed parity-only pair
            for spec in (False, True):
                row = best[spec]
                tps = row["tokens"] / row["seconds"]
                row["vs_off"] = (tps / spec_out[0]["tok_per_s"]
                                 if spec_out else 1.0)
                p = row["prefix"]
                rate = None if p is None else p["acceptance_rate"]
                print("speculative,%s,%d,%.2f,%.1f,%.2fx,%s,%s,%s" % (
                    "on" if spec else "off", row["tokens"], row["seconds"],
                    tps, row["vs_off"],
                    "-" if p is None else p["tokens_drafted"],
                    "-" if p is None else p["tokens_accepted"],
                    "-" if rate is None else "%.2f" % rate))
                spec_out.append(dict(variant="on" if spec else "off",
                                     tok_per_s=tps,
                                     acceptance_rate=rate, **row))
    # IDENTICAL geometry on the mixed workload. The byte ratio is the
    # acceptance gate (int8 padded pool <= 0.35x fp32: payload is a quarter,
    # scales add 2*L*N*Hkv floats); exact_match records how many greedy
    # tokens the requant drift actually flips vs the fp32 engine.
    kvq_out = []
    if engines is None or any(e.startswith("paged") for e in names):
        qreqs = _workload(np.random.default_rng(17), n)
        qwarm = _workload(np.random.default_rng(17), n)
        print("\n# kv int8 (paged+packed, mixed workload): kv_quant, tokens, "
              "s, tok/s, kv_MB(logical/padded), bytes_vs_fp32, exact_match")
        fp_row = fp_outputs = None
        for quant in ("none", "int8"):
            qcfg = cfg.replace(kv_quant=quant)
            row = _serve(
                lambda: PagedEngine(params, qcfg, block_size=BLOCK_SIZE,
                                    max_batch=MAX_BATCH, max_len=MAX_LEN,
                                    packed=True),
                qwarm, qreqs, keep_outputs=True)
            outputs = row.pop("outputs")
            tps = row["tokens"] / row["seconds"]
            if quant == "none":
                fp_row, fp_outputs = row, outputs
                ratio, match = 1.0, 1.0
            else:
                ratio = (row["cache_bytes_padded"]
                         / fp_row["cache_bytes_padded"])
                same = total = 0
                for uid, toks in fp_outputs.items():
                    q = outputs[uid]
                    total += max(len(toks), len(q))
                    same += sum(a == b for a, b in zip(toks, q))
                match = same / max(total, 1)
                assert ratio <= 0.35, f"int8 pool ratio {ratio:.3f} > 0.35"
            print("kv_int8,%s,%d,%.2f,%.1f,%.2f/%.2f,%.3fx,%.3f" % (
                quant, row["tokens"], row["seconds"], tps,
                row["cache_bytes_logical"] / 2**20,
                row["cache_bytes_padded"] / 2**20, ratio, match))
            kvq_out.append(dict(kv_quant=quant, tok_per_s=tps,
                                kv_bytes_vs_fp32=ratio,
                                greedy_exact_match=match, **row))

    # pipelined async loop: the same mixed workload on the paged+packed
    # engine, synchronous vs pipelined step loop. Timed in INTERLEAVED
    # passes (sync, async, sync, async; best pass per side) so box-speed
    # drift cancels out of the vs_sync ratio — the acceptance gate for the
    # pipelining win. Greedy outputs are asserted token-identical (the
    # zero-tolerance correctness gate); both engines run with telemetry ON
    # so the device-phase share doubles as the host-visible stall metric:
    # the sync loop fences at dispatch, the async loop fences one step
    # late at commit — time the host spends blocked on the device should
    # FALL when the pipeline overlaps it with bookkeeping.
    asy_out = None
    if engines is None or any(e.startswith("paged") for e in names):
        areqs = _workload(np.random.default_rng(47), n)
        awarm = _workload(np.random.default_rng(47), n)
        engs, best, outs = {}, {}, {}
        for mode in (False, True):
            tel = Telemetry(enabled=True)
            eng = PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                              max_batch=MAX_BATCH, max_len=MAX_LEN,
                              packed=True, async_loop=mode, telemetry=tel)
            for r in copy.deepcopy(awarm):
                eng.submit(r)
            eng.run()
            tel.reset()
            engs[mode] = eng
        for p in range(3):
            for mode in (False, True):
                eng = engs[mode]
                work = copy.deepcopy(areqs)
                for r in work:
                    eng.submit(r)
                row, done = _timed(eng, eng.run)
                outs.setdefault(mode, {r.uid: [int(t) for t in r.out_tokens]
                                       for r in done})
                if (best.get(mode) is None
                        or row["seconds"] < best[mode]["seconds"]):
                    best[mode] = row
        assert outs[False] == outs[True], \
            "the pipelined async loop changed greedy outputs"
        print("\n# async loop (paged+packed, mixed workload): loop, tokens, "
              "s, tok/s, vs_sync, device_stall_share, overlapped, fallbacks")
        rows = {}
        for mode in (False, True):
            row = best[mode]
            eng = engs[mode]
            tps = row["tokens"] / row["seconds"]
            # cumulative across the interleaved passes: the share metric,
            # not a per-pass timing, so pass-picking does not apply
            phases = eng.snapshot()["phases"]
            dev = phases["phases"].get("device", {})
            stall = dev.get("share_of_step")
            name = "async" if mode else "sync"
            rows[name] = dict(loop=name, tok_per_s=tps,
                              device_stall_share=stall,
                              overlapped_steps=eng.async_overlapped_steps,
                              sync_fallbacks=eng.async_sync_fallbacks,
                              **row)
            print("async_loop,%s,%d,%.2f,%.1f,%.2fx,%s,%d,%d" % (
                name, row["tokens"], row["seconds"], tps,
                tps / rows["sync"]["tok_per_s"],
                "-" if stall is None else "%.2f" % stall,
                eng.async_overlapped_steps, eng.async_sync_fallbacks))
        vs_sync = rows["async"]["tok_per_s"] / rows["sync"]["tok_per_s"]
        stall_ratio = (
            rows["async"]["device_stall_share"]
            / rows["sync"]["device_stall_share"]
            if rows["sync"]["device_stall_share"] else None)
        assert rows["async"]["overlapped_steps"] > 0, \
            "async loop never pipelined a step on the greedy workload"
        asy_out = dict(sync=rows["sync"], **{"async": rows["async"]},
                       vs_sync=vs_sync, stall_share_vs_sync=stall_ratio,
                       greedy_parity=1.0)

    # open-loop latency SLO: seeded Poisson arrivals drive the paged engine
    # (packed steps, prefix sharing on) through the step-at-a-time API.
    # Arrivals do NOT wait for the system, so admission queueing lands in
    # TTFT — the percentiles here measure what the batch-drain throughput
    # rows structurally cannot: latency under load.
    slo_out = None
    if engines is None or any(e.startswith("paged") for e in names):
        tel = Telemetry(enabled=True)
        sreqs = _prefix_workload(np.random.default_rng(23), n)
        swarm = _prefix_workload(np.random.default_rng(23), n)
        eng = PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                          max_batch=MAX_BATCH, max_len=MAX_LEN,
                          prefix_sharing=True, packed=True, telemetry=tel)
        # two warm-up drains: cold-prefix-cache then all-hit chunk shapes
        # (same reasoning as the prefix-sharing section's warmup_passes=2)
        for _ in range(2):
            for r in copy.deepcopy(swarm):
                eng.submit(r)
            eng.run()
        tel.reset()
        arrivals = np.cumsum(np.random.default_rng(29).exponential(
            1.0 / arrival_rate, n))
        row, done = _timed(
            eng, lambda: drive_open_loop(eng, copy.deepcopy(sreqs),
                                         arrivals))
        snap = row["snapshot"]
        lat, phases = snap["latency"], snap["phases"]
        tps = row["tokens"] / row["seconds"]
        slo_out = dict(arrival_rate=arrival_rate, requests=len(done),
                       tok_per_s=tps, ttft=lat["ttft"], tpot=lat["tpot"],
                       e2e=lat["e2e"], queue_wait=lat["queue_wait"],
                       queue_depth_peak=lat["queue_depth_peak"],
                       queue_depth_mean=lat["queue_depth_mean"],
                       phase_coverage=phases["coverage"], **row)
        print("\n# latency SLO (paged+packed+sharing, open-loop Poisson "
              "%g req/s): metric, p50_ms, p95_ms, p99_ms" % arrival_rate)
        for m in ("ttft", "tpot", "e2e", "queue_wait"):
            d = lat[m]
            print("latency_slo,%s,%.1f,%.1f,%.1f" % (
                m, 1e3 * d["p50"], 1e3 * d["p95"], 1e3 * d["p99"]))
        print("latency_slo,tok_per_s,%.1f  queue_depth_peak,%d  "
              "phase_coverage,%.2f" % (tps, lat["queue_depth_peak"],
                                       phases["coverage"] or 0))

    # OVERLOAD: the open-loop driver again, but hostile — ~2x measured
    # capacity on an UNDERSIZED pool, three priority classes, E2E deadlines,
    # bounded queue + shed backpressure, preemption on. The per-class miss
    # rates are the fairness signal (strict priority must protect the high
    # class); the parity sub-run is the correctness gate for preemption
    # resume (token-identical to an uncontended run, zero tolerance).
    ovl_out = None
    if engines is None or any(e.startswith("paged") for e in names):
        classes = 3
        # a QUARTER of the slot-arena equivalent: tight enough that the
        # reservation gate stalls under load, which is what routes overload
        # through preemption (not just queueing + shed)
        nblk = MAX_BATCH * (MAX_LEN // BLOCK_SIZE) // 4 + 1
        tel = Telemetry(enabled=True)
        eng = PagedEngine(
            params, cfg, block_size=BLOCK_SIZE, max_batch=MAX_BATCH,
            max_len=MAX_LEN, num_blocks=nblk, prefix_sharing=True,
            packed=True, telemetry=tel,
            admission=AdmissionConfig(max_queue=2 * MAX_BATCH,
                                      backpressure="shed-lowest-priority",
                                      preemption=True))
        # two warm drains: the first compiles, the second measures the
        # engine's CLOSED-LOOP capacity on this pool — which sets both the
        # 2x-overload arrival rate and a deadline the uncontended engine
        # would comfortably meet
        # capacity is measured on the SAME tiered workload the overload run
        # uses — the batch tier's long generations make it several times
        # heavier per request than the mixed workload, and calibrating on
        # the lighter mix would turn "2x capacity" into ~10x
        owarm = _overload_workload(np.random.default_rng(43), n,
                                   classes=classes)
        cap_rps = None
        for timed_pass in (False, True):
            # chunks of MAX_BATCH stay under the queue bound, so the warm
            # drains never shed work (a shed warm request would skew the
            # capacity estimate AND leave its jit shapes cold)
            work = copy.deepcopy(owarm)
            t0 = time.perf_counter()
            wdone = []
            while work:
                for r in work[:MAX_BATCH]:
                    eng.submit(r)
                work = work[MAX_BATCH:]
                wdone.extend(eng.run())
            if timed_pass:
                cap_rps = len(wdone) / (time.perf_counter() - t0)
        # the warm drains bumped the cumulative robustness counters and left
        # SLA shape: the interactive top class gets the tight deadline,
        # lower classes progressively looser ones (batch tiers tolerate
        # latency) — which also keeps low-class work ALIVE long enough for
        # the reservation gate to preempt it, instead of deadline expiry
        # acting as the only pressure valve
        deadline = 8.0 / cap_rps
        arrivals = np.cumsum(np.random.default_rng(47).exponential(
            1.0 / (2.0 * cap_rps), len(_overload_workload(
                np.random.default_rng(41), 2 * n, classes=classes))))
        # deadline misses under deliberate overload are BIMODAL on a
        # contended box: one mid-run stall (compile, GC, a scheduler
        # hiccup) and every in-flight deadline cascades, so EVERY class
        # fails ~everything and the fairness ordering carries no signal.
        # Same discipline as the multi-turn/speculative sections: retry
        # the deterministic segment (same seeds, clean engine state) and
        # keep the first run that produced signal.
        for attempt in range(3):
            # the warm drains (and a prior attempt) left a prefix-cache
            # cushion of evictable blocks (the gate prefers evicting those
            # over preempting) and bumped the cumulative robustness
            # counters; the timed segment starts clean
            eng.clear_prefix_cache()
            eng.robust_counters = RobustnessCounters()
            tel.reset()
            oreqs = _overload_workload(np.random.default_rng(41), 2 * n,
                                       classes=classes)
            # the interactive tier's deadline covers its own service time
            # plus bounded queueing (it must be MEETABLE under priority
            # protection — a deadline nobody can hit measures nothing);
            # the batch tier's is loose enough to survive being preempted
            # and resumed
            for r in oreqs:
                r.deadline_e2e = deadline * (4, 8, 16)[classes - 1
                                                       - r.priority]
            row, _ = _timed(eng,
                            lambda: drive_open_loop(eng, oreqs, arrivals))
            # the engine only returns what it finished or failed itself;
            # shed / rejected requests are marked in place, so outcomes
            # come off oreqs
            assert all(r.done or r.failed for r in oreqs), \
                "overload run left requests unaccounted"
            ttfts = {c: [] for c in range(classes)}
            for t in tel.metrics.finished:
                if t.ttft is not None:
                    ttfts[t.uid % classes].append(t.ttft)
            per_class = {}
            for c in range(classes):
                cs = [r for r in oreqs if r.priority == c]
                missed = sum((r.fail_reason or "").startswith("deadline")
                             for r in cs if r.failed)
                lost = sum(r.failed for r in cs) - missed
                p95 = percentile(ttfts[c], 95)
                per_class[str(c)] = dict(
                    submitted=len(cs), finished=sum(r.done for r in cs),
                    deadline_missed=missed, shed_or_rejected=lost,
                    deadline_miss_rate=missed / max(len(cs), 1),
                    # the fairness signal: the fraction of the class's
                    # traffic that failed its SLO for ANY reason (deadline,
                    # shed, rejected). Raw deadline-miss rate alone inverts
                    # under shed-lowest-priority — the low class gets shed
                    # before it can miss, which flatters its miss rate.
                    slo_fail_rate=(missed + lost) / max(len(cs), 1),
                    ttft_p95_ms=None if p95 is None else 1e3 * p95)
            hi = per_class[str(classes - 1)]["slo_fail_rate"]
            lo = per_class["0"]["slo_fail_rate"]
            if not (hi > 0.9 and lo > 0.7):      # produced signal: keep it
                break
            print("overload,collapse_retry,%d,hi=%.2f,lo=%.2f"
                  % (attempt, hi, lo))
        # the no-signal escape absorbs residual collapse runs (every retry
        # stalled — a box so loaded that EVERY class fails ~everything):
        # there hi and lo are both near 1 and the ordering carries no
        # signal. A genuine inversion (high class starved while the low
        # class is actually SERVED) shows hi >> lo with lo small, and
        # still fails.
        assert hi <= lo + 0.10 or (hi > 0.9 and lo > 0.7), (
            f"priority inversion under overload: class {classes - 1} failed "
            f"{hi:.0%} of its SLOs vs class 0's {lo:.0%}")
        rb = row["snapshot"]["robustness"]

        # parity sub-run: contention only (no deadlines, unbounded queue).
        # Low-class requests admit first and high-class arrivals then stall
        # the reservation gate, forcing real preemptions; every output must
        # match the uncontended reference token for token. Shared-prefix
        # traffic so the resumed victims' re-prefill rides the trie: the
        # system-prompt blocks stay live-referenced by the preempting high
        # class, hence survive the very pool pressure that evicted the
        # victims (skip rate asserted > 0 below).
        preqs = _prefix_workload(np.random.default_rng(53), n)
        ref_eng = PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                              max_batch=MAX_BATCH, max_len=MAX_LEN,
                              prefix_sharing=True, packed=True)
        for r in copy.deepcopy(preqs):
            ref_eng.submit(r)
        ref_out = {r.uid: [int(t) for t in r.out_tokens]
                   for r in ref_eng.run()}
        # a pool barely over twice one request's worst case: the high-class
        # arrivals cannot co-reside with the running low class, so the gate
        # stalls and preemption must actually fire (asserted below — a
        # parity gate over zero preemptions would be vacuous)
        peng = PagedEngine(params, cfg, block_size=BLOCK_SIZE,
                           max_batch=MAX_BATCH, max_len=MAX_LEN,
                           num_blocks=14, prefix_sharing=True, packed=True,
                           admission=AdmissionConfig(preemption=True))
        work = copy.deepcopy(preqs)
        for r in work:
            r.priority = r.uid % 2
        pdone = []
        for r in work:
            if r.priority == 0:
                peng.submit(r)
        # run the low class well into decode before the high class lands:
        # preempted mid-generation, the victims carry out_tokens as resume
        # state, so the re-prefill (and its trie skip rate) is exercised
        for _ in range(6):
            pdone.extend(peng.step())
        for r in work:
            if r.priority == 1:
                peng.submit(r)
        pdone.extend(peng.run())
        parity = (sum(ref_out[r.uid] == [int(t) for t in r.out_tokens]
                      for r in pdone) / max(len(pdone), 1))
        assert parity == 1.0, \
            f"preempted outputs diverged from uncontended run ({parity:.3f})"
        assert peng.robust_counters.preemptions > 0, \
            "parity sub-run forced no preemptions; the gate proved nothing"
        assert peng.robust_counters.reprefill_skipped > 0, \
            "resumed victims re-prefilled from scratch; trie riding broken"
        tps = row["tokens"] / row["seconds"]
        ovl_out = dict(arrival_rate=2.0 * cap_rps, capacity_rps=cap_rps,
                       requests=len(oreqs), classes=classes,
                       deadline_ms=1e3 * deadline, num_blocks=nblk,
                       tok_per_s=tps, per_class=per_class,
                       preemptions=rb["preemptions"],
                       exhaustion_events=rb["exhaustion_events"],
                       shed=rb["shed"], rejected=rb["rejected"],
                       deadline_misses=rb["deadline_misses"]["total"],
                       reprefill_skip_rate=rb["reprefill"]["skip_rate"],
                       resume_token_parity=parity,
                       parity_preemptions=(
                           peng.robust_counters.preemptions),
                       parity_reprefill_skip_rate=(
                           peng.robust_counters.snapshot()
                           ["reprefill"]["skip_rate"]), **row)
        print("\n# overload (paged+packed+sharing, %.0f req/s ~ 2x capacity, "
              "%d blocks, deadline %.0f ms): class, submitted, finished, "
              "miss_rate, slo_fail_rate, ttft_p95_ms"
              % (2.0 * cap_rps, nblk, 1e3 * deadline))
        for c in sorted(per_class, reverse=True):
            pc = per_class[c]
            print("overload,class%s,%d,%d,%.2f,%.2f,%s" % (
                c, pc["submitted"], pc["finished"], pc["deadline_miss_rate"],
                pc["slo_fail_rate"],
                "-" if pc["ttft_p95_ms"] is None
                else "%.1f" % pc["ttft_p95_ms"]))
        print("overload,totals,preempt=%d,exhaust=%d,shed=%d,misses=%d,"
              "reprefill_skip=%.2f,parity=%.2f(preempt=%d,skip=%.2f)" % (
                  rb["preemptions"], rb["exhaustion_events"], rb["shed"],
                  rb["deadline_misses"]["total"],
                  rb["reprefill"]["skip_rate"], parity,
                  peng.robust_counters.preemptions,
                  ovl_out["parity_reprefill_skip_rate"]))

    if json_path:
        with open(json_path, "w") as f:
            json.dump(dict(benchmark="serving_throughput",
                           max_batch=MAX_BATCH, max_len=MAX_LEN,
                           block_size=BLOCK_SIZE, requests=n,
                           system_prompt_len=SYSTEM_PROMPT_LEN,
                           multi_turn_sessions=MT_SESSIONS,
                           multi_turn_turns=MT_TURNS,
                           speculative_turns=SPEC_TURNS,
                           speculative_reply=SPEC_REPLY, engines=out,
                           prefill_heavy=packed_out,
                           prefix_sharing=prefix_out,
                           multi_turn=mt_out, speculative=spec_out,
                           kv_int8=kvq_out, async_loop=asy_out,
                           latency_slo=slo_out, overload=ovl_out),
                      f, indent=2)
        print(f"# wrote {json_path}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", action="append",
                    choices=["wave", "continuous", "continuous+kernel",
                             "paged", "paged+packed", "paged+kernel",
                             "paged+packed+kernel"],
                    help="engine row(s) to run (default: all but the "
                         "interpret-slow paged+packed+kernel)")
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="output path for the machine-readable results")
    ap.add_argument("--full", action="store_true",
                    help="4x larger workload")
    ap.add_argument("--arrival-rate", type=float, default=8.0, metavar="R",
                    help="open-loop Poisson arrival rate (req/s) for the "
                         "latency-SLO section (default 8)")
    args = ap.parse_args()
    if args.arrival_rate <= 0:
        ap.error(f"--arrival-rate must be > 0, got {args.arrival_rate}")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run(fast=not args.full, engines=args.engine, json_path=args.json,
        arrival_rate=args.arrival_rate)


if __name__ == "__main__":
    main()
