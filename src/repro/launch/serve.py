"""Serving launcher: batched prefill+decode, wave / continuous / paged
scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --requests 8 --new-tokens 16 --scheduler paged --decode-kernel fused

Multi-turn chat demo (each request becomes a session; follow-up turns reuse
the prior turns' KV — prompt AND generated — via decode-block sharing):

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --scheduler paged --decode-sharing --turns 4

Pipelined async loop (`--async-loop`): dispatch step N+1 while step N's
sampled tokens are still in flight — host bookkeeping commits one step
behind; greedy outputs are token-identical to the synchronous loop:

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --scheduler paged --async-loop --requests 8

Telemetry (serve/telemetry.py): `--telemetry` records request lifecycles
(TTFT/TPOT/E2E percentiles) and a per-step phase breakdown and prints the
unified snapshot; `--trace-out trace.jsonl` additionally writes the step
phases as Chrome-trace JSONL (open in Perfetto / chrome://tracing);
`--arrival-rate R` replaces the batch-drain demo with an OPEN-LOOP load
test — requests arrive on a seeded Poisson process at R req/s and latency
percentiles are measured under genuine queueing:

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --scheduler paged --arrival-rate 16 --trace-out trace.jsonl

Overload robustness (serve/admission.py; strictly opt-in): mixed priority
classes, per-request E2E deadlines, a bounded queue with backpressure, and
— on the paged engine — priority preemption by block reclaim:

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --scheduler paged --arrival-rate 32 --priority-classes 3 \
        --deadline-ms 4000 --queue-limit 8 --backpressure shed-lowest-priority

`--chaos SEED` replaces the demo with a seeded fault-injection run
(serve/chaos.py): arrival bursts, allocator exhaustion, mid-flight cancels,
preemption storms, and device-step failures, with the engine's block
-accounting invariants asserted after every step and a drain-to-empty check
at the end:

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --scheduler paged --chaos 0 --requests 24
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--scheduler", default="wave",
                    choices=["wave", "continuous", "paged"])
    ap.add_argument("--decode-kernel", default="none",
                    choices=["none", "fused", "static_max"])
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged KV block size (0 = cfg.block_size)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged KV pool size (0 = cfg.num_blocks, or "
                         "auto-size to half the dense arena)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="reuse full-block prompt-prefix KV across requests "
                         "(refcounted copy-on-write blocks; paged scheduler "
                         "only)")
    ap.add_argument("--decode-sharing", action="store_true",
                    help="additionally cache GENERATED blocks as they fill "
                         "at the decode frontier, so multi-turn sessions "
                         "(--turns) reuse prior replies' KV; implies "
                         "--prefix-sharing (paged scheduler only)")
    ap.add_argument("--turns", type=int, default=1,
                    help="multi-turn demo: serve each request as a session "
                         "of this many chat turns (every turn submits a "
                         "fresh --prompt-len user message on top of the "
                         "stored history; paged scheduler only)")
    ap.add_argument("--step-layout", default=None,
                    choices=["packed", "lockstep"],
                    help="paged step layout (default packed): 'packed' "
                         "flattens each step to a ragged token batch (rows "
                         "are tokens, zero padded decode-riding lanes); "
                         "'lockstep' keeps the (B, block_size)/(B, 1) "
                         "baseline shapes")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="store paged KV blocks as int8 with per-block "
                         "per-kv-head scales (quantize at write, dequantize "
                         "in-kernel at read; paged scheduler only)")
    ap.add_argument("--speculative", action="store_true",
                    help="trie-driven speculative decoding: draft up to "
                         "--draft-len tokens per decode step from the prefix "
                         "trie (n-gram prompt-lookup fallback) and verify "
                         "them all in ONE packed step; greedy outputs are "
                         "token-identical (paged scheduler, packed layout)")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="max draft tokens per decode step (--speculative)")
    ap.add_argument("--async-loop", action="store_true",
                    help="pipeline the paged engine's step loop: dispatch "
                         "step N+1 while step N's sampled tokens are still "
                         "in flight, committing host bookkeeping one step "
                         "behind (greedy outputs stay token-identical; "
                         "paged scheduler, packed layout)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="packed-step token lanes per chunk step "
                         "(0 = max_batch * block_size, one lockstep chunk "
                         "step's lane count)")
    ap.add_argument("--priority-classes", type=int, default=1, metavar="N",
                    help="assign demo requests round-robin to N priority/SLA "
                         "classes (0 = lowest); admission serves the highest "
                         "class first and the paged engine may PREEMPT a "
                         "lower class's blocks when a higher class would "
                         "otherwise starve (continuous/paged scheduler)")
    ap.add_argument("--deadline-ms", type=float, default=0.0, metavar="T",
                    help="per-request end-to-end deadline in milliseconds; "
                         "requests past it are failed at the next step "
                         "boundary (queued or running) with their blocks "
                         "freed (continuous/paged scheduler)")
    ap.add_argument("--queue-limit", type=int, default=0, metavar="N",
                    help="bound the ADMISSION QUEUE (not running slots) to N "
                         "requests; overflow is resolved by --backpressure "
                         "(0 = unbounded; continuous/paged scheduler)")
    ap.add_argument("--backpressure", default="reject",
                    choices=["reject", "shed-lowest-priority"],
                    help="bounded-queue overflow policy: 'reject' refuses "
                         "the incoming request (QueueFull, the HTTP-429 "
                         "analogue); 'shed-lowest-priority' drops the "
                         "lowest-class newest QUEUED request instead")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="replace the demo with a seeded fault-injection "
                         "run (serve/chaos.py): bursts, allocator "
                         "exhaustion, cancels, preemption storms, device "
                         "failures — engine invariants asserted after every "
                         "step, pool drained to empty at the end (paged "
                         "scheduler only)")
    ap.add_argument("--telemetry", action="store_true",
                    help="record request lifecycles (TTFT/TPOT/E2E "
                         "percentiles) and per-step phase timings, and print "
                         "the unified telemetry snapshot after serving")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the step-phase timeline as Chrome-trace "
                         "JSONL to PATH (load in Perfetto or "
                         "chrome://tracing); implies --telemetry")
    ap.add_argument("--arrival-rate", type=float, default=0.0, metavar="R",
                    help="serve OPEN-LOOP: requests arrive on a seeded "
                         "Poisson process at R req/s instead of being "
                         "batch-drained, so latency percentiles include real "
                         "queueing (continuous/paged scheduler, single-turn "
                         "only); implies --telemetry")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if (args.prefix_sharing or args.decode_sharing) \
            and args.scheduler != "paged":
        raise SystemExit("--prefix-sharing/--decode-sharing require "
                         "--scheduler paged (KV reuse needs the block pool)")
    if args.turns > 1 and args.scheduler != "paged":
        raise SystemExit("--turns drives the paged engine's multi-turn "
                         "session API; use --scheduler paged")
    if args.turns < 1:
        raise SystemExit(f"--turns must be >= 1, got {args.turns}")
    if args.scheduler != "paged" and (args.step_layout is not None
                                      or args.token_budget):
        raise SystemExit("--step-layout/--token-budget configure the paged "
                         "engine's packed token step; use --scheduler paged")
    if args.kv_quant != "none" and args.scheduler != "paged":
        raise SystemExit("--kv-quant quantizes the paged block pool; use "
                         "--scheduler paged")
    if args.speculative and args.scheduler != "paged":
        raise SystemExit("--speculative drafts against the paged engine's "
                         "prefix trie; use --scheduler paged")
    if args.speculative and args.step_layout == "lockstep":
        raise SystemExit("--speculative verifies all drafts in one packed "
                         "step; drop --step-layout lockstep")
    if args.async_loop and args.scheduler != "paged":
        raise SystemExit("--async-loop pipelines the paged engine's packed "
                         "token step; use --scheduler paged")
    if args.async_loop and args.step_layout == "lockstep":
        raise SystemExit("--async-loop pipelines the packed token step; "
                         "drop --step-layout lockstep")
    if args.arrival_rate < 0:
        raise SystemExit(f"--arrival-rate must be >= 0, got "
                         f"{args.arrival_rate}")
    if args.arrival_rate and args.scheduler == "wave":
        raise SystemExit("--arrival-rate drives the step-at-a-time engines; "
                         "the wave scheduler serves whole waves (use "
                         "--scheduler continuous or paged)")
    if args.arrival_rate and args.turns > 1:
        raise SystemExit("--arrival-rate is a single-turn open-loop load "
                         "test; drop --turns")
    if args.priority_classes < 1:
        raise SystemExit(f"--priority-classes must be >= 1, got "
                         f"{args.priority_classes}")
    if args.deadline_ms < 0:
        raise SystemExit(f"--deadline-ms must be >= 0, got "
                         f"{args.deadline_ms}")
    if args.queue_limit < 0:
        raise SystemExit(f"--queue-limit must be >= 0, got "
                         f"{args.queue_limit}")
    robust_on = bool(args.priority_classes > 1 or args.deadline_ms
                     or args.queue_limit or args.chaos is not None)
    if robust_on and args.scheduler == "wave":
        raise SystemExit("--priority-classes/--deadline-ms/--queue-limit/"
                         "--chaos drive the step-at-a-time admission layer; "
                         "use --scheduler continuous or paged")
    if args.chaos is not None and args.scheduler != "paged":
        raise SystemExit("--chaos injects faults into the paged block pool; "
                         "use --scheduler paged")
    if args.chaos is not None and (args.turns > 1 or args.arrival_rate):
        raise SystemExit("--chaos drives its own submission schedule; drop "
                         "--turns/--arrival-rate")
    telemetry_on = bool(args.telemetry or args.trace_out
                        or args.arrival_rate)

    import jax
    import numpy as np

    from repro.configs import get_config, reduced_config
    from repro.models import model as M
    from repro.serve import (AdmissionConfig, ChaosMonkey, ContinuousEngine,
                             PagedEngine, QueueFull, Request, ServeEngine,
                             Telemetry, drive_open_loop, format_snapshot)

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.decode_kernel != "none":
        # the engine constructor warns (once, with the blocking reason) when
        # the kernel cannot take effect — see warn_decode_kernel_fallback
        cfg = cfg.replace(decode_kernel=args.decode_kernel)
    if cfg.input_mode == "embeddings":
        raise SystemExit(f"{args.arch} takes embedding inputs; the serve demo "
                         "targets token models (see examples/serving.py)")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # a session's history grows every turn: the cache must hold all of them
    max_len = args.turns * (args.prompt_len + args.new_tokens) + 1
    tel = Telemetry(enabled=telemetry_on)
    # the robustness layer is strictly opt-in: admission=None keeps the
    # engines on the exact legacy fail-fast FIFO path (preemption only
    # exists on the paged engine's block pool; continuous ignores it)
    admission = None
    if robust_on:
        admission = AdmissionConfig(max_queue=args.queue_limit or None,
                                    backpressure=args.backpressure,
                                    preemption=(args.scheduler == "paged"))
    if args.scheduler == "paged":
        cfg = cfg.replace(cache_layout="paged",
                          prefix_sharing=args.prefix_sharing,
                          decode_sharing=args.decode_sharing,
                          kv_quant=args.kv_quant)
        eng = PagedEngine(params, cfg, max_batch=args.max_batch,
                          max_len=max_len,
                          block_size=args.block_size or None,
                          num_blocks=args.num_blocks or None,
                          packed=(args.step_layout != "lockstep"),
                          token_budget=args.token_budget or None,
                          speculative=args.speculative,
                          draft_len=args.draft_len,
                          async_loop=args.async_loop,
                          telemetry=tel, admission=admission)
    else:
        engine_cls = (ContinuousEngine if args.scheduler == "continuous"
                      else ServeEngine)
        kw = {} if args.scheduler == "wave" else dict(admission=admission)
        eng = engine_cls(params, cfg, max_batch=args.max_batch,
                         max_len=max_len, telemetry=tel, **kw)
    rng = np.random.default_rng(0)
    # with --prefix-sharing the single-turn demo traffic shares a system-
    # prompt-style prefix (~3/4 of the prompt, rounded DOWN to the block
    # size: sharing is block-granular, so a sub-block prefix can never hit —
    # pass a smaller --block-size if the default swallows the whole prompt).
    # The --turns demo gets its reuse from the session histories instead, so
    # its per-turn messages are fully random.
    shared_len = 0
    if args.prefix_sharing and args.turns == 1:
        bs = args.block_size or cfg.block_size
        shared_len = 3 * args.prompt_len // 4 // bs * bs
        if shared_len == 0:
            print(f"note: prompt-len {args.prompt_len} is under one KV block "
                  f"({bs} tokens); prefix sharing cannot hit — lower "
                  f"--block-size or raise --prompt-len")
    shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)

    def robust_kw(i):
        """Per-request robustness fields for demo request i (empty when the
        layer is off, so Request construction is unchanged)."""
        kw = {}
        if args.priority_classes > 1:
            kw["priority"] = int(i % args.priority_classes)
        if args.deadline_ms:
            kw["deadline_e2e"] = args.deadline_ms / 1000.0
        return kw

    if args.chaos is not None:
        crng = np.random.default_rng(args.chaos)

        def mk(i):
            plen = int(crng.integers(4, args.prompt_len + 1))
            return Request(
                uid=i,
                prompt=crng.integers(0, cfg.vocab_size,
                                     plen).astype(np.int32),
                max_new_tokens=int(crng.integers(2, args.new_tokens + 1)),
                **robust_kw(i))

        t0 = time.perf_counter()
        report = ChaosMonkey(eng, seed=args.chaos, make_request=mk,
                             n_requests=args.requests).run()
        dt = time.perf_counter() - t0
        done = report["finished"] + report["failed"]
        faults = ", ".join(f"{k} x{v}"
                           for k, v in sorted(report["faults"].items()))
        print(f"chaos(seed={args.chaos}): survived {report['steps']} steps "
              f"in {dt:.2f}s — {report['submitted']} submitted, "
              f"{len(report['finished'])} finished, "
              f"{len(report['failed'])} failed; "
              f"faults: {faults or 'none injected'}")
        print("invariants held after every step; pool fully reclaimed")
    elif args.turns > 1:
        # multi-turn demo: each "request" is a chat session; every turn
        # submits a fresh user message on top of the engine-stored history,
        # so with --decode-sharing the follow-up turns prefix-match prior
        # prompts AND replies instead of re-prefilling them
        t0 = time.perf_counter()
        done = []
        for turn in range(args.turns):
            for i in range(args.requests):
                msg = rng.integers(0, cfg.vocab_size,
                                   args.prompt_len).astype(np.int32)
                try:
                    eng.submit(Request(uid=args.requests * turn + i,
                                       prompt=msg,
                                       max_new_tokens=args.new_tokens,
                                       **robust_kw(i)),
                               session=f"session-{i}")
                except QueueFull:
                    pass    # rejected turn: the session stays reusable
            done.extend(eng.run())
        dt = time.perf_counter() - t0
        total_new = sum(len(r.out_tokens) for r in done)
        print(f"served {args.requests} sessions x {args.turns} turns, "
              f"{total_new} tokens in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    elif args.arrival_rate:
        # open-loop load test: arrivals come from a seeded Poisson process
        # and do NOT wait for the system, so queueing shows up in TTFT.
        # Warm the jit caches with one drained request first — otherwise
        # compile time masquerades as the head of the latency distribution.
        warm = rng.integers(0, cfg.vocab_size,
                            args.prompt_len).astype(np.int32)
        eng.submit(Request(uid=-1, prompt=warm, max_new_tokens=2))
        eng.run()
        tel.reset()
        reqs = []
        for i in range(args.requests):
            tail = rng.integers(0, cfg.vocab_size,
                                args.prompt_len - shared_len).astype(np.int32)
            reqs.append(Request(uid=i, prompt=np.concatenate([shared, tail]),
                                max_new_tokens=args.new_tokens,
                                **robust_kw(i)))
        arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                             args.requests))
        t0 = time.perf_counter()
        done = drive_open_loop(eng, reqs, arrivals)
        dt = time.perf_counter() - t0
        total_new = sum(len(r.out_tokens) for r in done)
        print(f"served {len(done)} requests open-loop at "
              f"{args.arrival_rate:g} req/s, {total_new} tokens in {dt:.2f}s "
              f"({total_new / dt:.1f} tok/s)")
    else:
        for i in range(args.requests):
            tail = rng.integers(0, cfg.vocab_size,
                                args.prompt_len - shared_len).astype(np.int32)
            try:
                eng.submit(Request(uid=i,
                                   prompt=np.concatenate([shared, tail]),
                                   max_new_tokens=args.new_tokens,
                                   **robust_kw(i)))
            except QueueFull:
                pass        # counted in robust_counters.rejected below
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        total_new = sum(len(r.out_tokens) for r in done)
        print(f"served {len(done)} requests, {total_new} tokens in {dt:.2f}s "
              f"({total_new / dt:.1f} tok/s)")
    if robust_on:
        rb = eng.robust_counters.snapshot()
        dm, rp = rb["deadline_misses"], rb["reprefill"]
        print(f"robustness: {rb['preemptions']} preemptions "
              f"({rb['exhaustion_events']} pool-exhaustion reclaims), "
              f"{rb['shed']} shed, {rb['rejected']} rejected, "
              f"{rb['cancelled']} cancelled, {dm['total']} deadline misses "
              f"(ttft {dm['ttft']}, e2e {dm['e2e']}), re-prefill "
              f"{rp['skipped']}/{rp['tokens']} tokens skipped")
        if args.priority_classes > 1:
            for p, c in sorted(rb["per_class"].items(),
                               key=lambda kv: -int(kv[0])):
                print(f"  class {p}: {c['submitted']} submitted, "
                      f"{c['finished']} finished, {c['preempted']} preempted, "
                      f"{c['deadline_misses']} deadline misses, "
                      f"{c['shed'] + c['rejected']} shed/rejected")
    cache = getattr(eng, "_cache", None)
    if cache is not None:
        # logical vs padded: with the decode kernel active the arena is
        # lane-padded, so the allocation can be 4x the logical cache
        from repro.serve import kv_cache_byte_stats
        cb = kv_cache_byte_stats(
            cache, cfg, None if args.scheduler == "paged" else max_len)
        print(f"kv cache: {cb['cache_bytes_logical'] / 2**20:.2f} MB logical, "
              f"{cb['cache_bytes_padded'] / 2**20:.2f} MB allocated")
    if args.scheduler == "paged":
        pad = eng.padding_stats()
        print(f"step padding: {pad['lanes_valid']}/{pad['lanes_total']} "
              f"token-lanes valid ({100 * pad['efficiency']:.0f}%), "
              f"{pad['pad_lanes_skipped']} lanes skipped by packing")
    if args.speculative:
        s = eng.prefix_stats()
        rate = s["acceptance_rate"]
        print(f"speculative: {s['tokens_drafted']} drafted, "
              f"{s['tokens_accepted']} accepted, "
              f"{s['tokens_rejected']} rejected "
              f"({'n/a' if rate is None else f'{100 * rate:.0f}%'} "
              f"acceptance) over {s['spec_steps']} verify steps, "
              f"{s['spec_rollbacks']} rollbacks")
    if args.prefix_sharing or args.decode_sharing:
        s = eng.prefix_stats()
        # the two prefill savings side by side: prefix sharing skips real
        # prompt tokens, packing skips padded token-lanes — with the skip
        # split by matched-block origin (prompt-cached vs decode-cached)
        print(f"prefix sharing: {s['hits']}/{s['lookups']} hits "
              f"({s['prompt_hits']} prompt-block, {s['decode_hits']} "
              f"decode-block), "
              f"{s['prefill_tokens_skipped']}/{s['prefill_tokens']} prefill "
              f"tokens skipped by prefix ({100 * s['skip_rate']:.0f}%: "
              f"{s['prompt_tokens_skipped']} prompt + "
              f"{s['decode_tokens_skipped']} decode) vs "
              f"{s['pad_lanes_skipped']} token-lanes skipped by packing, "
              f"{s['cow_copies']} COW copies, {s['evictions']} evictions, "
              f"{s['cached_blocks']} blocks cached "
              f"({s['cached_decode_blocks']} from decode)")
        if args.turns > 1:
            print(f"sessions: {100 * s['followup_skip_rate']:.0f}% of "
                  f"follow-up-turn prefill tokens "
                  f"({s['followup_tokens_skipped']}/"
                  f"{s['followup_prefill_tokens']}) served from cached KV")
    if telemetry_on:
        print(format_snapshot(eng.snapshot()))
    if args.trace_out:
        n = tel.profiler.write_chrome_trace(args.trace_out)
        print(f"wrote {n} trace events to {args.trace_out} "
              f"(load in Perfetto / chrome://tracing)")


if __name__ == "__main__":
    main()
