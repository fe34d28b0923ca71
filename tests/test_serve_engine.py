"""Continuous-batching engine behavior: slot reuse, mid-flight admission,
wave-vs-continuous greedy parity, finished-slot cache isolation, the fused
decode-kernel dispatch, paged-KV (block pool) parity + memory bounds, and
packed-token-step parity (token-centric chunked prefill)."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.serve import (BlockAllocator, BlockPoolExhausted, ContinuousEngine,
                         PagedEngine, Request, ServeEngine, kv_cache_bytes)


@pytest.fixture
def served(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(rng, n, lens=(5, 9, 13), max_new=6):
    return [Request(uid=i,
                    prompt=rng.integers(0, 256, int(rng.choice(lens))).astype(
                        np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def test_per_slot_cache_layout(tiny_cfg):
    cfg = tiny_cfg()
    c = M.init_cache(cfg, 4, 32, per_slot_lengths=True)
    assert c["length"].shape == (4,)
    assert c["layers"]["k"].shape == (cfg.num_layers, 4, cfg.num_kv_heads,
                                      32, cfg.head_dim)


def test_wave_vs_continuous_greedy_parity(served, rng):
    """Identical request sets must produce identical greedy outputs under
    both schedulers — scheduling must never change what is generated.
    Includes a max_new_tokens=1 request (budget consumed by the
    prefill-sampled token) batched with longer ones."""
    cfg, params = served
    reqs = _requests(rng, 6)
    reqs[2].max_new_tokens = 1
    reqs[4].max_new_tokens = 3
    wave = ServeEngine(params, cfg, max_batch=4, max_len=64)
    cont = ContinuousEngine(params, cfg, max_batch=4, max_len=64)
    rw, rc = copy.deepcopy(reqs), copy.deepcopy(reqs)
    for r in rw:
        wave.submit(r)
    for r in rc:
        cont.submit(r)
    got_w = {r.uid: r.out_tokens for r in wave.run()}
    got_c = {r.uid: r.out_tokens for r in cont.run()}
    assert got_w == got_c
    assert len(got_w[reqs[2].uid]) == 1


def test_wave_vs_continuous_parity_with_eos(served, rng):
    """EOS on the very first (prefill-sampled) token must stop BOTH
    schedulers at one token — the wave engine used to keep decoding."""
    cfg, params = served
    reqs = _requests(rng, 4, max_new=8)
    probe = ContinuousEngine(params, cfg, max_batch=4, max_len=64)
    pr = copy.deepcopy(reqs)
    for r in pr:
        probe.submit(r)
    eos = probe.run()[0].out_tokens[0]       # a token some request emits first
    wave = ServeEngine(params, cfg, max_batch=4, max_len=64, eos_id=eos)
    cont = ContinuousEngine(params, cfg, max_batch=4, max_len=64, eos_id=eos)
    rw, rc = copy.deepcopy(reqs), copy.deepcopy(reqs)
    for r in rw:
        wave.submit(r)
    for r in rc:
        cont.submit(r)
    got_w = {r.uid: r.out_tokens for r in wave.run()}
    got_c = {r.uid: r.out_tokens for r in cont.run()}
    assert got_w == got_c
    assert any(toks == [eos] for toks in got_w.values())


def test_continuous_matches_isolated_decode(served, rng):
    """Each request's output in a mixed, oversubscribed batch must equal its
    output when served completely alone (slot interference would break this)."""
    cfg, params = served
    reqs = _requests(rng, 5, lens=(4, 7, 11, 15), max_new=5)
    eng = ContinuousEngine(params, cfg, max_batch=2, max_len=64)
    batch = copy.deepcopy(reqs)
    for r in batch:
        eng.submit(r)
    got = {r.uid: r.out_tokens for r in eng.run()}
    for req in reqs:
        solo = ContinuousEngine(params, cfg, max_batch=2, max_len=64)
        r = copy.deepcopy(req)
        solo.submit(r)
        (done,) = solo.run()
        assert got[req.uid] == done.out_tokens, req.uid


def test_slot_reuse_after_eos(served, rng):
    """A slot freed by EOS admits the next queued request; everyone finishes."""
    cfg, params = served
    reqs = _requests(rng, 4, max_new=8)
    # find a token each request actually generates, then use the most common
    # first token as EOS so some requests terminate early
    probe = ContinuousEngine(params, cfg, max_batch=4, max_len=64)
    pr = copy.deepcopy(reqs)
    for r in pr:
        probe.submit(r)
    first_toks = [r.out_tokens[0] for r in probe.run()]
    eos = first_toks[0]

    eng = ContinuousEngine(params, cfg, max_batch=2, max_len=64, eos_id=eos)
    rs = copy.deepcopy(reqs)
    for r in rs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 4
    assert all(r.done for r in done)
    assert not eng._live.any() and not eng._queue
    for r in done:
        # EOS terminates the slot at the EOS token
        if eos in r.out_tokens:
            assert r.out_tokens[-1] == eos
            assert eos not in r.out_tokens[:-1]


def test_admission_mid_flight(served, rng):
    """With capacity 2 and 4 requests of unequal output lengths, later
    requests are admitted while earlier ones are still decoding."""
    cfg, params = served
    eng = ContinuousEngine(params, cfg, max_batch=2, max_len=64)
    lens = [(4, 12), (9, 3), (6, 9), (13, 4)]        # (prompt, max_new)
    for i, (pl, mn) in enumerate(lens):
        eng.submit(Request(uid=i, prompt=rng.integers(0, 256, pl).astype(
            np.int32), max_new_tokens=mn))
    occupancy = []
    finished = []
    while eng._queue or eng._live.any():
        finished.extend(eng._admit())
        occupancy.append(int(eng._live.sum()))
        if eng._live.any():
            finished.extend(eng._step())
    assert len(finished) == 4
    assert [len(r.out_tokens) for r in sorted(finished, key=lambda r: r.uid)] \
        == [12, 3, 9, 4]
    # the batch was full on (nearly) every step — requests 2/3 were admitted
    # into slots freed mid-flight, not after a wave drained
    assert max(occupancy) == 2
    assert occupancy.count(2) > len(occupancy) - 3


def test_finished_slot_cache_isolated(served, rng):
    """Regression: poisoning a finished slot's arena KV must not perturb any
    live slot's output (per-slot length masking + batch-axis independence)."""
    cfg, params = served

    def run(poison: bool):
        eng = ContinuousEngine(params, cfg, max_batch=2, max_len=64)
        eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32) + 3,
                           max_new_tokens=2))       # finishes early -> slot 0
        eng.submit(Request(uid=1, prompt=np.arange(7, dtype=np.int32) + 40,
                           max_new_tokens=10))
        finished = []
        poisoned = False
        while eng._queue or eng._live.any():
            finished.extend(eng._admit())
            if poison and not poisoned and not eng._live[0]:
                layers = eng._cache["layers"]
                layers = dict(layers,
                              k=layers["k"].at[:, 0].set(1e6),
                              v=layers["v"].at[:, 0].set(-1e6))
                eng._cache = dict(eng._cache, layers=layers)
                poisoned = True
            if eng._live.any():
                finished.extend(eng._step())
        assert not poison or poisoned    # slot 0 did finish first
        return {r.uid: r.out_tokens for r in finished}

    assert run(poison=False) == run(poison=True)


@pytest.mark.parametrize("mode", ["i16_div", "wide", "i8_div"])
def test_decode_kernel_engine_parity(tiny_cfg, rng, mode, make_engine):
    """The fused decode-kernel dispatch generates the same greedy tokens as
    the XLA STE decode path — for BOTH cache layouts (run with
    ``--cache-layout paged`` to drive hccs_packed_prefill instead of
    hccs_decode). For i8 modes the dispatch must fall back to the XLA path
    (the kernel cannot reproduce per-element i8 truncation), so parity there
    is trivially exact — the test guards against silent remapping."""
    base = dict(attention_prob="hccs", hccs_mode=mode)
    cfg0 = tiny_cfg(**base)
    cfgk = tiny_cfg(**base, decode_kernel="fused")
    params = M.init_params(jax.random.PRNGKey(0), cfg0)
    reqs = _requests(rng, 4)
    outs = []
    for cfg in (cfg0, cfgk):
        eng = make_engine(params, cfg, max_batch=4, max_len=64)
        rs = copy.deepcopy(reqs)
        for r in rs:
            eng.submit(r)
        outs.append({r.uid: r.out_tokens for r in eng.run()})
    assert outs[0] == outs[1]


def test_paged_vs_continuous_parity_and_memory(served, rng):
    """Acceptance: the paged engine produces greedy outputs token-identical
    to the continuous engine on a mixed-length workload, while its block
    pool allocates <= 50% of the dense slot-arena KV bytes at equal
    max_batch / max_len."""
    cfg, params = served
    reqs = _requests(rng, 8, lens=(4, 7, 11, 15, 21), max_new=6)
    reqs[1].max_new_tokens = 1           # budget consumed at prefill end
    reqs[5].max_new_tokens = 12
    cont = ContinuousEngine(params, cfg, max_batch=4, max_len=64)
    paged = PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16)
    rc, rp = copy.deepcopy(reqs), copy.deepcopy(reqs)
    for r in rc:
        cont.submit(r)
    for r in rp:
        paged.submit(r)
    got_c = {r.uid: r.out_tokens for r in cont.run()}
    got_p = {r.uid: r.out_tokens for r in paged.run()}
    assert got_c == got_p
    assert len(got_p[reqs[1].uid]) == 1
    assert kv_cache_bytes(paged._cache) <= 0.5 * kv_cache_bytes(cont._cache)
    # free-at-EOS: the whole pool is back on the free list after the run
    assert paged.alloc.num_free == paged.num_blocks - 1
    assert (paged._tables == -1).all()


def test_paged_matches_isolated_decode(served, rng):
    """Chunked prefill + block-table attention must be slot-interference-free:
    each request's output in an oversubscribed paged batch equals its output
    served alone (cf. the continuous-engine version of this test)."""
    cfg, params = served
    reqs = _requests(rng, 5, lens=(4, 7, 11, 15), max_new=5)
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16)
    batch = copy.deepcopy(reqs)
    for r in batch:
        eng.submit(r)
    got = {r.uid: r.out_tokens for r in eng.run()}
    for req in reqs:
        solo = PagedEngine(params, cfg, max_batch=2, max_len=64,
                           block_size=16)
        r = copy.deepcopy(req)
        solo.submit(r)
        (done,) = solo.run()
        assert got[req.uid] == done.out_tokens, req.uid


def test_paged_chunked_prefill_spans_blocks(served, rng):
    """A prompt much longer than block_size is fed in multiple chunks and
    still matches the continuous engine (which prefills it in one call)."""
    cfg, params = served
    prompt = rng.integers(0, 256, 41).astype(np.int32)   # 3 chunks of 16
    outs = []
    for make in (lambda: ContinuousEngine(params, cfg, max_batch=2,
                                          max_len=64),
                 lambda: PagedEngine(params, cfg, max_batch=2, max_len=64,
                                     block_size=16)):
        eng = make()
        eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=6))
        (done,) = eng.run()
        outs.append(done.out_tokens)
    assert outs[0] == outs[1]


def test_paged_allocator_exhaustion_and_admission_gate(served, rng):
    """Direct allocator exhaustion raises before corruption, and the engine's
    reservation-gated admission never over-commits the pool: with a pool too
    small for two full requests, they are served back-to-back, correctly."""
    cfg, params = served
    alloc = BlockAllocator(3)
    a, b = alloc.alloc(), alloc.alloc()
    assert {a, b} == {1, 2}
    with pytest.raises(BlockPoolExhausted):
        alloc.alloc()
    # deterministic refcount coverage for bare (no-hypothesis) environments:
    # a forked block needs BOTH references dropped before it is free again
    assert alloc.fork(a) == a and alloc.ref(a) == 2
    alloc.free([a])
    assert alloc.ref(a) == 1 and alloc.num_free == 0
    alloc.free([a, b])
    # pool of 4 usable blocks; each request needs ceil((13+6)/8) = 3
    eng = PagedEngine(params, cfg, max_batch=2, max_len=32, block_size=8,
                      num_blocks=5)
    reqs = _requests(rng, 2, lens=(13,), max_new=6)
    for r in copy.deepcopy(reqs):
        eng.submit(r)
    done = eng.run()
    assert len(done) == 2 and all(r.done for r in done)
    assert eng.alloc.num_free == 4
    # a request that can never fit the pool is rejected at submit: 2 usable
    # blocks but ceil((17 + 5) / 8) = 3 needed
    small = PagedEngine(params, cfg, max_batch=2, max_len=32, block_size=8,
                        num_blocks=3)
    with pytest.raises(ValueError):
        small.submit(Request(uid=9, prompt=rng.integers(0, 256, 17).astype(
            np.int32), max_new_tokens=5))


def test_prefix_sharing_cow_and_shared_kv_immutable(served, rng):
    """COW regression (cache-poisoning analog of the finished-slot test):
    requests sharing a prompt prefix, then diverging, produce greedy outputs
    token-identical to a prefix_sharing=off run — and the shared blocks' KV
    bytes are bit-unchanged after every request finished, even though one
    request (the full-prompt hit) had to WRITE inside the shared range and
    was copy-on-write'd onto a fresh block."""
    cfg, params = served
    shared = rng.integers(0, 256, 32).astype(np.int32)   # 2 full 16-blocks
    prompts = [
        np.concatenate([shared, rng.integers(0, 256, 7).astype(np.int32)]),
        np.concatenate([shared, rng.integers(0, 256, 11).astype(np.int32)]),
        shared.copy(),   # full-prompt hit: re-fed last token triggers COW
    ]

    def serve(sharing):
        eng = PagedEngine(params, cfg, max_batch=1, max_len=64, block_size=16,
                          prefix_sharing=sharing)
        outs, snap, blocks = {}, None, None
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p.copy(), max_new_tokens=5))
            (done,) = eng.run()
            outs[i] = done.out_tokens
            if sharing and i == 0:
                # request 0's two full prefix blocks are now cached; snapshot
                # their pool KV bytes before anyone reuses them
                blocks = [blk for _, blk in eng._match_prefix(shared)]
                assert len(blocks) == 2
                snap = (np.asarray(eng._cache["layers"]["k"][:, blocks]),
                        np.asarray(eng._cache["layers"]["v"][:, blocks]))
        if sharing:
            s = eng.prefix_stats()
            assert s["hits"] == 2 and s["lookups"] == 3
            assert s["cow_copies"] == 1        # only the full-prompt hit
            # request 1 skipped the full 32-token prefix; request 2 matched
            # everything but must re-feed its last token: 32 + 31
            assert s["prefill_tokens_skipped"] == 63
            after = (np.asarray(eng._cache["layers"]["k"][:, blocks]),
                     np.asarray(eng._cache["layers"]["v"][:, blocks]))
            np.testing.assert_array_equal(snap[0], after[0])
            np.testing.assert_array_equal(snap[1], after[1])
            # dropping the index references drains the pool completely
            eng.clear_prefix_cache()
            assert eng.alloc.num_free == eng.num_blocks - 1
        return outs

    assert serve(False) == serve(True)


def test_prefix_sharing_skip_rate_and_parity(served, rng):
    """Acceptance: a shared-system-prompt workload (every request starts with
    the same 48-token prefix) skips >= 30% of prefill tokens while producing
    greedy outputs token-identical to prefix_sharing=off."""
    cfg, params = served
    system = rng.integers(0, 256, 48).astype(np.int32)
    reqs = [Request(uid=i,
                    prompt=np.concatenate([system, rng.integers(
                        0, 256, int(rng.integers(3, 12))).astype(np.int32)]),
                    max_new_tokens=4)
            for i in range(8)]
    outs = {}
    for sharing in (False, True):
        eng = PagedEngine(params, cfg, max_batch=2, max_len=96, block_size=16,
                          prefix_sharing=sharing)
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        outs[sharing] = {r.uid: r.out_tokens for r in eng.run()}
        if sharing:
            s = eng.prefix_stats()
            assert s["skip_rate"] >= 0.30, s
            # every request admitted after the first prefill completed hits
            assert s["hits"] >= 6
            assert s["prefill_tokens_skipped"] >= 6 * 48
    assert outs[False] == outs[True]


def test_prefix_sharing_eviction_under_pool_pressure(served, rng):
    """Distinct prompts churning a tiny pool force LRU eviction of cached
    (index-only) blocks; the run still completes and never deadlocks."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16,
                      num_blocks=6, prefix_sharing=True)
    for i in range(6):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(0, 256, 35).astype(np.int32),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 6 and all(r.done for r in done)
    assert eng.prefix_stats()["evictions"] > 0
    # the index never points at a freed block
    for blk in eng.trie.blocks():
        assert eng.alloc.ref(blk) >= 1


@pytest.mark.parametrize("async_loop", [False, True])
@pytest.mark.parametrize("sharing", [False, True])
def test_multi_turn_session_parity(served, rng, sharing, async_loop):
    """Acceptance: session-continued greedy outputs are token-identical to
    re-feeding the full concatenated history from scratch — with
    decode-block sharing both OFF and ON, and with the pipelined async loop
    both OFF and ON. The reference engine never uses sessions or sharing:
    each turn it is fed the manually concatenated history (prior prompts +
    generated replies) as a plain prompt, so any divergence in the session
    bookkeeping, decode-block trie reuse, or COW path shows up as a token
    mismatch."""
    cfg, params = served
    n_sessions, turns = 2, 3
    # turn-1 geometry crosses a block boundary DURING decode (25 prompt + 7
    # written replies = KV frontier 32), so a generated block is cached and
    # follow-up turns exercise decode-block hits, not just prompt ones
    msgs = [[rng.integers(0, 256, int(n)).astype(np.int32)
             for n in (25, 7, 22)] for _ in range(n_sessions)]
    sess = PagedEngine(params, cfg, max_batch=2, max_len=128, block_size=16,
                       prefix_sharing=sharing, decode_sharing=sharing,
                       async_loop=async_loop)
    ref = PagedEngine(params, cfg, max_batch=2, max_len=128, block_size=16)
    hist = [np.zeros(0, np.int32)] * n_sessions
    for turn in range(turns):
        srun, rrun = [], []
        for s in range(n_sessions):
            sreq = Request(uid=s, prompt=msgs[s][turn].copy(),
                           max_new_tokens=8)
            sess.submit(sreq, session=f"chat{s}")
            srun.append(sreq)
            full = np.concatenate([hist[s], msgs[s][turn]])
            rreq = Request(uid=s, prompt=full, max_new_tokens=8)
            ref.submit(rreq)
            rrun.append(rreq)
        sess.run()
        ref.run()
        for s in range(n_sessions):
            assert srun[s].out_tokens == rrun[s].out_tokens, (turn, s)
            hist[s] = np.concatenate(
                [hist[s], msgs[s][turn],
                 np.asarray(rrun[s].out_tokens, np.int32)])
            # the engine's stored history equals the manual concatenation
            np.testing.assert_array_equal(sess.session_history(f"chat{s}"),
                                          hist[s])
    if sharing:
        s = sess.prefix_stats()
        # follow-up turns matched prior turns' blocks, generated ones
        # included, and split counters add up
        assert s["decode_hits"] > 0 and s["cached_decode_blocks"] > 0
        assert s["followup_tokens_skipped"] > 0
        assert (s["prompt_tokens_skipped"] + s["decode_tokens_skipped"]
                == s["prefill_tokens_skipped"])
    # sessions ended + cache cleared -> the pool fully drains
    for s in range(n_sessions):
        sess.end_session(f"chat{s}")
    sess.clear_prefix_cache()
    assert sess.alloc.num_free == sess.num_blocks - 1


def test_session_bookkeeping_guards(served, rng):
    """A session admits one turn at a time, histories are per-session, and
    end_session forgets the history (the next turn starts fresh)."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16,
                      decode_sharing=True)
    assert eng.prefix_sharing            # decode sharing implies the trie
    r0 = Request(uid=0, prompt=rng.integers(0, 256, 9).astype(np.int32),
                 max_new_tokens=4)
    eng.submit(r0, session="a")
    with pytest.raises(ValueError):      # turn 2 before turn 1 finished
        eng.submit(Request(uid=1, prompt=r0.prompt.copy()), session="a")
    eng.run()
    assert len(eng.session_history("a")) == len(r0.prompt) + 4
    assert eng.session_history("missing") is None
    eng.end_session("a")
    assert eng.session_history("a") is None
    # a fresh turn on the forgotten session is NOT a follow-up
    r1 = Request(uid=2, prompt=rng.integers(0, 256, 9).astype(np.int32),
                 max_new_tokens=4)
    eng.submit(r1, session="a")
    eng.run()
    assert len(eng.session_history("a")) == len(r1.prompt) + 4


def test_decode_block_churn_refcounts_and_drain(served, rng):
    """Pool hygiene under decode-block churn WITH eviction pressure (the
    PR-3 drain test extended to generated blocks): multi-turn sessions on a
    tiny pool force LRU eviction of cached blocks while decode-frontier
    registration keeps inserting new ones. Stepping the engine manually,
    every step must satisfy: allocator conservation (free + unique-live
    partitions the pool), the trie never points at a freed block, and every
    in-flight writer's table blocks stay referenced (in-flight-writer
    protection: a registered-while-decoding block has ref >= 2, so eviction
    can never reclaim it). Afterwards the pool fully drains."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      num_blocks=12, prefix_sharing=True, decode_sharing=True)

    def run_checked(engine):
        while engine._queue or engine._live.any():
            engine._admit()
            engine._step_packed()
            assert (engine.alloc.num_free + engine.alloc.num_live
                    == engine.num_blocks - 1)
            for blk in engine.trie.blocks():
                assert engine.alloc.ref(blk) >= 1
            for slot in np.flatnonzero(engine._live):
                row = engine._tables[slot]
                for blk in row[row >= 0]:
                    assert engine.alloc.ref(int(blk)) >= 1

    for i in range(4):                   # 4 sessions x 2 turns, distinct
        for turn in range(2):
            eng.submit(Request(
                uid=10 * i + turn,
                prompt=rng.integers(0, 256, 21).astype(np.int32),
                max_new_tokens=8), session=f"s{i}")
            run_checked(eng)
    s = eng.prefix_stats()
    assert s["evictions"] > 0            # the tiny pool did churn
    assert s["cached_decode_blocks"] > 0 or s["decode_hits"] > 0
    # all sessions finished: only trie references remain; dropping them
    # drains the pool completely — no leaked refcounts anywhere
    eng.clear_prefix_cache()
    assert eng.alloc.num_free == eng.num_blocks - 1
    assert eng.alloc.num_live == 0
    assert (eng._tables == -1).all()


def test_robust_block_churn_random_interleavings(served, rng):
    """The churn test above under adversarial scheduling: a ROBUST engine
    (priorities, deadlines on a fake clock, preemption) stepped manually
    while a seeded adversary interleaves preemptions, cancellations, clock
    jumps (deadline expiry) and late submissions between steps. After
    EVERY step the chaos invariant checker must hold; afterwards every
    request is terminal (done or failed) and the pool fully drains."""
    from repro.serve import AdmissionConfig, assert_drained, check_invariants
    cfg, params = served
    fake = [0.0]
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      num_blocks=12, prefix_sharing=True, decode_sharing=True,
                      admission=AdmissionConfig(preemption=True,
                                                clock=lambda: fake[0]))
    reqs = []
    for i in range(12):
        r = Request(uid=i,
                    prompt=rng.integers(0, 256,
                                        int(rng.choice([9, 13, 21]))).astype(
                        np.int32),
                    max_new_tokens=int(rng.choice([4, 8])),
                    priority=int(rng.integers(0, 3)))
        if i % 4 == 0:                   # some SLAs tight enough to expire
            r.deadline_e2e = 4.0         # on a clock-jump fault, some not
        if i % 4 == 2:
            r.deadline_ttft = 30.0
        reqs.append(r)
    i = steps = 0
    while i < len(reqs) or eng.busy:
        if i < len(reqs) and rng.random() < 0.6:
            eng.submit(reqs[i])
            i += 1
        act = rng.random()
        if act < 0.15:                   # preemption storm
            live = np.flatnonzero(eng._live)
            if len(live):
                eng._preempt_slot(int(rng.choice(live)))
        elif act < 0.30:                 # cancel a random uid (hit or miss)
            eng.cancel(int(rng.integers(0, len(reqs))))
        elif act < 0.40:                 # clock jump: deadlines expire
            fake[0] += 3.0
        eng.step()
        fake[0] += 0.1
        check_invariants(eng)
        steps += 1
        assert steps < 2000, "churn run did not converge"
    assert all(r.done or r.failed for r in reqs)
    assert eng.robust_counters.preemptions > 0
    assert_drained(eng)


def test_exhaustion_rollback_byte_identical(served, rng):
    """Hand-driven BlockPoolExhausted on a NON-robust engine: blocks stolen
    straight from the pool (below the reservation gate's assumptions) make
    the next decode-boundary growth raise out of step(). The journal must
    roll the step back to a byte-identical engine — free list ORDER,
    refcounts, tables, reservations, lengths, queue, trie — so the caller
    can free blocks and retry; the retried run finishes with outputs
    token-identical to an uncontended run."""
    cfg, params = served
    reqs = _requests(rng, 2, lens=(13,), max_new=12)
    ref_eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8)
    for r in copy.deepcopy(reqs):
        ref_eng.submit(r)
    ref_out = {r.uid: r.out_tokens for r in ref_eng.run()}

    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      num_blocks=9)

    def snap(e):
        return (list(e.alloc._free), dict(e.alloc._ref),
                e._tables.tolist(), e._resv.tolist(), e._lengths.tolist(),
                [r.uid for r in e._queue],
                sorted(int(b) for b in e.trie.blocks()))

    work = copy.deepcopy(reqs)
    for r in work:
        eng.submit(r)
    while not eng._live.any():           # drive both into decode
        eng.step()
    for _ in range(2):
        eng.step()
    stolen = [eng.alloc.alloc() for _ in range(eng.alloc.num_free)]
    assert eng.alloc.num_free == 0
    raised = False
    done = []
    while eng.busy and not raised:
        before = snap(eng)
        try:
            done.extend(eng.step())
        except BlockPoolExhausted:
            raised = True
            assert snap(eng) == before   # the rollback contract
    assert raised, "steal never forced a boundary crossing"
    assert all(not r.failed for r in work)
    eng.alloc.free(stolen)               # give the blocks back; retry runs
    done.extend(eng.run())
    assert {r.uid: r.out_tokens for r in done} == ref_out


def test_end_session_cancels_in_flight_turn(served, rng):
    """end_session() on a session whose turn is mid-decode: the turn is
    cancelled (failed, reason "cancelled", no history written), the
    session is immediately reusable, and a fresh turn on the same session
    id behaves exactly like a first turn on a fresh engine."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      num_blocks=12, prefix_sharing=True)
    p1 = rng.integers(0, 256, 11).astype(np.int32)
    p2 = rng.integers(0, 256, 9).astype(np.int32)
    r1 = Request(uid=1, prompt=p1, max_new_tokens=16)
    eng.submit(r1, session="s")
    eng.step()
    assert eng.busy and not r1.done
    eng.end_session("s")
    assert r1.failed and r1.fail_reason == "cancelled" and not r1.done
    assert not eng.busy
    # the aborted turn left no history: the next turn on "s" matches a
    # first turn on an untouched engine
    r2 = Request(uid=2, prompt=p2.copy(), max_new_tokens=6)
    eng.submit(r2, session="s")
    out = eng.run()
    assert [r.uid for r in out] == [2] and r2.done
    fresh = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                        prefix_sharing=True)
    rf = Request(uid=3, prompt=p2.copy(), max_new_tokens=6)
    fresh.submit(rf, session="x")
    fresh.run()
    assert r2.out_tokens == rf.out_tokens
    # nothing leaked: dropping the cache reclaims the whole pool
    eng.clear_prefix_cache()
    assert eng.alloc.num_free == eng.num_blocks - 1


def test_watermark_parent_survives_eviction_and_cache_clear(served, rng):
    """Regression: under first-writer-wins, a live slot's registration
    watermark can point at ANOTHER chain's indexed block that the slot holds
    no reference to (its own table carries a duplicate). Once the first
    writer finishes, that parent is a ref-1 evictable leaf — but evicting it
    while the follower still decodes would let the allocator recycle the id
    under the follower's future child inserts. Two identical prompts with
    different output budgets set up exactly that; eviction must refuse the
    live watermark parent, and clear_prefix_cache mid-flight must reset the
    watermark so registration re-walks from the slot's own table."""
    cfg, params = served
    prompt = rng.integers(0, 256, 9).astype(np.int32)
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      decode_sharing=True)
    # admitted together (no prefix hit yet): each prefills its own copy; the
    # follower's registrations then hit first-writer-wins on the leader's
    eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=8))
    eng.submit(Request(uid=1, prompt=prompt.copy(), max_new_tokens=20))
    saw_foreign_parent = cleared = False
    while eng._queue or eng._live.any():
        eng._admit()
        eng._step_packed()
        live = np.flatnonzero(eng._live)
        if len(live) == 1:                   # leader finished, follower live
            slot = int(live[0])
            parent = int(eng._reg_parent[slot])
            row = eng._tables[slot]
            if parent >= 0 and parent not in set(map(int, row[row >= 0])):
                saw_foreign_parent = True
                while eng._evict_one():      # drain all evictable blocks
                    pass
                # the foreign parent is still indexed — not recycled
                assert parent in set(map(int, eng.trie.blocks()))
                if not cleared:
                    # clearing the cache must also reset the watermark...
                    eng.clear_prefix_cache()
                    cleared = True
                    assert int(eng._reg_parent[slot]) == -1
                    assert int(eng._reg_level[slot]) == 0
        # ...and every trie entry stays reachable at all times
        for (par, _), blk in eng.trie._index.items():
            assert par == -1 or par in eng.trie._block_key
    assert saw_foreign_parent and cleared
    eng.clear_prefix_cache()
    assert eng.alloc.num_free == eng.num_blocks - 1


@pytest.mark.slow
def test_multi_turn_followup_skip_acceptance(served, rng):
    """Acceptance (slow job): in a chat-style session workload with
    decode-block sharing on, at least 30% of follow-up-turn prefill tokens
    are skipped (the benchmark gates tok/s on the same regime)."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=4, max_len=256, block_size=16,
                      decode_sharing=True)
    for i in range(3):
        for turn in range(4):
            eng.submit(Request(
                uid=10 * i + turn,
                prompt=rng.integers(0, 256, 25).astype(np.int32),
                max_new_tokens=8), session=f"s{i}")
            eng.run()
    s = eng.prefix_stats()
    assert s["followup_skip_rate"] >= 0.30, s
    assert s["decode_hits"] > 0, s


@pytest.mark.parametrize("sharing", [False, True])
def test_packed_step_parity_with_lockstep(served, rng, sharing):
    """Acceptance: the packed token step produces greedy outputs
    token-identical to the continuous engine's slot arena on a mixed
    workload — under prefix sharing both off AND on (the shared-prefix set
    includes a full-prompt hit, so the packed path exercises COW and the
    re-fed last token too)."""
    cfg, params = served
    shared = rng.integers(0, 256, 32).astype(np.int32)   # 2 full 16-blocks
    prompts = ([rng.integers(0, 256, int(n)).astype(np.int32)
                for n in (5, 13, 21)]
               + [np.concatenate([shared,
                                  rng.integers(0, 256, 7).astype(np.int32)]),
                  np.concatenate([shared,
                                  rng.integers(0, 256, 3).astype(np.int32)]),
                  shared.copy()])         # full-prompt hit: COW under sharing
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    reqs[1].max_new_tokens = 1
    outs = {}
    for eng in (ContinuousEngine(params, cfg, max_batch=4, max_len=64),
                PagedEngine(params, cfg, max_batch=4, max_len=64,
                            block_size=16, prefix_sharing=sharing)):
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        outs[type(eng)] = {r.uid: r.out_tokens for r in eng.run()}
    assert outs[ContinuousEngine] == outs[PagedEngine]
    # sharing engaged exactly when it was on
    assert (eng.prefix_stats()["prefill_tokens_skipped"] > 0) == sharing


@pytest.mark.parametrize("mode", ["sync", "async", "blockwise",
                                  "speculative"])
def test_packed_split_grids_parity_with_lockstep(served, rng, monkeypatch,
                                                 mode):
    """The XLA packed step's two attention grids give greedy outputs
    token-identical to the continuous engine's slot arena. Request 0 decodes
    while three
    prompts of 6, 7 and 1 tokens arrive: at width 16 and Wb 16 the chunk
    grid's floor is one row, so the 6-token chunk takes it and the 7-token
    prompt one lane, beside the one-token prefill and the decode rider.
    Also in the async loop, with every row on the blockwise path (8-key
    blocks), and with speculative verify rows (garbage drafts of 2, all
    rejected), which take the chunk grid's rungs above the floor: a verify
    row beside a 40-token prompt's chunk fills 2 rows at width 64 and Wb
    48, whose floor is one."""
    import repro.serve.paged as paged
    cfg, params = served
    if mode == "blockwise":
        cfg = cfg.replace(attention_impl="blockwise", block_k=8)
    lens = (5, 5, 5, 40) if mode == "speculative" else (5, 6, 7, 1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in lens]
    first = 3 if mode == "speculative" else 1
    steps = []
    real = paged.grid_steering

    def record(t_valid, off, sid, lengths, grid_widths, ssd=False):
        g = real(t_valid, off, sid, lengths, grid_widths, ssd)
        steps.append((np.asarray(t_valid).tolist(), len(sid),
                      g["chunk_q_pos"].shape if "chunk_q_pos" in g else None))
        return g
    monkeypatch.setattr(paged, "grid_steering", record)
    kw = dict(speculative=True, draft_len=2) if mode == "speculative" else {}
    paged = PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16,
                        async_loop=mode == "async", **kw)
    if kw:
        paged._propose_drafts = lambda live, remaining: {
            int(s): [7, 7] for s in np.flatnonzero(
                np.asarray(live) & (np.asarray(remaining) == 0))}
    outs = {}
    for eng in (ContinuousEngine(params, cfg, max_batch=4, max_len=64),
                paged):
        reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        for r in reqs[:first]:
            eng.submit(r)
        eng.step()                        # these decode from here on
        for r in reqs[first:]:
            eng.submit(r)
        done = eng.run()
        outs[type(eng)] = {r.uid: r.out_tokens for r in done}
    assert (outs[ContinuousEngine] == outs[PagedEngine]
            and len(outs[PagedEngine]) == 4)
    if mode == "speculative":
        assert any(shape and shape[0] > max(1, w // shape[1])
                   for _, w, shape in steps), steps
    else:
        assert ([1, 6, 1, 1], 16, (1, 16)) in steps, steps


# Token lanes the removed lockstep (B, block_size)/(B, 1) layout spent on
# test_packed_step_prefill_heavy_efficiency's traffic: 204 valid of 612, its
# padding_stats() on the last tree that had it, where the packed step read
# 204 of 288.
LOCKSTEP_EFFICIENCY = 204 / 612


def test_packed_step_prefill_heavy_efficiency(served, rng):
    """The packing acceptance regime: long prompts chunk-prefilling while
    short-prompt long-output requests decode alongside (a lockstep chunk
    step padded each rider to a full block_size row). The packed step's
    padding efficiency must be >= 2x the lockstep layout's on the same
    workload, and its outputs those of the continuous engine."""
    cfg, params = served
    reqs = ([Request(uid=i, prompt=rng.integers(0, 256, 5).astype(np.int32),
                     max_new_tokens=16) for i in range(3)]
            + [Request(uid=3 + j,
                       prompt=rng.integers(0, 256, 45).astype(np.int32),
                       max_new_tokens=4) for j in range(3)])
    outs = {}
    for eng in (ContinuousEngine(params, cfg, max_batch=4, max_len=64),
                PagedEngine(params, cfg, max_batch=4, max_len=64,
                            block_size=16, num_blocks=17)):
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        outs[type(eng)] = {r.uid: r.out_tokens for r in eng.run()}
    assert outs[ContinuousEngine] == outs[PagedEngine]
    eff = eng.padding_stats()["efficiency"]
    assert eff >= 2 * LOCKSTEP_EFFICIENCY, eff


def test_packed_step_budget_drives_chunk_size(served, rng):
    """The packed chunk size is budget-driven, not block_size-bound: with a
    large token budget a long prompt prefills in ONE step, and outputs stay
    token-identical to a small-budget engine (scheduling never changes what
    is generated)."""
    cfg, params = served
    prompt = rng.integers(0, 256, 41).astype(np.int32)
    outs, steps = [], []
    for budget in (4, 48):
        eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16,
                          token_budget=budget)
        eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=4))
        (done,) = eng.run()
        outs.append(done.out_tokens)
        steps.append(eng.occupancy_steps)
    assert outs[0] == outs[1]
    # 41-token prompt: one 48-lane chunk step + decode vs ceil(41/4) chunks
    assert steps[1] < steps[0]
    with pytest.raises(ValueError):
        PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16,
                    token_budget=2)                   # below max_batch


@pytest.mark.parametrize("mode", ["i16_div", "wide"])
def test_packed_decode_kernel_engine_parity(tiny_cfg, rng, mode):
    """cfg.decode_kernel under the packed layout dispatches EVERY step
    (chunks included) to the fused hccs_packed_prefill kernel; greedy outputs
    must match the packed XLA STE path bit-for-bit."""
    base = dict(attention_prob="hccs", hccs_mode=mode)
    cfg0 = tiny_cfg(**base)
    cfgk = tiny_cfg(**base, decode_kernel="fused")
    params = M.init_params(jax.random.PRNGKey(0), cfg0)
    reqs = _requests(rng, 4, lens=(5, 9, 19), max_new=4)
    outs = []
    for cfg in (cfg0, cfgk):
        eng = PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16)
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        outs.append({r.uid: r.out_tokens for r in eng.run()})
    assert outs[0] == outs[1]


def test_temperature_sampling_and_validation(served, rng):
    cfg, params = served
    eng = ContinuousEngine(params, cfg, max_batch=2, max_len=32)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=9, prompt=np.zeros(40, np.int32)))
    eng.submit(Request(uid=0, prompt=rng.integers(0, 256, 6).astype(np.int32),
                       max_new_tokens=5, temperature=0.8))
    (done,) = eng.run()
    assert len(done.out_tokens) == 5
    assert all(0 <= t < cfg.vocab_size for t in done.out_tokens)
