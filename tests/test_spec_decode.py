"""Trie-driven speculative decoding (serve/paged.py): greedy token parity
against never-drafted engines, rejected-draft no-trace rollback, the two
draft sources (trie path extension, n-gram prompt lookup), scheduler draft
budgeting, and acceptance telemetry.

The contract under test: speculative decoding is a pure THROUGHPUT change.
Every accepted token is one the never-drafted engine would have sampled at
the same (request, position) — verify lanes sample with the same
per-(uid, generation-index) keys and the first mismatch rolls the step
back. Rollback layering:

  * host bookkeeping — draft-only allocations freed in reverse order, so
    the free list / tables / reservations / registration watermarks are
    restored exactly (pinned here against a never-drafted twin);
  * fp pools — no device work: rejected rows sit beyond the committed
    frontier, masked by kv_len and overwritten before any read, so the
    raw pool bytes are NOT compared (only host state and tokens);
  * int8 pools — pre-step snapshot restore + committed-row replay, pinned
    BIT-exact against the never-drafted pool on a seeded workload.
"""
import numpy as np
import pytest

import jax

from repro.models import model as M
from repro.serve import PagedEngine, Request
from repro.serve.paged import (BlockAllocator, PrefixTrie, ngram_propose,
                               prefix_chunk, schedule_step_tokens)

BS = 8   # trie-level tests' block size (engine tests use 16 via kwargs)


@pytest.fixture
def served(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 256)
    kw.setdefault("block_size", 16)
    kw.setdefault("draft_len", 4)
    return PagedEngine(params, cfg, **kw)


def _run_sessions(eng, seed: int, sessions=3, turns=3, max_new=12):
    """A seeded multi-turn workload: every turn re-feeds the session history
    plus a short repetitive user message, so both the trie (decode sharing)
    and the n-gram fallback have material to draft from. Returns
    {uid: generated tokens} — the parity unit."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 256, 12).astype(np.int32)
    out = {}
    uid = 0
    for _ in range(turns):
        for s in range(sessions):
            extra = rng.integers(0, 256, 3).astype(np.int32)
            eng.submit(Request(uid=uid,
                               prompt=np.concatenate([base, extra]),
                               max_new_tokens=max_new),
                       session=f"s{s}")
            uid += 1
        for r in eng.run():
            out[r.uid] = tuple(r.out_tokens)
    return out


# ------------------------------------------------------------ drafting --


class TestNgramPropose:
    def test_longest_repeated_suffix_continuation(self):
        # suffix [1,2,3] recurs at the start; the tokens after it follow
        assert ngram_propose([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]

    def test_most_recent_occurrence_wins(self):
        # suffix [1,2] occurs twice earlier; the later one (followed by 7)
        # is the PLD prediction, not the first (followed by 5)
        assert ngram_propose([1, 2, 5, 1, 2, 7, 1, 2], 3) == [7, 1, 2]

    def test_no_repeat_returns_empty(self):
        assert ngram_propose([1, 2, 3, 4, 5], 4) == []

    def test_k_caps_proposal(self):
        assert ngram_propose([4, 5, 6, 7, 4, 5], 1) == [6]

    def test_tiny_sequences(self):
        assert ngram_propose([], 4) == []
        assert ngram_propose([3], 4) == []
        assert ngram_propose([3, 3], 2) == [3]


class TestExtendPath:
    def _trie(self, chains):
        """Build a trie holding token chains; each chain is a flat token
        list cut into BS-sized chunks."""
        alloc = BlockAllocator(64)
        trie = PrefixTrie(alloc, BS)
        for chain in chains:
            parent = -1
            for j in range(len(chain) // BS):
                blk = alloc.alloc()
                parent = trie.insert(parent, prefix_chunk(chain, j, BS),
                                     blk, "prompt")
                alloc.free([blk])
        return trie

    def test_continues_matched_path(self):
        chain = list(range(3 * BS))
        trie = self._trie([chain])
        # aligned at a block boundary: drafts read the next chunks verbatim
        assert trie.extend_path(chain[:BS], 2 * BS) == chain[BS:3 * BS]

    def test_partial_tail_content_match(self):
        chain = list(range(3 * BS))
        trie = self._trie([chain])
        # mid-block: only a child whose chunk CONTENT starts with the tail
        # extends; the draft resumes after the tail
        got = trie.extend_path(chain[:BS + 3], BS)
        assert got == chain[BS + 3:2 * BS + 3]

    def test_diverging_tail_returns_empty(self):
        chain = list(range(3 * BS))
        trie = self._trie([chain])
        assert trie.extend_path(chain[:BS] + [255], BS) == []

    def test_most_recent_child_wins(self):
        head = list(range(BS))
        a = head + [100] * BS
        b = head + [100] * (BS - 1) + [101]
        trie = self._trie([a, b])
        # both children of head's block start with tail [100]; chain b was
        # inserted later (more recently touched), so its chunk is the draft
        assert trie.extend_path(head + [100], BS)[:BS - 2] \
            == b[BS + 1:2 * BS - 1]

    def test_every_full_block_of_extension_rematches(self):
        # the drafting invariant: extend_path only proposes continuations
        # whose full blocks are themselves indexed reachable chains
        chain = list(range(4 * BS))
        trie = self._trie([chain])
        for cut in (BS, BS + 1, 2 * BS - 1, 2 * BS + 5):
            prefix = chain[:cut]
            drafts = trie.extend_path(prefix, 2 * BS)
            ext = prefix + drafts
            assert len(trie.match(ext)) == len(ext) // BS

    def test_pure_no_lru_touch(self):
        chain = list(range(2 * BS))
        trie = self._trie([chain])
        lru = dict(trie._lru)
        trie.extend_path(chain[:BS], BS)
        assert trie._lru == lru


class TestScheduleDrafts:
    def test_default_layout_unchanged(self):
        live = np.array([True, True, True])
        remaining = np.array([0, 5, 0])
        base = schedule_step_tokens(live, remaining, 16, 8)
        with_none = schedule_step_tokens(live, remaining, 16, 8, drafts=None)
        np.testing.assert_array_equal(base, with_none)

    def test_drafts_dealt_to_decode_slots_first(self):
        live = np.array([True, True, True])
        remaining = np.array([0, 50, 0])
        t = schedule_step_tokens(live, remaining, 8, 8,
                                 drafts=np.array([2, 0, 3]))
        # decode slots take 1 + their drafts before prefill leftovers
        np.testing.assert_array_equal(t, [3, 1, 4])

    def test_budget_truncates_drafts(self):
        live = np.array([True, True])
        remaining = np.array([0, 0])
        t = schedule_step_tokens(live, remaining, 4, None,
                                 drafts=np.array([4, 4]))
        assert t.sum() == 4 and t[0] == 3   # slot order, leftover to slot 0


# ------------------------------------------------------- engine parity --


class TestGreedyParity:
    @pytest.mark.parametrize("quant", ["none", "int8"])
    @pytest.mark.parametrize("sharing", [False, True])
    def test_multi_turn_token_identical(self, served, sharing, quant):
        cfg, params = served
        if quant != "none":
            cfg = cfg.replace(kv_quant=quant)
        outs, engines = {}, {}
        for spec in (False, True):
            eng = _engine(params, cfg, prefix_sharing=sharing,
                          decode_sharing=sharing, speculative=spec)
            outs[spec] = _run_sessions(eng, seed=7)
            engines[spec] = eng
        assert outs[True] == outs[False]
        # the run must actually exercise the draft/verify path
        assert engines[True].drafted_tokens > 0
        assert engines[True].accepted_tokens > 0
        assert engines[False].drafted_tokens == 0

    def test_acceptance_rate_on_repetitive_workload(self, served):
        cfg, params = served
        eng = _engine(params, cfg, prefix_sharing=True, decode_sharing=True,
                      speculative=True)
        _run_sessions(eng, seed=7)
        stats = eng.prefix_stats()
        assert stats["tokens_drafted"] == (stats["tokens_accepted"]
                                           + stats["tokens_rejected"])
        # conservative floor: the multi-turn re-feed workload accepts well
        # above this (the serving benchmark records the live number)
        assert stats["acceptance_rate"] >= 0.3

    def test_counters_zero_and_rate_none_without_drafting(self, served):
        cfg, params = served
        eng = _engine(params, cfg, speculative=False)
        eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                           max_new_tokens=4))
        eng.run()
        stats = eng.prefix_stats()
        assert stats["tokens_drafted"] == 0
        assert stats["acceptance_rate"] is None


# ----------------------------------------------------- no-trace rollback --


def _host_state(eng):
    """Everything the scheduler can observe: allocator, tables, frontiers,
    reservations, registration watermarks, and the trie index."""
    return dict(
        free=list(eng.alloc._free),
        ref=dict(eng.alloc._ref),
        tables=eng._tables.copy(),
        lengths=eng._lengths.copy(),
        resv=eng._resv.copy(),
        reg_level=eng._reg_level.copy(),
        reg_parent=eng._reg_parent.copy(),
        trie_index=dict(eng.trie._index),
        trie_kids={p: dict(k) for p, k in eng.trie._kids.items()},
    )


def _assert_host_state_equal(a, b):
    for name in ("free", "ref", "trie_index", "trie_kids"):
        assert a[name] == b[name], name
    for name in ("tables", "lengths", "resv", "reg_level", "reg_parent"):
        np.testing.assert_array_equal(a[name], b[name], err_msg=name)


class TestNoTrace:
    """Drive a speculative engine whose drafts are GARBAGE (monkeypatched
    constant tokens, rejected essentially every step) against a
    never-drafted twin: after the run every piece of host state must be
    indistinguishable, and on int8 pools the device blocks too — for ANY
    garbage token, not a lucky seed.

    Why that holds exactly: draft lanes fold with a CLAMPED block scale
    (paged_quant_scatter draft_rows), so they never requantize committed
    rows sharing their block — a committed lane's reads, and therefore its
    staged raw KV, are bit-identical to a never-drafted step's. The
    post-verification rewrite restores the pre-step snapshot and re-folds
    exactly the committed rows grow-wise, so an all-rejected step leaves
    the pool byte-for-byte as if it never drafted. (ACCEPTED draft lanes
    attend the clamped scratch rows of their accepted prefix, so their own
    committed KV may carry quantization-level drift — the pre-existing
    int8 multi-lane drift class; that is why the bit-exact comparison here
    drives all-rejected garbage.)"""

    GARBAGE = 7

    def _run_pair(self, served, quant, seed=0):
        cfg, params = served
        if quant != "none":
            cfg = cfg.replace(kv_quant=quant)
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, 256, int(rng.integers(3, 40)))
                   .astype(np.int32) for _ in range(4)]
        engines = []
        for spec in (False, True):
            eng = _engine(params, cfg, prefix_sharing=True,
                          decode_sharing=True, speculative=spec)
            if spec:
                g = self.GARBAGE

                def bad(live, remaining):
                    dec = np.flatnonzero(np.asarray(live)
                                         & (np.asarray(remaining) == 0))
                    return {int(s): [g, g, g] for s in dec}

                eng._propose_drafts = bad
            for i, p in enumerate(prompts):
                eng.submit(Request(uid=i, prompt=p.copy(),
                                   max_new_tokens=16))
            outs = {r.uid: tuple(r.out_tokens) for r in eng.run()}
            engines.append((eng, outs))
        return engines

    @pytest.mark.parametrize("quant", ["none", "int8"])
    def test_host_state_and_tokens(self, served, quant):
        (e0, out0), (e1, out1) = self._run_pair(served, quant)
        assert out1 == out0
        assert e1.spec_rollbacks > 0          # garbage was really rejected
        assert e1.rejected_tokens > 0
        _assert_host_state_equal(_host_state(e0), _host_state(e1))

    def test_int8_pool_bit_identical(self, served):
        (e0, _), (e1, _) = self._run_pair(served, "int8")
        for name in ("k", "v", "k_scale", "v_scale"):
            a = np.asarray(e0._cache["layers"][name])
            b = np.asarray(e1._cache["layers"][name])
            # block 0 is the trash target: rejected lanes are steered there
            # by design, so its bytes legitimately differ
            np.testing.assert_array_equal(a[:, 1:], b[:, 1:], err_msg=name)

    def test_pool_drains_clean_after_run(self, served):
        (_, _), (e1, _) = self._run_pair(served, "int8")
        e1.clear_prefix_cache()
        assert e1.alloc.num_free == e1.num_blocks - 1   # all but trash
        assert e1.alloc.num_live == 0


# --------------------------------------------------------- config guards --


class TestConfigGuards:
    def test_speculative_requires_paged_layout(self, tiny_cfg):
        with pytest.raises(ValueError, match="paged"):
            tiny_cfg(speculative=True)

    def test_draft_len_positive(self, tiny_cfg):
        with pytest.raises(ValueError, match="draft_len"):
            tiny_cfg(cache_layout="paged", speculative=True, draft_len=0)

    def test_speculative_requires_packed_step(self, served):
        """packed=False is refused outright, speculation included: the
        lockstep layout it asked for was removed."""
        cfg, params = served
        with pytest.raises(ValueError,
                           match="lockstep step layout was removed"):
            _engine(params, cfg, packed=False, speculative=True)

    def test_engine_kwarg_overrides_cfg(self, served):
        cfg, params = served
        eng = _engine(params, cfg.replace(cache_layout="paged",
                                          speculative=True),
                      speculative=False)
        assert not eng.speculative
