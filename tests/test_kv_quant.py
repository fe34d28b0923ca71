"""Int8-quantized paged KV blocks (cfg.kv_quant="int8"): rounding-mode
regression, per-row fold invariants, engine-level greedy parity across step
layouts and sharing settings, COW scale copying, shared-block immutability,
byte accounting, and the int8-vs-fp drift tolerance gate.

The central design fact under test: the quantizing write is a PER-ROW FOLD
(models/attention.py paged_quant_scatter) — each landing row grows its
block's scale monotonically and requantizes the existing payload by the
old/new ratio, so a block's bytes are a pure function of (row values, write
order), independent of how steps partition the rows. That is what makes
token budgets, sharing on/off, and engine reuse BIT-IDENTICAL under
quantization; only int8-vs-fp drift needs a tolerance regime.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.models.attention import (KV_QUANT_EPS, KV_QUANT_INV_QMAX,
                                    paged_quant_scatter)
from repro.quant.int8 import (dequantize, fake_quant, quantize, round_to_int)
from repro.serve import (ContinuousEngine, PagedEngine, Request, ServeEngine,
                         kv_cache_byte_stats)


@pytest.fixture
def served(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(rng, n, lens=(5, 9, 13, 21, 34), max_new=8):
    return [Request(uid=i,
                    prompt=rng.integers(0, 256, int(rng.choice(lens))).astype(
                        np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _serve(params, cfg, reqs, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 16)
    eng = PagedEngine(params, cfg, **kw)
    for r in copy.deepcopy(reqs):
        eng.submit(r)
    return {r.uid: r.out_tokens for r in eng.run()}, eng


# ----------------------------------------------------------- rounding mode --


class TestRoundingMode:
    """The paper's int8 MAC hardware rounds ties HALF AWAY FROM ZERO;
    IEEE-754 (and jnp.round) rounds ties TO EVEN. quant/int8.py makes the
    choice explicit and defaults to the hardware behavior — this class pins
    the tie handling so neither path can silently drift to the other. (The
    HCCS LOGIT quantization deliberately stays on jnp.round: the Pallas
    kernels round logits with jnp.round, and kernel/XLA bit-parity outranks
    hardware fidelity there — see quant/int8.py's module docstring.)"""

    def test_half_away_ties(self):
        x = jnp.array([0.5, -0.5, 1.5, -1.5, 2.5, -2.5, 0.49, -0.49])
        got = round_to_int(x, "half_away")
        np.testing.assert_array_equal(
            np.asarray(got), [1.0, -1.0, 2.0, -2.0, 3.0, -3.0, 0.0, -0.0])

    def test_nearest_even_ties(self):
        x = jnp.array([0.5, -0.5, 1.5, -1.5, 2.5, -2.5])
        got = round_to_int(x, "nearest_even")
        np.testing.assert_array_equal(
            np.asarray(got), [0.0, -0.0, 2.0, -2.0, 2.0, -2.0])

    def test_modes_disagree_exactly_on_even_ties(self):
        # the whole point of pinning: x.5 with even floor is where the two
        # conventions split (0.5, 2.5, 4.5, ... round differently)
        x = jnp.arange(0.5, 10.0, 1.0)
        away = np.asarray(round_to_int(x, "half_away"))
        even = np.asarray(round_to_int(x, "nearest_even"))
        disagree = away != even
        np.testing.assert_array_equal(disagree, (np.arange(10) % 2) == 0)

    def test_quantize_clips_and_rounds(self):
        x = jnp.array([0.05, -0.05, 20.0, -20.0])
        q = quantize(x, jnp.float32(0.1))
        assert q.dtype == jnp.int8
        # 0.05/0.1 = 0.5: half-away gives 1, nearest-even would give 0
        np.testing.assert_array_equal(np.asarray(q), [1, -1, 127, -128])

    def test_quantize_rejects_unknown_mode(self):
        with pytest.raises(ValueError, match="rounding"):
            round_to_int(jnp.zeros(1), "stochastic")

    def test_dequantize_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.normal(size=256).astype(np.float32))
        s = jnp.float32(np.abs(np.asarray(x)).max() / 127.0)
        err = np.abs(np.asarray(dequantize(quantize(x, s), s) - x))
        assert err.max() <= 0.5 * float(s) + 1e-7

    def test_fake_quant_matches_quant_dequant(self, rng):
        x = jnp.asarray(rng.normal(size=64).astype(np.float32))
        s = jnp.float32(0.03)
        np.testing.assert_array_equal(
            np.asarray(fake_quant(x, s)),
            np.asarray(dequantize(quantize(x, s), s)))


# ------------------------------------------------------------ per-row fold --


def _np_half_away(x):
    return np.sign(x) * np.floor(np.abs(x) + np.float32(0.5))


def _np_fold(pool, scales, rows, positions, hd):
    """Numpy reference of paged_quant_scatter's per-row fold, float32
    throughout so the arithmetic matches the jax implementation bit-for-bit."""
    n, hkv, bs, hd_c = pool.shape
    pool = pool.astype(np.float32).copy()
    scales = scales.astype(np.float32).copy()
    for x, p in zip(rows, positions):
        blk, r = int(p) // bs, int(p) % bs
        x = x.astype(np.float32)
        amax = np.abs(x).max(-1)
        s_new = np.maximum(scales[blk],
                           np.maximum(amax, np.float32(KV_QUANT_EPS))
                           * np.float32(KV_QUANT_INV_QMAX))
        ratio = (scales[blk] / s_new).astype(np.float32)
        payload = np.clip(_np_half_away(pool[blk] * ratio[:, None, None]),
                          -128, 127)
        payload[:, r, :hd] = np.clip(
            _np_half_away(x / s_new[:, None]), -128, 127)
        pool[blk] = payload
        scales[blk] = s_new
    return pool.astype(np.int8), scales


def _jax_fold(pool, scales, rows, positions):
    """Drive paged_quant_scatter with one (B=1, Hkv, t, hd) write group."""
    new_kv = jnp.asarray(np.stack(rows, axis=1)[None])   # (1, Hkv, t, hd)
    wp = jnp.asarray(np.asarray(positions, np.int32)[None])
    pool, scales = paged_quant_scatter(jnp.asarray(pool), jnp.asarray(scales),
                                       new_kv, wp)
    return np.asarray(pool), np.asarray(scales)


class TestQuantScatterFold:
    N, HKV, BS, HD = 3, 2, 4, 5

    def _rows(self, rng, t, scale=1.0):
        return [rng.normal(scale=scale,
                           size=(self.HKV, self.HD)).astype(np.float32)
                for _ in range(t)]

    def _zero_state(self):
        pool = np.zeros((self.N, self.HKV, self.BS, self.HD), np.int8)
        return pool, np.zeros((self.N, self.HKV), np.float32)

    def test_matches_numpy_reference_bit_exact(self, rng):
        pool, scales = self._zero_state()
        t = 2 * self.BS                       # fill blocks 0 and 1 fully
        rows = self._rows(rng, t)
        positions = np.arange(t)
        jp, js = _jax_fold(pool, scales, rows, positions)
        np_, ns = _np_fold(pool, scales, rows, positions, self.HD)
        np.testing.assert_array_equal(jp, np_)
        np.testing.assert_array_equal(js, ns)

    def test_partition_independent(self, rng):
        """Folding the same rows through ANY step partition yields the same
        final bytes — the invariant that makes steps of any token budget
        bit-identical under quantization."""
        t = 2 * self.BS
        rows = self._rows(rng, t)
        positions = np.arange(t)
        whole = _jax_fold(*self._zero_state(), rows, positions)
        for splits in ([1] * t, [3, 5], [self.BS, self.BS], [2, 5, 1]):
            pool, scales = map(jnp.asarray, self._zero_state())
            o = 0
            for g in splits:
                new_kv = jnp.asarray(np.stack(rows[o:o + g], axis=1)[None])
                wp = jnp.asarray(positions[None, o:o + g].astype(np.int32))
                pool, scales = paged_quant_scatter(pool, scales, new_kv, wp)
                o += g
            np.testing.assert_array_equal(np.asarray(pool), whole[0], splits)
            np.testing.assert_array_equal(np.asarray(scales), whole[1])

    def test_scales_grow_monotonically(self, rng):
        pool, scales = map(jnp.asarray, self._zero_state())
        prev = np.zeros((self.N, self.HKV), np.float32)
        for i, row in enumerate(self._rows(rng, self.BS, scale=3.0)):
            pool, scales = paged_quant_scatter(
                pool, scales, jnp.asarray(row[None, :, None]),
                jnp.asarray([[i]], jnp.int32))
            cur = np.asarray(scales)
            assert (cur >= prev - 0).all()
            prev = cur

    def test_requant_keeps_rows_representable(self, rng):
        """Already-written rows survive later scale growth: after every
        subsequent write, each row dequantizes to within half a quantization
        step (0.5 * final scale) of its original value — the device-side
        requant path's accuracy contract."""
        pool, scales = map(jnp.asarray, self._zero_state())
        rows = self._rows(rng, self.BS, scale=1.0)
        rows[-1] *= 50.0                      # late row forces a big rescale
        for i, row in enumerate(rows):
            pool, scales = paged_quant_scatter(
                pool, scales, jnp.asarray(row[None, :, None]),
                jnp.asarray([[i]], jnp.int32))
        deq = (np.asarray(pool)[0].astype(np.float32)
               * np.asarray(scales)[0][:, None, None])
        want = np.stack(rows, axis=0).transpose(1, 0, 2)  # (Hkv, bs, hd)
        err = np.abs(deq[:, :, :self.HD] - want)
        bound = 0.5 * np.asarray(scales)[0][:, None, None] + 1e-6
        # requant error compounds per rescale; allow 2 quantization steps
        assert (err <= 4 * bound).all(), err.max()

    def test_zero_scale_block_payload_reset(self):
        """A fresh block (scale 0) with stale garbage bytes: ratio 0 zeroes
        the payload before the first row lands — the device half of the
        fresh-block reset (the engine half zeroes the stale scale)."""
        pool = np.full((self.N, self.HKV, self.BS, self.HD), 77, np.int8)
        scales = np.zeros((self.N, self.HKV), np.float32)
        row = np.ones((self.HKV, self.HD), np.float32)
        jp, js = _jax_fold(pool, scales, [row], [self.BS])   # block 1, row 0
        assert (jp[1, :, 1:] == 0).all()      # stale rows zeroed by ratio 0
        np.testing.assert_array_equal(
            jp[1, :, 0], np.full((self.HKV, self.HD), 127, np.int8))
        assert (jp[0] == 77).all()            # untouched blocks keep bytes


# ------------------------------------------------------ engine-level parity --


class TestEnginePartitionParity:
    """Greedy outputs under kv_quant="int8" are BIT-IDENTICAL across every
    step partitioning of the same token stream — two token budgets, sharing
    on/off, fused kernel vs XLA — because the per-row fold makes block bytes
    partition-independent. (int8 vs fp is the only tolerance-gated axis; see
    TestDriftTolerance.)"""

    # a budget of max_batch lanes: every prompt prefills one lane per step
    # beside the decoding slots, where the default budget (4 x 16) takes
    # whole chunks — the most and the fewest step boundaries
    SMALL_BUDGET = 4

    def _cfgs(self, served):
        cfg, params = served
        return cfg.replace(kv_quant="int8"), params

    def test_packed_matches_lockstep(self, served, rng):
        """The default budget's whole-chunk steps and a max_batch budget's
        one-lane-per-prompt steps fold the same rows."""
        cfg, params = self._cfgs(served)
        reqs = _requests(rng, 6)
        wide, wide_eng = _serve(params, cfg, reqs)
        narrow, narrow_eng = _serve(params, cfg, reqs,
                                    token_budget=self.SMALL_BUDGET)
        assert wide == narrow
        assert narrow_eng.occupancy_steps > wide_eng.occupancy_steps

    @pytest.mark.parametrize("small_budget", [True, False])
    def test_sharing_matches_isolated(self, served, rng, small_budget):
        """Prefix + decode sharing reuse quantized blocks and COW-copy them
        (payload + scales): outputs must equal the sharing-off run, at the
        default token budget and at max_batch lanes."""
        cfg, params = self._cfgs(served)
        budget = self.SMALL_BUDGET if small_budget else None
        shared = rng.integers(0, 256, 16).astype(np.int32)
        reqs = [Request(uid=i, prompt=np.concatenate(
                    [shared, rng.integers(0, 256, 5).astype(np.int32)]),
                        max_new_tokens=8) for i in range(4)]
        plain, _ = _serve(params, cfg.replace(prefix_sharing=False,
                                              decode_sharing=False),
                          reqs, token_budget=budget)
        share, eng = _serve(params, cfg.replace(prefix_sharing=True,
                                                decode_sharing=True),
                            reqs, token_budget=budget)
        assert plain == share
        assert eng.prefix_hits > 0            # sharing actually engaged

    def test_kernel_matches_xla(self, served, rng):
        cfg, params = self._cfgs(served)
        reqs = _requests(rng, 4)
        xla, _ = _serve(params, cfg, reqs)
        fused, _ = _serve(params, cfg.replace(decode_kernel="fused"), reqs)
        assert xla == fused

    def test_engine_reuse_matches_fresh_engine(self, served, rng):
        """Blocks freed at EOS and REALLOCATED for later requests still hold
        the prior owner's scales; the fresh-block reset must zero them, or a
        reused engine diverges from a fresh one."""
        cfg, params = self._cfgs(served)
        first = _requests(rng, 4)
        second = _requests(rng, 4)
        eng = PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16)
        for r in copy.deepcopy(first):
            eng.submit(r)
        eng.run()
        for r in (batch2 := copy.deepcopy(second)):
            eng.submit(r)
        eng.run()
        reused = {r.uid: r.out_tokens for r in batch2}
        fresh, _ = _serve(params, cfg, second)
        assert reused == fresh


class TestSharedBlockIntegrity:
    def test_cow_copies_scales_and_shared_bytes_frozen(self, served, rng):
        """With prefix sharing, the full-prompt-hit COW path must copy the
        source block's scales with its payload, and the SHARED blocks' int8
        bytes + scales must be bit-unchanged after the forking requests run
        to completion (shared KV is immutable for its cached lifetime)."""
        cfg, params = served
        cfg = cfg.replace(kv_quant="int8", prefix_sharing=True)
        prompt = rng.integers(0, 256, 32).astype(np.int32)   # 2 full blocks
        eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16)
        eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=4))
        eng.run()
        shared = sorted(eng.trie.blocks())
        assert len(shared) == 2
        lay = eng._cache["layers"]
        snap = {n: np.asarray(lay[n][:, shared]).copy()
                for n in ("k", "v", "k_scale", "v_scale")}
        # identical prompt: full-prompt hit -> fork + COW copy of the last
        # shared block (re-fed final token writes inside it)
        eng.submit(Request(uid=1, prompt=prompt.copy(), max_new_tokens=4))
        eng.run()
        assert eng.cow_copies >= 1
        lay = eng._cache["layers"]
        for n in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                np.asarray(lay[n][:, shared]), snap[n], n)

    def test_cow_destination_dequantizes_identically(self, served, rng):
        """Directly check the copy: after _cow_shared duplicates a shared
        block, destination payload AND scales equal the source's."""
        cfg, params = served
        cfg = cfg.replace(kv_quant="int8", prefix_sharing=True)
        prompt = rng.integers(0, 256, 32).astype(np.int32)
        eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16)
        eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=4))
        eng.run()
        src = max(eng.trie.blocks())          # last shared block
        from repro.serve.paged import _copy_block_kv
        free = eng.alloc.alloc()
        eng._cache = dict(eng._cache, layers=_copy_block_kv(
            eng._cache["layers"], jnp.int32(src), jnp.int32(free)))
        lay = eng._cache["layers"]
        for n in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(np.asarray(lay[n][:, free]),
                                          np.asarray(lay[n][:, src]), n)


# -------------------------------------------------------------- tolerance --


class TestDriftTolerance:
    """int8-vs-fp KV is the ONLY tolerance-gated comparison. Thresholds are
    pinned from measurement on this exact seeded workload (tiny 2-layer
    model, 12 mixed-length requests x 12 greedy tokens): measured
    exact-match 0.979 (141/144), per-step logit MAE mean 0.024 / max 0.204
    against fp logits of absmax ~4.8. The max-MAE steps are POST-DIVERGENCE
    (once a greedy token flips, later steps compare logits of different
    inputs — sequence drift, not dequant error; pre-divergence steps measure
    ~0.03 max). Gates leave 2-3x margin — a regression in the fold, the
    dequant path, or the rounding mode blows well past them."""
    EXACT_MATCH_MIN = 0.90
    LOGIT_MAE_MEAN_MAX = 0.08
    LOGIT_MAE_STEP_MAX = 0.5

    def _run(self, params, cfg, reqs, record):
        eng = PagedEngine(params, cfg, max_batch=4, max_len=64, block_size=16)
        orig = eng._packed_fn

        def wrapped(w, hccs, toks, pos, cache, extras, lane_idx):
            logits, cache = orig(w, hccs, toks, pos, cache, extras, lane_idx)
            record.append(np.asarray(logits))
            return logits, cache

        eng._packed_fn = wrapped
        for r in copy.deepcopy(reqs):
            eng.submit(r)
        return {r.uid: r.out_tokens for r in eng.run()}

    def test_int8_drift_within_gate(self, served, rng):
        cfg, params = served
        reqs = _requests(rng, 12, max_new=12)
        lf, lq = [], []
        fp = self._run(params, cfg, reqs, lf)
        q8 = self._run(params, cfg.replace(kv_quant="int8"), reqs, lq)
        toks_f = [t for u in sorted(fp) for t in fp[u]]
        toks_q = [t for u in sorted(q8) for t in q8[u]]
        assert len(toks_f) == len(toks_q)
        match = np.mean([a == b for a, b in zip(toks_f, toks_q)])
        assert match >= self.EXACT_MATCH_MIN, match
        assert len(lf) == len(lq)
        maes = [np.abs(a - b).mean() for a, b in zip(lf, lq)]
        assert np.mean(maes) <= self.LOGIT_MAE_MEAN_MAX, np.mean(maes)
        assert np.max(maes) <= self.LOGIT_MAE_STEP_MAX, np.max(maes)


# ------------------------------------------------------------- byte stats --


class TestKVByteStats:
    def test_paged_int8_vs_fp32_exact_accounting(self, tiny_cfg):
        """int8 paged pools: payload bytes = fp32/4 under the same
        lane-padding rules, plus the per-block scale arrays counted IN FULL
        on both the logical and padded side."""
        cfg = tiny_cfg()
        from repro.serve.paged import init_paged_cache
        fp = init_paged_cache(cfg, 8, 16, 4)
        q8 = init_paged_cache(cfg.replace(kv_quant="int8"), 8, 16, 4)
        sf = kv_cache_byte_stats(fp, cfg, None)
        sq = kv_cache_byte_stats(q8, cfg, None)
        scale_bytes = 2 * cfg.num_layers * 8 * cfg.num_kv_heads * 4
        assert sq["cache_bytes_padded"] == \
            sf["cache_bytes_padded"] // 4 + scale_bytes
        assert sq["cache_bytes_logical"] == \
            sf["cache_bytes_logical"] // 4 + scale_bytes
        # the acceptance ratio the serving benchmark gates on
        assert sq["cache_bytes_padded"] <= 0.35 * sf["cache_bytes_padded"]

    def test_paged_int8_lane_padding_rules_unchanged(self, tiny_cfg):
        """With the fused kernel active the pool is lane-padded (head_dim ->
        128); quantization must not change the padding rule, only the
        itemsize — and scales (metadata) are never lane-padded."""
        cfg = tiny_cfg(attention_prob="hccs", decode_kernel="fused")
        from repro.serve.paged import init_paged_cache
        fp = init_paged_cache(cfg, 8, 16, 4)
        q8 = init_paged_cache(cfg.replace(kv_quant="int8"), 8, 16, 4)
        assert q8["layers"]["k"].shape == fp["layers"]["k"].shape
        assert q8["layers"]["k"].dtype == jnp.int8
        sq = kv_cache_byte_stats(q8, cfg, None)
        scale_bytes = 2 * cfg.num_layers * 8 * cfg.num_kv_heads * 4
        hd_c = fp["layers"]["k"].shape[-1]
        assert hd_c == 128                    # padding rule actually engaged
        payload_padded = 2 * fp["layers"]["k"].size        # 1 byte per elem
        payload_logical = payload_padded * cfg.head_dim // hd_c
        assert sq["cache_bytes_padded"] == payload_padded + scale_bytes
        assert sq["cache_bytes_logical"] == payload_logical + scale_bytes

    def test_slot_arena_dtype_accounting(self, tiny_cfg):
        """Slot arenas: bf16 halves fp32 bytes; max_len trimming applies to
        logical only — the fp-side rules this PR must not disturb."""
        cfg = tiny_cfg()
        c32 = M.init_cache(cfg, 4, 32, jnp.float32, per_slot_lengths=True)
        c16 = M.init_cache(cfg, 4, 32, jnp.bfloat16, per_slot_lengths=True)
        s32 = kv_cache_byte_stats(c32, cfg, 32)
        s16 = kv_cache_byte_stats(c16, cfg, 32)
        assert s16["cache_bytes_padded"] * 2 == s32["cache_bytes_padded"]
        assert s16["cache_bytes_logical"] * 2 == s32["cache_bytes_logical"]


# ------------------------------------------------- cache-dtype single source --


class TestCacheDtypeSingleSource:
    def test_default_flows_from_cfg_everywhere(self, tiny_cfg):
        cfg = tiny_cfg(cache_dtype="bfloat16")
        assert M.init_cache(cfg, 2, 32)["layers"]["k"].dtype == jnp.bfloat16
        from repro.serve.paged import init_paged_cache
        assert init_paged_cache(cfg, 4, 16, 2)["layers"]["k"].dtype \
            == jnp.bfloat16
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        paged = PagedEngine(params, cfg, max_len=32, block_size=16)
        for eng in (ServeEngine(params, cfg),
                    ContinuousEngine(params, cfg, max_len=32), paged):
            assert eng.cache_dtype == jnp.bfloat16
        assert paged._cache["layers"]["k"].dtype == jnp.bfloat16

    def test_explicit_override_still_wins(self, tiny_cfg):
        cfg = tiny_cfg(cache_dtype="bfloat16")
        c = M.init_cache(cfg, 2, 32, jnp.float32)
        assert c["layers"]["k"].dtype == jnp.float32

    def test_cfg_validation(self, tiny_cfg):
        with pytest.raises(ValueError, match="cache_dtype"):
            tiny_cfg(cache_dtype="int4")
        with pytest.raises(ValueError, match="kv_quant"):
            tiny_cfg(kv_quant="int4")

    def test_slot_engines_reject_kv_quant(self, tiny_cfg):
        cfg = tiny_cfg(kv_quant="int8")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(params, cfg)
        with pytest.raises(ValueError, match="paged"):
            ContinuousEngine(params, cfg, max_len=32)
