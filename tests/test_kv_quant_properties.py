"""Property tests for the kv_quant per-row fold (paged_quant_scatter).

The fold's contract, fuzzed here rather than spot-checked:
  * bit-exact agreement with an independent numpy model of the running-amax
    requant rule (float32 arithmetic end to end);
  * PARTITION INDEPENDENCE — folding the same rows through any sequence of
    write groups produces identical pool bytes and scales (the invariant
    that makes engine steps of any token budget bit-identical under
    quantization);
  * scales grow monotonically and always cover the rows written so far
    (every landed row's amax <= 127 * scale, so no row is ever clipped by a
    LATER write — the "already-written rows stay representable" half of the
    requant contract).
"""
from conftest import require_hypothesis

hypothesis = require_hypothesis()

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.attention import (KV_QUANT_EPS, KV_QUANT_INV_QMAX,
                                    paged_quant_scatter)

N, HKV, BS, HD = 3, 2, 4, 3


def _np_half_away(x):
    return np.sign(x) * np.floor(np.abs(x) + np.float32(0.5))


def _np_fold(pool, scales, rows, positions):
    pool = pool.astype(np.float32).copy()
    scales = scales.astype(np.float32).copy()
    for x, p in zip(rows, positions):
        blk, r = int(p) // BS, int(p) % BS
        x = x.astype(np.float32)
        s_new = np.maximum(scales[blk],
                           np.maximum(np.abs(x).max(-1),
                                      np.float32(KV_QUANT_EPS))
                           * np.float32(KV_QUANT_INV_QMAX))
        ratio = (scales[blk] / s_new).astype(np.float32)
        payload = np.clip(_np_half_away(pool[blk] * ratio[:, None, None]),
                          -128, 127)
        payload[:, r, :] = np.clip(_np_half_away(x / s_new[:, None]),
                                   -128, 127)
        pool[blk] = payload
        scales[blk] = s_new
    return pool.astype(np.int8), scales


def _jax_fold_groups(rows, positions, splits):
    pool = jnp.zeros((N, HKV, BS, HD), jnp.int8)
    scales = jnp.zeros((N, HKV), jnp.float32)
    o = 0
    for g in splits:
        new_kv = jnp.asarray(np.stack(rows[o:o + g], axis=1)[None])
        wp = jnp.asarray(np.asarray(positions[o:o + g], np.int32)[None])
        pool, scales = paged_quant_scatter(pool, scales, new_kv, wp)
        o += g
    return np.asarray(pool), np.asarray(scales)


@st.composite
def fold_case(draw):
    """Rows written in position order (the engine's write discipline: each
    slot's frontier only advances), values spanning ~4 orders of magnitude
    so running-amax growth and the eps floor both get exercised."""
    t = draw(st.integers(1, N * BS))
    vals = draw(st.lists(
        st.floats(-100.0, 100.0, width=32, allow_nan=False),
        min_size=t * HKV * HD, max_size=t * HKV * HD))
    rows = [np.asarray(vals[i * HKV * HD:(i + 1) * HKV * HD],
                       np.float32).reshape(HKV, HD) for i in range(t)]
    positions = list(range(t))
    # a random ordered partition of the t rows into write groups
    cuts = sorted(draw(st.sets(st.integers(1, t - 1), max_size=t - 1))) \
        if t > 1 else []
    splits = [b - a for a, b in zip([0] + cuts, cuts + [t])]
    return rows, positions, splits


@given(fold_case())
@settings(max_examples=60, deadline=None)
def test_fold_matches_numpy_model_and_is_partition_independent(case):
    rows, positions, splits = case
    ref_pool, ref_scales = _np_fold(
        np.zeros((N, HKV, BS, HD), np.int8),
        np.zeros((N, HKV), np.float32), rows, positions)
    # one-shot fold == numpy model, bit for bit
    pool1, scales1 = _jax_fold_groups(rows, positions, [len(rows)])
    np.testing.assert_array_equal(pool1, ref_pool)
    np.testing.assert_array_equal(scales1, ref_scales)
    # any partition of the same rows folds to the same bytes
    poolg, scalesg = _jax_fold_groups(rows, positions, splits)
    np.testing.assert_array_equal(poolg, ref_pool, splits)
    np.testing.assert_array_equal(scalesg, ref_scales, splits)


@given(fold_case())
@settings(max_examples=40, deadline=None)
def test_scales_monotone_and_cover_written_rows(case):
    rows, positions, _ = case
    pool = jnp.zeros((N, HKV, BS, HD), jnp.int8)
    scales = jnp.zeros((N, HKV), jnp.float32)
    prev = np.zeros((N, HKV), np.float32)
    amax_so_far = np.zeros((N, HKV), np.float32)
    for row, p in zip(rows, positions):
        pool, scales = paged_quant_scatter(
            pool, scales, jnp.asarray(row[None, :, None]),
            jnp.asarray([[p]], jnp.int32))
        cur = np.asarray(scales)
        assert (cur >= prev).all()            # grow-only running amax
        blk = p // BS
        amax_so_far[blk] = np.maximum(amax_so_far[blk], np.abs(row).max(-1))
        # every row written so far stays representable: amax <= 127 * scale
        assert (amax_so_far[blk] <= 127.0 * cur[blk] * (1 + 1e-6)).all()
        prev = cur
