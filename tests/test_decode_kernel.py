"""Parity tests for the fused single-query HCCS decode kernels.

hccs_decode is asserted against the pure-jnp oracle (kernels/ref.py) and
against hccs_mha_fused (the prefill kernel) on the last causal row, covering
causal semantics, GQA packing, per-slot padded lengths, and per-head theta.
hccs_paged_decode (the block-table gather variant) is asserted against its
own oracle and against hccs_decode on an equivalent contiguous layout,
covering sentinel skipping, scrambled physical block order, and sub-block
tiling. hccs_packed_prefill (the packed step's token-centric variant) is
asserted against its own oracle and against hccs_paged_decode with tokens
expanded to slots, covering the slot-id indirection, per-token frontiers,
and pad lanes. All cases run in interpret mode (CPU); on TPU they lower to
Mosaic.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import default_params
from repro.kernels import (hccs_attention, hccs_decode, hccs_packed_prefill,
                           hccs_paged_decode)
from repro.kernels import ref as REF

pytestmark = pytest.mark.kernel


def _case(rng, b, h, hkv, tmax, d, uniform_theta=True):
    q = jnp.asarray(rng.normal(0, 1, (b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, hkv, tmax, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, hkv, tmax, d)), jnp.float32)
    B, S, D = default_params(max(tmax, 4))
    theta = np.tile(np.asarray([[B, S, D]], np.int32), (h, 1))
    if not uniform_theta:
        # distinct per-head calibration: perturb D and zero one head's S
        theta[:, 2] = np.maximum(theta[:, 2] - 8 * np.arange(h), 1)
        theta[-1, 1] = 0
    scale = jnp.full((h,), 0.05, jnp.float32)
    return q, k, v, scale, jnp.asarray(theta)


@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("tmax,d", [(64, 32), (130, 32), (96, 128)])
def test_decode_vs_oracle_full_length(gqa, tmax, d, rng):
    h, hkv = gqa
    b = 3
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    lengths = jnp.full((b,), tmax, jnp.int32)
    got = hccs_decode(q, k, v, lengths, scale, theta, block_k=32)
    want = REF.hccs_decode_ref(q, k, v, lengths, scale, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


def test_decode_padded_lengths_mask_stale_cache(rng):
    """Mixed-progress slots: entries past each slot's length must not leak.
    Poisoning the tail of the cache with huge values must not change output."""
    b, h, hkv, tmax, d = 4, 4, 2, 96, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    lengths = jnp.asarray([1, 17, 64, 96], jnp.int32)
    got = hccs_decode(q, k, v, lengths, scale, theta, block_k=32)
    want = REF.hccs_decode_ref(q, k, v, lengths, scale, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)
    # poison beyond the frontier
    mask = (np.arange(tmax)[None, None, :, None]
            >= np.asarray(lengths)[:, None, None, None])
    k_p = jnp.where(jnp.asarray(mask), 1e6, k)
    v_p = jnp.where(jnp.asarray(mask), -1e6, v)
    poisoned = hccs_decode(q, k_p, v_p, lengths, scale, theta, block_k=32)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(got),
                               atol=1e-6)


def test_decode_zero_length_slot_returns_zeros(rng):
    b, h, hkv, tmax, d = 2, 4, 2, 64, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    lengths = jnp.asarray([0, 64], jnp.int32)
    out = np.asarray(hccs_decode(q, k, v, lengths, scale, theta, block_k=32))
    np.testing.assert_allclose(out[0], 0.0, atol=1e-7)
    assert np.abs(out[1]).max() > 0


def test_decode_per_head_theta(rng):
    b, h, hkv, tmax, d = 2, 4, 2, 64, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d,
                                  uniform_theta=False)
    lengths = jnp.asarray([40, 64], jnp.int32)
    got = hccs_decode(q, k, v, lengths, scale, theta, block_k=32)
    want = REF.hccs_decode_ref(q, k, v, lengths, scale, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


@pytest.mark.parametrize("mode", ["wide", "i16_div", "i16_clb"])
def test_decode_normalization_modes(mode, rng):
    b, h, hkv, tmax, d = 2, 4, 2, 64, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    lengths = jnp.asarray([33, 64], jnp.int32)
    got = hccs_decode(q, k, v, lengths, scale, theta, mode=mode, block_k=32)
    want = REF.hccs_decode_ref(q, k, v, lengths, scale, theta, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


def test_decode_static_max_single_pass(rng):
    b, h, hkv, tmax, d = 2, 4, 2, 64, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    # calibrate the scale so row maxima land near the int8 ceiling (the
    # static-max operating regime; see core/hccs.py)
    lengths = jnp.asarray([48, 64], jnp.int32)
    got = hccs_decode(q, k, v, lengths, scale, theta, static_max=True,
                      block_k=32)
    want = REF.hccs_decode_ref(q, k, v, lengths, scale, theta,
                               static_max=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


def test_decode_matches_fused_prefill_last_row(rng):
    """The decode kernel on the last causal query row must agree with the
    fused prefill kernel's last row (same 'wide' semantics, same KV window)."""
    b, h, hkv, t, d = 2, 4, 2, 64, 32
    qfull = jnp.asarray(rng.normal(0, 1, (b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, hkv, t, d)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, hkv, t, d)), jnp.float32)
    B, S, D = default_params(t)
    scale = jnp.full((h,), 0.05, jnp.float32)
    theta = jnp.tile(jnp.asarray([[B, S, D]], jnp.int32), (h, 1))
    full = hccs_attention(qfull, k, v, scale, theta, causal=True,
                          block_q=32, block_k=32)
    dec = hccs_decode(qfull[:, :, -1, :], k, v,
                      jnp.full((b,), t, jnp.int32), scale, theta, block_k=32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, :, -1, :]),
                               atol=5e-3)


def test_decode_block_size_invariant(rng):
    b, h, hkv, tmax, d = 2, 4, 2, 96, 32
    q, k, v, scale, theta = _case(rng, b, h, hkv, tmax, d)
    lengths = jnp.asarray([31, 96], jnp.int32)
    a = hccs_decode(q, k, v, lengths, scale, theta, block_k=16)
    c = hccs_decode(q, k, v, lengths, scale, theta, block_k=96)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


# ---------------------------------------------------------------- paged --

def _paged_case(rng, b, h, hkv, d, bs, nblk, lengths):
    """Random pool + valid block tables: each slot's first ceil(len/bs)
    table entries get distinct pool blocks (scrambled order), the rest are
    the -1 sentinel. Returns the paged operands plus the equivalent
    contiguous (B, Hkv, nblk*bs, d) k/v for cross-checking."""
    num_blocks = 1 + b * nblk                 # block 0 reserved (trash)
    q = jnp.asarray(rng.normal(0, 1, (b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(0, 1, (num_blocks, hkv, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(0, 1, (num_blocks, hkv, bs, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, num_blocks))
    table = np.full((b, nblk), -1, np.int32)
    taken = 0
    for i, ln in enumerate(lengths):
        held = -(-ln // bs)
        table[i, :held] = perm[taken:taken + held]
        taken += held
    B, S, D = default_params(max(nblk * bs, 4))
    theta = jnp.tile(jnp.asarray([[B, S, D]], jnp.int32), (h, 1))
    scale = jnp.full((h,), 0.05, jnp.float32)
    kc = np.asarray(kp)[np.maximum(table, 0)].transpose(0, 2, 1, 3, 4)
    vc = np.asarray(vp)[np.maximum(table, 0)].transpose(0, 2, 1, 3, 4)
    kc = jnp.asarray(kc.reshape(b, hkv, nblk * bs, d))
    vc = jnp.asarray(vc.reshape(b, hkv, nblk * bs, d))
    return q, kp, vp, jnp.asarray(table), scale, theta, kc, vc


@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("mode", ["wide", "i16_div", "i16_clb"])
def test_paged_decode_vs_oracle(gqa, mode, rng):
    h, hkv = gqa
    b, d, bs, nblk = 3, 32, 16, 4
    lengths = [40, 16, 7]
    q, kp, vp, table, scale, theta, _, _ = _paged_case(
        rng, b, h, hkv, d, bs, nblk, lengths)
    ln = jnp.asarray(lengths, jnp.int32)
    got = hccs_paged_decode(q, kp, vp, table, ln, scale, theta, mode=mode)
    want = REF.hccs_paged_decode_ref(q, kp, vp, table, ln, scale, theta,
                                     mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)


def test_paged_decode_matches_contiguous_kernel(rng):
    """Block-table gather over a scrambled pool must equal hccs_decode on the
    contiguous equivalent — physical block placement is semantically inert."""
    b, h, hkv, d, bs, nblk = 3, 4, 2, 32, 16, 4
    lengths = [40, 64, 1]
    q, kp, vp, table, scale, theta, kc, vc = _paged_case(
        rng, b, h, hkv, d, bs, nblk, lengths)
    ln = jnp.asarray(lengths, jnp.int32)
    got = hccs_paged_decode(q, kp, vp, table, ln, scale, theta)
    want = hccs_decode(q, kc, vc, ln, scale, theta, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_paged_decode_subblock_tiling_invariant(rng):
    """block_k < block_size sweeps each pool block in sub-tiles; the result
    must not depend on the tiling."""
    b, h, hkv, d, bs, nblk = 2, 4, 2, 32, 32, 3
    lengths = [50, 23]
    q, kp, vp, table, scale, theta, _, _ = _paged_case(
        rng, b, h, hkv, d, bs, nblk, lengths)
    ln = jnp.asarray(lengths, jnp.int32)
    a = hccs_paged_decode(q, kp, vp, table, ln, scale, theta, block_k=32)
    c = hccs_paged_decode(q, kp, vp, table, ln, scale, theta, block_k=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


# --------------------------------------------------------------- packed --

def _packed_case(rng, b, h, hkv, d, bs, nblk, slens, sid, lens):
    """A paged pool/table pair plus a packed token batch over it: sid (T,)
    assigns each token a slot (-1 = pad lane), lens (T,) its causal
    frontier. Reuses _paged_case for the pool/table construction."""
    _, kp, vp, table, scale, theta, _, _ = _paged_case(
        rng, b, h, hkv, d, bs, nblk, slens)
    t = len(sid)
    q = jnp.asarray(rng.normal(0, 1, (t, h, d)), jnp.float32)
    return (q, kp, vp, table, jnp.asarray(sid, jnp.int32),
            jnp.asarray(lens, jnp.int32), scale, theta)


@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("mode", ["wide", "i16_div", "i16_clb"])
def test_packed_prefill_vs_oracle(gqa, mode, rng):
    """Ragged mixed batch: several tokens of one slot at successive
    frontiers (a prefill chunk), single tokens of others (decode riders),
    and pad lanes — against the pure-jnp oracle."""
    h, hkv = gqa
    b, d, bs, nblk = 3, 32, 16, 4
    sid = [0, 0, 0, 1, 2, 2, 0, 1, -1, -1]
    lens = [38, 39, 40, 16, 6, 7, 17, 3, 0, 0]
    case = _packed_case(rng, b, h, hkv, d, bs, nblk, [40, 16, 7], sid, lens)
    got = hccs_packed_prefill(*case, mode=mode)
    want = REF.hccs_packed_prefill_ref(*case, mode=mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-3)
    # pad lanes return exact zeros
    np.testing.assert_allclose(np.asarray(got)[-2:], 0.0, atol=1e-7)


def test_packed_prefill_matches_paged_decode_per_token(rng):
    """A packed batch of T tokens must equal T single-slot hccs_paged_decode
    rows: the slot-id indirection is the only difference between the two
    walks."""
    b, h, hkv, d, bs, nblk = 3, 4, 2, 32, 16, 4
    sid = np.asarray([2, 0, 1, 0, 2], np.int32)
    lens = np.asarray([7, 40, 16, 39, 3], np.int32)
    q, kp, vp, table, sidj, lensj, scale, theta = _packed_case(
        rng, b, h, hkv, d, bs, nblk, [40, 16, 7], sid, lens)
    got = hccs_packed_prefill(q, kp, vp, table, sidj, lensj, scale, theta)
    want = hccs_paged_decode(q, kp, vp, table[sid], lensj, scale, theta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_packed_prefill_chunk_causality(rng):
    """Tokens of one chunk at successive frontiers: token i's output must
    not change when KV rows PAST its own frontier are poisoned — intra-chunk
    causality comes entirely from the per-token lengths."""
    b, h, hkv, d, bs, nblk = 2, 4, 2, 32, 16, 3
    sid = np.asarray([0, 0, 0, 1], np.int32)
    lens = np.asarray([33, 34, 35, 10], np.int32)
    q, kp, vp, table, sidj, lensj, scale, theta = _packed_case(
        rng, b, h, hkv, d, bs, nblk, [35, 10], sid, lens)
    got = hccs_packed_prefill(q, kp, vp, table, sidj, lensj, scale, theta)
    # poison slot 0's rows 33+ (the last two tokens of its final block):
    # only the tokens whose frontier covers them may change
    tbl = np.asarray(table)
    blk = int(tbl[0, 2])                      # slot 0's third block: rows 32+
    kp_p = np.asarray(kp).copy()
    kp_p[blk, :, 33 - 2 * bs:, :] = 1e6
    poisoned = hccs_packed_prefill(jnp.asarray(q), jnp.asarray(kp_p), vp,
                                   table, sidj, lensj, scale, theta)
    np.testing.assert_allclose(np.asarray(poisoned)[0], np.asarray(got)[0],
                               atol=1e-6)    # frontier 33: sees rows < 33
    np.testing.assert_allclose(np.asarray(poisoned)[3], np.asarray(got)[3],
                               atol=1e-6)    # other slot: structurally blind
    assert np.abs(np.asarray(poisoned)[2] - np.asarray(got)[2]).max() > 0


def test_paged_decode_sentinel_blocks_inert(rng):
    """Poisoning every block the tables do NOT own (incl. the trash block)
    must not change any output — dead entries are skipped, tails masked."""
    b, h, hkv, d, bs, nblk = 3, 4, 2, 32, 16, 4
    lengths = [40, 16, 0]                     # slot 2 holds nothing
    q, kp, vp, table, scale, theta, _, _ = _paged_case(
        rng, b, h, hkv, d, bs, nblk, lengths)
    ln = jnp.asarray(lengths, jnp.int32)
    got = hccs_paged_decode(q, kp, vp, table, ln, scale, theta)
    np.testing.assert_allclose(np.asarray(got)[2], 0.0, atol=1e-7)
    owned = np.unique(np.asarray(table)[np.asarray(table) >= 0])
    mask = np.ones(kp.shape[0], bool)
    mask[owned] = False
    kp_p = jnp.where(jnp.asarray(mask)[:, None, None, None], 1e6, kp)
    vp_p = jnp.where(jnp.asarray(mask)[:, None, None, None], -1e6, vp)
    poisoned = hccs_paged_decode(q, kp_p, vp_p, table, ln, scale, theta)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(got),
                               atol=1e-6)
    # the partially-filled tail of a live block is masked too
    tail = np.array(kp)
    blk40 = int(np.asarray(table)[0, 2])      # slot 0's third block: rows 8+
    tail[blk40, :, 40 - 2 * bs:, :] = 1e6
    poisoned2 = hccs_paged_decode(q, jnp.asarray(tail), vp, table, ln,
                                  scale, theta)
    np.testing.assert_allclose(np.asarray(poisoned2), np.asarray(got),
                               atol=1e-6)
