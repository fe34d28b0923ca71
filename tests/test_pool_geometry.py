"""The paged pool's two geometries hold the same values.

The XLA attention path keeps its pool token-major, (L, N, bs, Hkv*hd), and
writes and reads it through the whole stack by layer index; the fused
kernels keep one head-major (N, Hkv, bs, hd) pool per layer. Each
layer-indexed helper must give, at every layer, exactly what the per-layer
head-major helper gives at that layer, and leave every other layer as it
was: same bytes for the plain and the quantizing scatter, same values for
the gather.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (_paged_gather, _paged_scatter,
                                    paged_quant_scatter)
from repro.serve.paged import init_paged_cache

L, N, HKV, BS, HD, B, T = 3, 6, 2, 4, 5, 2, 5


def token_major(head_major):
    """(L, N, Hkv, bs, hd) -> (L, N, bs, Hkv*hd)."""
    l, n, hkv, bs, hd = head_major.shape
    return head_major.transpose(0, 1, 3, 2, 4).reshape(l, n, bs, hkv * hd)


def _write_pos():
    """Slot 0 writes rows 2..6 (across blocks 3 and 1), slot 1 two rows of
    block 5 and three pad lanes steered into the trash block 0."""
    s0 = [3 * BS + 2, 3 * BS + 3, 1 * BS + 0, 1 * BS + 1, 1 * BS + 2]
    s1 = [5 * BS + 0, 5 * BS + 1, 0 * BS + 0, 0 * BS + 1, 0 * BS + 2]
    return jnp.asarray([s0, s1], jnp.int32)


def _pools(rng, quant):
    if quant:
        hm = rng.integers(-128, 128, (L, N, HKV, BS, HD)).astype(np.int8)
        sc = rng.uniform(0.01, 0.05, (L, N, HKV)).astype(np.float32)
        sc[:, 5] = 0.0                     # a freshly allocated block
        return jnp.asarray(hm), jnp.asarray(sc)
    return jnp.asarray(rng.normal(size=(L, N, HKV, BS, HD)), jnp.float32), None


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_scatter_matches_head_major_at_every_layer(rng, quant):
    hm, sc = _pools(rng, quant)
    tm = token_major(hm)
    new_kv = jnp.asarray(rng.normal(scale=2.0, size=(B, HKV, T, HD)),
                         jnp.float32)
    wp = _write_pos()
    draft = jnp.asarray([[False, False, True, True, False],
                         [True, False, False, False, False]])
    for li in range(L):
        if quant:
            got, got_sc = paged_quant_scatter(tm, sc, new_kv, wp, draft, li)
            one, one_sc = paged_quant_scatter(hm[li], sc[li], new_kv, wp,
                                              draft_rows=draft)
            want_sc = sc.at[li].set(one_sc)
            np.testing.assert_array_equal(np.asarray(got_sc),
                                          np.asarray(want_sc))
        else:
            got = _paged_scatter(tm, new_kv, wp, li)
            one = _paged_scatter(hm[li], new_kv, wp)
        want = token_major(hm.at[li].set(one))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_gather_matches_head_major_at_every_layer(rng, quant):
    hm, sc = _pools(rng, quant)
    tm = token_major(hm)
    table = jnp.asarray([[3, 1, -1], [5, 2, 4]], jnp.int32)   # -1: sentinel
    for li in range(L):
        got = _paged_gather(tm, table, HD, sc, li)
        want = _paged_gather(hm[li], table, HD,
                             None if sc is None else sc[li])
        assert got.shape == (B, HKV, 3 * BS, HD)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kernel,shape", [
    ("none", (2, 8, 16, 2 * 16)),          # token-major: Hkv 2 x hd 16
    ("fused", (2, 8, 2, 16, 128)),         # head-major, hd padded to 128
])
def test_pool_geometry_follows_its_reader(tiny_cfg, kernel, shape):
    cfg = tiny_cfg(attention_prob="hccs", decode_kernel=kernel)
    pool = init_paged_cache(cfg, 8, 16, 4)["layers"]["k"]
    assert (cfg.num_layers, cfg.num_kv_heads, cfg.head_dim) == (2, 2, 16)
    assert pool.shape == shape
