"""granite-4.0-h's layer pattern served through PagedEngine's packed step,
against the plain float32 reference of bench/archs/hybrid.py.

A tiny configuration of two periods (Mamba-2, Mamba-2, attention) runs in
float32 on the CPU with the benchmark's seeded weights. Each step's logits
at the positions the engine samples are compared with the reference's full
forward over the same tokens: prefill then decode through the state arena,
prompts that take several chunked-prefill steps with ragged lengths in one
step, a slot reused after a finish, pad lanes beside a decoding slot, and
the conv state across step boundaries.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as BM
from bench import reference as R
from bench import registry

SEED = 2 ** 31 + 17
# Both sides compute in float32 from the same bf16-rounded weights and
# differ only by summation order (the chunked SSD against the reference's
# sequential recurrence, the packed grid against per-sequence attention):
# at most 6.5e-8 over these cases, on logits that spread over about 0.06.
# An HCCS logit rounding across a quantisation step on one side only would
# move them by about 5e-5; none does here. 1e-5 leaves 150x room, and
# fails a state kept in bfloat16 (9e-5 to 1e-3), a conv left out (0.04 to
# 0.06) and a pad lane let into the state.
TOL = 1e-5


def tiny_config():
    c = registry.config("granite-4.0-h-micro")
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, shared_intermediate_size=128,
             vocab_size=256, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=6,
             layer_types=["mamba", "mamba", "attention"] * 2)
    c["model"].update(head_dim=16, vocab_size=256, dtype="float32")
    c["serving"].update(cache_dtype="float32", max_batch=3, max_len=128,
                        block_size=8, num_blocks=60)
    return c


@pytest.fixture(scope="module")
def setup():
    c = tiny_config()
    arch = registry.arch(c["arch"])
    cfg = arch.model_config(c)
    key = BM.seed_key(SEED)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32),
                           jax.jit(BM.weights_fn(c, cfg, arch))(key))
    return c, arch, cfg, {"weights": weights, "hccs": arch.hccs_params(c)}


def reference_logits(c, arch, prompt, out):
    """The reference's logits at each position whose output was served."""
    key = BM.seed_key(SEED)
    feed = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    gather = len(prompt) - 1 + np.arange(len(out))
    h = arch.hidden(key, jnp.asarray(feed[None]), jnp.asarray(gather[None]),
                    c, R._mm("f32"))[0]
    shape, _, rule = arch.leaf_table(c)["embed/table"]
    embed = BM.leaf_value(key, "embed/table", shape, rule, dtype=jnp.float32)
    return np.asarray(jnp.matmul(h, embed.T, precision=R.HIGHEST))


def serve(setup, prompts, max_new, max_batch=3, first=None, **kw):
    """Serve the prompts, the first `first` of them (all by default) one
    step before the rest; returns the requests and, per request uid, the
    logits the engine sampled each of its tokens from."""
    from repro.serve import PagedEngine, Request
    c, arch, cfg, params = setup
    sv = c["serving"]
    eng = PagedEngine(params, cfg, max_batch=max_batch, max_len=sv["max_len"],
                      block_size=sv["block_size"],
                      num_blocks=sv["num_blocks"], **kw)
    seen = {}
    real = eng._sample_and_finish

    def record(step, logits):
        samples = step.live & (step.t_valid >= step.remaining)
        for slot in np.flatnonzero(samples):
            seen.setdefault(eng._slots[slot].uid, []).append(
                np.asarray(logits[slot], np.float32))
        return real(step, logits)

    eng._sample_and_finish = record
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, max_new))]
    for r in reqs[:first]:
        eng.submit(r)
    if first is not None:
        eng.step()
    for r in reqs[len(reqs[:first]):]:
        eng.submit(r)
    eng.run()
    return eng, reqs, {u: np.stack(v) for u, v in seen.items()}


def gaps(setup, reqs, seen):
    c, arch = setup[0], setup[1]
    out = []
    for r in reqs:
        ref = reference_logits(c, arch, np.asarray(r.prompt),
                               np.asarray(r.out_tokens))
        got = seen[r.uid][:, :c["vocab_size"]]
        assert got.shape == ref.shape
        out.append(float(np.abs(got - ref).max()))
    return out


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def test_prefill_then_decode_through_the_arena(setup):
    eng, reqs, seen = serve(setup, prompts(5, 7), [12, 9])
    assert max(gaps(setup, reqs, seen)) < TOL
    st = eng.padding_stats()
    assert st["state_slots_moved"] > st["state_slots_live"] > 0
    assert st["state_bytes"] > 0


def test_chunked_prefill_with_ragged_lengths_in_one_step(setup):
    # a slot's chunk is capped at 4 blocks (32 tokens), so each prompt
    # takes several steps, and the steps mix 32-token chunks, tails and
    # decoding slots
    eng, reqs, seen = serve(setup, prompts(75, 41, 13, seed=1), [6, 10, 8])
    assert max(gaps(setup, reqs, seen)) < TOL


def test_a_reused_slot_starts_from_zero_state(setup):
    # one slot serves the three requests in turn
    eng, reqs, seen = serve(setup, prompts(20, 9, 17, seed=2), [5, 7, 4],
                            max_batch=1)
    assert max(gaps(setup, reqs, seen)) < TOL


def test_pad_lanes_beside_a_decoding_slot(setup):
    # the short prompt decodes while the long one prefills: its grid row
    # holds one token and Wb - 1 pad cells in every chunk step
    eng, reqs, seen = serve(setup, prompts(3, 90, seed=3), [20, 3])
    assert max(gaps(setup, reqs, seen)) < TOL


@pytest.mark.parametrize("lengths,step", [
    # the short prompt decodes while prompts of 7 and 8 tokens take two
    # rows of the chunk grid (Wb 8, P 3 at width 24)
    ((3, 7, 8), ([1, 7, 8], 24, (3, 8))),
    # ...and while a 40-token prompt takes 22 (the chunk grid's one row at
    # width 24 and Wb 24) and a 50-token prompt one lane (the decode grid)
    ((3, 40, 50), ([1, 22, 1], 24, (1, 24)))], ids=["two-chunks", "fitted"])
def test_a_decoding_slot_beside_two_prefilling_slots(setup, monkeypatch,
                                                     lengths, step):
    import repro.serve.paged as paged
    real, steps = paged.grid_steering, []

    def record(t_valid, *a, **kw):
        g = real(t_valid, *a, **kw)
        steps.append((np.asarray(t_valid).tolist(), g["attn_cell"].size,
                      g["chunk_q_pos"].shape if "chunk_q_pos" in g else None))
        return g
    monkeypatch.setattr(paged, "grid_steering", record)
    eng, reqs, seen = serve(setup, prompts(*lengths, seed=6), [10, 4, 4],
                            first=1)
    assert step in steps, steps
    assert max(gaps(setup, reqs, seen)) < TOL


def test_a_pad_lane_leaves_the_state_unchanged(setup):
    """On the packed step's (B, Wb) slot grid, a slot with no token keeps
    its states bit for bit, and a row whose tokens are followed by pad
    cells ends in the state those tokens give."""
    from repro.models import ssm
    c, arch, cfg, params = setup
    p = jax.tree.map(lambda a: a[0], params["weights"]["layers"]["mamba"])
    rng = np.random.default_rng(4)
    st = jnp.asarray(rng.normal(0, 1, (3, cfg.ssm_heads, cfg.ssm_head_dim,
                                       cfg.ssm_state)), jnp.float32)
    cs = jnp.asarray(rng.normal(0, 1, (3, cfg.ssm_conv - 1, cfg.conv_dim)),
                     jnp.float32)
    # slots 0, 1, 2 bring 0, 3 and 8 tokens; lane 11 is a pad lane
    wb, sid = 8, np.array([1] * 3 + [2] * 8 + [-1], np.int32)
    cell = np.array([1 * wb + i for i in range(3)]
                    + [2 * wb + i for i in range(8)] + [3 * wb], np.int32)
    length = jnp.asarray([5, 7, 2], jnp.int32)
    cache = {"slot_ids": jnp.asarray(sid), "grid_pos": jnp.asarray(cell),
             "q_pos_grid": jnp.zeros((3, wb), jnp.int32), "length": length,
             "kv_len_slot": length + jnp.asarray([0, 3, 8])}
    x = jnp.asarray(rng.normal(0, 1, (1, 12, cfg.d_model)), jnp.float32)
    _, st_g, cs_g = ssm.apply_ssd_packed(p["ssm"], x, cfg, st, cs, cache)
    np.testing.assert_array_equal(np.asarray(st_g[0]), np.asarray(st[0]))
    np.testing.assert_array_equal(np.asarray(cs_g[0]), np.asarray(cs[0]))
    _, st1, cs1 = ssm.apply_ssd(p["ssm"], x[:, :3], cfg, st[1:2], cs[1:2])
    np.testing.assert_allclose(np.asarray(st_g[1]), np.asarray(st1[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cs_g[1]), np.asarray(cs1[0]))


def test_the_conv_state_carries_across_step_boundaries(setup):
    """The mixer over 11 tokens at once equals it over 4 + 1 + 6 tokens,
    each call continuing from the last one's SSM and conv states."""
    from repro.models import ssm
    c, arch, cfg, params = setup
    p = jax.tree.map(lambda a: a[1], params["weights"]["layers"]["mamba"])
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (2, 11, 64)),
                    jnp.float32)
    whole, st_w, cs_w = ssm.apply_ssd(p["ssm"], x, cfg)
    st, cs = ssm.init_state(cfg, 2)
    parts = []
    for lo, hi in ((0, 4), (4, 5), (5, 11)):
        y, st, cs = ssm.apply_ssd(p["ssm"], x[:, lo:hi], cfg, st, cs)
        parts.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, 1)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cs), np.asarray(cs_w))


@pytest.mark.parametrize("cfg_kw,eng_kw,name", [
    ({}, dict(prefix_sharing=True), "prefix_sharing"),
    ({}, dict(decode_sharing=True), "decode_sharing"),
    ({}, dict(speculative=True), "speculative"),
    ({}, dict(async_loop=True), "async_loop"),
    ({"preemption": True}, {}, "preemption"),
    ({"kv_quant": "int8"}, {}, 'kv_quant="int8"'),
    ({"decode_kernel": "fused"}, {}, "decode_kernel='fused'"),
    ({}, dict(packed=False), "packed=False"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_switches_a_slot_state_cannot_keep_are_refused(setup, cfg_kw, eng_kw,
                                                       name):
    """Each switch the state arena cannot keep is refused by name; the
    lockstep layout (packed=False) is refused for every config."""
    from repro.serve import PagedEngine
    c, arch, cfg, params = setup
    match = ("lockstep step layout was removed" if name == "packed=False"
             else f"recurrent state.*{name}")
    with pytest.raises(ValueError, match=match):
        PagedEngine(params, cfg.replace(**cfg_kw), max_batch=2, max_len=64,
                    block_size=8, num_blocks=20, **eng_kw)
