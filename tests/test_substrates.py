"""Substrate tests: checkpoint roundtrip/elastic restore, MoE dispatch, SSD
chunked-vs-recurrent, optimizer, grad compression, data determinism, serving,
blockwise==dense attention, fault-tolerant loop."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import model as M

jax.config.update("jax_platform_name", "cpu")


def tiny_cfg(**kw):
    base = dict(name="t", family="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                vocab_pad_multiple=1)
    base.update(kw)
    return ModelConfig(**base)


# ----------------------------------------------------------- checkpoint ---

class TestCheckpoint:
    def test_roundtrip_exact(self):
        from repro.checkpoint import CheckpointManager
        cfg = tiny_cfg()
        tcfg = TrainConfig()
        from repro.train import make_train_state
        state = make_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(7, state, cfg=cfg)
            restored, manifest = mgr.restore(state, cfg=cfg)
            assert manifest["step"] == 7
            for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_config_mismatch_rejected(self):
        from repro.checkpoint import CheckpointManager
        cfg = tiny_cfg()
        tree = {"w": jnp.ones((4,))}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, tree, cfg=cfg)
            with pytest.raises(ValueError):
                mgr.restore(tree, cfg=tiny_cfg(d_model=128))

    def test_latest_pointer_and_gc(self):
        from repro.checkpoint import CheckpointManager
        tree = {"w": jnp.ones((4,))}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            for s in (1, 2, 3, 4):
                mgr.save(s, tree)
            assert mgr.latest_step() == 4
            kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
            assert len(kept) == 2

    @pytest.mark.slow
    def test_async_save(self):
        from repro.checkpoint import CheckpointManager
        tree = {"w": jnp.arange(8.0)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save_async(3, tree)
            mgr.wait()
            restored, _ = mgr.restore(tree)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.arange(8.0))

    @pytest.mark.slow
    def test_elastic_restore_new_sharding(self):
        """Checkpoint written unsharded restores under explicit shardings
        (the elastic-remesh path)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, tree)
            restored, _ = mgr.restore(tree, shardings=sh)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(tree["w"]))


# ------------------------------------------------------------------ MoE ---

class TestMoE:
    def test_dispatch_combine_identity_single_expert(self):
        """E=1, K=1, ample capacity: MoE == plain FFN on every token."""
        from repro.models.moe import apply_moe, init_moe
        cfg = tiny_cfg(family="moe", num_experts=1, experts_per_token=1,
                       moe_capacity_factor=2.0)
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 8, 64)),
                        jnp.float32)
        out, aux = apply_moe(p, x, cfg)
        w = p["experts"]
        xf = x.reshape(-1, 64)
        h = jax.nn.silu(xf @ w["w_gate"][0]) * (xf @ w["w_in"][0])
        want = (h @ w["w_out"][0]).reshape(2, 8, 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=1e-5)

    def test_capacity_drops_overflow(self):
        from repro.models.moe import apply_moe, init_moe
        cfg = tiny_cfg(family="moe", num_experts=4, experts_per_token=1,
                       moe_capacity_factor=0.25)  # tiny capacity
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 16, 64)),
                        jnp.float32)
        out, aux = apply_moe(p, x, cfg)   # must not error; some tokens zeroed
        assert bool(jnp.isfinite(out).all())

    def test_hccs_router_ordering_matches_quantized_logits(self):
        """HCCS preserves ordering OF THE QUANTIZED LOGITS exactly (ties in
        the int8 grid are ties in HCCS too); hence expert selection equals
        softmax-on-quantized-logits selection up to in-tie permutation."""
        from repro.core.constraints import default_params
        from repro.core.hccs import HCCSParams, hccs_qat
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(0, 2, (32, 16)), jnp.float32)
        scale = 0.05
        B, S, D = default_params(16)
        p = HCCSParams(B=jnp.int32(B), S=jnp.int32(S), D=jnp.int32(D))
        probs_h = np.asarray(hccs_qat(logits, scale, p, "i16_div"))
        q = np.clip(np.round(np.asarray(logits) / scale), -128, 127)
        for row_p, row_q in zip(probs_h, q):
            # strictly larger quantized logit => prob >= (monotone)
            order = np.argsort(row_q, kind="stable")
            assert (np.diff(row_p[order]) >= -1e-9).all()
            # equal quantized logits => exactly equal probs (ties preserved)
            for val in np.unique(row_q):
                ps = row_p[row_q == val]
                assert np.allclose(ps, ps[0], atol=1e-9)


# ------------------------------------------------------------------ SSD ---

class TestSSD:
    def test_chunked_matches_recurrent(self):
        """The chunked SSD (training path) == step-by-step recurrence, the
        conv state carried from step to step."""
        from repro.models.ssm import apply_ssd, init_ssm, init_state
        cfg = tiny_cfg(family="ssm", num_heads=0, num_kv_heads=0, d_ff=0,
                       ssm_state=8, ssm_head_dim=16, ssm_chunk=4)
        p = init_ssm(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 12, 64)),
                        jnp.float32)
        y_chunked, state_final, conv_final = apply_ssd(p, x, cfg)
        state, conv = init_state(cfg, 2)
        ys = []
        for t in range(12):
            y_t, state, conv = apply_ssd(p, x[:, t:t + 1], cfg, state, conv)
            ys.append(y_t)
        y_rec = jnp.concatenate(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_chunked), np.asarray(y_rec),
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(state_final), np.asarray(state),
                                   atol=2e-4)
        np.testing.assert_array_equal(np.asarray(conv_final),
                                      np.asarray(conv))

    @pytest.mark.slow
    def test_chunk_size_invariance(self):
        from repro.models.ssm import apply_ssd, init_ssm
        cfg4 = tiny_cfg(family="ssm", num_heads=0, num_kv_heads=0, d_ff=0,
                        ssm_state=8, ssm_head_dim=16, ssm_chunk=4)
        cfg6 = cfg4.replace(ssm_chunk=6)
        p = init_ssm(jax.random.PRNGKey(0), cfg4)
        x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (1, 24, 64)),
                        jnp.float32)
        y4, s4, _ = apply_ssd(p, x, cfg4)
        y6, s6, _ = apply_ssd(p, x, cfg6)
        np.testing.assert_allclose(np.asarray(y4), np.asarray(y6), atol=2e-4)
        np.testing.assert_allclose(np.asarray(s4), np.asarray(s6), atol=2e-4)


# ------------------------------------------------------- optim/compress ---

class TestOptim:
    def test_adamw_decreases_quadratic(self):
        from repro.optim import adamw
        tcfg = TrainConfig(learning_rate=0.1, warmup_steps=0, total_steps=100,
                           weight_decay=0.0)
        params = {"w": jnp.asarray([3.0, -2.0])}
        state = adamw.init(params)
        for _ in range(60):
            g = {"w": 2 * params["w"]}
            params, state, _ = adamw.apply_updates(params, g, state, tcfg)
        assert float(jnp.abs(params["w"]).max()) < 0.5

    def test_grad_clip(self):
        from repro.optim import adamw
        tcfg = TrainConfig(learning_rate=0.0, grad_clip=1.0)
        params = {"w": jnp.zeros(3)}
        state = adamw.init(params)
        _, _, stats = adamw.apply_updates(params, {"w": jnp.full(3, 100.0)},
                                          state, tcfg)
        assert float(stats["grad_norm"]) > 100

    @pytest.mark.slow
    def test_compression_error_feedback_unbiased(self):
        """With EF, the running sum of dequantized grads tracks the true sum."""
        from repro.optim import compression
        rng = np.random.default_rng(0)
        g_true = jnp.asarray(rng.normal(0, 1, (64,)), jnp.float32)
        err = None
        acc = jnp.zeros(64)
        key = jax.random.PRNGKey(0)
        for i in range(50):
            key, sub = jax.random.split(key)
            deq, err = compression.compress_grads({"g": g_true},
                                                  {"g": err["g"]} if err else None,
                                                  sub)
            acc = acc + deq["g"]
            err = {"g": err["g"]}
        rel = float(jnp.linalg.norm(acc / 50 - g_true) /
                    jnp.linalg.norm(g_true))
        assert rel < 0.05


# ------------------------------------------------------------- data ---

class TestData:
    def test_deterministic_across_restarts(self):
        from repro.data import LMStream, LMStreamConfig
        c = LMStreamConfig(vocab_size=128, seq_len=16, global_batch=4, seed=1)
        a = LMStream(c).batch_at(7)
        b = LMStream(c).batch_at(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_sharding_partitions_batch(self):
        from repro.data import LMStream, LMStreamConfig
        c = LMStreamConfig(vocab_size=128, seq_len=16, global_batch=4, seed=1)
        s0 = LMStream(c, shard=0, num_shards=2).batch_at(3)
        s1 = LMStream(c, shard=1, num_shards=2).batch_at(3)
        assert s0["tokens"].shape == (2, 16)
        assert not np.array_equal(s0["tokens"], s1["tokens"])

    def test_cls_task_learnable_signal(self):
        from repro.data import ClsTask, ClsTaskConfig
        task = ClsTask(ClsTaskConfig(vocab_size=1000, seq_len=32, seed=0))
        b = task.batch_at(0, 64)
        assert set(np.unique(b["cls_labels"])) <= {0, 1}
        v = task.batch_at(0, 64, split="val")
        assert not np.array_equal(b["tokens"], v["tokens"])


# ----------------------------------------------------------- serving ---

class TestServing:
    def test_wave_engine_greedy_matches_manual_decode(self):
        from repro.serve import Request, ServeEngine
        cfg = tiny_cfg()
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompt = np.arange(6, dtype=np.int32) + 5
        eng = ServeEngine(params, cfg, max_batch=2, max_len=32)
        eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        done = eng.run()
        # manual greedy
        lg, cache = M.prefill(params["weights"], params["hccs"],
                              {"tokens": jnp.asarray(prompt)[None]}, cfg,
                              max_len=32, cache_dtype=jnp.float32)
        toks = [int(jnp.argmax(lg[0]))]
        for _ in range(3):
            lg, cache = M.decode_step(params["weights"], params["hccs"],
                                      jnp.asarray([[toks[-1]]]), cache, cfg)
            toks.append(int(jnp.argmax(lg[0])))
        assert done[0].out_tokens == toks

    def test_wave_batching_by_length(self):
        from repro.serve import Request, ServeEngine
        cfg = tiny_cfg()
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, max_batch=4, max_len=32)
        for i, ln in enumerate([5, 5, 7, 5]):
            eng.submit(Request(uid=i, prompt=np.arange(ln, dtype=np.int32),
                               max_new_tokens=2))
        done = eng.run()
        assert len(done) == 4
        assert all(r.done for r in done)


# -------------------------------------------------------------- loop ---

class TestTrainLoop:
    def test_straggler_monitor(self):
        from repro.train.loop import StepTimeMonitor
        mon = StepTimeMonitor(k_sigma=3.0)
        for i in range(20):
            mon.observe(i, 0.01 + 0.0001 * (i % 3))
        assert mon.observe(20, 0.5)          # 50x slower step flagged
        assert mon.stragglers[-1][0] == 20

    def test_nan_circuit_breaker(self):
        from repro.train.loop import train_loop
        calls = {"n": 0}

        def bad_step(state, batch):
            calls["n"] += 1
            return state, {"loss": jnp.asarray(float("nan"))}

        state, hist = train_loop({}, bad_step, lambda s: {}, total_steps=10,
                                 log_every=0)
        assert calls["n"] == 1               # aborted immediately
