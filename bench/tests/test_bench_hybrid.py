"""The hybrid architecture module (archs/hybrid.py): its leaves are the
program's at full width, its FLOPs are hand counts, a tiny run of the
harness passes the program and fails the float8 control, and the two
state-arena readers read the engine's counters."""
import json
import shutil
import types

import jax
import numpy as np
import pytest

from bench import harness, registry
from bench import model as BM

hybrid = registry.arch("hybrid")
SEED = 2 ** 31 + 23
# the tiny model's readings on the CPU (seeds 1-4 and 2**31 + 23): program
# 9.1e-5 to 9.3e-4, float8 control 4.7e-3 to 1.1e-2 (the logits are
# divided by logits_scaling 8)
TINY_LIMIT = 0.0025


def tiny_config():
    """Two periods of (Mamba-2, Mamba-2, attention) at d_model 64."""
    c = registry.config("granite-4.0-h-micro")
    c["name"] = "tiny-hybrid"
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, shared_intermediate_size=128,
             vocab_size=256, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=8, num_hidden_layers=6,
             layer_types=["mamba", "mamba", "attention"] * 2)
    c["model"].update(head_dim=16, vocab_size=256)
    c["serving"].update(max_batch=4, max_len=128, block_size=8,
                        num_blocks=70)
    return c


def test_leaves_are_the_programs_at_full_width():
    from repro.models import model as M
    c = registry.config("granite-4.0-h-micro")
    cfg = hybrid.model_config(c)
    got = jax.eval_shape(BM.weights_fn(c, cfg, hybrid), BM.seed_key(SEED))
    want = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                                       tree)
    assert shapes(got) == shapes(want["weights"])
    assert shapes(hybrid.hccs_params(c)) == shapes(want["hccs"])
    assert want["weights"]["layers"]["mamba"]["ssm"]["in_xbc"].shape == (
        36, 2048, 4352)
    assert want["hccs"]["B"].shape == (4, 32)
    # 3.19B parameters, all 40 layers and the whole vocabulary
    n = sum(np.prod(a.shape) for a in jax.tree.leaves(got))
    assert 3.18e9 < n < 3.20e9


def test_the_file_builds_the_programs_configuration():
    from repro.configs import get_config
    c = registry.config("granite-4.0-h-micro")
    assert c["reduced"] == [] and c["num_hidden_layers"] == 40
    built = hybrid.model_config(c)
    ref = get_config("granite-4.0-h-micro")
    for field in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "head_dim", "d_ff", "vocab_size", "norm_eps", "rope",
                  "layer_types", "ssm_state", "ssm_expand", "ssm_head_dim",
                  "ssm_groups", "ssm_chunk", "ssm_conv", "ssm_heads",
                  "embedding_multiplier", "attention_multiplier",
                  "residual_multiplier", "logits_scaling"):
        assert getattr(built, field) == getattr(ref, field), field


def test_step_flops_is_a_hand_count():
    c = tiny_config()
    # a Mamba-2 layer: in_proj 64 x (128 + 160 + 8), out_proj 128 x 64,
    # SwiGLU 3 x 64 x 128, conv 4 x 160; an attention layer: q and o
    # 64 x 64 each, k and v 64 x 32 each, the same SwiGLU
    mamba = 64 * 296 + 128 * 64 + 3 * 64 * 128 + 4 * 160
    attn = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    assert hybrid.matmul_flops_per_token(c) == 2 * (4 * mamba + 2 * attn)
    # state update and readout: 4 x 8 heads x 16 x 16, in 4 layers
    assert hybrid.ssd_flops_per_token(c) == 4 * 8 * 16 * 16 * 4
    # QK and PV: 2 x 2 x 4 heads x 16 per key, in 2 layers
    assert hybrid.attention_flops(c, [3, 4]) == 2 * 2 * 4 * 16 * 2 * 7
    assert hybrid.head_flops(c) == 2 * 64 * 256
    assert hybrid.step_flops(c, [3, 4], 1) == (
        2 * (hybrid.matmul_flops_per_token(c) + hybrid.ssd_flops_per_token(c))
        + hybrid.attention_flops(c, [3, 4]) + hybrid.head_flops(c))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    for d in ("metrics", "archs"):
        shutil.copytree(registry.ROOT / d, root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(registry.ROOT / "peaks.json", root)
    for d in ("configs", "traffic", "checks"):
        (root / d).mkdir()
    (root / "configs" / "tiny-hybrid.json").write_text(
        json.dumps(tiny_config()))
    (root / "traffic" / "closed.json").write_text(json.dumps(dict(
        loop="closed", clients=4, per_client=400, lead_in_s=0.5,
        prompt={"dist": "uniform", "min": 8, "max": 40},
        output={"dist": "uniform", "min": 4, "max": 12})))
    (root / "checks" / "tiny-hybrid.closed.json").write_text(json.dumps(
        {"requests": 8, "logit_gap_max": {"limit": TINY_LIMIT}}))
    bench = registry.load_benchmark()
    bench["workloads"] = [{"name": "tiny-hybrid.closed",
                           "config": "tiny-hybrid", "traffic": "closed",
                           "chips": 1, "why": "test"}]
    for m in bench["per_layer"] + bench["end_to_end"]:
        m.pop("workloads", None)
    return root, bench


def test_program_passes_and_the_control_fails(tiny):
    root, bench = tiny
    out = harness.run("tiny-hybrid.closed", SEED, 2.0, True, root=root,
                      bench=bench, require_tpu=False, control=True)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["check"]["logit_gap_max"]["value"] > TINY_LIMIT
    # a traced run reads the arena's live share; its I/O share needs device
    # busy time, which a CPU trace does not hold (the reader's test below)
    assert 0 < out["metrics"]["ssm.state_live_share"]["value"] <= 100


def _ctx(start, end, busy_ns=None):
    trace = None
    if busy_ns is not None:
        trace = types.SimpleNamespace(ops=[("op", 0, busy_ns)])
    return types.SimpleNamespace(
        counters={"start": start, "end": end}, trace=trace,
        peak={"hbm_bytes_per_s": 819e9})


def test_state_readers_read_the_counter_deltas(monkeypatch):
    from bench import trace as T
    io = registry.reader("ssm.state_io_share")
    live = registry.reader("ssm.state_live_share")
    start = {"state_bytes": 10, "state_slots_moved": 64,
             "state_slots_live": 40}
    end = {"state_bytes": 10 + 819e6, "state_slots_moved": 64 + 320,
           "state_slots_live": 40 + 240}
    monkeypatch.setattr(T, "busy_ns", lambda tr: 4e6)       # 4 ms busy
    assert io(_ctx(start, end, busy_ns=4e6)) == pytest.approx(25.0)
    assert live(_ctx(start, end)) == pytest.approx(75.0)
    # a program that keeps no such counters: nothing, and no error
    dense = {"lanes_valid": 1, "lanes_total": 2}
    assert io(_ctx(dense, dense, busy_ns=4e6)) is None
    assert live(_ctx(dense, dense)) is None
    assert io(types.SimpleNamespace(counters=None, trace=None)) is None
