"""Model FLOPs from the configuration's shapes (the dense architecture
module's functions), pinned against hand counts, and the table of
peaks."""
import json

import numpy as np

from bench import registry

dense = registry.arch("dense")


def starcoder2():
    """StarCoder2-3B's published shape (hf:bigcode/starcoder2-3b) in a
    configuration file's layout: GELU MLP and LayerNorm, unlike granite."""
    c = registry.config("granite-3-2b")
    c["name"] = "starcoder2-3b"
    c["model"].update(num_layers=30, d_model=3072, num_heads=24,
                      num_kv_heads=2, head_dim=128, d_ff=12288,
                      vocab_size=49152, activation="gelu", norm="layernorm")
    return c


def test_starcoder2_file_holds_the_published_shape():
    c = registry.config("starcoder2-3b")
    assert c["arch"] == "dense"
    assert c["model"] == dict(starcoder2()["model"], rope_theta=c["model"][
        "rope_theta"])
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["hidden_size"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["intermediate_size"], pub["vocab_size"]) == (
        30, 3072, 24, 2, 12288, 49152)
    assert c["model"]["rope_theta"] == pub["rope_theta"]


def test_granite_per_token_flops():
    c = registry.config("granite-3-2b")
    # per layer: q 2048x2048, k and v 2048x512 each, o 2048x2048, SwiGLU
    # 3 x 2048x8192 = 60,817,408 weights; 2 FLOPs each, 40 layers
    assert dense.matmul_flops_per_token(c) == 2 * 60_817_408 * 40
    # QK and PV: 2 x 2 x 32 heads x 64 per key, 40 layers
    assert dense.attention_flops(c, 1000) == 2 * 2 * 32 * 64 * 40 * 1000
    assert dense.head_flops(c) == 2 * 2048 * 49155


def test_starcoder2_per_token_flops():
    c = starcoder2()
    # q 3072x3072, k and v 3072x256 each, o 3072x3072, GELU MLP
    # 2 x 3072x12288 = 95,944,704 weights a layer, 30 layers
    assert dense.matmul_flops_per_token(c) == 2 * 95_944_704 * 30
    assert dense.attention_flops(c, [10, 20]) == 2 * 2 * 24 * 128 * 30 * 30
    assert dense.head_flops(c) == 2 * 3072 * 49152


def test_step_flops_counts_valid_tokens_and_samples():
    c = registry.config("granite-3-2b")
    f = dense.step_flops(c, [1, 2, 3], 2)
    assert f == (3 * dense.matmul_flops_per_token(c)
                 + dense.attention_flops(c, 6) + 2 * dense.head_flops(c))


def test_step_contexts_read_the_recorded_inputs():
    from bench import harness
    ex = {"kv_len": np.array([5, 6, 7, 0]),
          "slot_ids": np.array([0, 0, 1, -1])}
    ctx = harness.step_contexts([ex, ex])
    assert [list(c) for c in ctx] == [[5, 6, 7], [5, 6, 7]]
    # inputs the program no longer passes so: nothing to read
    assert harness.step_contexts([{"kv_len": ex["kv_len"]}]) is None
    assert harness.step_contexts([None]) is None


def test_peaks_table():
    peaks = json.loads((registry.ROOT / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"],
            v5e["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    assert "Google Cloud" in v5e["source"]


def test_params_are_whole_models():
    # embedding + layers (matmuls and norms) + final norm; published
    # sizes 2.5B and 3.0B
    granite = 49155 * 2048 + 40 * (60_817_408 + 2 * 2048) + 2048
    starcoder = 49152 * 3072 + 30 * (95_944_704 + 4 * 3072) + 2 * 3072
    for c, n in ((registry.config("granite-3-2b"), granite),
                 (starcoder2(), starcoder)):
        total = 0
        for path, (shape, layers, _) in dense.leaf_table(c).items():
            k = 1
            for s in shape:
                k *= s
            total += k * max(layers, 1)
        assert total == n
