"""The dense decoder: every layer one pre-norm block of GQA attention with
RoPE and an MLP (SwiGLU or GELU), RMS or layer norm, tied embeddings.

What a configuration naming `"arch": "dense"` runs: the program's
ModelConfig built from the file's own numbers, the weights' leaves, the
HCCS constants, the model FLOPs a packed step requires, and the plain
reference forward. Only `model_config` imports the program; the rest
reads the configuration file alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import leaf_value
from bench.reference import _hccs_attention, _norm, _rope


def model_config(c: dict):
    """The repo's ModelConfig for configuration file `c`: every field that
    decides the served function or the step's shapes is set from the file."""
    from repro.configs.base import ModelConfig
    m, s, h = c["model"], c["serving"], c["hccs"]
    return ModelConfig(
        name=c["name"], family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        activation=m["activation"], norm=m["norm"], rope="rope",
        rope_theta=m["rope_theta"], causal=True, window=0,
        attention_prob="hccs", hccs_mode=h["mode"],
        tie_embeddings=m["tie_embeddings"], dtype=m["dtype"],
        attention_impl="auto", cache_layout="paged",
        decode_kernel=s["decode_kernel"], block_size=s["block_size"],
        num_blocks=s["num_blocks"], prefix_sharing=s["prefix_sharing"],
        decode_sharing=False, cache_dtype=s["cache_dtype"],
        kv_quant=s["kv_quant"], speculative=s["speculative"],
        async_loop=s["async_loop"])


def leaf_table(c: dict) -> dict:
    """Leaf path -> (shape, layers it is stacked over, 0 for none, rule).
    A rule is ("std", sigma), ("one", spread) for a norm scale 1 +- spread,
    or ("zero", spread) for a norm bias 0 +- spread."""
    m = c["model"]
    d, f, v, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    hq, hkv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    layernorm = m["norm"] == "layernorm"
    t = {"embed/table": ((v, d), 0, ("std", d ** -0.5))}
    for norm in ("layers/norm1", "layers/norm2", "final_norm"):
        layers = L if norm.startswith("layers/") else 0
        t[f"{norm}/scale"] = ((d,), layers, ("one", 0.1))
        if layernorm:
            t[f"{norm}/bias"] = ((d,), layers, ("zero", 0.1))
    t["layers/attn/wq"] = ((d, hq), L, ("std", d ** -0.5))
    t["layers/attn/wk"] = ((d, hkv), L, ("std", d ** -0.5))
    t["layers/attn/wv"] = ((d, hkv), L, ("std", d ** -0.5))
    t["layers/attn/wo"] = ((hq, d), L, ("std", hq ** -0.5))
    t["layers/mlp/w_in"] = ((d, f), L, ("std", d ** -0.5))
    t["layers/mlp/w_out"] = ((f, d), L, ("std", f ** -0.5))
    if m["activation"] == "swiglu":
        t["layers/mlp/w_gate"] = ((d, f), L, ("std", d ** -0.5))
    return t


def hccs_params(c: dict) -> dict:
    """The configuration's HCCS constants per (layer, head), in the layout
    the program reads (int32 B, S, D and a float32 logit scale)."""
    m, h = c["model"], c["hccs"]
    shape = (m["num_layers"], m["num_heads"])
    return {"B": jnp.full(shape, h["B"], jnp.int32),
            "S": jnp.full(shape, h["S"], jnp.int32),
            "D": jnp.full(shape, h["D"], jnp.int32),
            "scale": jnp.full(shape, h["scale"], jnp.float32)}


# --------------------------------------------------------------- FLOPs --

def matmul_flops_per_token(c: dict) -> int:
    """FLOPs of every projection and the MLP for one token, all layers."""
    m = c["model"]
    d, f = m["d_model"], m["d_ff"]
    hq, hkv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    mlp_mats = 3 if m["activation"] == "swiglu" else 2
    per_layer = d * hq + 2 * d * hkv + hq * d + mlp_mats * d * f
    return 2 * per_layer * m["num_layers"]


def attention_flops(c: dict, context) -> float:
    """QK^T and PV FLOPs of one token attending over `context` keys (its
    own causal context, position + 1), all layers; `context` may be an
    array, and the result is then summed over it."""
    m = c["model"]
    per_key = 2 * 2 * m["num_heads"] * m["head_dim"] * m["num_layers"]
    return float(per_key * np.sum(np.asarray(context, np.float64)))


def head_flops(c: dict) -> int:
    """The output head for one sampled position (the real vocabulary)."""
    return 2 * c["model"]["d_model"] * c["model"]["vocab_size"]


def step_flops(c: dict, contexts, n_sampled: int) -> float:
    """Model FLOPs a step requires: `contexts` holds each valid token's
    causal context (pad lanes left out), `n_sampled` the positions whose
    logits were used."""
    n = len(np.asarray(contexts))
    return (n * matmul_flops_per_token(c) + attention_flops(c, contexts)
            + n_sampled * head_flops(c))


# ----------------------------------------------------------- reference --

def hidden(key, tokens, gather, c: dict, mm):
    """The reference forward: tokens (n, T), gather (n, K) -> the
    final-normed hidden state at the gathered positions (n, K, d), with
    every matmul through `mm`. Each layer's weights are made again from
    the seed inside a scan, in float32; attention is causal HCCS."""
    m, hc_cfg = c["model"], c["hccs"]
    L = m["num_layers"]
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, norm = m["norm_eps"], m["norm"]
    table = leaf_table(c)
    f32 = jnp.float32
    hc = {k: jnp.float32(hc_cfg[k]) for k in ("B", "S", "D", "scale")}
    t = tokens.shape[1]
    q_block = min(256, t)

    def leaf(name, layer=None):
        shape, _, rule = table[name]
        return leaf_value(key, name, shape, rule, layer, dtype=f32)

    embed = leaf("embed/table")
    x = embed[tokens]                                      # (n, T, d)

    def layer(x, l):
        p = {name[len("layers/"):]: leaf(name, l)
             for name in table if name.startswith("layers/")}
        n1 = {"scale": p["norm1/scale"], "bias": p.get("norm1/bias")}
        n2 = {"scale": p["norm2/scale"], "bias": p.get("norm2/bias")}

        def one(xs):                                       # (T, d)
            hn = _norm(xs, n1, norm, eps)
            q = mm(hn, p["attn/wq"]).reshape(t, H, hd).transpose(1, 0, 2)
            k = mm(hn, p["attn/wk"]).reshape(t, Hkv, hd).transpose(1, 0, 2)
            v = mm(hn, p["attn/wv"]).reshape(t, Hkv, hd).transpose(1, 0, 2)
            q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
            a = _hccs_attention(q, k, v, hc, mm, q_block)
            xs = xs + mm(a.transpose(1, 0, 2).reshape(t, H * hd),
                         p["attn/wo"])
            hn = _norm(xs, n2, norm, eps)
            u = mm(hn, p["mlp/w_in"])
            if m["activation"] == "swiglu":
                u = jax.nn.silu(mm(hn, p["mlp/w_gate"])) * u
            elif m["activation"] == "gelu":
                u = jax.nn.gelu(u, approximate=True)
            else:
                raise ValueError(m["activation"])
            return xs + mm(u, p["mlp/w_out"])

        return jax.lax.map(one, x), None

    x, _ = jax.lax.scan(layer, x, jnp.arange(L))
    fin = {"scale": leaf("final_norm/scale"),
           "bias": leaf("final_norm/bias") if "final_norm/bias" in table
           else None}
    h = jnp.take_along_axis(x, gather[..., None], axis=1)  # (n, K, d)
    return _norm(h, fin, norm, eps)
