"""The Mamba-2 / attention hybrid of granite-4.0-h (`model_type`
granitemoehybrid, no experts): `layer_types` names each layer's mixer,
Mamba-2 or NoPE GQA attention, and every mixer is followed by a SwiGLU
MLP, each behind an RMS norm. The embedding is multiplied by
`embedding_multiplier`, each mixer's and MLP's output by
`residual_multiplier` before it joins the residual stream, attention
logits by `attention_multiplier` in place of 1/sqrt(head_dim), and the
logits are divided by `logits_scaling`.

A Mamba-2 layer: in_proj to z, xBC and dt (held as three leaves, in_z,
in_xbc and in_dt, one per output); a depthwise causal conv of
width `mamba_d_conv` with bias over xBC, then SiLU; dt = softplus(dt +
dt_bias); the SSD recurrence per head, h_t = exp(-exp(A_log) dt_t) h_{t-1}
+ dt_t B_t x_t^T and y_t = C_t h_t + D x_t; RMSNorm of y * silu(z) with
the configuration's eps; out_proj.

A configuration file naming `"arch": "hybrid"` holds the published
config.json keys at its top level, `model` the sizes the published file
leaves out (head_dim, dtype) and the vocabulary the harness reads, `hccs`
and `serving` as the dense configurations do. Each kind's leaves are
stacked over that kind's layers, in layer order, as the program holds
them: `layers/mamba/...` over the Mamba-2 layers and `layers/attention/...`
over the attention layers.

`hidden` is the plain reference: float32, every matmul through `mm`, the
SSD as a sequential scan over positions (the recurrence, not the chunked
algorithm). It returns the final-normed hidden state divided by
`logits_scaling`, so that the shared tied head in bench/reference.py
(`_stats`) gives the published logits unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import leaf_value
from bench.reference import HIGHEST, _hccs_attention, _norm

KINDS = ("attention", "mamba")


def _sizes(c: dict) -> dict:
    """The sizes the functions below use, from the published keys."""
    d, hd = c["hidden_size"], c["model"]["head_dim"]
    di = c["mamba_expand"] * d
    nh = c["mamba_n_heads"]
    if nh * c["mamba_d_head"] != di:
        raise ValueError(f"{c['name']}: mamba_n_heads x mamba_d_head "
                         f"!= mamba_expand x hidden_size")
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    return dict(d=d, hd=hd, di=di, nh=nh, p=c["mamba_d_head"],
                n=c["mamba_d_state"], g=c["mamba_n_groups"], gn=gn,
                conv=di + 2 * gn, k=c["mamba_d_conv"],
                f=c["shared_intermediate_size"], v=c["vocab_size"],
                h=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                counts={k: c["layer_types"].count(k) for k in KINDS})


def model_config(c: dict):
    """The repo's ModelConfig for configuration file `c`: every field that
    decides the served function or the step's shapes is set from the file.
    Keys this architecture does not implement are refused."""
    from repro.configs.base import ModelConfig
    s, h = c["serving"], c["hccs"]
    unsupported = {"position_embedding_type": "nope", "hidden_act": "silu",
                   "normalization_function": "rmsnorm",
                   "attention_bias": False, "mamba_proj_bias": False,
                   "mamba_conv_bias": True, "num_local_experts": 0}
    for key, want in unsupported.items():
        if c[key] != want:
            raise ValueError(f"{c['name']}: {key}={c[key]!r}, the hybrid "
                             f"architecture implements {want!r}")
    z = _sizes(c)
    return ModelConfig(
        name=c["name"], family="hybrid", num_layers=c["num_hidden_layers"],
        d_model=z["d"], num_heads=z["h"], num_kv_heads=z["hkv"],
        head_dim=z["hd"], d_ff=z["f"], vocab_size=z["v"],
        activation="swiglu", norm="rmsnorm", norm_eps=c["rms_norm_eps"],
        rope="none", causal=True, window=0,
        layer_types=tuple(c["layer_types"]), ssm_state=z["n"],
        ssm_expand=c["mamba_expand"], ssm_head_dim=z["p"],
        ssm_groups=z["g"], ssm_chunk=c["mamba_chunk_size"],
        ssm_conv=z["k"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        attention_prob="hccs", hccs_mode=h["mode"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["model"]["dtype"],
        attention_impl="auto", cache_layout="paged",
        decode_kernel=s["decode_kernel"], block_size=s["block_size"],
        num_blocks=s["num_blocks"], prefix_sharing=s["prefix_sharing"],
        decode_sharing=False, cache_dtype=s["cache_dtype"],
        kv_quant=s["kv_quant"], speculative=s["speculative"],
        async_loop=s["async_loop"])


def leaf_table(c: dict) -> dict:
    """Leaf path -> (shape, layers it is stacked over, 0 for none, rule),
    with the rules of archs/dense.py. The embedding's scale is dense's
    divided by embedding_multiplier, so the scaled input has dense's scale:
    at dense's, the scaled input would outweigh what 40 layers add to it
    at residual_multiplier, and the tied head would return every token as
    its own successor. The Mamba-2 scalars: A_log uniform in [-4, 4), so
    the heads' decay rates spread over three orders of magnitude, dt_bias
    and the conv bias uniform in [-0.1, 0.1), D 1 +- 0.1."""
    z = _sizes(c)
    d, f, di = z["d"], z["f"], z["di"]
    hq, hkv = z["h"] * z["hd"], z["hkv"] * z["hd"]
    emb = d ** -0.5 / c["embedding_multiplier"]
    t = {"embed/table": ((z["v"], d), 0, ("std", emb)),
         "final_norm/scale": ((d,), 0, ("one", 0.1))}
    for kind in KINDS:
        n = z["counts"][kind]
        pre = f"layers/{kind}"
        t[f"{pre}/norm1/scale"] = ((d,), n, ("one", 0.1))
        t[f"{pre}/norm2/scale"] = ((d,), n, ("one", 0.1))
        t[f"{pre}/mlp/w_in"] = ((d, f), n, ("std", d ** -0.5))
        t[f"{pre}/mlp/w_gate"] = ((d, f), n, ("std", d ** -0.5))
        t[f"{pre}/mlp/w_out"] = ((f, d), n, ("std", f ** -0.5))
    n = z["counts"]["attention"]
    t["layers/attention/attn/wq"] = ((d, hq), n, ("std", d ** -0.5))
    t["layers/attention/attn/wk"] = ((d, hkv), n, ("std", d ** -0.5))
    t["layers/attention/attn/wv"] = ((d, hkv), n, ("std", d ** -0.5))
    t["layers/attention/attn/wo"] = ((hq, d), n, ("std", hq ** -0.5))
    n, nh = z["counts"]["mamba"], z["nh"]
    for name, width in (("in_z", di), ("in_xbc", z["conv"]), ("in_dt", nh)):
        t[f"layers/mamba/ssm/{name}"] = ((d, width), n, ("std", d ** -0.5))
    t["layers/mamba/ssm/conv_w"] = ((z["k"], z["conv"]), n,
                                    ("std", z["k"] ** -0.5))
    t["layers/mamba/ssm/conv_b"] = ((z["conv"],), n, ("zero", 0.1))
    t["layers/mamba/ssm/out_proj"] = ((di, d), n, ("std", di ** -0.5))
    t["layers/mamba/ssm/A_log"] = ((nh,), n, ("zero", 4.0))
    t["layers/mamba/ssm/dt_bias"] = ((nh,), n, ("zero", 0.1))
    t["layers/mamba/ssm/D"] = ((nh,), n, ("one", 0.1))
    t["layers/mamba/ssm/norm"] = ((di,), n, ("one", 0.1))
    return t


def hccs_params(c: dict) -> dict:
    """The HCCS constants per (attention layer, head), in the layout the
    program reads (int32 B, S, D and a float32 logit scale)."""
    h = c["hccs"]
    shape = (c["layer_types"].count("attention"), c["num_attention_heads"])
    return {"B": jnp.full(shape, h["B"], jnp.int32),
            "S": jnp.full(shape, h["S"], jnp.int32),
            "D": jnp.full(shape, h["D"], jnp.int32),
            "scale": jnp.full(shape, h["scale"], jnp.float32)}


# --------------------------------------------------------------- FLOPs --

def matmul_flops_per_token(c: dict) -> int:
    """FLOPs of every projection, the MLPs and the conv for one token, all
    layers."""
    z = _sizes(c)
    d, hq, hkv = z["d"], z["h"] * z["hd"], z["hkv"] * z["hd"]
    mlp = 3 * d * z["f"]
    attn = d * hq + 2 * d * hkv + hq * d + mlp
    mamba = (d * (z["di"] + z["conv"] + z["nh"]) + z["di"] * d + mlp
             + z["k"] * z["conv"])
    return 2 * (attn * z["counts"]["attention"]
                + mamba * z["counts"]["mamba"])


def ssd_flops_per_token(c: dict) -> int:
    """The SSD recurrence for one token, all Mamba-2 layers: the state
    update and the readout, 4 FLOPs per state element."""
    z = _sizes(c)
    return 4 * z["nh"] * z["p"] * z["n"] * z["counts"]["mamba"]


def attention_flops(c: dict, context) -> float:
    """QK^T and PV FLOPs of one token attending over `context` keys (its
    causal context), in the attention layers; `context` may be an array,
    and the result is then summed over it."""
    z = _sizes(c)
    per_key = 2 * 2 * z["h"] * z["hd"] * z["counts"]["attention"]
    return float(per_key * np.sum(np.asarray(context, np.float64)))


def head_flops(c: dict) -> int:
    """The output head for one sampled position (the real vocabulary)."""
    return 2 * c["hidden_size"] * c["vocab_size"]


def step_flops(c: dict, contexts, n_sampled: int) -> float:
    """Model FLOPs a step requires: `contexts` holds each valid token's
    causal context (pad lanes left out), `n_sampled` the positions whose
    logits were used."""
    n = len(np.asarray(contexts))
    return (n * (matmul_flops_per_token(c) + ssd_flops_per_token(c))
            + attention_flops(c, contexts) + n_sampled * head_flops(c))


# ----------------------------------------------------------- reference --

def _mamba(xs, p, c, z, mm, eps):
    """One Mamba-2 mixer over (n, T, d), from zero state."""
    nb, t, _ = xs.shape
    di, nh, hp, g, n, k = z["di"], z["nh"], z["p"], z["g"], z["n"], z["k"]
    gate, xbc, dt = (mm(xs, p[f"ssm/{name}"])
                     for name in ("in_z", "in_xbc", "in_dt"))
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + t] * p["ssm/conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["ssm/conv_b"])
    rep = nh // g
    x = xbc[..., :di].reshape(nb, t, g, rep, hp)
    B = xbc[..., di:di + g * n].reshape(nb, t, g, n)
    C = xbc[..., di + g * n:].reshape(nb, t, g, n)
    dt = jax.nn.softplus(dt + p["ssm/dt_bias"]).reshape(nb, t, g, rep)
    decay = jnp.exp(-jnp.exp(p["ssm/A_log"]).reshape(g, rep) * dt)

    def step(h, inp):                                # h (n, g, rep, N, P)
        x_t, b_t, c_t, dt_t, a_t = inp
        h = (a_t[..., None, None] * h + dt_t[..., None, None]
             * b_t[:, :, None, :, None] * x_t[..., None, :])
        return h, jnp.einsum("bgn,bgrnp->bgrp", c_t, h, precision=HIGHEST)

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((nb, g, rep, n, hp), jnp.float32),
                        (seq(x), seq(B), seq(C), seq(dt), seq(decay)))
    y = (jnp.moveaxis(y, 0, 1)
         + p["ssm/D"].reshape(g, rep)[:, :, None] * x)
    y = y.reshape(nb, t, di) * jax.nn.silu(gate)
    y = _norm(y, {"scale": p["ssm/norm"]}, "rmsnorm", eps)
    return mm(y, p["ssm/out_proj"])


def hidden(key, tokens, gather, c: dict, mm):
    """The reference forward: tokens (n, T), gather (n, K) -> the
    final-normed hidden state at the gathered positions over
    logits_scaling (n, K, d), with every matmul through `mm`. Each layer's
    weights are made again from the seed inside a scan over the layer
    pattern's periods, in float32; attention is causal NoPE HCCS."""
    z = _sizes(c)
    types = list(c["layer_types"])
    hc_cfg = c["hccs"]
    H, Hkv, hd = z["h"], z["hkv"], z["hd"]
    eps = c["rms_norm_eps"]
    table = leaf_table(c)
    f32 = jnp.float32
    hc = {k: jnp.float32(hc_cfg[k]) for k in ("B", "S", "D", "scale")}
    t = tokens.shape[1]
    q_block = min(256, t)
    # q.k x attention_multiplier = (q x attention_multiplier sqrt(hd)).k /
    # sqrt(hd), the scaling _hccs_attention applies
    q_mult = c["attention_multiplier"] * hd ** 0.5
    rm = c["residual_multiplier"]
    period = next(p for p in range(1, len(types) + 1)
                  if len(types) % p == 0
                  and all(types[i] == types[i % p] for i in range(len(types))))
    per = {k: types[:period].count(k) for k in KINDS}

    def leaf(name, layer=None):
        shape, _, rule = table[name]
        return leaf_value(key, name, shape, rule, layer, dtype=f32)

    def weights(kind, layer):
        pre = f"layers/{kind}/"
        return {name[len(pre):]: leaf(name, layer)
                for name in table if name.startswith(pre)}

    def mlp(xs, p):
        hn = _norm(xs, {"scale": p["norm2/scale"]}, "rmsnorm", eps)
        u = jax.nn.silu(mm(hn, p["mlp/w_gate"])) * mm(hn, p["mlp/w_in"])
        return xs + rm * mm(u, p["mlp/w_out"])

    def attention(xs, p):                                 # (T, d)
        hn = _norm(xs, {"scale": p["norm1/scale"]}, "rmsnorm", eps)
        q = mm(hn, p["attn/wq"]).reshape(t, H, hd).transpose(1, 0, 2)
        k = mm(hn, p["attn/wk"]).reshape(t, Hkv, hd).transpose(1, 0, 2)
        v = mm(hn, p["attn/wv"]).reshape(t, Hkv, hd).transpose(1, 0, 2)
        a = _hccs_attention(q * q_mult, k, v, hc, mm, q_block)
        return xs + rm * mm(a.transpose(1, 0, 2).reshape(t, H * hd),
                            p["attn/wo"])

    x = leaf("embed/table")[tokens] * c["embedding_multiplier"]

    def one_period(x, i):
        seen = dict.fromkeys(KINDS, 0)
        for kind in types[:period]:
            p = weights(kind, i * per[kind] + seen[kind])
            seen[kind] += 1
            if kind == "attention":
                x = jax.lax.map(lambda xs, p=p: mlp(attention(xs, p), p), x)
            else:
                hn = _norm(x, {"scale": p["norm1/scale"]}, "rmsnorm", eps)
                x = x + rm * _mamba(hn, p, c, z, mm, eps)
                x = jax.lax.map(lambda xs, p=p: mlp(xs, p), x)
        return x, None

    x, _ = jax.lax.scan(one_period, x, jnp.arange(len(types) // period))
    h = jnp.take_along_axis(x, gather[..., None], axis=1)  # (n, K, d)
    h = _norm(h, {"scale": leaf("final_norm/scale")}, "rmsnorm", eps)
    return h / c["logits_scaling"]
