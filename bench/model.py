"""A configuration file's model: the repo's ModelConfig built from the file's
own numbers, the weights made from the seed, and the model FLOPs a step
requires.

Weights are a pure function of (seed, leaf name, layer): every value is a
16-bit random integer times one float32 constant, rounded once to
bfloat16. The served weights are made in one jitted call; the reference
(reference.py) makes each layer again from the same function, so both see
the same numbers without the reference taking anything from the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# standard deviation of a uniform int16: 65536 / sqrt(12)
_INT16_STD = 65536 / 12 ** 0.5


def seed_key(seed: int):
    """A PRNG key from any seed below 2**64 (PRNGKey alone would wrap
    seeds past 32 bits silently)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


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
    """Leaf path -> (shape, stacked over layers, rule). A rule is
    ("std", sigma), ("one", spread) for a norm scale 1 +- spread, or
    ("zero", spread) for a norm bias 0 +- spread."""
    m = c["model"]
    d, f, v = m["d_model"], m["d_ff"], m["vocab_size"]
    hq, hkv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    layernorm = m["norm"] == "layernorm"
    t = {"embed/table": ((v, d), False, ("std", d ** -0.5))}
    for norm in ("layers/norm1", "layers/norm2", "final_norm"):
        stacked = norm.startswith("layers/")
        t[f"{norm}/scale"] = ((d,), stacked, ("one", 0.1))
        if layernorm:
            t[f"{norm}/bias"] = ((d,), stacked, ("zero", 0.1))
    t["layers/attn/wq"] = ((d, hq), True, ("std", d ** -0.5))
    t["layers/attn/wk"] = ((d, hkv), True, ("std", d ** -0.5))
    t["layers/attn/wv"] = ((d, hkv), True, ("std", d ** -0.5))
    t["layers/attn/wo"] = ((hq, d), True, ("std", hq ** -0.5))
    t["layers/mlp/w_in"] = ((d, f), True, ("std", d ** -0.5))
    t["layers/mlp/w_out"] = ((f, d), True, ("std", f ** -0.5))
    if m["activation"] == "swiglu":
        t["layers/mlp/w_gate"] = ((d, f), True, ("std", d ** -0.5))
    return t


def leaf_value(key, name: str, shape, rule, layer=None, dtype=jnp.bfloat16):
    """One leaf (or one layer of a stacked leaf): uniform, from its own key."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    u = jax.lax.bitcast_convert_type(
        jax.random.bits(k, shape, jnp.uint16), jnp.int16).astype(jnp.int32)
    kind, a = rule
    # one float multiply of an exact integer, so no fusion (an FMA) can
    # round differently in the served call and in the reference's
    if kind == "std":
        x = u.astype(jnp.float32) * jnp.float32(a / _INT16_STD)
    else:   # uniform in [-a, a), centred on 1 for a scale, on 0 for a bias
        if kind == "one":
            u = u + round(32768 / a)
        x = u.astype(jnp.float32) * jnp.float32(a / 32768)
    return x.astype(jnp.bfloat16).astype(dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = val
    return out


def weights_fn(c: dict, cfg):
    """key -> the served weights in the program's layout. Stacked leaves
    are made one layer at a time inside the call (lax.map), so its peak
    stays near the weights' own bytes. The embedding is padded to the
    program's padded vocabulary with zero rows, which no token reads and
    the head masks."""
    table = leaf_table(c)
    L = c["model"]["num_layers"]
    vp = cfg.padded_vocab

    def build(key):
        flat = {}
        for name, (shape, stacked, rule) in table.items():
            if stacked:
                flat[name] = jax.lax.map(
                    lambda l, n=name, s=shape, r=rule: leaf_value(key, n, s, r, l),
                    jnp.arange(L))
            else:
                flat[name] = leaf_value(key, name, shape, rule)
        e = flat["embed/table"]
        flat["embed/table"] = jnp.pad(e, ((0, vp - e.shape[0]), (0, 0)))
        return _nest(flat)

    return build


def make_params(c: dict, seed: int, cfg):
    """The served parameters on the default device, the weights made in one
    jitted call, checked against the program's own init shapes."""
    from repro.models import model as M
    weights = jax.jit(weights_fn(c, cfg))(seed_key(seed))
    got = {"weights": weights, "hccs": hccs_params(c)}
    want = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    if shapes(got) != shapes(want):
        raise ValueError(f"{c['name']}: the benchmark's parameters do not "
                         "match the program's layout: "
                         f"{shapes(got)} != {shapes(want)}")
    return got


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
