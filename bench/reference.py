"""The plain reference: the configuration's model in straightforward
jax.numpy, float32 at the highest matmul precision, with no cache, no
paging and no batching of requests.

It imports nothing of the program. Weights come from the seed through
`bench.model.leaf_value`, one layer at a time inside a scan, so the
reference holds one layer's weights (in float32) at a time. Attention is
HCCS as the configuration states it: float logits q.k / sqrt(head_dim)
quantised to int8 by the logit scale (round half to even, clip to
[-128, 127]), the row max over the causal context, s = B - S * min(m - q,
D) on that context and 0 elsewhere, p = s / sum(s) ("wide"
normalisation). RoPE rotates the two halves of each head. Norms are RMS
or layer norm with the configuration's eps.

`precision="fp8"` is the control: the same function with every matmul
operand rounded to float8_e4m3fn under a per-tensor scale (amax / 448),
the nearest precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as BM

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(precision):
    if precision == "f32":
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _norm(x, p, kind, eps):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """x: (heads, T, hd), position t = row index."""
    t, hd = x.shape[1], x.shape[2]
    half = hd // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hccs_attention(q, k, v, hc, mm, q_block):
    """q: (H, T, hd), k/v: (Hkv, T, hd) -> (H, T, hd), causal HCCS."""
    h, t, hd = q.shape
    g = h // k.shape[0]
    kk = jnp.repeat(k, g, axis=0)
    vv = jnp.repeat(v, g, axis=0)
    keys = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        rows = i * q_block + jnp.arange(q_block)
        logits = mm(qb, kk.transpose(0, 2, 1)) / jnp.sqrt(jnp.float32(hd))
        valid = keys[None, None, :] <= rows[None, :, None]
        qi = jnp.clip(jnp.round(logits / hc["scale"]), -128.0, 127.0)
        m = jnp.where(valid, qi, -1e9).max(-1, keepdims=True)
        s = jnp.where(valid, hc["B"] - hc["S"] * jnp.minimum(m - qi, hc["D"]),
                      0.0)
        p = s / jnp.maximum(s.sum(-1, keepdims=True), 1.0)
        return mm(p, vv)

    out = jax.lax.map(block, jnp.arange(t // q_block))     # (nb, H, qb, hd)
    return out.transpose(1, 0, 2, 3).reshape(h, t, hd)


@functools.partial(jax.jit, static_argnames=("c_json", "precision"))
def _hidden(key, tokens, gather, *, c_json, precision):
    """tokens (n, T), gather (n, K) -> final-normed hidden (n, K, d)."""
    import json
    c = json.loads(c_json)
    m, hc_cfg = c["model"], c["hccs"]
    L, d = m["num_layers"], m["d_model"]
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, norm = m["norm_eps"], m["norm"]
    mm = _mm(precision)
    table = BM.leaf_table(c)
    f32 = jnp.float32
    hc = {k: jnp.float32(hc_cfg[k]) for k in ("B", "S", "D", "scale")}
    t = tokens.shape[1]
    q_block = min(256, t)

    def leaf(name, layer=None):
        shape, _, rule = table[name]
        return BM.leaf_value(key, name, shape, rule, layer, dtype=f32)

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


@functools.partial(jax.jit, static_argnames=("c_json", "precision"))
def _stats(key, h, toks, *, c_json, precision):
    """h (n, K, d), toks (n, K) -> the logits' max, the logit of each token
    in toks, and the argmax, each (n, K)."""
    import json
    c = json.loads(c_json)
    shape, _, rule = BM.leaf_table(c)["embed/table"]
    embed = BM.leaf_value(key, "embed/table", shape, rule, dtype=jnp.float32)
    logits = _mm(precision)(h, embed.T)                    # (n, K, V)
    at = jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]
    return logits.max(-1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def logit_stats(c: dict, seed: int, sequences, *, t: int, k: int, n=None,
                tokens=None, precision: str = "f32"):
    """sequences: list of (prompt, served tokens). The model is fed
    prompt + served[:-1] from position 0; at every position whose output
    was served it returns, per sequence, numpy arrays of the logits' max,
    the logit of the token given there (by default the served one; else
    tokens[i]) and the argmax. Feeds are padded to t positions, served
    tokens to k and the batch to n sequences (repeating the first), all
    fixed per cell, so the reference compiles once per cell."""
    import json
    c_json = json.dumps(c, sort_keys=True)
    key = BM.seed_key(seed)
    feeds = [np.concatenate([np.asarray(p), np.asarray(o[:-1])]).astype(
        np.int32) for p, o in sequences]
    if max(len(f) for f in feeds) > t or max(len(o) for _, o in sequences) > k:
        raise ValueError("a sequence is longer than the reference's shape")
    real = len(sequences)
    n = max(n or real, real)
    sequences = list(sequences) + [sequences[0]] * (n - real)
    feeds = feeds + [feeds[0]] * (n - real)
    if tokens is not None:
        tokens = list(tokens) + [tokens[0]] * (n - real)
    fed = np.zeros((len(feeds), t), np.int32)
    gather = np.zeros((len(feeds), k), np.int32)
    toks = np.zeros((len(feeds), k), np.int32)
    for i, (f, (p, o)) in enumerate(zip(feeds, sequences)):
        fed[i, :len(f)] = f
        gather[i, :len(o)] = len(p) - 1 + np.arange(len(o))
        toks[i, :len(o)] = o if tokens is None else tokens[i]
    h = _hidden(key, jnp.asarray(fed), jnp.asarray(gather),
                c_json=c_json, precision=precision)
    mx, at, am = (np.asarray(a) for a in _stats(
        key, h, jnp.asarray(toks), c_json=c_json, precision=precision))
    return [(mx[i, :len(o)], at[i, :len(o)], am[i, :len(o)])
            for i, (_, o) in enumerate(sequences[:real])]
