"""Primitive layers: norms, rotary embeddings (RoPE / M-RoPE), MLP variants.

Everything is functional: `init_*` builds a param dict, `apply` fns are pure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import constrain


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------- norms ---

def init_norm(cfg, d=None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    return p


def apply_norm(p, x, cfg):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ RoPE ---

def rope_angles(positions: jax.Array, dim: int, theta: float) -> tuple:
    """positions: (..., T) int -> cos/sin of shape (..., T, dim//2)."""
    half = dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, T, d); positions: (B, T)."""
    d = x.shape[-1]
    cos, sin = rope_angles(positions, d, theta)        # (B, T, d/2)
    cos = cos[:, None]
    sin = sin[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: tuple[int, ...]) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): positions3 (3, B, T) = (t, h, w) ids.

    The head-dim halves are split into `sections` (summing to d/2); each
    section rotates with its own positional stream.
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # per-frequency-slot section id -> which positional stream drives it
    sec_id = np.repeat(np.arange(len(sections)), sections)       # (half,)
    pos = positions3.astype(jnp.float32)                         # (3, B, T)
    pos_sel = pos[sec_id]                                        # (half, B, T)
    ang = jnp.moveaxis(pos_sel, 0, -1) * freq                    # (B, T, half)
    cos = jnp.cos(ang)[:, None]
    sin = jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLP ---

def init_mlp(rng, cfg):
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    std = d ** -0.5
    p = {"w_in": jax.random.normal(k1, (d, f), _dtype(cfg)) * std,
         "w_out": jax.random.normal(k2, (f, d), _dtype(cfg)) * (f ** -0.5)}
    if cfg.activation in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k3, (d, f), _dtype(cfg)) * std
    return p


def apply_mlp(p, x, cfg):
    h = x @ p["w_in"]
    h = constrain(h, "batch", "attn_seq", "ffn")
    if cfg.activation == "swiglu":
        g = x @ p["w_gate"]
        h = jax.nn.silu(g) * h
    elif cfg.activation == "geglu":
        g = x @ p["w_gate"]
        h = jax.nn.gelu(g) * h
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.activation == "squared_relu":       # nemotron-4
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(cfg.activation)
    out = h @ p["w_out"]
    return constrain(out, "batch", "seq_act", "embed")


# ------------------------------------------------------------- embedding ---

def init_embed(rng, cfg):
    std = cfg.d_model ** -0.5
    p = {"table": jax.random.normal(rng, (cfg.padded_vocab, cfg.d_model),
                                    _dtype(cfg)) * std}
    return p


def embed_tokens(p, tokens, cfg):
    out = jnp.take(p["table"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        out = out * jnp.asarray(cfg.embedding_multiplier, out.dtype)
    return constrain(out, "batch", "seq_act", "embed")


def lm_logits(embed_p, head_p, x, cfg):
    """Logits over the padded vocab, divided by cfg.logits_scaling; pad
    lanes masked to -inf (Megatron-style padded vocab keeps the table
    TP-divisible; semantics unchanged)."""
    if cfg.tie_embeddings:
        w = embed_p["table"].T
    else:
        w = head_p["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    logits = constrain(logits, "batch", "attn_seq", "vocab")
    if cfg.padded_vocab != cfg.vocab_size:
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(lane < cfg.vocab_size, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits
