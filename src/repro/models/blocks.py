"""Transformer blocks: dense, MoE, Mamba2(SSD), and Hymba-style hybrid.

All blocks share a uniform signature so the model can lax.scan over stacked
layer params:

    apply_block(p, x, cfg, hccs, cache, positions, mrope_positions)
        -> (x, new_cache, aux)

cache is a per-layer dict (may contain 'k','v' for attention and/or the
'ssm' and 'conv' states); `length` is carried by the model, not per layer.
A configuration with `layer_types` builds each layer from its kind
(`kind=`): "attention" is the dense block, "mamba" the same block with the
Mamba-2 mixer in attention's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


def _residual(x, y, cfg):
    """x + y, y scaled by cfg.residual_multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def init_block(rng, cfg, kind=None):
    ks = jax.random.split(rng, 4)
    p: dict = {"norm1": init_norm(cfg)}
    if kind is not None:
        if kind == "attention":
            p["attn"] = attn.init_attention(ks[0], cfg)
        else:
            p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
        if cfg.d_ff > 0:
            p["norm2"] = init_norm(cfg)
            p["mlp"] = init_mlp(ks[3], cfg)
        return p
    fam = cfg.family
    if fam in ("dense", "moe", "audio", "vlm", "encoder", "hybrid"):
        p["attn"] = attn.init_attention(ks[0], cfg)
    if fam == "hybrid":
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
    if fam == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
    if fam == "moe":
        p["norm2"] = init_norm(cfg)
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
    elif fam != "ssm" and cfg.d_ff > 0:
        p["norm2"] = init_norm(cfg)
        p["mlp"] = init_mlp(ks[3], cfg)
    return p


def init_layer_cache(cfg, batch, max_len, cache_dtype=None, kind=None):
    """Zero cache for ONE layer (the model stacks L of these).

    cache_dtype=None resolves to cfg.cache_dtype (the single-sourced default
    shared with every engine — see model.init_cache).

    When the fused decode kernel is active, K/V are allocated lane-padded
    (head_dim -> 128-lane tile, seq rounded to the kernel block) so the
    kernel's zero-copy pass-through branch runs every decode step instead of
    a per-step full-cache pad-and-copy (see attention.kv_store_geometry)."""
    if cache_dtype is None:
        cache_dtype = jnp.dtype(cfg.cache_dtype)
    c: dict = {}
    if kind == "mamba":
        c["ssm"], c["conv"] = ssm_mod.init_state(cfg, batch)
        return c
    if kind == "attention" or cfg.family in ("dense", "moe", "audio", "vlm",
                                             "hybrid"):
        hkv = cfg.num_kv_heads
        hd_c, len_c = attn.kv_store_geometry(cfg, max_len)
        c["k"] = jnp.zeros((batch, hkv, len_c, hd_c), cache_dtype)
        c["v"] = jnp.zeros((batch, hkv, len_c, hd_c), cache_dtype)
        if cfg.hot_buffer > 0:
            # hot buffers block the decode kernel, so hd_c == head_dim here
            c["hot_k"] = jnp.zeros((batch, hkv, cfg.hot_buffer, hd_c),
                                   cache_dtype)
            c["hot_v"] = jnp.zeros((batch, hkv, cfg.hot_buffer, hd_c),
                                   cache_dtype)
    if kind is None and cfg.family in ("ssm", "hybrid"):
        c["ssm"], c["conv"] = ssm_mod.init_state(cfg, batch)
    return c


def _ssm_mixer(p, h, cfg, cache):
    """The Mamba-2 mixer over h with the layer's carried states: none,
    (B, T) rows, or the paged engine's packed step (slot_ids)."""
    if cache is None:
        y, _, _ = ssm_mod.apply_ssd(p, h, cfg)
        return y, {}
    if "slot_ids" in cache:
        y, st, cs = ssm_mod.apply_ssd_packed(p, h, cfg, cache["ssm"],
                                             cache["conv"], cache)
    else:
        y, st, cs = ssm_mod.apply_ssd(p, h, cfg, cache["ssm"], cache["conv"])
    return y, {"ssm": st, "conv": cs}


def apply_block(p, x, cfg, hccs=None, cache=None, length=None, positions=None,
                mrope_positions=None, kind=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    fam = cfg.family
    h = apply_norm(p["norm1"], x, cfg)

    if kind == "mamba" or (kind is None and fam == "ssm"):
        y, new_cache = _ssm_mixer(p["ssm"], h, cfg, cache)
        x = _residual(x, y, cfg)
    elif kind is None and fam == "hybrid":
        ac = None
        if cache is not None:
            ac = {k_: v_ for k_, v_ in cache.items()
                  if k_ not in ("ssm", "conv")}
            ac["length"] = length
        ya, nc = attn.apply_attention(p["attn"], h, cfg, hccs, positions, ac,
                                      mrope_positions)
        ys, sc = _ssm_mixer(p["ssm"], h, cfg, cache)
        if cache is not None:
            new_cache.update({k_: v_ for k_, v_ in nc.items()
                              if k_ != "length"})
            new_cache.update(sc)
        x = x + 0.5 * (ya + ys)      # mean-fused parallel heads (Hymba-style)
    else:
        ac = None
        if cache is not None:
            ac = dict(cache)
            ac["length"] = length
        y, nc = attn.apply_attention(p["attn"], h, cfg, hccs, positions, ac,
                                     mrope_positions)
        if cache is not None:
            new_cache.update({k_: v_ for k_, v_ in nc.items()
                              if k_ != "length"})
        x = _residual(x, y, cfg)

    if "moe" in p:
        y, aux = moe_mod.apply_moe(p["moe"], apply_norm(p["norm2"], x, cfg), cfg)
        x = _residual(x, y, cfg)
    elif "mlp" in p:
        h = apply_norm(p["norm2"], x, cfg)
        with jax.named_scope("mlp"):
            x = _residual(x, apply_mlp(p["mlp"], h, cfg), cfg)
    # Megatron-style sequence parallelism on the residual stream: between
    # blocks the carry is sharded over ("batch", seq->model); with remat=full
    # this shrinks the saved per-layer carry by the TP degree. 'seq_act' maps
    # to None unless the launcher enables it (decode steps keep t=1).
    from repro.parallel.sharding import constrain as _c
    x = _c(x, "batch", "seq_act", None)
    return x, new_cache, aux
