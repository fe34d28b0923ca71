"""The LM: embedding -> lax.scan over stacked blocks -> norm -> logits.

scan-over-layers keeps HLO size O(1) in depth (94-layer MoE compiles in the
same HLO footprint as a 2-layer toy) and is the natural remat unit. A
configuration with `layer_types` scans the pattern's period instead, each
kind's layers stacked over one axis of their own (layer_pattern).

Params layout:
    {"weights": {"embed": ..., "pos_embed"?: ..., "layers": <stacked block
     pytree>, "final_norm": ..., "lm_head"?: ..., "cls_head"?: ...},
     "hccs": {"B","S","D","scale" : (L, H)} | {} }

With `layer_types`, "layers" holds one stacked block pytree per kind,
{"attention": ..., "mamba": ...}, over that kind's layers in layer order,
and `hccs` is stacked over the attention layers alone.

`hccs` holds the paper's frozen per-head calibration constants — they are
deliberately OUTSIDE "weights" so the optimizer never touches them (the paper
freezes theta during QAT) while still being checkpointed and shardable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.attention import init_hccs_head_params
from repro.models.layers import (apply_norm, embed_tokens, init_embed,
                                 init_norm, lm_logits)
from repro.parallel.sharding import constrain


def init_params(rng, cfg, hccs_n_ref: int = 128):
    kE, kL, kH, kC = jax.random.split(rng, 4)
    if cfg.layer_types:
        layers = {}
        for i, kind in enumerate(sorted(set(cfg.layer_types))):
            keys = jax.random.split(jax.random.fold_in(kL, i),
                                    cfg.layers_of(kind))
            layers[kind] = jax.vmap(
                lambda k, kind=kind: blocks.init_block(k, cfg, kind))(keys)
    else:
        layer_keys = jax.random.split(kL, cfg.num_layers)
        layers = jax.vmap(lambda k: blocks.init_block(k, cfg))(layer_keys)
    weights = {"embed": init_embed(kE, cfg), "layers": layers,
               "final_norm": init_norm(cfg)}
    if cfg.rope == "learned":
        weights["pos_embed"] = (
            jax.random.normal(kH, (cfg.max_position, cfg.d_model),
                              jnp.dtype(cfg.dtype)) * 0.02)
    if not cfg.tie_embeddings:
        weights["lm_head"] = (
            jax.random.normal(kH, (cfg.d_model, cfg.padded_vocab),
                              jnp.dtype(cfg.dtype)) * cfg.d_model ** -0.5)
    if cfg.num_classes:
        weights["cls_head"] = (
            jax.random.normal(kC, (cfg.d_model, cfg.num_classes),
                              jnp.dtype(cfg.dtype)) * cfg.d_model ** -0.5)

    hccs = {}
    n_attn = (cfg.layers_of("attention") if cfg.layer_types
              else cfg.num_layers)
    if cfg.attention_prob == "hccs" and cfg.num_heads > 0 and n_attn:
        one = init_hccs_head_params(cfg, n_ref=hccs_n_ref)
        hccs = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_attn,) + a.shape), one)
        hccs = jax.tree.map(jnp.asarray, hccs)
    return {"weights": weights, "hccs": hccs}


def _block_caller(cfg, kind=None):
    def call(lp, x, hc, cache, length, positions, mrope_positions):
        return blocks.apply_block(lp, x, cfg, hc, cache, length, positions,
                                  mrope_positions, kind=kind)

    if cfg.remat == "full":
        return jax.checkpoint(call)
    if cfg.remat == "dots":
        return jax.checkpoint(
            call, policy=jax.checkpoint_policies.checkpoint_dots)
    return call


def forward(weights, hccs, batch, cfg, cache=None):
    """batch: {"tokens": (B,T)} or {"embeddings": (B,T,D)}, optional
    "positions" (B,T), "mrope_positions" (3,B,T).

    Returns (hidden/logits, new_cache, aux). cache is the full model cache:
        {"layers": <stacked per-layer cache>, "length": int32 scalar}
    """
    if cfg.input_mode == "embeddings" and "embeddings" in batch:
        x = batch["embeddings"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(weights["embed"], batch["tokens"], cfg)
    b, t = x.shape[:2]
    length = cache["length"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = batch.get("positions")
    if positions is None:
        # length is a scalar (lockstep decode / training) or a (B,) per-slot
        # vector (continuous batching: every slot at its own position)
        positions = jnp.atleast_1d(length)[:, None] + jnp.arange(t)[None, :]
        positions = jnp.broadcast_to(positions, (b, t))
    if cfg.rope == "learned":
        x = x + jnp.take(weights["pos_embed"], positions, axis=0)
    mrope_positions = batch.get("mrope_positions")
    x = constrain(x, "batch", "seq_act", "embed")
    # hot-buffer decode: tokens past prompt_len live in the replicated hot
    # buffer; per-layer attention needs the split point
    hot_len = None
    if cache is not None and cfg.hot_buffer > 0:
        hot_len = length - cache.get("prompt_len", jnp.zeros((), jnp.int32))
    # paged cache: the block table + this step's write targets are model-level
    # state shared by every layer (one table, per-layer pools); inject them
    # into each per-layer cache the same way hot_len rides along. `slot_ids`
    # only rides on packed token steps (token-centric chunked prefill).
    paged_extras = None
    if cache is not None and "block_table" in cache:
        paged_extras = {kk: cache[kk]
                        for kk in ("block_table", "write_pos", "kv_len",
                                   "slot_ids", "attn_cell", "dec_kv_len",
                                   "chunk_slots", "chunk_q_pos",
                                   "chunk_kv_len", "q_pos_grid", "grid_pos",
                                   "kv_len_slot", "fresh_blocks",
                                   "stage_rows", "draft_rows")
                        if kk in cache}

    hccs = jax.tree.map(jax.lax.stop_gradient, hccs)  # theta frozen (paper QAT)
    scan = _scan_pattern if cfg.layer_types else _scan_stacked
    x, aux, new_layer_caches = scan(
        weights["layers"], hccs, x, cfg, cache, length, positions,
        mrope_positions, hot_len, paged_extras)

    x = apply_norm(weights["final_norm"], x, cfg)
    new_cache = None
    if cache is not None:
        new_cache = {"layers": new_layer_caches, "length": length + t}
        if "prompt_len" in cache:
            new_cache["prompt_len"] = cache["prompt_len"]
    return x, new_cache, aux


def _scan_stacked(layers, hccs, x, cfg, cache, length, positions,
                  mrope_positions, hot_len, paged_extras):
    """Every layer one block of cfg.family, stacked over num_layers, in one
    lax.scan. Returns (x, aux, new per-layer caches)."""
    call = _block_caller(cfg)

    hccs_xs = hccs if hccs else None
    cache_xs = cache["layers"] if cache is not None else None
    L = cfg.num_layers
    # A token-major paged pool (L, N, bs, Hkv*hd), the one the XLA attention
    # path reads, rides the scan's CARRY: each layer scatters into and
    # gathers from its own slice by layer index, in place. As xs/ys the
    # scan would slice every layer's pool out and write it back each step.
    # Head-major pools (L, N, Hkv, bs, hd_c), read by the fused kernels,
    # stay xs/ys. Per-layer outputs (staged_k/staged_v) stay ys either way.
    pools = {}
    if paged_extras is not None and cache_xs["k"].ndim == 4:
        pools = {kk: cache_xs[kk] for kk in ("k", "v", "k_scale", "v_scale")
                 if kk in cache_xs}
        cache_xs = jnp.arange(L)
    xs = (layers, hccs_xs, cache_xs)
    # lax.scan requires every xs leaf to have leading dim L; None legs are
    # replaced by dummy per-layer zeros.
    if hccs_xs is None:
        xs = (xs[0], jnp.zeros((L,)), xs[2])
    if cache_xs is None:
        xs = (xs[0], xs[1], jnp.zeros((L,)))

    def scan_body(carry, xs_):
        lp, hc, lc = xs_
        x, aux, pools_ = carry
        hc = hc if isinstance(hc, dict) else None
        if pools_:
            lc = dict(pools_, layer=lc)
        lc = lc if isinstance(lc, dict) else None
        if lc is not None and hot_len is not None:
            lc = dict(lc, hot_len=hot_len)
        if lc is not None and paged_extras is not None:
            lc = dict(lc, **paged_extras)
        x, new_lc, aux_l = call(lp, x, hc, lc, length, positions,
                                mrope_positions)
        if new_lc and "hot_len" in new_lc:
            new_lc = {k_: v_ for k_, v_ in new_lc.items() if k_ != "hot_len"}
        if pools_:
            return ((x, aux + aux_l, {k_: new_lc[k_] for k_ in pools_}),
                    {k_: v_ for k_, v_ in new_lc.items() if k_ not in pools_})
        return (x, aux + aux_l, pools_), (new_lc if new_lc else jnp.zeros(()))

    (x, aux, pools), new_layer_caches = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32), pools), xs,
        unroll=min(cfg.scan_unroll, L))
    if pools:
        new_layer_caches = dict(new_layer_caches, **pools)
    return x, aux, new_layer_caches


def layer_pattern(cfg):
    """The period of cfg.layer_types as runs: the smallest P dividing
    num_layers with layer_types[i] == layer_types[i % P], and the period's
    runs of consecutive layers of one kind, each (kind, index of its first
    layer among the period's layers of that kind, count)."""
    types = cfg.layer_types
    n = len(types)
    period = next(p for p in range(1, n + 1) if n % p == 0 and all(
        types[i] == types[i % p] for i in range(n)))
    runs, seen, i = [], {}, 0
    while i < period:
        j = i
        while j < period and types[j] == types[i]:
            j += 1
        runs.append((types[i], seen.get(types[i], 0), j - i))
        seen[types[i]] = seen.get(types[i], 0) + j - i
        i = j
    return period, runs


# the carried cache leaves each layer kind reads and writes at its index,
# and the scope that reads and writes them
_KIND_STATE = {"attention": ("k", "v"), "mamba": ("ssm", "conv")}
_STATE_SCOPE = {"attention": "attn.kv_write", "mamba": "ssm.state"}


def _scan_pattern(layers, hccs, x, cfg, cache, length, positions,
                  mrope_positions, hot_len, paged_extras):
    """The layer pattern: lax.scan over its periods, and inside a period
    over each run of one kind's layers, so the HLO holds one body per run.

    Each kind's cache leaves are the whole stack over that kind's layers
    (attention K/V, the Mamba-2 SSM and conv states) and ride the scans'
    carry: a layer reads its own slice by index and writes it back in
    place, as the token-major paged pool does in _scan_stacked. A paged
    pool is handed to attention whole, with the layer index. Returns
    (x, aux, new cache leaves)."""
    if hot_len is not None:
        raise ValueError("hot buffers are not supported with layer_types")
    period, runs = layer_pattern(cfg)
    n_per = cfg.num_layers // period
    per = {k: cfg.layers_of(k) // n_per for k in layers}
    calls = {k: _block_caller(cfg, k) for k in layers}
    paged = paged_extras is not None
    state = dict(cache["layers"]) if cache is not None else {}

    def one_layer(kind, st, li, x):
        # weights are read at the layer's index in the whole stack: a
        # period's slice as scan xs would be copied out of it every period
        lp = jax.tree.map(lambda a: a[li], layers[kind])
        hc = (jax.tree.map(lambda a: a[li], hccs)
              if kind == "attention" and hccs else None)
        names = _KIND_STATE[kind]
        lc = None
        if cache is not None:
            if kind == "attention" and paged:
                lc = dict({n: st[n] for n in names}, layer=li)
            else:
                with jax.named_scope(_STATE_SCOPE[kind]):
                    lc = {n: st[n][li] for n in names}
            if paged:
                lc = dict(lc, length=length, **paged_extras)
        x, new_lc, aux = calls[kind](lp, x, hc, lc, length, positions,
                                     mrope_positions)
        if cache is not None:
            if kind == "attention" and paged:
                st = dict(st, **{n: new_lc[n] for n in names})
            else:
                with jax.named_scope(_STATE_SCOPE[kind]):
                    st = dict(st, **{n: st[n].at[li].set(new_lc[n])
                                     for n in names})
        return x, st, aux

    def period_body(carry, pi):
        for kind, start, n in runs:
            def run_body(c, j, kind=kind, base=pi * per[kind] + start):
                x, aux, st = c
                x, st, a = one_layer(kind, st, base + j, x)
                return (x, aux + a, st), None

            carry, _ = jax.lax.scan(run_body, carry, jnp.arange(n))
        return carry, None

    (x, aux, state), _ = jax.lax.scan(
        period_body, (x, jnp.zeros((), jnp.float32), state),
        jnp.arange(n_per))
    return x, aux, state


def logits_from_hidden(weights, x, cfg):
    with jax.named_scope("head"):
        return lm_logits(weights["embed"], weights, x, cfg)


def lm_loss(weights, hccs, batch, cfg):
    """Next-token cross-entropy. batch needs "labels" (B, T) with -100 = pad.

    The gold logit is gathered with a one-hot einsum (not take_along_axis):
    under vocab-sharded logits the einsum reduces over the sharded axis with
    a cheap partial-sum + all-reduce instead of all-gathering the full
    (B, T, V) logits tensor.
    """
    x, _, aux = forward(weights, hccs, batch, cfg)
    logits = logits_from_hidden(weights, x, cfg)
    labels = batch["labels"]
    mask = labels >= 0
    labels_c = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels_c, logits.shape[-1], dtype=logits.dtype)
    onehot = constrain(onehot, "batch", "attn_seq", "vocab")
    gold = jnp.einsum("btv,btv->bt", logits, onehot).astype(jnp.float32)
    nll = (logz - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1)
    return loss, {"lm_loss": loss, "aux_loss": aux}


def cls_loss(weights, hccs, batch, cfg):
    """Sequence classification via first-token pooling (BERT-style)."""
    x, _, aux = forward(weights, hccs, batch, cfg)
    pooled = x[:, 0]
    logits = (pooled @ weights["cls_head"]).astype(jnp.float32)
    labels = batch["cls_labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    loss = jnp.mean(logz - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"cls_loss": loss, "acc": acc, "aux_loss": aux}


def init_cache(cfg, batch_size: int, max_len: int, cache_dtype=None,
               per_slot_lengths: bool = False):
    """per_slot_lengths=True makes `length` a (batch,) vector — the slot-arena
    layout for continuous batching, where every slot decodes at its own
    frontier (attention then masks/writes per slot).

    cache_dtype=None (the default) resolves to cfg.cache_dtype — the single
    source every engine and bare prefill caller shares, so KV dtype/bytes can
    never silently disagree between a direct init_cache call and an engine."""
    if cache_dtype is None:
        cache_dtype = jnp.dtype(cfg.cache_dtype)
    stacks = ([(k, cfg.layers_of(k)) for k in sorted(set(cfg.layer_types))]
              if cfg.layer_types else [(None, cfg.num_layers)])
    layers = {}
    for kind, n in stacks:
        one = blocks.init_layer_cache(cfg, batch_size, max_len, cache_dtype,
                                      kind)
        layers.update(jax.tree.map(
            lambda a, n=n: jnp.asarray(jnp.broadcast_to(a[None],
                                                        (n,) + a.shape)),
            one))
    shape = (batch_size,) if per_slot_lengths else ()
    c = {"layers": layers, "length": jnp.zeros(shape, jnp.int32)}
    if cfg.hot_buffer > 0:
        if per_slot_lengths:
            raise ValueError("hot buffers are lockstep-only: they track a "
                             "single scalar prompt_len, incompatible with "
                             "per-slot lengths")
        c["prompt_len"] = jnp.zeros((), jnp.int32)
    return c


def prefill(weights, hccs, batch, cfg, max_len: int, cache_dtype=None):
    """Run the prompt through the model, filling the cache. Returns
    (last-token logits, cache). cache_dtype=None -> cfg.cache_dtype."""
    b, t = (batch["tokens"].shape if "tokens" in batch
            else batch["embeddings"].shape[:2])
    cache = init_cache(cfg, b, max_len, cache_dtype)
    x, cache, _ = forward(weights, hccs, batch, cfg, cache=cache)
    if cfg.hot_buffer > 0:
        cache = dict(cache, prompt_len=cache["length"])
    logits = logits_from_hidden(weights, x[:, -1:], cfg)
    return logits[:, 0], cache


def decode_step(weights, hccs, tokens, cache, cfg, embeddings=None):
    """One-token decode. tokens: (B, 1) (or embeddings (B,1,D)).
    Returns (logits (B, vocab), new_cache)."""
    batch = ({"embeddings": embeddings} if embeddings is not None
             else {"tokens": tokens})
    x, cache, _ = forward(weights, hccs, batch, cfg, cache=cache)
    logits = logits_from_hidden(weights, x, cfg)
    return logits[:, 0], cache
