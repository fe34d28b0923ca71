"""Logical-axis sharding rules -> PartitionSpec / NamedSharding.

Model code annotates activations with `constrain(x, 'batch', None, 'model')`
using *logical* names; the mapping to physical mesh axes is set per-launch via
`use_rules(mesh)`. Outside any rules context the calls are no-ops, so the same
model code runs on 1 CPU device and on a 512-chip mesh unchanged.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> physical mesh axis (or tuple of axes)
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": "data",        # sequence parallelism (long-context variants)
    "embed": None,
    "model": "model",        # tensor parallel
    "kv_model": "model",
    "vocab": "model",
    "expert": "model",       # expert parallel (EP == TP axis)
    "expert_cap": "data",    # MoE capacity dim sharded with the token shards
    "ffn": "model",
    "fsdp": "data",          # FSDP/ZeRO-3: weights sharded over the DP axis,
                             # all-gathered per scanned layer
    "seq_act": None,         # sequence parallelism on the residual stream
                             # (launcher maps it to "model" for train/prefill)
    "attn_seq": None,        # sequence sharding INSIDE attention (serve_sp
                             # profile: q/k/v stay seq-sharded, heads local)
    "ssd_chunk": "model",    # SSD intra-chunk tensors shard their chunk dim
                             # over the TP axis (the (b,nc,L,L,nh) decay/score
                             # tensors are the SSD memory hot-spot; chunks are
                             # independent outside the tiny state scan)
    "moe_group": ("pod", "data"),  # MoE dispatch groups live with the token
                                   # shards (both pod and data batch axes)
    "moe_embed": "model",    # inside dispatch/combine the embedding dim shards
                             # over the TP axis: gathers pass it through, so
                             # the (G, M*K, D) entry tensors and their grads
                             # stay 256-way sharded instead of model-replicated
    "layers": None,
}

_CTX: contextvars.ContextVar[tuple[Mesh, dict] | None] = contextvars.ContextVar(
    "sharding_ctx", default=None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: dict[str, Any] | None = None):
    rules = dict(DEFAULT_RULES, **(rules or {}))
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def _resolve(mesh: Mesh, rules: dict, logical: tuple) -> P:
    axes = []
    used: set = set()
    for name in logical:
        if name is None:
            axes.append(None)
            continue
        phys = rules.get(name, None)
        if phys is None:
            axes.append(None)
        elif isinstance(phys, tuple):
            present = tuple(a for a in phys
                            if a in mesh.axis_names and a not in used)
            used.update(present)
            axes.append(present if present else None)
        else:
            if phys in mesh.axis_names and phys not in used:
                used.add(phys)
                axes.append(phys)
            else:  # earlier dim already claimed this mesh axis
                axes.append(None)
    return P(*axes)


def spec(*logical) -> P:
    """Resolve logical axes to a PartitionSpec under the active rules."""
    ctx = _CTX.get()
    if ctx is None:
        return P(*([None] * len(logical)))
    mesh, rules = ctx
    return _resolve(mesh, rules, logical)


def constrain(x: jax.Array, *logical) -> jax.Array:
    """with_sharding_constraint under the active rules; no-op without a mesh."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    s = _resolve(mesh, rules, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))


# ---------------------------------------------------------------------------
# Parameter partition specs, by path-name pattern
# ---------------------------------------------------------------------------

# Ordered (regex, logical axes *excluding* the stacked-layer leading dim).
# TP ("model") on the head/ffn/vocab dim + FSDP ("fsdp" -> data axis) on the
# other dim: weights and f32 Adam moments both shard 256-way, which is what
# lets yi-34b / qwen3-235B optimizer state fit 16 GB/chip; the per-layer
# all-gather happens inside the layer scan (ZeRO-3 style).
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed/table",            ("vocab", "fsdp")),
    (r"pos_embed",              (None, "fsdp")),
    (r"lm_head",                ("fsdp", "vocab")),
    (r"(wq|wk|wv)$",            ("fsdp", "model")),
    (r"wo$",                    ("model", "fsdp")),
    (r"experts/(w_in|w_gate)",  ("expert", "fsdp", None)),
    (r"experts/w_out",          ("expert", None, "fsdp")),
    (r"(w_in|w_gate)$",         ("fsdp", "ffn")),
    (r"w_out$",                 ("ffn", "fsdp")),
    (r"router",                 ("fsdp", None)),
    (r"ssm/in_",                ("fsdp", None)),   # proj dim not TP-divisible for hymba
    (r"ssm/out_proj",           ("model", "fsdp")),
    (r"ssm/(A_log|dt_bias|D)",  (None,)),
    (r"(norm|scale|bias|ln)",   (None,)),
    (r"hccs",                   (None,)),
    (r"cls_head",               ("fsdp", None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):          # dataclass fields (GetAttrKey)
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def param_spec_tree(params, stacked_prefix: str = "layers"):
    """PartitionSpec pytree for a param tree; leaves under `stacked_prefix`
    get a leading None for the scan-stacked layer dim."""
    def one(path, leaf):
        name = _path_str(path)
        stacked = f"{stacked_prefix}/" in name
        for pat, logical in _PARAM_RULES:
            if re.search(pat, name):
                # pad/trim logical axes to leaf rank (minus stacked dim)
                rank = leaf.ndim - (1 if stacked else 0)
                ax = list(logical)[:rank]
                ax += [None] * (rank - len(ax))
                full = ([None] if stacked else []) + ax
                return spec(*full)
        return spec(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(one, params)


def named_sharding_tree(params, mesh: Mesh, stacked_prefix: str = "layers"):
    specs = param_spec_tree(params, stacked_prefix)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def bytes_per_device(params, mesh: Mesh) -> float:
    """Rough parameter bytes per device under the param sharding rules."""
    specs = param_spec_tree(params)
    total = 0.0
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for leaf, s in zip(jax.tree.leaves(params),
                       jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        shard = 1
        for ax in s:
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                if a is not None:
                    shard *= axis_sizes.get(a, 1)
        total += leaf.size * leaf.dtype.itemsize / shard
    return total
