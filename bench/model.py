"""A configuration's weights, made from the seed, for every architecture.

Weights are a pure function of (seed, leaf name, layer): every value is a
16-bit random integer times one float32 constant, rounded once to
bfloat16. Which leaves there are, their shapes, the layers each is
stacked over and their scales come from the architecture module's
`leaf_table` (archs/<arch>.py). The served weights are made in one jitted
call; the reference makes each layer again from the same function, so
both see the same numbers without the reference taking anything from the
program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

# standard deviation of a uniform int16: 65536 / sqrt(12)
_INT16_STD = 65536 / 12 ** 0.5


def seed_key(seed: int):
    """A PRNG key from any seed below 2**64 (PRNGKey alone would wrap
    seeds past 32 bits silently)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


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


def weights_fn(c: dict, cfg, arch):
    """key -> the served weights in the program's layout. Stacked leaves
    are made one layer at a time inside the call (lax.map), so its peak
    stays near the weights' own bytes. The embedding is padded to the
    program's padded vocabulary with zero rows, which no token reads and
    the head masks."""
    table = arch.leaf_table(c)
    vp = cfg.padded_vocab

    def build(key):
        flat = {}
        for name, (shape, layers, rule) in table.items():
            if layers:
                flat[name] = jax.lax.map(
                    lambda l, n=name, s=shape, r=rule: leaf_value(key, n, s, r, l),
                    jnp.arange(layers))
            else:
                flat[name] = leaf_value(key, name, shape, rule)
        e = flat["embed/table"]
        flat["embed/table"] = jnp.pad(e, ((0, vp - e.shape[0]), (0, 0)))
        return _nest(flat)

    return build


def make_params(c: dict, seed: int, cfg, arch):
    """The served parameters on the default device, the weights made in one
    jitted call, checked against the program's own init shapes; `arch` is
    the configuration's architecture module."""
    from repro.models import model as M
    weights = jax.jit(weights_fn(c, cfg, arch))(seed_key(seed))
    got = {"weights": weights, "hccs": arch.hccs_params(c)}
    want = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    if shapes(got) != shapes(want):
        raise ValueError(f"{c['name']}: the benchmark's parameters do not "
                         "match the program's layout: "
                         f"{shapes(got)} != {shapes(want)}")
    return got
