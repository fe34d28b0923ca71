"""Pallas TPU kernel: fused single-query HCCS decode attention.

The serving hot path: one new query token per slot against the slot's KV-cache
ring buffer. Where `hccs_mha_fused` pads the query axis to a full 128-row tile
(127/128 wasted MXU work at decode) and masks with a single global KV length,
this kernel is shaped for continuous batching:

  * queries are packed per KV head — a (1, g, d) tile of the g GQA query heads
    that share one K/V stream, so each K block is loaded once per group, not
    once per query head;
  * the KV length is per *slot* (the `lengths` vector of the slot arena), so a
    mixed-progress batch masks each row at its own cache frontier;
  * KV blocks entirely beyond a slot's length are skipped with `pl.when`
    (no matmul issued), so a fresh request in a mostly-empty slot costs
    O(length), not O(max_len).

Two variants, selected statically:

  row-max (default, the paper's Algorithm 1): phase 0 sweeps KV once for the
  quantized row max (Stage 1), phase 1 re-sweeps fusing distance/clamp/affine
  (Stages 2-3), Z (Stage 4) and s @ V, with one final normalization (Stage 5).
  HCCS linearity means no per-block rescale — only the single 1/Z at the end.

  static-max (`static_max=True`, the beyond-paper ConSmax-style variant):
  distances are taken against the int8 ceiling (127) instead of the row max,
  deleting phase 0 entirely — a single KV pass per decode step. Requires the
  logit scale calibrated to place row maxima near 127 (see core/hccs.py).

Normalization is mode-aware (the same post-hoc trick as the blockwise XLA
path): HCCS linearity lets the integer reciprocal truncation be applied to the
accumulated numerator, keeping the kernel consistent with the dense i16 modes.
i8 modes floor per element *after* the rho multiply, which is not post-hoc
linear; they fall back to the wide (exact 1/Z) scale, as everywhere else.

A third entry point, `hccs_paged_decode`, runs the same sweep against the
paged KV pool of serve/paged.py: the KV BlockSpec index_map reads the slot's
scalar-prefetched *block table* instead of a contiguous offset, so the block
gather is free (it steers the DMA), and sentinel (-1) table entries reuse the
dead-block `pl.when` skip path. HCCS linearity is what makes paging trivial
here — partial sums over blocks are exact, so no per-block rescaling is ever
needed regardless of the physical block order. No engine step calls it:
every paged step is a packed step, which runs the fourth variant below; its
tests and chip_smoke.py keep it checked.

A fourth, `hccs_packed_prefill`, is the token-centric variant for the packed
step (serve/paged.py): rows are TOKENS, not slots. Each of the T
packed tokens carries a slot id and a per-token frontier; the KV index_map
walks `block_table[slot_ids[token]]` — one extra scalar indirection on top of
the paged walk — so a ragged mixed prefill/decode batch runs as T independent
single-query sweeps with zero padded query lanes. Pad lanes (slot id -1)
reuse the dead-block skip and return zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hccs import hccs_mode_inv

_NEG_BIG = -(2 ** 30)


def _decode_tile(scale_ref, theta_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, z_scr, acc_scr, *, kv, nk, col0, block_live,
                 group: int, mode: str, static_max: bool, sm_denom: float,
                 k_scale=None, v_scale=None):
    """One (phase, KV-tile) step of the single-query HCCS sweep, shared by the
    dense slot-arena kernel and the paged block-table kernel. The callers
    differ only in how the current tile was located (contiguous offset vs
    block-table gather) — `nk` is the slot frontier, `col0` the tile's first
    *logical* KV position, `block_live` whether the tile holds any live KV.
    `k_scale`/`v_scale` (kv_quant="int8" pools only) are this tile's
    per-(block, kv-head) dequant scalars: the int8 K/V tiles are dequantized
    elementwise right after the load — the identical values the XLA gather
    path produces, so kernel/XLA bit-parity survives quantization."""
    ph = pl.program_id(1)                     # phase (always 0 if static_max)
    ki = pl.program_id(2)                     # KV tile
    last_ph = 0 if static_max else 1

    # per-row (= per query head) calibration columns; group is static so this
    # unrolls to `group` scalar SMEM reads
    heads = [kv * group + j for j in range(group)]
    scale_col = jnp.stack([scale_ref[h] for h in heads])[:, None]
    B_col = jnp.stack([theta_ref[h, 0] for h in heads])[:, None]
    S_col = jnp.stack([theta_ref[h, 1] for h in heads])[:, None]
    D_col = jnp.stack([theta_ref[h, 2] for h in heads])[:, None]

    if not static_max:
        @pl.when((ph == 0) & (ki == 0))
        def _():
            m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)

    @pl.when((ph == last_ph) & (ki == 0))
    def _():
        z_scr[...] = jnp.zeros_like(z_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def quantized_logits():
        q = q_ref[0].astype(jnp.float32)                       # (g, d)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        if k_scale is not None:
            k = k * k_scale                    # int8 block pool -> float
        logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        # divide (not multiply-by-reciprocal): the XLA STE paths divide by
        # sqrt(hd), and a 1-ulp difference here can flip jnp.round at an
        # int8 bin boundary — bit-parity with the dense path requires the
        # identical operation
        logits = logits / sm_denom
        q_int = jnp.clip(jnp.round(logits / scale_col),
                         -128., 127.).astype(jnp.int32)        # (g, bk)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, q_int.shape, 1)
        valid = cols < nk
        return jnp.where(valid, q_int, _NEG_BIG), valid

    if not static_max:
        @pl.when(block_live & (ph == 0))
        def _():  # Stage 1: running row max over the KV sweep
            q_int, _ = quantized_logits()
            bmax = jnp.max(q_int, axis=-1, keepdims=True)      # (g, 1)
            m_scr[:, 0:1] = jnp.maximum(m_scr[:, 0:1], bmax)

    @pl.when(block_live & (ph == last_ph))
    def _():  # Stages 2-4 + s @ V accumulation
        q_int, valid = quantized_logits()
        m = jnp.full_like(q_int[:, 0:1], 127) if static_max else m_scr[:, 0:1]
        delta = jnp.minimum(m - q_int, D_col)
        s = B_col - S_col * delta
        s = jnp.where(valid, s, 0).astype(jnp.float32)
        z_scr[:, 0:1] += jnp.sum(s, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                    # (bk, d)
        if v_scale is not None:
            v = v * v_scale                    # int8 block pool -> float
        acc_scr[...] += jax.lax.dot_general(
            s, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((ph == last_ph) & (ki == pl.num_programs(2) - 1))
    def _():  # Stage 5: single mode-aware normalization (shared with the
        # blockwise XLA path so kernel and STE decode stay bit-consistent)
        z = jnp.maximum(z_scr[:, 0:1], 1.0)
        o_ref[0] = (acc_scr[...] * hccs_mode_inv(z, mode)).astype(o_ref.dtype)


def _decode_kernel(scale_ref, theta_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, z_scr, acc_scr, *, num_kv: int, group: int,
                   block_k: int, mode: str, static_max: bool,
                   sm_denom: float):
    i = pl.program_id(0)                      # slot * num_kv + kv head
    ki = pl.program_id(2)                     # KV block
    slot = i // num_kv
    kv = jax.lax.rem(i, num_kv)
    nk = len_ref[slot]                        # this slot's cache frontier
    col0 = ki * block_k
    _decode_tile(scale_ref, theta_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, z_scr, acc_scr, kv=kv, nk=nk, col0=col0,
                 block_live=col0 < nk,        # skip blocks past the frontier
                 group=group, mode=mode, static_max=static_max,
                 sm_denom=sm_denom)


_SC_TILE = 8 * 128      # flattened scales per (8, 128) f32 VMEM tile


def _flat_scales(scales):
    """(N, Hkv) per-block dequant scales -> lane-dense (rows, 128) f32, row-
    major over (block, kv head) and zero-padded to whole (8, 128) tiles.
    Scale (e, kv) sits at flat index e * Hkv + kv."""
    flat = scales.astype(jnp.float32).reshape(-1)
    return jnp.pad(flat, (0, -flat.size % _SC_TILE)).reshape(-1, 128)


def _scale_at(tile_ref, f):
    """Flat scale f, read from the (8, 128) tile holding it, as a (1, 1)
    value. The sum adds exact zeros to the one selected element, so the
    value is bit-identical to an XLA gather of it."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    hit = rows * 128 + lanes == jax.lax.rem(f, _SC_TILE)
    return jnp.sum(jnp.where(hit, tile_ref[...], 0.0), keepdims=True)


def _tile_scales(scale_refs, entry, kv, num_kv: int):
    """This tile's (k, v) dequant scales on an int8 pool, else (None, None).
    A dead entry reads block 0's, which the masked tile never uses."""
    if not scale_refs:
        return None, None
    f = jnp.maximum(entry, 0) * num_kv + kv
    return tuple(_scale_at(r, f) for r in scale_refs)


def _paged_kernel(tbl_ref, len_ref, scale_ref, theta_ref, q_ref, k_ref,
                  v_ref, *rest, num_kv: int, group: int, block_size: int,
                  block_k: int, mode: str, static_max: bool,
                  sm_denom: float):
    *scale_refs, o_ref, m_scr, z_scr, acc_scr = rest
    i = pl.program_id(0)                      # slot * num_kv + kv head
    ki = pl.program_id(2)                     # sub-tile of a table entry
    slot = i // num_kv
    kv = jax.lax.rem(i, num_kv)
    per = block_size // block_k               # kernel tiles per KV block
    ti = ki // per                            # block-table column
    entry = tbl_ref[slot, ti]                 # pool block id, -1 = dead
    nk = len_ref[slot]
    col0 = ti * block_size + jax.lax.rem(ki, per) * block_k
    k_s, v_s = _tile_scales(scale_refs, entry, kv, num_kv)
    # dead-block skip: a sentinel table entry is the paged analogue of the
    # dense kernel's past-the-frontier block (same pl.when skip path); the
    # frontier check also covers trailing sub-tiles of a partially-filled
    # final block
    _decode_tile(scale_ref, theta_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, z_scr, acc_scr, kv=kv, nk=nk, col0=col0,
                 block_live=(entry >= 0) & (col0 < nk),
                 group=group, mode=mode, static_max=static_max,
                 sm_denom=sm_denom, k_scale=k_s, v_scale=v_s)


def _packed_kernel(sid_ref, tbl_ref, len_ref, scale_ref, theta_ref, q_ref,
                   k_ref, v_ref, *rest, num_kv: int, group: int,
                   block_size: int, block_k: int, mode: str,
                   static_max: bool, sm_denom: float):
    *scale_refs, o_ref, m_scr, z_scr, acc_scr = rest
    i = pl.program_id(0)                      # token * num_kv + kv head
    ki = pl.program_id(2)                     # sub-tile of a table entry
    tok = i // num_kv
    kv = jax.lax.rem(i, num_kv)
    per = block_size // block_k               # kernel tiles per KV block
    ti = ki // per                            # block-table column
    slot = sid_ref[tok]                       # owning slot, -1 = pad lane
    entry = tbl_ref[jnp.maximum(slot, 0), ti]
    nk = len_ref[tok]                         # per-TOKEN causal frontier
    col0 = ti * block_size + jax.lax.rem(ki, per) * block_k
    k_s, v_s = _tile_scales(scale_refs, entry, kv, num_kv)
    # a pad lane (slot < 0) is a whole-row dead block: every tile skipped,
    # the epilogue still writes zeros (acc/z are zeroed unconditionally)
    _decode_tile(scale_ref, theta_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, z_scr, acc_scr, kv=kv, nk=nk, col0=col0,
                 block_live=(slot >= 0) & (entry >= 0) & (col0 < nk),
                 group=group, mode=mode, static_max=static_max,
                 sm_denom=sm_denom, k_scale=k_s, v_scale=v_s)


def _lane_pad_q(q, hkv: int, d_pad: int):
    """Pack per-KV-head query groups and pad head_dim to the lane tile:
    (rows, H, d) -> (rows * Hkv, g, d_pad) float32. Shared prologue of all
    three single-query kernels (rows are slots or packed tokens)."""
    rows, h, d = q.shape
    g = h // hkv
    qg = q.astype(jnp.float32).reshape(rows * hkv, g, d)
    return jnp.zeros((rows * hkv, g, d_pad), jnp.float32).at[:, :, :d].set(qg)


def _lane_pad_pool(k_pool, v_pool, d_pad: int):
    """Lane-pad a (N, Hkv, bs, dp) KV block pool to d_pad, passing a
    lane-padded pool (the production layout from serve/paged.py) through
    zero-copy so blocks stream straight from the pool."""
    n, hkv, bs, dp = k_pool.shape
    if dp == d_pad:
        return k_pool, v_pool
    kp = jnp.zeros((n, hkv, bs, d_pad), k_pool.dtype).at[..., :dp].set(k_pool)
    vp = jnp.zeros((n, hkv, bs, d_pad), v_pool.dtype).at[..., :dp].set(v_pool)
    return kp, vp


def _decode_scratch(g: int, d_pad: int):
    """VMEM scratch triple (running max, Z accumulator, s @ V accumulator)
    shared by every _decode_tile caller."""
    return [pltpu.VMEM((g, 128), jnp.int32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d_pad), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("mode", "static_max", "block_k",
                                             "interpret"))
def hccs_decode(q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
                scale: jax.Array, theta: jax.Array, *, mode: str = "wide",
                static_max: bool = False, block_k: int = 128,
                interpret: bool = True) -> jax.Array:
    """Single-query HCCS attention against a slot-arena KV cache.

    q: (B, H, d) — one query per slot; k, v: (B, Hkv, Tmax, d) ring buffers;
    lengths: (B,) int32 valid-KV counts (the slot frontier, *including* the
    current token's K/V already written at lengths-1); scale: (H,) f32 per-head
    int8 logit scales; theta: (H, 3) int32 per-head (B, S, D).
    Returns (B, H, d) in q.dtype. Rows with lengths == 0 return zeros.
    """
    b, h, d = q.shape
    _, hkv, tmax, dk = k.shape
    assert h % hkv == 0
    g = h // hkv
    sm_denom = float(d) ** 0.5
    d_pad = max(-(-d // 128) * 128, 128)
    tk_pad = -(-tmax // block_k) * block_k
    qp = _lane_pad_q(q, hkv, d_pad)
    # the decode step runs per generated token: when the cache arena is
    # already tile-aligned (head_dim padded to the lane multiple, max_len a
    # block_k multiple — what init_cache allocates whenever the kernel is
    # enabled, see attention.kv_store_geometry), pass it through without any
    # per-step full-cache pad-and-copy. The copy below only runs for caches
    # allocated outside that path (e.g. direct kernel calls in tests).
    if tk_pad == tmax and d_pad == dk:
        kp, vp = k, v
    else:
        kp = jnp.zeros((b, hkv, tk_pad, d_pad),
                       k.dtype).at[:, :, :tmax, :dk].set(k)
        vp = jnp.zeros((b, hkv, tk_pad, d_pad),
                       v.dtype).at[:, :, :tmax, :dk].set(v)
    num_phases = 1 if static_max else 2
    grid = (b * hkv, num_phases, tk_pad // block_k)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, num_kv=hkv, group=g,
                          block_k=block_k, mode=mode, static_max=static_max,
                          sm_denom=sm_denom),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scale (H,)
            pl.BlockSpec(memory_space=pltpu.SMEM),            # theta (H,3)
            pl.BlockSpec(memory_space=pltpu.SMEM),            # lengths (B,)
            pl.BlockSpec((1, g, d_pad), lambda i, ph, ki: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d_pad),
                         lambda i, ph, ki, KV=hkv: (i // KV, i % KV, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_pad),
                         lambda i, ph, ki, KV=hkv: (i // KV, i % KV, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d_pad), lambda i, ph, ki: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d_pad), q.dtype),
        scratch_shapes=_decode_scratch(g, d_pad),
        interpret=interpret,
    )(scale.astype(jnp.float32), theta.astype(jnp.int32),
      lengths.astype(jnp.int32), qp, kp, vp)
    return out[:, :, :d].reshape(b, h, d)


def _pool_operands(tile, g: int, d_pad: int, bk: int, per: int, qp, kp, vp,
                   k_scales, v_scales):
    """BlockSpecs and operands (q, k, v[, k_scales, v_scales]) of the two
    block-table kernels. `tile(i, ki, *prefetch_refs)` names the (pool block,
    kv head) that grid step (i, ki) reads. An int8 pool's (N, Hkv) scales are
    read through that same steer, as the (8, 128) VMEM tile of _flat_scales
    holding the pair's scale, so nothing the size of the pool lands in SMEM
    (SMEM pads an (N, Hkv) array's minor dimension to 128)."""
    hkv = kp.shape[1]
    kv = pl.BlockSpec((1, 1, bk, d_pad), lambda i, ph, ki, *refs: (
        *tile(i, ki, *refs), jax.lax.rem(ki, per), 0))
    in_specs = [pl.BlockSpec((1, g, d_pad), lambda i, ph, ki, *_: (i, 0, 0)),
                kv, kv]
    operands = [qp, kp, vp]
    if k_scales is not None:
        def sc_map(i, ph, ki, *refs):
            e, kv_head = tile(i, ki, *refs)
            return (e * hkv + kv_head) // _SC_TILE, 0

        sc = pl.BlockSpec((8, 128), sc_map)
        in_specs += [sc, sc]
        operands += [_flat_scales(k_scales), _flat_scales(v_scales)]
    return in_specs, operands


@functools.partial(jax.jit, static_argnames=("mode", "static_max", "block_k",
                                             "interpret"))
def hccs_paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_table: jax.Array, lengths: jax.Array,
                      scale: jax.Array, theta: jax.Array, *,
                      mode: str = "wide", static_max: bool = False,
                      block_k: int = 128, interpret: bool = True,
                      k_scales: jax.Array | None = None,
                      v_scales: jax.Array | None = None) -> jax.Array:
    """Single-query HCCS attention against a PAGED KV pool (serve/paged.py).

    Where `hccs_decode` reads slot `b`'s KV from a contiguous (Tmax, d) ring,
    this variant walks slot `b`'s *block table*: grid step (i, ph, ki) DMAs
    pool block `block_table[slot, ki // per]` (scalar-prefetched, so the
    gather happens in the BlockSpec index_map — no host-side copy), covering
    logical positions [ti*block_size, (ti+1)*block_size).

    q: (B, H, d) one query per slot; k_pool/v_pool: (N, Hkv, block_size, dp)
    global block pools (dp = d or lane-padded 128); block_table: (B, nblk)
    int32 pool block ids, -1 = unallocated (sentinel rows are skipped with the
    same pl.when path as the dense kernel's dead blocks); lengths: (B,) valid
    logical-KV counts; scale: (H,) f32; theta: (H, 3) int32.
    With kv_quant="int8" pools, `k_scales`/`v_scales` (N, Hkv) f32 carry the
    per-block, per-kv-head dequant scales (each read per tile through the
    same block-table steer as its KV tile); each KV tile is dequantized
    in-register after the load.
    Returns (B, H, d) in q.dtype. Rows with lengths == 0 return zeros.
    """
    b, h, d = q.shape
    n, hkv, bs, dp = k_pool.shape
    assert h % hkv == 0
    g = h // hkv
    sm_denom = float(d) ** 0.5
    bk = min(block_k, bs)
    assert bs % bk == 0, (bs, bk)
    per = bs // bk
    d_pad = max(-(-d // 128) * 128, 128)
    qp = _lane_pad_q(q, hkv, d_pad)
    kp, vp = _lane_pad_pool(k_pool, v_pool, d_pad)
    nblk = block_table.shape[1]
    num_phases = 1 if static_max else 2
    grid = (b * hkv, num_phases, nblk * per)

    def tile(i, ki, tbl, *_):
        # the block-table gather: sentinel entries are clamped to pool block
        # 0 so the DMA has a valid source; the kernel body never reads the
        # tile (block_live is False), so the clamp is semantically inert
        return jnp.maximum(tbl[i // hkv, ki // per], 0), jax.lax.rem(i, hkv)

    in_specs, operands = _pool_operands(tile, g, d_pad, bk, per, qp, kp, vp,
                                        k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # table, lengths, scale, theta
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d_pad), lambda i, ph, ki, *_: (i, 0, 0)),
        scratch_shapes=_decode_scratch(g, d_pad),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, num_kv=hkv, group=g, block_size=bs,
                          block_k=bk, mode=mode, static_max=static_max,
                          sm_denom=sm_denom),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, d_pad), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      scale.astype(jnp.float32), theta.astype(jnp.int32), *operands)
    return out[:, :, :d].reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("mode", "static_max", "block_k",
                                             "interpret"))
def hccs_packed_prefill(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_table: jax.Array, slot_ids: jax.Array,
                        lengths: jax.Array, scale: jax.Array,
                        theta: jax.Array, *, mode: str = "wide",
                        static_max: bool = False, block_k: int = 128,
                        interpret: bool = True,
                        k_scales: jax.Array | None = None,
                        v_scales: jax.Array | None = None) -> jax.Array:
    """Token-centric HCCS attention over a PAGED pool: one query per TOKEN.

    The packed chunked-prefill step (serve/paged.py packed mode) flattens a
    mixed prefill/decode batch into T ragged tokens; each runs the same
    single-query sweep as `hccs_paged_decode`, but the KV walk is steered by
    the token's OWNING SLOT: tile ki of token t DMAs pool block
    `block_table[slot_ids[t], ki // per]`. Causality inside a chunk needs no
    extra mask — token t's frontier `lengths[t]` (its logical position + 1)
    already stops the sweep before any later token's KV.

    q: (T, H, d) one query per packed token; k_pool/v_pool:
    (N, Hkv, block_size, dp) global pools (dp = d or lane-padded 128);
    block_table: (B, nblk) int32 pool ids, -1 = unallocated; slot_ids: (T,)
    int32 owning slot per token, -1 = pad lane (returns zeros); lengths: (T,)
    per-token valid-KV counts *including* the token's own K/V; scale: (H,)
    f32; theta: (H, 3) int32. `k_scales`/`v_scales` (N, Hkv) f32: per-block
    dequant scales for kv_quant="int8" pools (see hccs_paged_decode).
    Returns (T, H, d) in q.dtype.
    """
    t, h, d = q.shape
    n, hkv, bs, dp = k_pool.shape
    assert h % hkv == 0
    g = h // hkv
    sm_denom = float(d) ** 0.5
    bk = min(block_k, bs)
    assert bs % bk == 0, (bs, bk)
    per = bs // bk
    d_pad = max(-(-d // 128) * 128, 128)
    qp = _lane_pad_q(q, hkv, d_pad)
    kp, vp = _lane_pad_pool(k_pool, v_pool, d_pad)
    nblk = block_table.shape[1]
    num_phases = 1 if static_max else 2
    grid = (t * hkv, num_phases, nblk * per)

    def tile(i, ki, sid, tbl, *_):
        # the slot-indirect block-table gather: pad lanes clamp to slot 0 and
        # sentinel entries to pool block 0 so the DMA has a valid source; the
        # kernel body never reads those tiles (block_live is False)
        slot = jnp.maximum(sid[i // hkv], 0)
        return jnp.maximum(tbl[slot, ki // per], 0), jax.lax.rem(i, hkv)

    in_specs, operands = _pool_operands(tile, g, d_pad, bk, per, qp, kp, vp,
                                        k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # sid, table, lengths, scale, theta
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d_pad), lambda i, ph, ki, *_: (i, 0, 0)),
        scratch_shapes=_decode_scratch(g, d_pad),
    )
    out = pl.pallas_call(
        functools.partial(_packed_kernel, num_kv=hkv, group=g, block_size=bs,
                          block_k=bk, mode=mode, static_max=static_max,
                          sm_denom=sm_denom),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t * hkv, g, d_pad), q.dtype),
        interpret=interpret,
    )(slot_ids.astype(jnp.int32), block_table.astype(jnp.int32),
      lengths.astype(jnp.int32), scale.astype(jnp.float32),
      theta.astype(jnp.int32), *operands)
    return out[:, :, :d].reshape(t, h, d)
