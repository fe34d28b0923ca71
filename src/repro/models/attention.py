"""GQA attention with a pluggable probability function — this is where HCCS
plugs into every architecture.

Two XLA implementations with identical semantics (plus the Pallas fused kernel
in kernels/attention.py for TPU runtime):
  dense     — materialize (B,H,Tq,Tk) scores; short sequences & decode rows.
  blockwise — two-pass lax.scan over KV blocks, O(Tq * block_k) live memory;
              the XLA analogue of the fused kernel, used for long sequences.

HCCS semantics are the differentiable QAT form (fake-quant + STE integer
pipeline) so the same code trains and serves. Masked lanes score 0 and are
excluded from Z (the causal generalization of the paper's unmasked rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hccs import HCCSParams, hccs_mode_inv, hccs_qat
from repro.models.layers import apply_mrope, apply_rope
from repro.parallel.sharding import constrain
from repro.quant.int8 import round_to_int

NEG_INF = -1e30

# eager-mode capture hook for offline calibration: inside
# `capture_attention_logits()` every dense-attention call appends its float
# logits (B, H, Tq, Tk). Run UNJITTED (the calibration pass is tiny).
_CAPTURE: list | None = None


class capture_attention_logits:
    def __enter__(self):
        global _CAPTURE
        _CAPTURE = []
        return _CAPTURE

    def __exit__(self, *a):
        global _CAPTURE
        _CAPTURE = None
        return False


def init_attention(rng, cfg):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    std = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * std,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), dt) * std,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), dt) * std,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * (h * hd) ** -0.5,
    }


def init_hccs_head_params(cfg, n_ref: int = 128) -> dict:
    """Per-head HCCS (B, S, D) + int8 logit scale for one layer: shapes (H,).

    Initialized at the constraint-feasible default; replaced by offline
    calibration (core/calibrate.py). Stacked to (L, H) by the model init.
    """
    from repro.core.constraints import default_params
    B, S, D = default_params(n_ref)
    h = max(cfg.num_heads, 1)
    return {
        "B": jnp.full((h,), B, jnp.int32),
        "S": jnp.full((h,), S, jnp.int32),
        "D": jnp.full((h,), D, jnp.int32),
        "scale": jnp.full((h,), 0.1, jnp.float32),
    }


def _ste(v_hard, v_soft):
    return v_soft + jax.lax.stop_gradient(v_hard - v_soft)


def decode_kernel_blockers(cfg) -> list:
    """Static config conditions that keep the fused decode kernel from
    dispatching, as human-readable strings (empty = eligible). The per-call
    conditions — decode step t==1, cache present, hccs params present, no hot
    buffer in flight — are checked at the dispatch site. Shared with the
    serve launcher so its no-effect warning cannot drift from the gate."""
    blockers = []
    if cfg.attention_prob != "hccs":
        blockers.append(f"attention_prob={cfg.attention_prob}")
    if cfg.hccs_mode not in ("wide", "i16_div", "i16_clb"):
        # i8 per-element truncation is not post-hoc linear (see kernels/decode.py)
        blockers.append(f"hccs_mode={cfg.hccs_mode} (i8 is XLA-only)")
    if cfg.window:
        blockers.append(f"window={cfg.window}")
    if cfg.hot_buffer:
        blockers.append(f"hot_buffer={cfg.hot_buffer}")
    if cfg.attention_multiplier:
        # the kernels scale q.k by 1/sqrt(head_dim)
        blockers.append(f"attention_multiplier={cfg.attention_multiplier}")
    return blockers


def xla_reads_kv(cfg) -> bool:
    """True when attention reads the cache through XLA, not a fused kernel:
    no kernel is asked for, or the config blocks it (decode_kernel_blockers).
    The one gate for the kernel dispatch (which also needs hccs params), the
    lane padding below and the paged pool's geometry (serve/paged.py
    init_paged_cache)."""
    return cfg.decode_kernel == "none" or bool(decode_kernel_blockers(cfg))


def kv_store_geometry(cfg, max_len: int) -> tuple[int, int]:
    """Storage shape (head_dim, seq) for cache K/V buffers.

    When the fused decode kernel will consume the arena every step, allocate
    it lane-padded up front — head_dim padded to the 128-lane tile and seq
    rounded to the kernel's default block — so hccs_decode's zero-copy
    pass-through branch runs instead of a per-step full-cache pad-and-copy.
    Writers use dynamic_update_slice (update may be smaller than the target),
    XLA readers slice back to [..., :head_dim]; padded lanes stay zero and
    padded rows sit beyond every slot's length mask.
    """
    if xla_reads_kv(cfg):
        return cfg.head_dim, max_len
    hd = max(-(-cfg.head_dim // 128) * 128, 128)
    return hd, -(-max_len // 128) * 128


def _project_out(out, p, b, t):
    """Shared attention epilogue: merge heads -> output projection -> residual
    sharding constraint. out: (B, H, T, hd) or (B, T, H*hd)."""
    with jax.named_scope("attn.proj"):
        if out.ndim == 4:
            out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
        out = out @ p["wo"]
        return constrain(out, "batch", "seq_act", "embed")


def _slot_scatter(cache_kv, new_kv, lengths):
    """Per-slot KV write: slot b's (Hkv, t, hd) update lands at its own cache
    frontier lengths[b] (continuous batching: slots progress independently).
    vmap-of-dynamic_update_slice lowers to a batched scatter."""
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u.astype(c.dtype), (0, i, 0)))(cache_kv, new_kv, lengths)


# transient per-step keys the paged engine attaches to the cache; they steer
# the step and are not part of the carried cache state. `slot_ids`,
# `q_pos_grid`, `grid_pos` and `kv_len_slot` only ride on packed token
# steps: `slot_ids` selects the token-centric branch, the other three steer
# the XLA path's per-slot attention grid (see _packed_attention).
# `fresh_blocks` only rides on kv_quant="int8" steps: block ids allocated
# since the last step, whose stale per-block scales must be reset to zero
# before this step's quantized writes (padded with the trash block 0).
# `stage_rows`/`draft_rows` only ride on speculative verify steps over a
# quantized pool: stage_rows makes each layer emit its RAW new KV rows
# (staged_k/staged_v) alongside the quantized write, and draft_rows marks
# the provisional draft lanes whose fold clamps the block scale
# (paged_quant_scatter) — after verification the engine restores a pre-step
# block snapshot and re-folds exactly the committed rows from the staged
# copies (the scale fold cannot be un-folded in place).
# `layer` only rides on steps over a token-major pool: the pool leaves are
# then the whole (L, ...) stack, carried through the layer scan, and each
# layer writes and reads its own slice by this index (model.forward).
_PAGED_TRANSIENT = ("block_table", "write_pos", "kv_len", "slot_ids",
                    "q_pos_grid", "grid_pos", "kv_len_slot", "fresh_blocks",
                    "stage_rows", "draft_rows", "layer")


def _paged_scatter(pool, new_kv, write_pos, layer=None):
    """Write t new KV vectors per slot into the global paged block pool.

    pool: one layer's head-major (N, Hkv, block_size, hd_c) pool or, with
    `layer`, the token-major stack (L, N, block_size, Hkv*hd), written in
    place at [layer, block, offset] one (Hkv*hd) row per token; new_kv:
    (B, Hkv, t, hd); write_pos: (B, t) int32 flat pool positions
    (block_id * block_size + offset), host-computed by the engine — tokens
    past a slot's valid count point at the reserved trash block 0, so the
    scatter keeps a static shape without polluting any live block."""
    pos = write_pos.reshape(-1)
    upd = new_kv.transpose(0, 2, 1, 3)                    # (B, t, Hkv, hd)
    if layer is not None:
        bs = pool.shape[2]
        rows = upd.reshape(pos.shape[0], -1).astype(pool.dtype)
        return pool.at[layer, pos // bs, pos % bs].set(rows)
    n, hkv, bs, hd_c = pool.shape
    upd = upd.reshape(-1, hkv, new_kv.shape[-1])
    return pool.at[pos // bs, :, pos % bs, :upd.shape[-1]].set(
        upd.astype(pool.dtype))


# amax floor shared with quant.int8.per_channel_scale: a block whose rows
# are all (near-)zero still gets a positive scale, so the requant ratio and
# the dequant multiply never divide by zero
KV_QUANT_EPS = 1e-6
# scale rule uses an explicit f32 reciprocal MULTIPLY, not amax / 127: XLA
# compiles constant divisions to reciprocal multiplies anyway (1 ULP apart
# from true division), so writing the multiply makes the arithmetic identical
# eager/jit/numpy-model — the fold's bit-exactness contract depends on it
KV_QUANT_INV_QMAX = jnp.float32(1.0 / 127.0)


def paged_quant_scatter(pool, scales, new_kv, write_pos, draft_rows=None,
                        layer=None):
    """Quantizing write into an int8 paged pool with per-block scales.

    pool: (N, Hkv, block_size, hd_c) int8; scales: (N, Hkv) float32 — one
    symmetric scale per (block, kv-head); new_kv: (B, Hkv, t, hd) float;
    write_pos: (B, t) flat positions exactly as in _paged_scatter.
    draft_rows: optional (B, t) bool — rows that fold with a CLAMPED scale
    (speculative verify steps; see below). With `layer`, pool is the
    token-major stack (L, N, block_size, Hkv*hd) and scales (L, N, Hkv):
    the fold runs on a (block_size, Hkv, hd) view of [layer, block], with
    the same arithmetic, so the bytes are those of the head-major fold.

    Rows are folded IN POSITION ORDER, one at a time (lax.fori_loop):

        s_new   = max(s_old, max(amax(row), eps) / 127)    # grow-only amax
        payload = requant(payload, s_old -> s_new)         # device-side
        payload[row] = quantize(row, s_new)

    The per-ROW fold (rather than quantizing a step's rows against the
    step-final scale in one shot) is what keeps a block's bytes a pure
    function of the row values and their order: lockstep and packed steps
    partition the same rows into different step boundaries, but the fold
    they apply is the identical composition either way — so packed/lockstep,
    prefix-/decode-sharing and session re-feed parity all stay bit-exact
    under quantization. The requant multiply is the identity when the scale
    did not grow (ratio == 1.0 exactly), and zeroes stale bytes on a freshly
    allocated block (scale reset to 0 by the engine => ratio == 0.0).
    Quantization rounds half-away-from-zero (quant/int8.py's documented
    hardware mode). Returns (pool, scales).

    Rows flagged in `draft_rows` are PROVISIONAL (speculative draft lanes):
    they fold with the block's existing scale CLAMPED — quantized (clipped)
    at s_old instead of growing it — so they never requantize committed
    rows sharing their block, and every committed lane's read of history
    stays bit-identical to a never-drafted step. A draft row landing in a
    scale-0 block (freshly allocated for the drafts themselves, holding no
    committed rows) still sets the scale from its own amax so later verify
    lanes read something meaningful. Draft folds are scratch either way:
    the engine restores the pre-step snapshot and re-folds the committed
    rows (without the flag) after every verify step."""
    token_major = layer is not None
    hkv = scales.shape[-1]
    bs = pool.shape[2] if token_major else pool.shape[-2]
    pos = write_pos.reshape(-1)
    upd = new_kv.transpose(0, 2, 1, 3).reshape(-1, hkv, new_kv.shape[-1])
    upd = upd.astype(jnp.float32)
    hd = upd.shape[-1]
    draft = (None if draft_rows is None
             else draft_rows.reshape(-1).astype(bool))

    def write_row(i, carry):
        pool, scales = carry
        blk, row = pos[i] // bs, pos[i] % bs
        at = (layer, blk) if token_major else (blk,)
        x = upd[i]                                         # (Hkv, hd)
        s_old = scales[at]                                 # (Hkv,)
        amax = jnp.abs(x).max(-1)
        s_new = jnp.maximum(s_old, jnp.maximum(amax, KV_QUANT_EPS)
                            * KV_QUANT_INV_QMAX)
        if draft is not None:
            s_new = jnp.where(draft[i] & (s_old > 0), s_old, s_new)
        ratio = s_old / s_new                              # s_new >= eps/127
        q = jnp.clip(round_to_int(x / s_new[:, None]), -128, 127)
        if token_major:
            payload = (pool[at].reshape(bs, hkv, hd).astype(jnp.float32)
                       * ratio[None, :, None])
            payload = jnp.clip(round_to_int(payload), -128, 127)
            payload = payload.at[row].set(q).reshape(bs, hkv * hd)
        else:
            payload = pool[at].astype(jnp.float32) * ratio[:, None, None]
            payload = jnp.clip(round_to_int(payload), -128, 127)
            payload = payload.at[:, row, :hd].set(q)
        return (pool.at[at].set(payload.astype(pool.dtype)),
                scales.at[at].set(s_new))

    return jax.lax.fori_loop(0, pos.shape[0], write_row, (pool, scales))


def _paged_gather(pool, block_table, hd, scales=None, layer=None):
    """Contiguous (B, Hkv, nblk*block_size, hd) view of each slot's blocks —
    the XLA attention path over a paged cache (the Pallas kernel instead
    gathers block-by-block in its BlockSpec index_map, see kernels/decode.py).
    pool: one layer's head-major (N, Hkv, bs, hd_c) pool or, with `layer`,
    the token-major stack (L, N, bs, Hkv*hd), read at pool[layer, table].
    Sentinel (-1) entries gather the trash block; they only occur at or past
    the slot's frontier, so the kv_len mask hides them. With `scales`
    (N, Hkv, or (L, N, Hkv) with `layer`; kv_quant="int8") the int8 payload
    is dequantized per block elementwise BEFORE attention — the same values
    the fused kernel's tile dequant produces, keeping XLA/kernel bit-parity."""
    b, nblk = block_table.shape
    tbl = jnp.maximum(block_table, 0)
    if layer is not None:
        bs = pool.shape[2]
        g = pool[layer, tbl].reshape(b, nblk, bs, -1, hd)  # (.., Hkv, hd)
        if scales is not None:
            g = (g.astype(jnp.float32)
                 * scales[layer, tbl][:, :, None, :, None])
        return g.reshape(b, nblk * bs, -1, hd).transpose(0, 2, 1, 3)
    n, hkv, bs, hd_c = pool.shape
    g = pool[tbl]                                  # (B, nblk, Hkv, bs, hd_c)
    if scales is not None:
        g = g.astype(jnp.float32) * scales[tbl][..., None, None]
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nblk * bs, hd_c)[..., :hd]


def _scale_logits(logits, cfg, hd):
    """q.k times cfg.attention_multiplier, or over sqrt(head_dim) where
    that is 0."""
    if cfg.attention_multiplier:
        return logits * jnp.float32(cfg.attention_multiplier)
    return logits / jnp.sqrt(jnp.float32(hd))


def _block_valid(cfg, q_pos, k_pos, k_len=None):
    """Validity mask (B, 1, Tq, Tk_blk) from positions, computed lazily.

    q_pos: (B, Tq); k_pos: (Tk_blk,) global key positions; k_len: (B,) or None.
    """
    qp = q_pos[:, None, :, None]
    kp = k_pos[None, None, None, :]
    valid = jnp.ones(qp.shape[:3] + (k_pos.shape[0],), bool)
    if cfg.causal:
        valid &= kp <= qp
    if cfg.window:
        valid &= kp > qp - cfg.window
    if k_len is not None:
        valid &= kp < k_len[:, None, None, None]
    return valid


def _dense_attention(q, k, v, valid, cfg, hccs):
    """q: (B,H,Tq,hd), k/v: (B,Hkv,Tk,hd), valid: (B,1,Tq,Tk)."""
    b, h, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, tq, hd)
    logits = jnp.einsum("bkgqd,bktd->bkgqt", qg, k).astype(jnp.float32)
    logits = _scale_logits(logits, cfg, hd).reshape(b, h, tq, tk)
    if _CAPTURE is not None:
        _CAPTURE.append(logits)
    if cfg.attention_prob == "hccs" and hccs is not None:
        params = HCCSParams(B=hccs["B"][:, None, None], S=hccs["S"][:, None, None],
                            D=hccs["D"][:, None, None])
        p = hccs_qat(logits, hccs["scale"][:, None, None], params,
                     mode=cfg.hccs_mode, hard=True, mask=valid)
    else:
        p = jax.nn.softmax(jnp.where(valid, logits, NEG_INF), axis=-1)
    pg = p.reshape(b, hkv, g, tq, tk).astype(v.dtype)
    out = jnp.einsum("bkgqt,bktd->bkgqd", pg, v)
    return out.reshape(b, h, tq, hd)


def _blockwise_attention(q, k, v, q_pos, k_len, cfg, hccs):
    """Two-pass KV-block scan; per-block masks computed from positions.

    HCCS: pass 1 = row max of quantized logits (the paper's Stage 1 over a KV
    sweep); pass 2 = distance/clamp/affine (Stages 2-3), Z (Stage 4) and s@V,
    with a single final normalization (Stage 5) — no per-block rescale, since
    HCCS is linear in the active window. Softmax: classic online rescale.
    """
    b, h, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    bk = min(cfg.block_k, tk)
    nblk = -(-tk // bk)
    tk_pad = nblk * bk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tk_pad - tk), (0, 0)))
    kb = jnp.moveaxis(kp.reshape(b, hkv, nblk, bk, hd), 2, 0)
    vb = jnp.moveaxis(vp.reshape(b, hkv, nblk, bk, hd), 2, 0)
    starts = jnp.arange(nblk) * bk
    if k_len is None:
        k_len = jnp.full((b,), tk, jnp.int32)
    qg = q.reshape(b, hkv, g, tq, hd)
    sm = (jnp.float32(cfg.attention_multiplier) if cfg.attention_multiplier
          else 1.0 / jnp.sqrt(jnp.float32(hd)))

    def logits_of(kblk):
        lg = jnp.einsum("bkgqd,bktd->bkgqt", qg, kblk).astype(jnp.float32) * sm
        return lg.reshape(b, h, tq, bk)

    if cfg.attention_prob == "hccs" and hccs is not None:
        scale = hccs["scale"][:, None, None]
        B = hccs["B"][:, None, None].astype(jnp.float32)
        S = hccs["S"][:, None, None].astype(jnp.float32)
        D = hccs["D"][:, None, None].astype(jnp.float32)

        def qint_of(kblk, start):
            k_pos = start + jnp.arange(bk)
            vmask = _block_valid(cfg, q_pos, k_pos, k_len)
            lg = logits_of(kblk) / scale
            qi = _ste(jnp.clip(jnp.round(lg), -128.0, 127.0), lg)
            qi = jnp.where(vmask, qi, -1e9)
            return qi, vmask

        def max_step(m, xs):
            kblk, start = xs
            qi, _ = qint_of(kblk, start)
            return jnp.maximum(m, qi.max(-1)), None

        m0 = jnp.full((b, h, tq), -1e9, jnp.float32)
        m, _ = jax.lax.scan(max_step, m0, (kb, starts))
        m = jax.lax.stop_gradient(m)[..., None]

        def acc_step(carry, xs):
            acc, zsum = carry
            kblk, vblk, start = xs
            qi, vmask = qint_of(kblk, start)
            delta = jnp.minimum(m - qi, D)
            s = jnp.where(vmask, B - S * delta, 0.0)
            zsum = zsum + s.sum(-1)
            sg = s.reshape(b, hkv, g, tq, bk).astype(vblk.dtype)
            acc = acc + jnp.einsum("bkgqt,bktd->bkgqd", sg, vblk).reshape(
                b, h, tq, hd)
            return (acc, zsum), None

        acc0 = jnp.zeros((b, h, tq, hd), v.dtype)
        z0 = jnp.zeros((b, h, tq), jnp.float32)
        (acc, zsum), _ = jax.lax.scan(acc_step, (acc0, z0), (kb, vb, starts))
        z = jnp.maximum(zsum, 1.0)[..., None]
        # mode-aware final scale: HCCS linearity lets the integer rho
        # truncation be applied to the accumulated numerator post-hoc
        # (sum_i s_i*rho*v_i = rho * sum_i s_i*v_i), keeping blockwise
        # bit-consistent with the dense path for the i16 modes.
        inv = hccs_mode_inv(z, cfg.hccs_mode)
        return (acc.astype(jnp.float32) * inv).astype(q.dtype)

    def step(carry, xs):
        acc, zsum, m = carry
        kblk, vblk, start = xs
        k_pos = start + jnp.arange(bk)
        vmask = _block_valid(cfg, q_pos, k_pos, k_len)
        lg = jnp.where(vmask, logits_of(kblk), NEG_INF)
        m_new = jnp.maximum(m, lg.max(-1))
        corr = jnp.exp(m - m_new)
        e = jnp.exp(lg - m_new[..., None])
        zsum = zsum * corr + e.sum(-1)
        eg = e.reshape(b, hkv, g, tq, bk).astype(vblk.dtype)
        pv = jnp.einsum("bkgqt,bktd->bkgqd", eg, vblk).reshape(b, h, tq, hd)
        acc = acc * corr[..., None].astype(acc.dtype) + pv
        return (acc, zsum, m_new), None

    acc0 = jnp.zeros((b, h, tq, hd), v.dtype)
    z0 = jnp.zeros((b, h, tq), jnp.float32)
    m0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    (acc, zsum, _), _ = jax.lax.scan(step, (acc0, z0, m0), (kb, vb, starts))
    z = jnp.maximum(zsum, 1e-20)[..., None]
    return (acc.astype(jnp.float32) / z).astype(q.dtype)


def _merge_segments(parts, cfg, hccs):
    """Combine per-segment attention partials computed against a SHARED max.

    parts: list of (s_sum (B,H,Tq), acc (B,H,Tq,hd)) — for HCCS these are
    sums of clipped-linear scores (linear => additive); for softmax they are
    exp-sums against the shared max. out = sum(acc) / sum(Z).
    """
    zsum = sum(p[0] for p in parts)
    acc = sum(p[1] for p in parts)
    z = jnp.maximum(zsum, 1.0 if (cfg.attention_prob == "hccs" and hccs)
                    else 1e-20)[..., None]
    return (acc.astype(jnp.float32) / z)


def _segment_partials(q, k, v, valid, m, cfg, hccs):
    """One segment's (Z_partial, acc_partial) against shared max m (B,H,Tq,1).

    HCCS: s = B - S*min(m - qint, D) on valid lanes (clipped-linear — partial
    sums are exact). Softmax: e = exp(logits - m).
    """
    b, h, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, tq, hd)
    logits = jnp.einsum("bkgqd,bktd->bkgqt", qg, k).astype(jnp.float32)
    logits = _scale_logits(logits, cfg, hd).reshape(b, h, tq, tk)
    if cfg.attention_prob == "hccs" and hccs is not None:
        scale = hccs["scale"][:, None, None]
        B = hccs["B"][:, None, None].astype(jnp.float32)
        S = hccs["S"][:, None, None].astype(jnp.float32)
        D = hccs["D"][:, None, None].astype(jnp.float32)
        qi = _ste(jnp.clip(jnp.round(logits / scale), -128., 127.),
                  logits / scale)
        qi = jnp.where(valid, qi, -1e9)
        s = jnp.where(valid, B - S * jnp.minimum(m - qi, D), 0.0)
    else:
        s = jnp.where(valid, jnp.exp(logits - m), 0.0)
    sg = s.reshape(b, hkv, g, tq, tk).astype(v.dtype)
    acc = jnp.einsum("bkgqt,bktd->bkgqd", sg, v).reshape(b, h, tq, hd)
    return s.sum(-1), acc


def _segment_max(q, k, valid, cfg, hccs):
    """Per-row max of (quantized) logits over one segment; (B,H,Tq)."""
    b, h, tq, hd = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, tq, hd)
    logits = jnp.einsum("bkgqd,bktd->bkgqt", qg, k).astype(jnp.float32)
    logits = _scale_logits(logits, cfg, hd).reshape(b, h, tq, tk)
    if cfg.attention_prob == "hccs" and hccs is not None:
        logits = jnp.round(jnp.clip(logits / hccs["scale"][:, None, None],
                                    -128., 127.))
    return jnp.where(valid, logits, -1e9).max(-1)


def _packed_attention(q, k_pool, v_pool, cache, cfg, hccs, hd,
                      k_scales=None, v_scales=None):
    """Token-centric attention for the packed paged step.

    q: (1, H, T, hd) — the T lanes are ragged tokens from different slots;
    cache carries `slot_ids` (T,) owning slot (-1 = pad lane), `kv_len` (T,)
    per-token causal frontiers (position + 1), `block_table` (B, nblk), and
    the grid steering below. Each token attends only within ITS slot's
    blocks, so cross-slot leakage is structurally impossible. Returns
    (1, T, H*hd).

    With cfg.decode_kernel active, the whole ragged batch runs the fused
    `hccs_packed_prefill` kernel — per-token single-query sweeps whose
    BlockSpec index_map walks `block_table[slot_ids[token]]` (a gather-free
    DMA steer).

    The XLA path instead rides the packed tokens through a compact PER-SLOT
    GRID for the attention core only: `grid_pos` (T,) scatters each token to
    cell (slot, position - frontier) of a (B, Wb) grid (Wb = this step's
    bucketed max per-slot chunk, carried by `q_pos_grid`'s static shape; pad
    lanes land in a spill row), the grid runs the SAME dense/blockwise
    attention as the lockstep layout at width Wb — one per-slot KV gather,
    NOT one per token, which is what makes the packed step cheaper rather
    than gather-bound — and the outputs gather back to packed lanes. Every
    other layer (projections, MLP, norms, logits) stays token-packed: that
    is where the padding FLOPs go, while the attention core's work is
    identical to lockstep's for the same tokens (bit-parity for free).
    """
    b, h, t, _ = q.shape
    sid = cache["slot_ids"]
    qt = q[0].transpose(1, 0, 2)                          # (T, H, hd)
    if not xla_reads_kv(cfg) and hccs is not None:
        from repro.kernels.ops import hccs_packed_prefill
        theta = jnp.stack([hccs["B"], hccs["S"], hccs["D"]], axis=-1)
        with jax.named_scope("attn.core"):
            o = hccs_packed_prefill(
                qt.astype(jnp.float32), k_pool, v_pool, cache["block_table"],
                sid, cache["kv_len"], hccs["scale"], theta,
                mode=cfg.hccs_mode,
                static_max=(cfg.decode_kernel == "static_max"),
                k_scales=k_scales, v_scales=v_scales)
        return o.astype(q.dtype).reshape(1, t, h * hd)
    q_pos_grid = cache["q_pos_grid"]                      # (B, Wb)
    gp = cache["grid_pos"]                                # (T,) spill = B*Wb
    k_len = cache["kv_len_slot"]                          # (B,)
    bs_, wb = q_pos_grid.shape
    with jax.named_scope("attn.gather"):
        qg = jnp.zeros((bs_ * wb + 1, h, qt.shape[-1]),
                       qt.dtype).at[gp].set(qt)
        qg = qg[:bs_ * wb].reshape(bs_, wb, h, -1).transpose(0, 2, 1, 3)
        li = cache.get("layer")
        kg = _paged_gather(k_pool, cache["block_table"], hd, k_scales,
                           li)                            # (B, Hkv, L, hd)
        vg = _paged_gather(v_pool, cache["block_table"], hd, v_scales, li)
    tk = kg.shape[2]
    use_blockwise = (cfg.attention_impl == "blockwise" or
                     (cfg.attention_impl == "auto" and wb > 1 and
                      tk >= cfg.blockwise_threshold))
    with jax.named_scope("attn.core"):
        if use_blockwise:
            out = _blockwise_attention(qg, kg, vg, q_pos_grid, k_len, cfg,
                                       hccs)
        else:
            valid = _block_valid(cfg, q_pos_grid, jnp.arange(tk), k_len)
            out = _dense_attention(qg, kg, vg, valid, cfg, hccs)
    with jax.named_scope("attn.gather"):
        out = out.transpose(0, 2, 1, 3).reshape(bs_ * wb, h * hd)
        return out[jnp.where(sid >= 0, gp, 0)][None]      # (1, T, H*hd)


def apply_attention(p, x, cfg, hccs=None, positions=None, cache=None,
                    mrope_positions=None):
    """x: (B, T, D). Returns (out, new_cache).

    cache: None (self-attention over x) or dict(k, v, length) for decode —
    k/v: (B, Hkv, Tmax, hd); new k/v are written at offset `length`.
    With cfg.hot_buffer > 0 the cache also carries (hot_k, hot_v, hot_len):
    decode appends there (replicated, static-shard-safe) and attention merges
    the main + hot segments against a shared max.
    PAGED layout (serve/paged.py): k/v are instead global block pools —
    one layer's head-major (N, Hkv, block_size, hd_c) pool for the fused
    kernels, or the token-major (L, N, block_size, Hkv*hd) stack with this
    layer's `layer` index for the XLA path — and the cache carries
    `block_table` (B, nblk), `write_pos` (B, T) flat scatter targets, and
    `kv_len` (B,) per-slot valid counts — the dispatch keys off
    `block_table`'s presence, the paged analogue of `length` going
    scalar-vs-vector for the slot arena.
    PACKED paged steps additionally carry `slot_ids` (T,): x is then a
    (1, T) ragged token batch (rows are tokens, not slots), positions are
    per-token, and `kv_len` is per-TOKEN — see _packed_attention.
    Prefix sharing changes nothing here: a slot admitted past a shared
    prefix arrives with cache["length"] already at the partial-prefill start
    offset (so the default `positions = length + arange(t)` resumes RoPE at
    the right absolute position), block tables may alias shared pool blocks
    read-only, and the engine guarantees `write_pos` never targets a block
    with refcount > 1 (copy-on-write runs host-side before the step).
    """
    b, t, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # constrain the flat projections (h*hd is always divisible by the TP
    # degree even when the head count is not, e.g. hymba's 25 heads);
    # 'attn_seq' is None under the TP training profile and carries the
    # sequence shard under the serve_sp inference profile
    with jax.named_scope("attn.proj"):
        qf = constrain(x @ p["wq"], "batch", "attn_seq", "model")
        kf = constrain(x @ p["wk"], "batch", "attn_seq", "kv_model")
        vf = constrain(x @ p["wv"], "batch", "attn_seq", "kv_model")
        q = qf.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        k = kf.reshape(b, t, hkv, hd).transpose(0, 2, 1, 3)
        v = vf.reshape(b, t, hkv, hd).transpose(0, 2, 1, 3)

    if positions is None:
        base = cache["length"] if cache is not None else jnp.zeros((), jnp.int32)
        # base is a scalar (lockstep decode) or a (B,) per-slot length vector
        positions = jnp.atleast_1d(base)[:, None] + jnp.arange(t)[None, :]
        positions = jnp.broadcast_to(positions, (b, t))
    with jax.named_scope("attn.proj"):
        if cfg.rope == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        elif cfg.rope == "mrope":
            p3 = mrope_positions
            if p3 is None:
                p3 = jnp.broadcast_to(positions[None], (3,) + positions.shape)
            q = apply_mrope(q, p3, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, p3, cfg.rope_theta, cfg.mrope_sections)

    # ---- hot-buffer decode: append to the small replicated buffer and
    # merge main + hot segments against a shared max (see §Perf D) ----
    if cache is not None and "hot_k" in cache and t <= 8:
        hot_len = cache["hot_len"]
        with jax.named_scope("attn.kv_write"):
            hk = jax.lax.dynamic_update_slice(
                cache["hot_k"], k.astype(cache["hot_k"].dtype),
                (0, 0, hot_len, 0))
            hv = jax.lax.dynamic_update_slice(
                cache["hot_v"], v.astype(cache["hot_v"].dtype),
                (0, 0, hot_len, 0))
        new_cache = dict(cache, hot_k=hk, hot_v=hv, hot_len=hot_len + t,
                         length=cache["length"] + t)
        main_len_s = cache["length"] - hot_len          # prompt tokens
        mk, mv = cache["k"], cache["v"]
        with jax.named_scope("attn.core"):
            valid_main = _block_valid(cfg, positions, jnp.arange(mk.shape[2]),
                                      jnp.full((b,), main_len_s, jnp.int32))
            hot_pos = main_len_s + jnp.arange(hk.shape[2])
            valid_hot = _block_valid(
                cfg, positions, hot_pos,
                jnp.full((b,), cache["length"] + t, jnp.int32))
            m = jnp.maximum(_segment_max(q, mk, valid_main, cfg, hccs),
                            _segment_max(q, hk, valid_hot, cfg, hccs))
            m = jax.lax.stop_gradient(m)[..., None]
            parts = [_segment_partials(q, mk, mv, valid_main, m, cfg, hccs),
                     _segment_partials(q, hk, hv, valid_hot, m, cfg, hccs)]
            out = _merge_segments(parts, cfg, hccs).astype(q.dtype)
        return _project_out(out, p, b, t), new_cache

    new_cache = None
    k_len = None
    paged = cache is not None and "block_table" in cache
    per_slot = (cache is not None and not paged
                and jnp.ndim(cache["length"]) > 0)
    if paged:
        # paged arena: K/V live in a global block pool addressed through
        # per-slot block tables; the write targets (incl. trash routing for
        # tokens past each slot's valid count) were resolved on the host.
        # A token-major pool arrives as the whole (L, ...) stack with this
        # layer's index; a head-major pool as this layer's own slice.
        li = cache.get("layer")
        with jax.named_scope("attn.kv_write"):
            quant = "k_scale" in cache
            if quant:
                # kv_quant="int8": reset scales of blocks allocated since
                # the last step (their pool bytes and scales are stale from
                # a prior owner), then run the quantizing per-row fold. COW
                # copies are NOT in fresh_blocks — they arrive with
                # payload+scales copied.
                ks, vs = cache["k_scale"], cache["v_scale"]
                fresh = cache.get("fresh_blocks")
                if fresh is not None:
                    at = (fresh,) if li is None else (li, fresh)
                    ks = ks.at[at].set(0.0)
                    vs = vs.at[at].set(0.0)
                dr = cache.get("draft_rows")
                kc, ks = paged_quant_scatter(cache["k"], ks, k,
                                             cache["write_pos"], dr, li)
                vc, vs = paged_quant_scatter(cache["v"], vs, v,
                                             cache["write_pos"], dr, li)
            else:
                ks = vs = None
                kc = _paged_scatter(cache["k"], k, cache["write_pos"], li)
                vc = _paged_scatter(cache["v"], v, cache["write_pos"], li)
        new_cache = {kk: vv for kk, vv in cache.items()
                     if kk not in _PAGED_TRANSIENT}
        new_cache.update(k=kc, v=vc, length=cache["length"] + t)
        if quant:
            new_cache.update(k_scale=ks, v_scale=vs)
        if "stage_rows" in cache:
            # speculative verify step: stage this layer's raw (pre-quant) KV
            # rows for the engine's rollback replay. model.forward's layer
            # scan stacks these into (L, B, Hkv, t, hd); the engine pops
            # them out of the returned cache after the step.
            new_cache.update(staged_k=k.astype(jnp.float32),
                             staged_v=v.astype(jnp.float32))
        # per-slot valid-KV counts for this step (length + per-slot t_valid;
        # chunked prefill makes t_valid ragged, so `length + t` is wrong here)
        k_len = cache["kv_len"]
        if "slot_ids" in cache:
            # PACKED token step (b == 1): lane i of the t axis is an
            # independent single-query token owned by slot_ids[i], at global
            # position positions[0, i], with causal frontier kv_len[i] —
            # rows are tokens, so a ragged mixed prefill/decode batch runs
            # with zero padded query lanes (see serve/paged.py packed mode)
            out = _packed_attention(q, kc, vc, cache, cfg, hccs, hd,
                                    k_scales=ks, v_scales=vs)
            return _project_out(out, p, b, t), new_cache
        if t == 1 and not xla_reads_kv(cfg) and hccs is not None:
            # block-sparse fused decode: the kernel walks the block table
            from repro.kernels.ops import hccs_paged_decode
            theta = jnp.stack([hccs["B"], hccs["S"], hccs["D"]], axis=-1)
            with jax.named_scope("attn.core"):
                o = hccs_paged_decode(
                    q[:, :, 0, :].astype(jnp.float32), kc, vc,
                    cache["block_table"], k_len, hccs["scale"], theta,
                    mode=cfg.hccs_mode,
                    static_max=(cfg.decode_kernel == "static_max"),
                    k_scales=ks, v_scales=vs)
            out = o.astype(q.dtype).reshape(b, 1, h * hd)
            return _project_out(out, p, b, 1), new_cache
        with jax.named_scope("attn.gather"):
            k = _paged_gather(kc, cache["block_table"], hd, ks, li)
            v = _paged_gather(vc, cache["block_table"], hd, vs, li)
    elif cache is not None:
        with jax.named_scope("attn.kv_write"):
            if per_slot:
                # continuous batching: every slot writes at its own frontier
                kc = _slot_scatter(cache["k"], k, cache["length"])
                vc = _slot_scatter(cache["v"], v, cache["length"])
            elif cache["k"].shape[2:] == k.shape[2:]:
                # prompt fills the whole cache (prefill at max_len): a
                # plain overwrite avoids the dynamic-update-slice on the
                # sharded seq dim, which XLA can only partition via a full
                # gather (a lane-padded arena never matches and takes the
                # DUS path below)
                kc = k.astype(cache["k"].dtype)
                vc = v.astype(cache["v"].dtype)
            else:
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype),
                    (0, 0, cache["length"], 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype),
                    (0, 0, cache["length"], 0))
        # dict(cache, ...) preserves extra entries (hot buffers during the
        # prefill pass of a hot-buffer cache)
        new_cache = dict(cache, k=kc, v=vc, length=cache["length"] + t)
        k, v = kc, vc
        k_len = jnp.broadcast_to(cache["length"] + t, (b,)).astype(jnp.int32)

    # ---- fused decode kernel: single new token against the cache ring
    # buffer, per-slot length masking (kernels/decode.py) ----
    if (cache is not None and t == 1 and not xla_reads_kv(cfg)
            and hccs is not None and "hot_k" not in cache):
        from repro.kernels.ops import hccs_decode
        theta = jnp.stack([hccs["B"], hccs["S"], hccs["D"]], axis=-1)
        with jax.named_scope("attn.core"):
            o = hccs_decode(q[:, :, 0, :].astype(jnp.float32),
                            k, v, k_len, hccs["scale"], theta,
                            mode=cfg.hccs_mode,
                            static_max=(cfg.decode_kernel == "static_max"))
        out = o.astype(q.dtype).reshape(b, 1, h * hd)
        return _project_out(out, p, b, 1), new_cache

    if cache is not None and k.shape[-1] != hd:
        # lane-padded arena (kv_store_geometry): the kernel consumed the
        # padded buffer zero-copy above; XLA paths read the true lanes
        k, v = k[..., :hd], v[..., :hd]

    with jax.named_scope("attn.core"):
        tk = k.shape[2]
        use_blockwise = (cfg.attention_impl == "blockwise" or
                         (cfg.attention_impl == "auto" and t > 1 and
                          tk >= cfg.blockwise_threshold))
        if use_blockwise:
            # single explicit gather point: both HCCS passes (max + accumulate)
            # read the same seq-replicated K/V instead of re-gathering per pass
            k = constrain(k, "batch", "kv_model", None, None)
            v = constrain(v, "batch", "kv_model", None, None)
            out = _blockwise_attention(q, k, v, positions, k_len, cfg, hccs)
        else:
            valid = _block_valid(cfg, positions, jnp.arange(tk), k_len)
            out = _dense_attention(q, k, v, valid, cfg, hccs)

    return _project_out(out, p, b, t), new_cache
