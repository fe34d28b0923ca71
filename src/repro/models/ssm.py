"""The Mamba-2 mixer (arXiv:2405.21060): in_proj -> depthwise causal conv1d
and SiLU over xBC -> SSD -> gated RMSNorm -> out_proj.

Per head h with state size N, head dim P:
    h_t = exp(a_t) * h_{t-1} + dt_t * B_t^T x_t        (a_t = -exp(A_log)*dt_t)
    y_t = C_t h_t + D * x_t
Chunked algorithm: within-chunk attention-like masked matmul (the "duality"),
across-chunk scan over per-chunk states — all einsums, MXU-friendly. One
token per row runs the recurrence itself.

A sequence carries two states between calls: the SSM state (nh, P, N) in
float32 (the state dimension minor: at N = 128 the TPU keeps it in its
native tiling) and the conv state, the last `ssm_conv - 1` xBC rows before the
conv. The entry points:
    apply_ssd         (B, T) rows, every position valid; at T = 1 the
                      recurrence
    apply_ssd_packed  the paged engine's packed ragged step: each slot's
                      tokens are scattered onto its row of the (B, Wb) slot
                      grid and run from the slot's carried states
A position past a row's valid count gets dt = 0: its decay is 1 and its
input 0, so the state passes through it unchanged, and the conv state is
read after the row's last valid token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain


def init_ssm(rng, cfg):
    """Every leaf in the compute dtype, as a published checkpoint holds
    them; A_log, dt_bias and D are read in float32."""
    d = cfg.d_model
    di = cfg.d_inner
    nh = cfg.ssm_heads
    ks = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.dtype)
    # the published in_proj, by its three outputs: z gate (di), xBC
    # (conv_dim = di + 2*g*n) and dt (nh). Held whole, its width is no
    # multiple of 128, and the TPU stores the stack transposed and copies
    # it back every step
    kz, kx, kd = jax.random.split(ks[0], 3)
    k = cfg.ssm_conv
    return {
        "in_z": jax.random.normal(kz, (d, di), dt) * d ** -0.5,
        "in_xbc": jax.random.normal(kx, (d, cfg.conv_dim), dt) * d ** -0.5,
        "in_dt": jax.random.normal(kd, (d, nh), dt) * d ** -0.5,
        "conv_w": jax.random.normal(ks[2], (k, cfg.conv_dim), dt) * k ** -0.5,
        "conv_b": jnp.zeros((cfg.conv_dim,), dt),
        "out_proj": jax.random.normal(ks[1], (di, d), dt) * di ** -0.5,
        "A_log": jnp.zeros((nh,), dt),
        "dt_bias": jnp.zeros((nh,), dt),
        "D": jnp.ones((nh,), dt),
        "norm": jnp.ones((di,), dt),
    }


def init_state(cfg, batch):
    """Zero (ssm, conv) states for `batch` sequences of one layer."""
    return (jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32),
            jnp.zeros((batch, cfg.ssm_conv - 1, cfg.conv_dim),
                      jnp.dtype(cfg.dtype)))


def _proj(p, x, cfg):
    with jax.named_scope("ssm.proj"):
        return tuple(constrain(x @ p[name], "batch", "attn_seq", "model")
                     for name in ("in_z", "in_xbc", "in_dt"))


def _gated_norm(p, y, z, cfg):
    yf = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    ms = jnp.mean(jnp.square(yf), -1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + cfg.norm_eps)
            * p["norm"].astype(jnp.float32)).astype(y.dtype)


def _out(p, y, z, cfg):
    with jax.named_scope("ssm.proj"):
        y = _gated_norm(p, y, z, cfg)
        return y @ p["out_proj"]


def _conv(p, xbc, conv_state, n_valid, cfg):
    """Depthwise causal conv of width K over xBC (B, W, C), continuing from
    conv_state (B, K-1, C), then SiLU. Returns the activations and the new
    conv state: the K-1 rows before row b's position n_valid[b] (all W
    valid when n_valid is None)."""
    b, w, c = xbc.shape
    k = cfg.ssm_conv
    with jax.named_scope("ssm.conv"):
        if conv_state is None:
            conv_state = jnp.zeros((b, k - 1, c), xbc.dtype)
        full = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        wgt = p["conv_w"].astype(jnp.float32)
        acc = p["conv_b"].astype(jnp.float32)
        for i in range(k):
            acc = acc + full[:, i:i + w].astype(jnp.float32) * wgt[i]
        out = jax.nn.silu(acc).astype(xbc.dtype)
        if n_valid is None:
            new = full[:, w:]
        else:
            idx = n_valid[:, None] + jnp.arange(k - 1)[None, :]
            new = jnp.take_along_axis(full, idx[..., None], axis=1)
    return out, new


def _ssd_chunked(xh, Bh, Ch, dt, a, h0, cfg):
    """The chunked SSD over (b, t) rows from initial states h0 (b, nh, hp, n);
    returns y (b, t, nh, hp) without the D term, and the final states."""
    b, t, nh, hp = xh.shape
    g, n = Bh.shape[2], Bh.shape[3]
    L = min(cfg.ssm_chunk, t)
    rep = nh // g
    nc = -(-t // L)
    t_pad = nc * L
    xh = jnp.pad(xh, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    Bh = jnp.pad(Bh, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    Ch = jnp.pad(Ch, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    dtp = jnp.pad(dt, ((0, 0), (0, t_pad - t), (0, 0)))
    ap = jnp.pad(a, ((0, 0), (0, t_pad - t), (0, 0)))

    # chunk views: (b, nc, L, ...) — chunks shard over the TP axis (they are
    # independent except for the small inter-chunk state scan)
    xc = constrain(xh.reshape(b, nc, L, nh, hp), "batch", "ssd_chunk",
                   None, None, None)
    Bc = constrain(Bh.reshape(b, nc, L, g, n), "batch", "ssd_chunk",
                   None, None, None)
    Cc = constrain(Ch.reshape(b, nc, L, g, n), "batch", "ssd_chunk",
                   None, None, None)
    dtc = constrain(dtp.reshape(b, nc, L, nh), "batch", "ssd_chunk", None, None)
    ac = constrain(ap.reshape(b, nc, L, nh), "batch", "ssd_chunk", None, None)

    cum = jnp.cumsum(ac, axis=2)                                       # (b,nc,L,nh)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]                # (b,nc,Lq,Lk,nh)
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    # group-broadcast B/C inside the einsums (materializing repeated
    # (b,nc,L,nh,n) tensors costs GBs at hymba/mamba2 scale)
    xg = xc.reshape(b, nc, L, g, rep, hp)
    dtg = dtc.reshape(b, nc, L, g, rep)
    # intra-chunk ("attention") term, per group
    cb = jnp.einsum("bclgn,bcsgn->bclsg", Cc, Bc)                      # (b,nc,L,L,g)
    wg = cb[..., None] * decay.reshape(b, nc, L, L, g, rep)[:, :, :, :]
    wg = wg * dtg[:, :, None]                                          # (b,nc,Lq,Lk,g,rep)
    y_intra = jnp.einsum("bclsgr,bcsgrp->bclgrp", wg, xg).reshape(
        b, nc, L, nh, hp)

    # per-chunk input state contribution
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)                    # (b,nc,L,nh)
    wde = (decay_to_end * dtc).reshape(b, nc, L, g, rep)
    Sc = jnp.einsum("bclgn,bclgr,bclgrp->bcgrpn", Bc, wde, xg).reshape(
        b, nc, nh, hp, n)
    a_tot = cum[:, :, -1, :]                                           # (b,nc,nh)

    # inter-chunk recurrence: h_c = exp(a_tot_c) h_{c-1} + S_c
    def scan_fn(h, xs):
        s_c, atot_c = xs
        h_new = jnp.exp(atot_c)[..., None, None] * h + s_c
        return h_new, h            # emit the state ENTERING this chunk
    hT, h_all = jax.lax.scan(scan_fn, h0, (jnp.moveaxis(Sc, 1, 0),
                                           jnp.moveaxis(a_tot, 1, 0)))
    h_in = jnp.moveaxis(h_all, 0, 1)   # (b,nc,nh,hp,n): state entering chunk c

    decay_from_start = jnp.exp(cum).reshape(b, nc, L, g, rep)          # (b,nc,L,g,rep)
    hg = h_in.reshape(b, nc, g, rep, hp, n)
    y_inter = jnp.einsum("bclgn,bclgr,bcgrpn->bclgrp", Cc,
                         decay_from_start, hg).reshape(b, nc, L, nh, hp)

    y_sum = constrain(y_intra + y_inter, "batch", "ssd_chunk",
                      None, None, None)
    return y_sum.reshape(b, t_pad, nh, hp)[:, :t], hT


def _ssd_recurrent(xh, Bh, Ch, dt, a, h0):
    """One token per row: xh (b, nh, hp), Bh/Ch (b, g, n), dt/a (b, nh)."""
    rep = xh.shape[1] // Bh.shape[1]
    Br = jnp.repeat(Bh, rep, axis=1)                                   # (b,nh,n)
    Cr = jnp.repeat(Ch, rep, axis=1)
    upd = jnp.einsum("bhn,bhp->bhpn", Br, xh * dt[..., None])
    h = jnp.exp(a)[..., None, None] * h0 + upd
    return jnp.einsum("bhn,bhpn->bhp", Cr, h), h


def _mix(p, xbc, dt_raw, cfg, state, conv_state, n_valid):
    """conv, SSD and the D term over (B, W) rows of raw xBC and dt from
    carried states (None: zero). Returns y (B, W, d_inner) before the gated
    norm, and the new SSM and conv states."""
    b, w, _ = xbc.shape
    nh, hp, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                    cfg.ssm_state)
    xbc, conv_new = _conv(p, xbc, conv_state, n_valid, cfg)
    with jax.named_scope("ssm.scan"):
        di = cfg.d_inner
        xh = xbc[..., :di].reshape(b, w, nh, hp).astype(jnp.float32)
        Bh = xbc[..., di:di + g * n].reshape(b, w, g, n).astype(jnp.float32)
        Ch = xbc[..., di + g * n:].reshape(b, w, g, n).astype(jnp.float32)
        f32 = lambda name: p[name].astype(jnp.float32)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + f32("dt_bias"))
        if n_valid is not None:
            valid = jnp.arange(w)[None, :] < n_valid[:, None]
            dt = jnp.where(valid[..., None], dt, 0.0)
        a = -jnp.exp(f32("A_log")) * dt                                # <= 0
        h0 = (jnp.zeros((b, nh, hp, n), jnp.float32) if state is None
              else state.astype(jnp.float32))
        if w == 1:
            y, hT = _ssd_recurrent(xh[:, 0], Bh[:, 0], Ch[:, 0], dt[:, 0],
                                   a[:, 0], h0)
            y = y[:, None]
        else:
            y, hT = _ssd_chunked(xh, Bh, Ch, dt, a, h0, cfg)
        y = y + f32("D")[None, None, :, None] * xh
        y = y.reshape(b, w, cfg.d_inner).astype(xbc.dtype)
    return y, hT, conv_new


def apply_ssd(p, x, cfg, state=None, conv_state=None):
    """x: (B, T, D). state: None or (B, nh, P, N); conv_state: None or
    (B, ssm_conv - 1, conv_dim).

    Returns (out (B, T, D), final state, final conv state)."""
    z, xbc, dt = _proj(p, x, cfg)
    y, st, cs = _mix(p, xbc, dt, cfg, state, conv_state, None)
    out = _out(p, y, z, cfg)
    return constrain(out, "batch", "seq_act", "embed"), st, cs


def apply_ssd_packed(p, x, cfg, state, conv_state, cache):
    """The packed paged step: x (1, T, D) holds ragged tokens of many slots.
    `cache` carries the attention grid's steering (models/attention.py
    _packed_attention): `grid_pos` (T,) puts each token at cell (slot, i) of
    the (B, Wb) slot grid (pad lanes at the spill cell B*Wb), `q_pos_grid`
    gives Wb, and `kv_len_slot - length` is each slot's token count. Row b
    runs from slot b's carried states; a slot with no token keeps them.

    Returns (out (1, T, D), new states (B, ...), new conv states)."""
    z, xbc, dt = _proj(p, x, cfg)
    gp, sid = cache["grid_pos"], cache["slot_ids"]
    bsz, wb = cache["q_pos_grid"].shape
    c = cfg.conv_dim
    with jax.named_scope("ssm.state"):
        lanes = jnp.concatenate([xbc[0], dt[0].astype(xbc.dtype)], axis=-1)
        grid = jnp.zeros((bsz * wb + 1, lanes.shape[-1]),
                         lanes.dtype).at[gp].set(lanes)
        grid = grid[:bsz * wb].reshape(bsz, wb, -1)
        n_valid = cache["kv_len_slot"] - cache["length"]
    y, st, cs = _mix(p, grid[..., :c], grid[..., c:], cfg, state,
                     conv_state, n_valid)
    with jax.named_scope("ssm.state"):
        y = y.reshape(bsz * wb, -1)[jnp.where(sid >= 0, gp, 0)][None]
    return _out(p, y, z, cfg), st, cs
