"""Property tests for the packed-token-step packer (serve/paged.py).

The packer is the host half of the token-centric chunked-prefill path: it
turns per-slot progress into a ragged (token, slot_id, position) batch padded
to a fixed budget. These properties pin the contract the device step relies
on: budget respected, every live slot scheduled, contiguous per-slot
segments/positions, and no cross-slot leakage — a token's write position only
ever lands in a block its OWN slot's table row owns, and its causal frontier
never reaches past its own segment.

Needs hypothesis (skips cleanly without it, like the allocator suite).
"""
import numpy as np

from conftest import require_hypothesis

hypothesis = require_hypothesis()
from hypothesis import given, settings          # noqa: E402
from hypothesis import strategies as st         # noqa: E402

import pytest                                   # noqa: E402

from repro.serve.paged import (TRASH_BLOCK, chunk_grid_rows,  # noqa: E402
                               fit_chunk_grid, grid_steering, pack_slot_ids,
                               packed_write_positions, schedule_step_tokens)


@st.composite
def step_states(draw):
    """A random mid-flight engine state: live mask, per-slot prompt tokens
    remaining (0 = decoding), per-slot cache frontiers, a budget that can
    schedule every live slot, and an optional per-slot chunk cap."""
    b = draw(st.integers(1, 8))
    live = np.asarray(draw(st.lists(st.booleans(), min_size=b, max_size=b)))
    remaining = np.asarray(
        draw(st.lists(st.integers(0, 40), min_size=b, max_size=b)),
        np.int64) * live
    lengths = np.asarray(
        draw(st.lists(st.integers(0, 60), min_size=b, max_size=b)), np.int64)
    budget = draw(st.integers(max(int(live.sum()), 1), 64))
    chunk_cap = draw(st.one_of(st.none(), st.integers(1, 64)))
    return live, remaining, lengths, budget, chunk_cap


@settings(max_examples=150, deadline=None)
@given(step_states())
def test_schedule_budget_and_liveness(state):
    live, remaining, _, budget, chunk_cap = state
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap)
    # budget respected
    assert int(t_valid.sum()) <= budget
    # every live slot scheduled, every dead slot idle
    assert (t_valid[live] >= 1).all()
    assert (t_valid[~live] == 0).all()
    # decode slots take exactly one lane; prefill slots never overshoot
    # their remaining prompt or the per-slot chunk cap
    decode = live & (remaining == 0)
    assert (t_valid[decode] == 1).all()
    prefill = live & (remaining > 0)
    assert (t_valid[prefill] <= remaining[prefill]).all()
    if chunk_cap is not None:
        assert (t_valid[prefill] <= chunk_cap).all()


@settings(max_examples=150, deadline=None)
@given(step_states())
def test_pack_segments_contiguous(state):
    live, remaining, lengths, budget, chunk_cap = state
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap)
    width = budget
    sid, off = pack_slot_ids(t_valid, width)
    n = int(t_valid.sum())
    # valid lanes form one contiguous run, pad lanes (-1) are the tail
    assert (sid[:n] >= 0).all() and (sid[n:] == -1).all()
    for slot in np.flatnonzero(t_valid > 0):
        lanes = np.flatnonzero(sid == slot)
        # each slot's segment is contiguous at its offset, with its count
        assert len(lanes) == int(t_valid[slot])
        assert lanes[0] == int(off[slot])
        assert (np.diff(lanes) == 1).all()
        # positions are contiguous per slot: lengths[s] + 0..tv-1 — so the
        # per-token causal frontier (position + 1) never reaches past the
        # slot's own segment end (no intra-chunk future leakage)
        positions = lengths[slot] + np.arange(len(lanes))
        assert (positions + 1 <= lengths[slot] + t_valid[slot]).all()


@settings(max_examples=150, deadline=None)
@given(step_states())
def test_write_positions_no_cross_slot_leakage(state):
    live, remaining, lengths, budget, chunk_cap = state
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap)
    width = budget
    sid, off = pack_slot_ids(t_valid, width)
    # give every slot its own disjoint block ids, covering the write range
    bs = 8
    b = len(t_valid)
    nblk = int((lengths + t_valid).max() + bs) // bs + 1
    tables = np.arange(1, 1 + b * nblk, dtype=np.int32).reshape(b, nblk)
    wp = packed_write_positions(t_valid, off, tables, lengths, bs, width)
    for lane in range(width):
        slot = int(sid[lane])
        blk = int(wp[lane]) // bs
        if slot < 0:
            # pad lanes only ever scatter into the trash block
            assert blk == TRASH_BLOCK
            continue
        # a token's KV bytes land ONLY in a block owned by its own slot's
        # table row — cross-slot leakage is structurally impossible
        assert blk in tables[slot]
        # and at exactly its logical position
        i = lane - int(off[slot])
        gpos = int(lengths[slot]) + i
        assert blk == tables[slot, gpos // bs]
        assert int(wp[lane]) % bs == gpos % bs


@st.composite
def spec_step_states(draw):
    """A step state plus per-slot draft proposals (speculative decoding):
    drafts are drawn for EVERY slot — the scheduler must ignore them on
    prefilling and dead slots (only decode slots verify drafts)."""
    state = draw(step_states())
    b = len(state[0])
    drafts = np.asarray(
        draw(st.lists(st.integers(0, 6), min_size=b, max_size=b)), np.int64)
    return state, drafts


@settings(max_examples=150, deadline=None)
@given(spec_step_states())
def test_schedule_drafts_contract(state_and_drafts):
    """Speculative draft lanes ride the same packer contract: budget and
    chunk cap still bind, every live slot still gets its guaranteed lane,
    draft lanes go ONLY to decode slots, and the leftover budget is dealt
    to decode drafts FIRST (slot order), prefill chunks after."""
    (live, remaining, _, budget, chunk_cap), drafts = state_and_drafts
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap,
                                   drafts=drafts)
    cap = chunk_cap if chunk_cap is not None else budget
    assert int(t_valid.sum()) <= budget
    assert (t_valid[live] >= 1).all()
    assert (t_valid[~live] == 0).all()
    # decode slots: one committed lane + at most min(drafts, cap-1) draft
    # lanes; prefill slots never read the drafts array at all
    decode = live & (remaining == 0)
    assert (t_valid[decode] <= 1 + np.minimum(drafts[decode],
                                              max(cap, 1) - 1)).all()
    prefill = live & (remaining > 0)
    assert (t_valid[prefill] <= remaining[prefill]).all()
    assert (t_valid[prefill] <= max(cap, 1)).all()
    # drafts-first priority: any prefill slot holding extra lanes means
    # every drafting decode slot already took its full draft allotment
    if (t_valid[prefill] > 1).any():
        want = 1 + np.minimum(drafts[decode], max(cap, 1) - 1)
        assert (t_valid[decode] == want).all()
    # FIFO among drafting decode slots: a later slot only gets draft lanes
    # after every earlier one is maxed out
    drafting = np.flatnonzero(decode & (drafts > 0))
    for a, b_ in zip(drafting, drafting[1:]):
        if t_valid[b_] > 1:
            assert t_valid[a] == 1 + min(int(drafts[a]), max(cap, 1) - 1)
    # all-zero drafts is EXACTLY the pinned non-speculative layout
    base = schedule_step_tokens(live, remaining, budget, chunk_cap)
    spec0 = schedule_step_tokens(live, remaining, budget, chunk_cap,
                                 drafts=np.zeros_like(drafts))
    assert (base == spec0).all()


@settings(max_examples=80, deadline=None)
@given(step_states())
def test_schedule_is_greedy_fifo(state):
    """Leftover budget is dealt to prefilling slots in slot order: a later
    prefilling slot only gets more than its single guaranteed lane after
    every earlier one is either fully scheduled (up to the chunk cap) or
    the budget ran dry."""
    live, remaining, _, budget, chunk_cap = state
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap)
    cap = chunk_cap if chunk_cap is not None else budget
    prefill = np.flatnonzero(live & (remaining > 0))
    for a, b_ in zip(prefill, prefill[1:]):
        if t_valid[b_] > 1:
            assert t_valid[a] == min(remaining[a], max(cap, 1))


GRID_WIDTHS = [1] + list(range(8, 65, 8))


@settings(max_examples=150, deadline=None)
@given(step_states())
def test_grid_steering_cells(state):
    """The XLA packed step's attention grids: every valid lane lands in a
    cell of its own — a single-token slot in its row of the (B, 1) decode
    grid, a multi-token slot's i-th lane at column i of its own row of the
    (P, Wb) chunk grid — and pad lanes in the spill cell; each row carries
    its slot's positions and keys, and P follows the rung rule."""
    live, remaining, lengths, budget, chunk_cap = state
    t_valid = schedule_step_tokens(live, remaining, budget, chunk_cap)
    width = budget
    sid, off = pack_slot_ids(t_valid, width)
    g = grid_steering(t_valid, off, sid, lengths, GRID_WIDTHS)
    b = len(t_valid)
    cell = g["attn_cell"]
    wb = min(w for w in GRID_WIDTHS if w >= max(int(t_valid.max()), 1))
    multi = np.flatnonzero(t_valid > 1)
    if wb == 1:
        assert "chunk_slots" not in g and len(multi) == 0
        p = 0
    else:
        p, wb_ = g["chunk_q_pos"].shape
        assert wb_ == wb
        floor = max(1, width // wb)
        assert p == chunk_grid_rows(width, wb, len(multi), b)
        # the smallest of floor, 2 floor, 4 floor, ... that holds every
        # multi-token slot, at most B
        k = 0
        while floor * 2 ** k < len(multi):
            k += 1
        assert p == min(b, floor * 2 ** k)
    spill = b + p * wb
    valid = sid >= 0
    assert (cell[~valid] == spill).all()
    assert len(set(cell[valid].tolist())) == int(valid.sum())
    assert (cell[valid] < spill).all()
    for slot in range(b):
        tv = int(t_valid[slot])
        lanes = np.arange(int(off[slot]), int(off[slot]) + tv)
        if tv == 1:
            assert cell[lanes[0]] == slot
            assert g["dec_kv_len"][slot] == lengths[slot] + 1
            continue
        assert g["dec_kv_len"][slot] == 0
        if tv == 0:
            continue
        r = int(np.flatnonzero(multi == slot)[0])
        assert g["chunk_slots"][r] == slot
        assert (cell[lanes] == b + r * wb + np.arange(tv)).all()
        assert (g["chunk_q_pos"][r] == lengths[slot] + np.arange(wb)).all()
        assert g["chunk_kv_len"][r] == lengths[slot] + tv
    if p:
        assert (g["chunk_kv_len"][len(multi):] == 0).all()
    # the SSD's (B, Wb) slot grid rides only where asked for
    assert "grid_pos" not in g
    ssd = grid_steering(t_valid, off, sid, lengths, GRID_WIDTHS, ssd=True)
    assert ssd["q_pos_grid"].shape == (b, wb)
    gp = ssd["grid_pos"]
    assert (gp[~valid] == b * wb).all()
    lane = np.flatnonzero(valid)
    assert (gp[valid] == sid[valid] * wb + lane - off[sid[valid]]).all()
    assert (ssd["kv_len_slot"] == lengths + t_valid).all()


@settings(max_examples=150, deadline=None)
@given(spec_step_states())
def test_fitted_prefill_chunks_take_the_floor_rung(state_and_drafts):
    """fit_chunk_grid leaves at most max(1, width // Wb) multi-token
    prefilling slots at the width it returns, the first in slot order, and
    cuts the others to one lane; that width is the smallest rung holding
    the lanes left. Without verify rows the chunk grid then has its
    floor's P rows. Decode slots and their verify rows keep their lanes."""
    (live, remaining, lengths, budget, chunk_cap), drafts = state_and_drafts
    t0 = schedule_step_tokens(live, remaining, budget, chunk_cap,
                              drafts=drafts)
    widths = sorted({max(budget // 4, 1), max(budget // 2, 1), budget})
    prefill = remaining > 0
    t_valid, width = fit_chunk_grid(t0, prefill, budget, widths,
                                    GRID_WIDTHS)
    assert width == min(w for w in widths if w >= t_valid.sum())
    wb = min(w for w in GRID_WIDTHS if w >= max(int(t_valid.max()), 1))
    kept = np.flatnonzero(prefill & (t_valid > 1))
    assert len(kept) <= max(1, width // wb)
    assert (t_valid[~prefill] == t0[~prefill]).all()
    cut = t_valid != t0
    assert (t_valid[cut] == 1).all() and (t0[cut] > 1).all()
    if cut.any():                  # the first chunks in slot order stay
        assert kept.max() < np.flatnonzero(cut).min()
    if not (~prefill & (t_valid > 1)).any():
        sid, off = pack_slot_ids(t_valid, width)
        g = grid_steering(t_valid, off, sid, lengths, GRID_WIDTHS)
        if "chunk_q_pos" in g:
            p, wb = g["chunk_q_pos"].shape
            assert p == min(len(t_valid), max(1, width // wb))


@pytest.mark.parametrize("config", ["granite-3-2b", "granite-4.0-h-micro"])
def test_warm_up_lands_on_the_floor_rung(tiny_cfg, config):
    """bench/harness.py's warm-up sends, for each (step width, Wb) pair of
    the engine's ladders, n fresh prompts of p tokens; at the benchmark
    cells' max_batch and block_size each first step lands on that pair,
    whole, with P = width // Wb rows (the floor rung), so warming up
    compiles every attention program a step without verify rows meets."""
    import jax
    from bench import harness, registry
    from repro.models import model as M
    from repro.serve import PagedEngine

    sv = registry.config(config)["serving"]
    b, bs = sv["max_batch"], sv["block_size"]
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedEngine(params, cfg, max_batch=b, max_len=8 * bs,
                      block_size=bs)
    for w, wb, n, p in harness.ladder(sv):
        live = np.arange(b) < n
        remaining = np.where(live, p, 0)
        needed = min(n * min(p, eng._chunk_cap), eng.token_budget)
        width = min(x for x in eng._widths if x >= needed)
        assert width == w
        t_valid = schedule_step_tokens(live, remaining, width,
                                       eng._chunk_cap)
        fitted, fitted_width = fit_chunk_grid(t_valid, live, width,
                                              eng._widths, eng._grid_widths)
        assert (fitted == t_valid).all() and fitted_width == width
        sid, off = pack_slot_ids(t_valid, width)
        g = grid_steering(t_valid, off, sid, np.zeros(b, np.int64),
                          eng._grid_widths)
        if wb == 1:
            assert "chunk_q_pos" not in g
        else:
            assert g["chunk_q_pos"].shape == (min(b, max(1, w // wb)), wb)
