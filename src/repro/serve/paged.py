"""Paged KV-cache serving: block pool, block-table arena, chunked prefill,
refcounted copy-on-write prefix sharing.

Layout — a GLOBAL pool of fixed-size KV blocks plus per-request block tables
(vLLM-style), replacing the continuous engine's per-slot (max_len,) KV
reservation. Blocks are REFCOUNTED: `fork()` lets several holders (slots
and the prefix index) reference the same physical block, and `free()` only
returns a block to the free list when its last reference drops:

    block pool (device, per layer)            block tables (host, per slot)
    ┌───────────────────────────────┐
    │ blk 0  ████  trash      ref – │   slot 0 ──▶ [ 3, 7, 1, -1]  len 40
    │ blk 1  ███░  slot0      ref 1 │   slot 1 ──▶ [ 3, 7, 5, -1]  len 37
    │ blk 2  ░░░░  free       ref 0 │                 │  │  └ COW copy of blk 1
    │ blk 3  ████  shared     ref 3 │                 │  └ forked (prefix hit)
    │ blk 4  ░░░░  free       ref 0 │                 └ forked (prefix hit)
    │ blk 5  ████  slot1 COW  ref 1 │   free list: [2, 4, ...]
    │ blk 7  ████  shared     ref 3 │   prefix trie: (root, chunk 0) ─▶ 3
    └───────────────────────────────┘                 (blk 3, chunk 1) ─▶ 7
    pool k/v, stacked over layers, in one of two geometries chosen by the
    pool's reader (init_paged_cache):

        XLA attention path    (L, num_blocks, block_size, Hkv*hd)
                              token-major: a token's heads are one row;
                              carried through the layer scan and updated
                              in place by layer index (model.forward)
        fused Pallas kernels  (L, num_blocks, Hkv, block_size, hd_c)
                              head-major, head_dim lane-padded to 128 for
                              the kernels' BlockSpec tiles; scanned as xs/ys

    Either way logical position p of slot b lives at pool block
    table[b, p // block_size], row p % block_size.
    With cfg.kv_quant="int8" the pools are int8 and each layer adds
    k_scale/v_scale (num_blocks, Hkv) f32 — one symmetric per-(block,
    kv-head) dequant scale alongside the payload:

    │ blk 3  ████  int8 payload    │   k_scale[3] = [s_h0, s_h1, ...]
    │               row = q*scale  │   v_scale[3] = [s_h0, s_h1, ...]

    Writes run a per-row FOLD (models/attention.py paged_quant_scatter):
    each landing row grows the block scale monotonically to cover its amax
    and requantizes the existing payload by the old/new ratio, so the block
    bytes are a pure function of (row values, write order) — independent of
    how steps partition the rows. That is what keeps packed vs lockstep,
    sharing on/off, and session re-feeds BIT-IDENTICAL under quantization;
    only int8-vs-fp drift needs a tolerance gate (tests/test_kv_quant.py).
    COW copies carry payload AND scales (same bytes, same dequant); trie
    registration needs no extra freeze step — shared blocks are immutable
    because writers only ever touch refcount-1 blocks, which pins payload
    and scale together. Freed-then-reallocated blocks are listed as FRESH
    for one step so their stale scales reset to zero before the fold.
    Above: slots 0 and 1 share the 2-block prompt prefix in blks 3 and 7
    (ref 3 = two slots + the index); slot 1 needed to write into the last
    shared block, so it was copied first (blk 1 -> blk 5, COW) — a holder
    may only write into a block whose refcount is 1.

Memory now scales with LIVE tokens, not max_batch * max_len: blocks are
allocated when a slot's frontier crosses into them (alloc-on-frontier-
crossing) and dereferenced at EOS (free-at-EOS). Block 0 is reserved as the
*trash block*: the jitted step has static shapes, so token lanes past a
slot's valid count still scatter somewhere — they are steered into block 0,
which no request ever owns and every mask hides.

Admission uses CHUNKED PREFILL: a long prompt is fed `block_size` tokens at a
time inside the regular batched step — decoding slots ride along with
t_valid = 1 — instead of the continuous engine's separate bucket-padded
prefill call. That kills the O(log max_len) prefill retrace buckets: the
engine compiles exactly two step shapes, (B, block_size) and (B, 1).

PACKED TOKEN STEPS (packed=True, the default): the lockstep chunk layout
above still pads every decode-riding slot to a full (block_size,) row — a
step with one prefilling prompt and seven decoders burns 8 x 16 = 128 token
lanes for 23 useful tokens. The packed step flattens the step's work into a
RAGGED TOKEN BATCH instead (vLLM-v2 style): rows are tokens, not slots.

    lockstep chunk step (B=4, bs=4)        packed step (budget T=8)
    slot 0  p4 p5 p6 p7   ← prefilling     lane     0  1  2  3  4  5  6  7
    slot 1  d  ░  ░  ░    ← decode rides   token   p4 p5 p6 p7 d  d  d  ░
    slot 2  d  ░  ░  ░      with 3 pad     slot_id  0  0  0  0  1  2  3 -1
    slot 3  d  ░  ░  ░      lanes each     pos      4  5  6  7  9  12 5  0
    12/16 lanes wasted                     7/8 lanes useful

The host packer emits (token, slot_id, position) triples padded to a fixed
token budget: each live decode slot contributes exactly one token, each
prefilling slot a chunk of any length up to the leftover budget (the chunk
size is BUDGET-driven, no longer hard-wired to block_size), and per-token
`kv_len = position + 1` frontiers replace the per-slot mask. A token only
attends within its own slot's blocks: the fused packed kernel
(kernels/decode.py hccs_packed_prefill) walks `block_table[slot_ids[t]]` in
its scalar-prefetched index_map (a gather-free DMA steer), while the XLA
path scatters the tokens into a compact (B, Wb) per-slot grid for the
attention core only — one per-slot KV gather, not one per token — and keeps
every other layer token-packed (see models/attention.py
_packed_attention). Each step runs at the
smallest rung of a 4-entry chunk-width ladder (max_batch ... token_budget,
default budget max_batch * block_size) that covers its pending work, so
prompt tails and rider-dominated steps don't pad to the full budget — at
most 4 traced shapes, still O(1). The lockstep layout stays available
(packed=False) as the parity/benchmark baseline.

PREFIX SHARING (cfg.prefix_sharing / --prefix-sharing): as a request's
prefill fills a block entirely with prompt tokens, the engine registers it
in a prefix TRIE keyed by (parent block id, chunk token bytes) — exact
content, no hash collisions, O(block_size) per level. Admission walks the
trie over the longest run of full-block chunks of the new prompt and maps
the hits into the new request's block table with `fork()` — skipping both
the prefill FLOPs and the duplicate KV bytes — and chunked prefill starts
at the first unmatched token (the per-slot `length` frontier doubles as the
partial-prefill start offset for RoPE positions and write targets). The
index holds its own reference, so cached prefixes survive the registering
request's EOS; index-only LEAF blocks (ref 1, no indexed children) are
evicted LRU-first under pool pressure — leaf-first keeps every surviving
chain reachable from the root. At
least the last prompt token is always re-fed (a fully-matched prompt still
needs logits to sample from), which lands a write inside a shared block —
the copy-on-write rule copies that block to a fresh one first, so shared KV
bytes are immutable for their whole cached lifetime.

DECODE-BLOCK SHARING + SESSIONS (cfg.decode_sharing / --decode-sharing): the
prefix trie above only keys on prompt tokens known at submit, so a follow-up
turn of a conversation re-prefills every token the engine itself GENERATED
last turn. With decode sharing on, blocks are inserted into the trie as they
fill during decode too (vLLM-style full-sequence chunk hashing over
prompt + output tokens — same (parent block id, chunk bytes) keys, tagged
with a "decode" origin): a block that reaches block_size tokens at the
decode frontier is registered at that step, refcount rules unchanged, and is
COW-safe for the same reason prompt blocks are — cached blocks are immutable
because writers only ever touch refcount-1 blocks. On top of that sits the
multi-turn SESSION API: `submit(req, session="chat-1")` prepends the
session's stored history (prompt + generated tokens of every prior turn) to
the request's prompt, so admission prefix-matches the full prior
conversation and a follow-up turn skips both the prefill FLOPs and the
duplicate KV for everything already decoded. The session layer is
correctness-orthogonal: with sharing off it degenerates to re-feeding the
concatenated history (token-identical outputs, property of the parity
tests); sharing only makes it cheap. prefix_stats() splits the reuse
telemetry into prompt_hits/decode_hits (and the matching token counters) so
prompt-prefix reuse and decode-block reuse are separately visible.

SPECULATIVE DECODING (cfg.speculative / --speculative, packed steps only):
each decode step proposes up to draft_len tokens per decoding slot and
verifies them ALL in one packed step — the packed layout already runs
multi-token slots with per-token causal frontiers, so a verify step is just
a decode step whose slots own several lanes:

    draft    trie.extend_path(prompt + output) — continue the slot's matched
             chain through the prefix trie (decode sharing keeps generated
             blocks indexed, so multi-turn traffic drafts from prior turns);
             n-gram prompt-lookup over the slot's own tokens when the trie
             path runs dry
    verify   lanes [x0, d0, d1, ..., dk-1] at positions [L, L+1, ..., L+k];
             lane i's logits sample token t_i with the SAME per-(request,
             position) key a never-drafted engine would fold — accept the
             longest prefix with d_i == t_i, emit t_0..t_j (j = first
             mismatch; the mismatched lane's own sample is the correction,
             so every verify step emits >= 1 token)
    rollback rejected lanes leave no trace: draft-only block allocations are
             freed in reverse order (the free list is restored exactly),
             fp-pool rows beyond the new frontier are dead (masked by
             kv_len, overwritten before any read). int8 pools fold draft
             lanes with a CLAMPED scale — never growing a block's scale, so
             committed lanes read history bit-exactly — and after EVERY
             verify step restore a pre-step snapshot of the touched blocks
             and re-fold just the committed rows from the staged raw KV
             (bytes are a pure function of row values + order, so the pool
             is bit-identical to never having drafted)

Accepted tokens amortize the per-step dispatch cost (the serving win the
speculative benchmark section measures); greedy outputs are token-identical
with speculation on or off, property-tested in tests/test_spec_decode.py.

RECURRENT STATE (configs whose `layer_types` hold Mamba-2 layers): beside the
paged KV pool, which then covers only the attention layers, each slot owns
a fixed row of a per-slot STATE ARENA, allocated from the config at
construction and riding the cache like the pool:

    ssm   (L_mamba, max_batch, heads, head_dim, state)   float32
    conv  (L_mamba, max_batch, ssm_conv - 1, conv_dim)   compute dtype

A slot's rows are zeroed when a request is admitted into it (the
`serve.state_reset` span), and every step reads and writes the rows of
every slot in each Mamba layer: the packed step scatters each slot's tokens
onto its row of the (B, Wb) grid the attention core already uses, runs the
conv and the SSD from the slot's carried state, and writes the new state
back in place (models/ssm.py apply_ssd_packed). A slot with no token in the
step passes its state through unchanged. A slot's state is a function of
its whole history, which a block table cannot share or roll back, so prefix
and decode sharing, speculation, preemption, the async loop, the lockstep
layout, int8 KV and the fused kernels are refused for such configs (state
snapshots at block boundaries would lift that). padding_stats() counts the
slot-states the steps moved, those of slots with a token, and the state
bytes read and written.

Attention dispatch (models/attention.py) keys off `block_table` in the cache:
the XLA path gathers each slot's blocks into a contiguous view; with
cfg.decode_kernel != "none" the t == 1 hot path runs the block-sparse Pallas
kernel `hccs_paged_decode` (kernels/decode.py), whose KV BlockSpec index_map
walks the scalar-prefetched block table directly — the gather steers the DMA
and sentinel entries reuse the dead-block skip.

Admission is deadlock-free by reservation: a request is admitted only when
the unreserved free-block count covers its worst case
ceil((prompt + max_new) / block_size), so alloc-on-frontier-crossing can
never exhaust the pool mid-flight (the allocator still raises
BlockPoolExhausted before corrupting state if driven past capacity by hand).

OVERLOAD ROBUSTNESS (admission=AdmissionConfig, serve/admission.py —
strictly opt-in; without it every path above is byte-identical): requests
carry a priority/SLA class and optional TTFT/E2E deadlines, the queue
becomes bounded with a backpressure policy, and the engine gains preemption
by block reclaim, cancel(uid), and graceful pool exhaustion. A request's
life under the layer:

                 submit()                       _admit()
    queued ────────────────► AdmissionQueue ──────────────► running
      │  (priority order,         │                            │
      │   bounded + shed/reject)  │ deadline past              │ EOS/budget
      │                           ▼ (expire in place)          ▼
      │ cancel(uid) ─────► failed("cancelled"|                done
      │                     "deadline_*"|"shed")               ▲
      ▼                                                        │
    running ──► PREEMPTED (blocks freed refcount-aware;        │
                out_tokens become resume state) ──► RE-QUEUED  │
                (original seq + SLA clock) ──► RESUMED ────────┘
                (re-prefill prompt + out_tokens rides the prefix
                 trie, so most of it is skipped; sampling keys fold
                 (uid, generation index) — resumed outputs are
                 token-identical to a never-preempted run)

Preemption picks the lowest class, most recently admitted victim
(choose_victim); graceful exhaustion catches BlockPoolExhausted inside
step(), unwinds the failing phase's partial allocations exactly (the
alloc/COW journal), and preempts instead of crashing. serve/chaos.py is
the seeded fault injector + invariant checker exercising all of it.

PIPELINED ASYNC LOOP (cfg.async_loop / PagedEngine(async_loop=True) —
strictly opt-in; packed layout only): the synchronous loop serializes
[dispatch N → fence → commit N → dispatch N+1], leaving the device idle
while the host samples, detects EOS, registers prefixes and runs
telemetry. The async loop dispatches step N+1 BEFORE committing step N,
so step N's host bookkeeping overlaps step N+1's device execution (JAX
async dispatch returns at enqueue; the donated pool buffer serializes the
device side, so N+1 never reads a half-written pool):

      device   ──[ step N ]──────[ step N+1 ]─────[ step N+2 ]──►
                     │  sampled_N     ▲   │ sampled_N+1   ▲
                     ▼  (on device)   │   ▼               │
      host     ──[ dispatch N+1 ]──[ commit N ]──[ dispatch N+2 ]──
                  tok_src indirection:    │  fence + land sampled_N:
                  decode lanes read       │  append tokens, EOS/budget
                  sampled_N on device     │  finishes, trie registration,
                  (never lands on host)   │  telemetry — one step LATE
                                          ▼
                               _release_slot dead-marks the in-flight
                               record; its writes for that slot die inert

    Commit boundary contract: frontiers (_lengths/_prompt_pos, alloc/COW)
    advance at DISPATCH — step N+1 schedules against post-N state without
    knowing N's token VALUES (greedy only: the device argmax is
    bit-identical to the host sampler's greedy path). Token-dependent
    control flow moves to the commit, one step late: budget/cache-full
    finishes are PREDICTED and excluded from the next schedule; EOS is
    not predictable, so an EOS slot runs one extra in-flight step whose
    writes are discarded at release (freed-block phantom rows are masked
    by position-ordered write-before-read + int8 fresh-block scale
    zeroing). Hot sampling and speculative drafting need landed values —
    those steps degrade to commit-then-sync-step (async_sync_fallbacks).
    Greedy outputs are token-identical with the loop on or off
    (tests/test_async_loop.py runs the packed x sharing x int8 x
    speculative parity matrix).

When to prefer which engine: see the module docstrings of engine.py (wave)
and continuous.py (slot arena), and ROADMAP.md "Serving architecture".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.attention import (kv_store_geometry, paged_quant_scatter,
                                    xla_reads_kv)
from repro.serve.admission import (AdmissionQueue, QueueFull,
                                   RobustnessCounters, as_admission,
                                   choose_victim)
from repro.serve.engine import (Request, kv_cache_byte_stats, sample_tokens,
                                validate_prompt,
                                warn_decode_kernel_fallback)
from repro.serve.telemetry import as_telemetry, make_snapshot

TRASH_BLOCK = 0


class BlockPoolExhausted(RuntimeError):
    """Raised by BlockAllocator.alloc when the free list is empty — before
    any table entry or pool block is touched, so engine state stays valid."""


class BlockAllocator:
    """Host-side refcounted free-list allocator for the global KV block pool.

    A block is born with one reference (`alloc`), gains references when a new
    holder maps it (`fork` — prefix hits and the prefix index itself), and
    `free` drops one reference per entry, returning the block to the free
    list only when the count reaches zero.

    Invariants (property-tested in tests/test_paged_alloc.py):
      * free + unique-live partitions {1, ..., num_blocks-1} (conservation);
      * alloc never hands out a block with a nonzero refcount (no aliasing
        except through explicit fork);
      * freeing below zero (double free) and freeing/forking unknown blocks
        raise without mutating state;
      * block 0 (the trash block) is never handed out, forked, or freed;
      * exhaustion raises BlockPoolExhausted without mutating state.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 usable + trash), got {num_blocks}")
        self.num_blocks = num_blocks
        # pop() hands out low block ids first (cosmetic: keeps pools dense)
        self._free = list(range(num_blocks - 1, TRASH_BLOCK, -1))
        self._ref: dict[int, int] = {}        # block -> refcount (>= 1)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """Unique live blocks (each counted once regardless of refcount)."""
        return len(self._ref)

    def ref(self, blk) -> int:
        """Current refcount of a block (0 if free / never allocated)."""
        return self._ref.get(int(blk), 0)

    def alloc(self) -> int:
        if not self._free:
            raise BlockPoolExhausted(
                f"KV block pool exhausted: {self.num_blocks - 1} usable "
                f"blocks all live")
        blk = self._free.pop()
        self._ref[blk] = 1
        return blk

    def fork(self, blk) -> int:
        """Add a reference to a live block (a new holder maps it read-only);
        returns the block id for `table[j] = alloc.fork(blk)` chaining."""
        blk = int(blk)
        if blk == TRASH_BLOCK:
            raise ValueError("the trash block is never forked")
        if blk not in self._ref:
            raise ValueError(f"forking block {blk} that is not live")
        self._ref[blk] += 1
        return blk

    def free(self, blocks) -> None:
        """Drop ONE reference per entry; a block only returns to the free
        list when its last reference is dropped."""
        for blk in blocks:
            blk = int(blk)
            if blk == TRASH_BLOCK:
                raise ValueError("the trash block is never freed")
            n = self._ref.get(blk)
            if n is None:
                raise ValueError(f"freeing block {blk} that is not live")
            if n == 1:
                del self._ref[blk]
                self._free.append(blk)
            else:
                self._ref[blk] = n - 1


def prefix_chunk(prompt, j: int, block_size: int) -> bytes:
    """Exact content bytes of prompt chunk j (tokens [j*bs, (j+1)*bs)). The
    prefix index keys on (parent block id, chunk bytes) — a trie: the parent
    id pins the whole history, so two chunks with equal tokens but different
    prefixes stay distinct (zero collisions) at O(block_size) per level
    instead of the O(prefix_len) a whole-prefix key would cost."""
    return np.ascontiguousarray(
        np.asarray(prompt[j * block_size:(j + 1) * block_size],
                   np.int32)).tobytes()


def sequence_chunk(prompt, out_tokens, j: int, block_size: int) -> bytes:
    """Chunk j's bytes of the full sequence prompt + out_tokens, without
    materializing the whole concatenation — registration only ever needs the
    newly filled block's O(block_size) span."""
    lo, hi = j * block_size, (j + 1) * block_size
    plen = len(prompt)
    if hi <= plen:
        return prefix_chunk(prompt, j, block_size)
    head = np.asarray(prompt[lo:plen] if lo < plen else [], np.int32)
    tail = np.asarray(out_tokens[max(lo - plen, 0):hi - plen], np.int32)
    return np.ascontiguousarray(np.concatenate([head, tail])).tobytes()


class PrefixTrie:
    """Exact-content prefix trie over full-block token chunks -> pool block.

    Keys are (parent block id | -1 for the root, chunk bytes): the parent id
    pins the whole history, so equal chunk content under different prefixes
    stays distinct (zero collisions) at O(block_size) per level. The trie
    holds its OWN allocator reference on every indexed block (fork at
    insert, free at evict/clear), so cached KV outlives the registering
    request. Entries carry the origin of their tokens — "prompt" (known at
    submit) or "decode" (generated, possibly a boundary block mixing both) —
    so engine telemetry can split prompt-prefix reuse from decode-block
    (multi-turn) reuse.

    Invariants (property-tested in tests/test_prefix_trie.py):
      * reachability: an indexed key's parent is the root or itself an
        indexed block — match() threads each level's block id into the next
        key, so a chain can never dangle;
      * insert is first-writer-wins: an existing key is touched and
        returned, never replaced (the caller keeps using its own duplicate
        block, which dies with the caller);
      * evict_one only removes LEAF entries (no indexed children) whose
        block has no holder besides the trie (ref == 1), least-recently-
        touched first — so surviving chains stay reachable and in-flight
        writers/holders are structurally protected;
      * every indexed block has refcount >= 1 (the trie's own reference).
    """

    def __init__(self, alloc: BlockAllocator, block_size: int):
        self.alloc = alloc
        self.block_size = block_size
        self._index: dict[tuple, int] = {}   # (parent, chunk bytes) -> block
        self._block_key: dict[int, tuple] = {}      # block -> its trie key
        self._children: dict[int, int] = {}         # parent -> indexed kids
        # parent -> {chunk bytes -> block}: the downward index extend_path
        # drafts from (match() only ever walks exact keys downward; drafting
        # needs "which chunks continue this parent")
        self._kids: dict[int, dict[bytes, int]] = {}
        self._lru: dict[tuple, int] = {}            # key -> last touch
        self._origin: dict[tuple, str] = {}         # key -> prompt | decode
        self._clock = 0

    def __len__(self) -> int:
        return len(self._index)

    def blocks(self):
        """The indexed pool block ids (for pool-hygiene checks)."""
        return self._index.values()

    def origin(self, key: tuple) -> str:
        return self._origin[key]

    def origin_counts(self) -> dict:
        counts = {"prompt": 0, "decode": 0}
        for o in self._origin.values():
            counts[o] += 1
        return counts

    def touch(self, key: tuple):
        self._clock += 1
        self._lru[key] = self._clock

    def match(self, tokens) -> list[tuple[tuple, int]]:
        """Longest contiguous run of full-block chunks of `tokens` present in
        the trie, as [(key, block id), ...] from block 0 up. Each hit's block
        id threads into the next level's key, so the walk stops naturally at
        the first missing level — a deeper entry without its parents is
        unreachable by construction. Pure: does not touch the LRU (callers
        touch the keys they actually map)."""
        bs = self.block_size
        matched = []
        parent, j = -1, 0
        while (j + 1) * bs <= len(tokens):
            key = (parent, prefix_chunk(tokens, j, bs))
            blk = self._index.get(key)
            if blk is None:
                break
            matched.append((key, blk))
            parent, j = blk, j + 1
        return matched

    def extend_path(self, tokens, k: int) -> list[int]:
        """Draft up to k tokens continuing `tokens` along indexed chains:
        after the longest full-block matched path, descend through children
        whose chunk CONTENT starts with the sequence's partial tail (int32
        token bytes, so a bytes-prefix test IS a token-prefix test), reading
        the draft straight out of the stored chunk. Among several matching
        children the most recently touched wins (the trie's own recency
        signal — no extra state). Pure: no LRU touches, no allocator
        effects; a wrong draft is rejected by verification at zero cost.

        Property (tests/test_spec_decode.py): every full block of
        tokens + drafts re-matches, i.e.
        len(match(tokens + drafts)) == len(tokens + drafts) // block_size."""
        bs = self.block_size
        matched = self.match(tokens)
        parent = matched[-1][1] if matched else -1
        tail = np.ascontiguousarray(
            np.asarray(tokens[len(matched) * bs:], np.int32)).tobytes()
        if len(tail) >= bs * 4:
            return []            # unmatched FULL block: no chain extends it
        out: list[int] = []
        while len(out) < k:
            kids = self._kids.get(parent)
            if not kids:
                break
            best = None
            for chunk, blk in kids.items():
                if chunk.startswith(tail) and len(chunk) > len(tail):
                    stamp = self._lru[(parent, chunk)]
                    if best is None or stamp > best[0]:
                        best = (stamp, chunk, blk)
            if best is None:
                break
            _, chunk, blk = best
            out.extend(np.frombuffer(chunk, np.int32)[len(tail) // 4:]
                       .tolist())
            parent, tail = blk, b""
        return out[:k]

    def insert(self, parent: int, chunk: bytes, blk, origin: str) -> int:
        """Index `blk` under (parent, chunk) and take a reference on it;
        first writer wins — an existing key is touched and its block
        returned, so chains stay rooted in index blocks even when the caller
        holds a COW copy or a duplicate. Returns the indexed block id (the
        caller threads it into the next level's parent)."""
        key = (int(parent), chunk)
        have = self._index.get(key)
        if have is not None:
            self.touch(key)
            return have
        blk = int(blk)
        self._index[key] = self.alloc.fork(blk)
        self._block_key[blk] = key
        self._origin[key] = origin
        self._children[key[0]] = self._children.get(key[0], 0) + 1
        self._kids.setdefault(key[0], {})[key[1]] = blk
        self.touch(key)
        return blk

    def evict_one(self, protect=frozenset()) -> int | None:
        """Reclaim the least-recently-used index-only LEAF block (ref == 1:
        no live slot or session holds it; no indexed children: evicting an
        interior node would orphan its whole subtree — unreachable entries
        squatting on pool blocks). Returns the freed block id, or None when
        nothing is evictable."""
        for key in sorted(self._lru, key=self._lru.get):
            blk = self._index[key]
            if (blk in protect or self.alloc.ref(blk) != 1
                    or self._children.get(blk, 0)):
                continue
            del self._index[key]
            del self._block_key[blk]
            del self._lru[key]
            del self._origin[key]
            parent = key[0]          # a block id, or -1 for the trie root
            self._children[parent] -= 1
            if not self._children[parent]:
                del self._children[parent]
            kids = self._kids[parent]
            del kids[key[1]]
            if not kids:
                del self._kids[parent]
            self.alloc.free([blk])
            return blk
        return None

    def clear(self):
        """Drop every index reference; blocks with no other holder return to
        the free list immediately."""
        blocks = list(self._index.values())
        self._index.clear()
        self._block_key.clear()
        self._children.clear()
        self._kids.clear()
        self._lru.clear()
        self._origin.clear()
        self.alloc.free(blocks)


def schedule_step_tokens(live, remaining, budget: int,
                         chunk_cap: int | None = None, drafts=None):
    """Per-slot token counts for one packed step (pure; property-tested in
    tests/test_packed_step.py).

    live: (B,) bool; remaining: (B,) prompt tokens still to feed (0 for
    decoding slots); budget: total token lanes this step. Every live slot is
    scheduled: decode slots take exactly one lane, prefilling slots at least
    one, and the leftover budget is dealt to prefilling slots in slot order
    (greedy FIFO fill), at most `chunk_cap` tokens per slot — the cap bounds
    the attention-grid width a single long prompt can force on every other
    slot's grid row (see PagedEngine._grid_widths). Requires
    budget >= live.sum().

    drafts ((B,) int, speculative decoding): proposed draft-token counts per
    DECODE slot; leftover budget is dealt to decode slots' draft lanes FIRST
    (a verified draft advances a whole token, a prefill lane only a prompt
    position), in slot order, still at most chunk_cap lanes per slot. The
    default (None) preserves the pinned decode-slots-take-one-lane layout
    exactly."""
    live = np.asarray(live, bool)
    remaining = np.asarray(remaining, np.int64)
    cap = int(chunk_cap) if chunk_cap else int(budget)
    t_valid = np.zeros(live.shape[0], np.int32)
    t_valid[live] = 1
    left = int(budget) - int(t_valid.sum())
    if left < 0:
        raise ValueError(
            f"token budget {budget} below live slot count {live.sum()}")
    if drafts is not None:
        drafts = np.asarray(drafts, np.int64)
        for slot in np.flatnonzero(live & (remaining == 0) & (drafts > 0)):
            take = min(int(drafts[slot]), cap - 1, left)
            t_valid[slot] += take
            left -= take
            if not left:
                break
    for slot in np.flatnonzero(live & (remaining > 0)):
        take = min(int(remaining[slot]) - 1, cap - 1, left)
        t_valid[slot] += take
        left -= take
        if not left:
            break
    return t_valid


def ngram_propose(seq, k: int, max_n: int = 3) -> list[int]:
    """Prompt-lookup drafting fallback (PLD-style): find the longest n-gram
    suffix of `seq` (n = max_n down to 1) that occurred EARLIER in seq, and
    propose the k tokens that followed its most recent earlier occurrence.
    Pure host-side; O(len(seq) * max_n) in VECTORIZED numpy — this runs per
    decoding slot per speculative step, and a Python-level scan of a few
    hundred history positions costs more than the verify step it feeds
    (~4ms vs ~5ms measured). Returns [] when no suffix repeats — drafting
    is best-effort, verification catches everything."""
    seq = np.asarray(seq, np.int32)
    n_tot = len(seq)
    for n in range(min(max_n, n_tot - 1), 0, -1):
        suffix = seq[n_tot - n:]
        # all length-n windows at once; candidate starts exclude the suffix
        # itself (the window at n_tot - n), most recent earlier one wins
        win = np.lib.stride_tricks.sliding_window_view(seq, n)
        hits = np.flatnonzero((win[:-1] == suffix).all(axis=1))
        if len(hits):
            s = int(hits[-1])
            # s + n <= n_tot - 1, so the follow run is never empty
            return [int(x) for x in seq[s + n:s + n + k]]
    return []


@jax.jit
def _gather_block_state(layers, blocks):
    """Device-side snapshot of `blocks` (S,) across all layers — int8
    payload and per-block scales — taken BEFORE a speculative verify step
    folds its rows, so the post-verification rewrite can restore them
    exactly (see _restore_and_replay). Trash-padded duplicate entries are
    fine: gathers read, they don't race."""
    return {name: layers[name][:, blocks]
            for name in ("k", "v", "k_scale", "v_scale")}


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_and_replay(layers, snap, blocks, fresh_mask, staged_k,
                        staged_v, replay_pos):
    """Post-verification int8 rewrite, run after EVERY speculative verify
    step: restore the pre-step snapshot of every block the drafting slots'
    rows touched (the in-step folds were scratch — draft lanes clamped the
    scale, and a committed lane's grow cannot be un-grown in place),
    re-zero the scales of snapshot blocks freshly allocated this step that
    stay live (the replay fold must see the same zeroed scale a real step
    sees; freed draft blocks instead keep their restored stale scale,
    exactly the state a never-drafted run leaves on a never-allocated
    block), then re-fold ONLY the committed rows from the staged raw KV,
    rejected lanes steered into the trash block. Block bytes are a pure
    function of (row values, order) — paged_quant_scatter's fold contract
    — so the result is bit-identical to a step that never drafted
    (tests/test_spec_decode.py pins this).

    layers: the full per-layer cache dict (donated); snap: the
    _gather_block_state dict; blocks: (S,) int32; fresh_mask: (S,) bool;
    staged_k/staged_v: (L, 1, Hkv, W, hd) raw rows; replay_pos: (1, W)."""
    out = dict(layers)
    for name, staged in (("k", staged_k), ("v", staged_v)):
        pool = out[name].at[:, blocks].set(snap[name])
        sc = out[name + "_scale"].at[:, blocks].set(
            jnp.where(fresh_mask[None, :, None], 0.0,
                      snap[name + "_scale"]))
        if pool.ndim == 4:          # token-major stack: fold layer by layer
            pool, sc = jax.lax.fori_loop(
                0, pool.shape[0],
                lambda li, c: paged_quant_scatter(
                    c[0], c[1], staged[li], replay_pos, None, li),
                (pool, sc))
        else:
            pool, sc = jax.vmap(paged_quant_scatter,
                                in_axes=(0, 0, 0, None))(pool, sc, staged,
                                                         replay_pos)
        out[name], out[name + "_scale"] = pool, sc
    return out


def pack_slot_ids(t_valid, width: int):
    """Flatten per-slot counts into the packed lane layout: slot segments
    are contiguous, in slot order, pad lanes (-1) at the tail. Returns
    (slot_ids (width,) int32, per-slot lane offsets (B,) int32)."""
    t_valid = np.asarray(t_valid)
    sid = np.full(width, -1, np.int32)
    off = np.zeros(t_valid.shape[0], np.int32)
    c = 0
    for slot in np.flatnonzero(t_valid > 0):
        tv = int(t_valid[slot])
        off[slot] = c
        sid[c:c + tv] = slot
        c += tv
    return sid, off


def _slot_write_targets(table_row, start: int, tv: int, bs: int):
    """Flat pool positions for one slot's next tv tokens: token i lands at
    table_row[(start+i)//bs] * bs + (start+i) % bs. The single source of the
    block-addressing rule, shared by the lockstep and packed layouts."""
    gpos = start + np.arange(tv)
    return np.asarray(table_row)[gpos // bs].astype(np.int64) * bs + gpos % bs


def packed_write_positions(t_valid, off, tables, lengths, block_size: int,
                           width: int):
    """Flat pool scatter targets (width,): lane off[b] + i of slot b lands at
    tables[b, (len+i)//bs] * bs + (len+i) % bs. Pad lanes are steered into
    the trash block (row lane % bs — colliding writes are fine, it is
    trash). _cow_shared ran before this, so no target block is shared."""
    bs = block_size
    wp = TRASH_BLOCK * bs + np.arange(width, dtype=np.int64) % bs
    tables = np.asarray(tables)
    for slot in np.flatnonzero(np.asarray(t_valid) > 0):
        tv = int(t_valid[slot])
        o = int(off[slot])
        wp[o:o + tv] = _slot_write_targets(tables[slot], int(lengths[slot]),
                                           tv, bs)
    return wp.astype(np.int32)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_slot_state(layers, slot):
    """Zero slot `slot`'s recurrent state in every Mamba layer, in place: an
    admitted request starts from no history, whatever the slot's previous
    owner left there."""
    return dict(layers, **{name: layers[name].at[:, slot].set(0)
                           for name in ("ssm", "conv")})


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block_kv(layers, src, dst):
    """Copy-on-write: duplicate pool block `src` into `dst` across all layers
    for both k and v — and, on kv_quant="int8" pools, the per-block scales
    (a COW copy must reproduce the block bit-for-bit: same int8 payload,
    same dequant scale). One traced shape per pool geometry (src/dst are
    traced scalars); donation lets XLA rewrite the pool in place."""
    out = dict(layers)
    for name in ("k", "v", "k_scale", "v_scale"):
        leaf = layers.get(name)
        if leaf is not None:
            out[name] = leaf.at[:, dst].set(leaf[:, src])
    return out


def init_paged_cache(cfg, num_blocks: int, block_size: int, max_batch: int,
                     cache_dtype=None):
    """Model cache in the paged layout: per-layer block pools plus the (B,)
    per-slot length frontier. The pool's geometry follows its reader
    (attention.xla_reads_kv):

        XLA attention path:  k/v (L, N, bs, Hkv*hd)    token-major
        fused Pallas kernels: k/v (L, N, Hkv, bs, hd_c) head-major

    Token-major puts one token's heads in one (Hkv*hd)-lane row, so the
    pool carries no lane padding, and model.forward carries it through the
    layer scan and updates it in place by layer index. Head-major gives the
    kernels' BlockSpecs their per-head tiles; head_dim is lane-padded
    exactly when the dense arena would be (kv_store_geometry), so the
    paged kernel's zero-copy branch runs whenever the dense kernel's would.

    cache_dtype=None resolves to cfg.cache_dtype (the single-sourced default
    shared with init_cache and every engine). With cfg.kv_quant="int8" the
    pools are int8 regardless of cache_dtype, in the same geometry, and each
    layer additionally carries `k_scale`/`v_scale` (num_blocks, Hkv) float32
    — one symmetric dequant scale per (block, kv-head), zero meaning "never
    written":

        k/v:             int8 payload in either geometry above
        k_scale/v_scale: (L, N, Hkv)       f32, row value = q * scale

    Scales are state, not steering: they ride the carried cache through the
    step (attention's per-row fold grows them monotonically as rows land)
    and are COW-copied with their block's payload (_copy_block_kv).

    A config with `layer_types` pools K/V for its attention layers alone,
    and gets the per-slot state arena of its Mamba layers (module
    docstring, "Recurrent state"):

        ssm:  (L_mamba, max_batch, heads, head_dim, state)   f32
        conv: (L_mamba, max_batch, ssm_conv - 1, conv_dim)   cfg.dtype"""
    if cache_dtype is None:
        cache_dtype = jnp.dtype(cfg.cache_dtype)
    hkv = cfg.num_kv_heads
    L = cfg.layers_of("attention") if cfg.layer_types else cfg.num_layers
    if xla_reads_kv(cfg):
        shape = (L, num_blocks, block_size, hkv * cfg.head_dim)
    else:
        hd_c = kv_store_geometry(cfg, block_size)[0]
        shape = (L, num_blocks, hkv, block_size, hd_c)
    quant = cfg.kv_quant == "int8"
    pool_dtype = jnp.int8 if quant else cache_dtype
    layers = {"k": jnp.zeros(shape, pool_dtype),
              "v": jnp.zeros(shape, pool_dtype)}
    if quant:
        layers["k_scale"] = jnp.zeros((L, num_blocks, hkv), jnp.float32)
        layers["v_scale"] = jnp.zeros((L, num_blocks, hkv), jnp.float32)
    m = cfg.layers_of("mamba")
    if m:
        layers["ssm"] = jnp.zeros((m, max_batch, cfg.ssm_heads,
                                   cfg.ssm_head_dim, cfg.ssm_state),
                                  jnp.float32)
        layers["conv"] = jnp.zeros((m, max_batch, cfg.ssm_conv - 1,
                                    cfg.conv_dim), jnp.dtype(cfg.dtype))
    return {"layers": layers,
            "length": jnp.zeros((max_batch,), jnp.int32)}


class PagedEngine:
    def __init__(self, params, cfg, *, max_batch: int = 8,
                 max_len: int = 512, eos_id: int | None = None,
                 cache_dtype=None, block_size: int | None = None,
                 num_blocks: int | None = None,
                 prefix_sharing: bool | None = None,
                 decode_sharing: bool | None = None,
                 packed: bool | None = None,
                 token_budget: int | None = None,
                 speculative: bool | None = None,
                 draft_len: int | None = None,
                 async_loop: bool | None = None,
                 telemetry=None, admission=None):
        if cfg.hot_buffer != 0:
            raise ValueError(
                "paged batching uses the block pool, not hot buffers "
                f"(cfg.hot_buffer={cfg.hot_buffer})")
        if cfg.family in ("ssm", "hybrid") and not cfg.layer_types:
            raise ValueError(
                f"paged serving of a {cfg.family} model needs `layer_types`: "
                "its SSM state lives in the per-slot state arena, whose "
                "layers the pattern names")
        warn_decode_kernel_fallback(cfg)
        if cache_dtype is None:
            cache_dtype = jnp.dtype(cfg.cache_dtype)
        self.w = params["weights"]
        self.hccs = params["hccs"]
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        # kv_quant="int8": pools are int8 with per-block scales; the engine's
        # only extra duty is the fresh-block list (see _take_fresh)
        self.quantized = cfg.kv_quant == "int8"
        bs = int(block_size if block_size is not None else cfg.block_size)
        # same contract ModelConfig.block_size enforces: a power of two >= 8
        # tiles any kernel block_k <= 128 evenly (constructor args like the
        # launcher's --block-size bypass the config dataclass)
        if bs < 8 or (bs & (bs - 1)):
            raise ValueError(
                f"block_size must be a power of two >= 8, got {bs}")
        if max_len < bs:
            raise ValueError(f"block_size {bs} exceeds max_len {max_len}")
        self.block_size = bs
        self._nblk_per_seq = -(-max_len // bs)       # block-table width
        if num_blocks is None:
            num_blocks = cfg.num_blocks
        if not num_blocks:
            # auto-size: half the equivalent dense slot arena (the memory win
            # that pays for paging), floored at one full-length request +
            # trash + one spare so any admissible request fits
            num_blocks = max(max_batch * self._nblk_per_seq // 2,
                             self._nblk_per_seq + 2)
        self.num_blocks = int(num_blocks)
        self.alloc = BlockAllocator(self.num_blocks)
        # opt-in overload-robustness layer (serve/admission.py + module
        # docstring). admission=None with an all-default cfg keeps the
        # legacy unbounded FIFO list and the fail-fast exhaustion path —
        # byte-identical to the pre-robustness engine.
        self._adm = as_admission(admission, cfg)
        self._robust = self._adm is not None
        if self._robust:
            self._queue = AdmissionQueue(self._adm)
        else:
            self._queue: list[Request] = []
        self.robust_counters = RobustnessCounters()
        self._admit_counter = 0              # monotone admission order
        self._key = jax.random.PRNGKey(0)
        # request-lifecycle tracing + step-phase profiling (telemetry.py);
        # disabled by default — every hook below is a no-op flag check then
        self.telemetry = as_telemetry(telemetry)
        # the UNIFIED serving clock: deadline decisions, queue timestamps
        # and telemetry latencies all read one timebase (the Telemetry
        # instance's clock — telemetry.SERVING_CLOCK unless injected). An
        # explicit AdmissionConfig.clock still wins for deadline decisions,
        # so tests can pin admission to a fake clock independently.
        self._clock = (self._adm.clock
                       if self._robust and self._adm.clock is not None
                       else self.telemetry.clock)
        # occupancy telemetry: running sum/count, O(1) state
        self.occupancy_sum = 0.0
        self.occupancy_steps = 0

        # packed token steps (the default): rows are tokens, not slots —
        # chunk size is budget-driven, decode slots cost one lane each.
        # packed=False keeps the lockstep (B, block_size)/(B, 1) layout as
        # the parity/benchmark baseline. The default budget matches one
        # lockstep chunk step's lane count (max_batch * block_size): any
        # lockstep step's work fits in one packed step, so the packed step
        # COUNT never exceeds lockstep's (per-step dispatch overhead is the
        # other half of the padding tax) while ragged packing keeps the
        # lanes that lockstep would pad doing useful prefill work instead.
        self.packed = True if packed is None else bool(packed)
        budget = (int(token_budget) if token_budget
                  else max_batch * bs)
        if budget < max_batch:
            raise ValueError(
                f"token_budget {budget} cannot schedule every live slot "
                f"(max_batch {max_batch})")
        self.token_budget = budget
        # kv_quant fresh-block list: blocks allocated by _grow_tables since
        # the last step. A freed-then-reallocated block still holds the prior
        # owner's per-block scale; the step must reset it to zero BEFORE the
        # quantizing fold runs, or the stale scale would fold into the new
        # owner's rows. COW destinations are deliberately NOT fresh — they
        # arrive with payload AND scales copied (_copy_block_kv), and zeroing
        # them would destroy the copied rows' dequant factor. The list rides
        # into the step as a static-size int32 array padded with the trash
        # block (re-zeroing trash's scale every step is harmless — its rows
        # are never read unmasked). Cap: a step writing <= budget tokens over
        # <= max_batch slots crosses at most budget/bs + 2*max_batch new
        # blocks (ceil + boundary straddle per slot).
        self._fresh: list[int] = []
        self._fresh_cap = budget // bs + 2 * max_batch
        # chunk-width ladder: a packed step runs at the smallest traced width
        # that covers its work, so prompt-tail and rider-dominated steps
        # don't pad all the way to the budget. At most 4 traced shapes (5
        # with speculative decoding's 2*max_batch rung, added below) — still
        # O(1), vs the O(log max_len) prefill buckets paging killed.
        self._widths = sorted({max_batch, max(budget // 4, max_batch),
                               max(budget // 2, max_batch), budget})
        # attention-grid width ladder: the XLA packed path runs its attention
        # core on a (B, Wb) per-slot grid (models/attention.py
        # _packed_attention) where Wb buckets this step's max per-slot chunk
        # — 1 for pure decode (the lockstep decode shape), exact block_size
        # multiples otherwise. Per-slot chunks are capped at 4 blocks so a
        # long prompt can neither monopolize the step nor blow the grid up
        # for every rider's row (grid rounding waste stays < one block/slot,
        # same as lockstep's ragged final chunk) while chunk steps still
        # prefill 4x the tokens a lockstep step can.
        self._chunk_cap = min(4 * bs, budget)
        # trie-driven speculative decoding (module docstring): decode slots
        # draft up to draft_len tokens per step, verified in one packed step
        self.speculative = bool(cfg.speculative if speculative is None
                                else speculative)
        self.draft_len = int(cfg.draft_len if draft_len is None
                             else draft_len)
        if self.draft_len < 1:
            raise ValueError(
                f"draft_len must be >= 1, got {self.draft_len}")
        if self.speculative and not self.packed:
            raise ValueError(
                "speculative decoding verifies all drafts in one packed "
                "step; it requires packed=True (the lockstep layout has no "
                "multi-token decode lanes)")
        if self.speculative:
            # verify lanes ride on top of a pure-decode step's max_batch
            # lanes, so give the ladder a 2*max_batch rung: without it a
            # lightly-drafting step jumps straight from max_batch to
            # budget//4 lanes and the padding eats the speculative win
            # (a 5th traced shape, still O(1))
            self._widths = sorted(set(self._widths)
                                  | {min(2 * max_batch, budget)})
        # acceptance telemetry (prefix_stats): drafted = accepted + rejected
        self.spec_steps = 0
        self.spec_rollbacks = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        # int8 rollback snapshot cap: each drafting slot's verify rows span
        # at most ceil((1 + draft_len)/bs) + 1 boundary-straddling blocks
        self._snap_cap = max_batch * ((self.draft_len + 1) // bs + 2)
        self._grid_widths = [1] + [k * bs for k in
                                   range(1, self._chunk_cap // bs + 1)]
        if self._grid_widths[-1] < self._chunk_cap:
            self._grid_widths.append(self._chunk_cap)
        if self.speculative:
            # verify steps put 1 + draft_len tokens on every drafting slot's
            # grid row; without a matching rung they round up to a full
            # block_size row and the attention core pays ~3x padding —
            # enough to erase the whole speculative win on its own
            self._grid_widths = sorted(set(self._grid_widths)
                                       | {min(1 + self.draft_len,
                                              self._chunk_cap)})
        # with the fused packed kernel active, attention never reads the
        # grid-steering arrays — omit them so the step traces once per chunk
        # width, not once per (chunk width, grid width) pair
        self._use_grid = xla_reads_kv(cfg) or not params["hccs"]
        # pipelined async loop (module docstring, "Pipelined async loop"):
        # dispatch step N+1 while step N's tokens are still in flight, with
        # host commit running one step behind. Opt-in; packed-only (the
        # lockstep layout is the parity baseline and stays strictly
        # synchronous).
        self.async_loop = bool(cfg.async_loop if async_loop is None
                               else async_loop)
        if self.async_loop and not self.packed:
            raise ValueError(
                "async_loop pipelines the packed token step; it requires "
                "packed=True (the lockstep layout is the synchronous "
                "parity baseline)")
        # the in-flight packed step awaiting host commit (one deep — JAX
        # queues the dispatch, the donated pool serializes execution):
        # None, or the dict _dispatch_packed_async builds. See
        # _commit_pending for the record's contract.
        self._pending: dict | None = None
        # pipelining accounting: steps that dispatched ahead of the
        # previous step's commit vs. steps that had to commit first
        # (hot sampling / speculative drafting need landed tokens)
        self.async_overlapped_steps = 0
        self.async_sync_fallbacks = 0
        # token-lane telemetry: padding efficiency is lanes_valid/lanes_total;
        # pad_lanes_skipped estimates the lanes the lockstep layout would
        # have burned for the same steps (packing's analogue of the prefix
        # index's prefill_tokens_skipped)
        self.lanes_valid = 0
        self.lanes_total = 0
        self.pad_lanes_skipped = 0
        # work counters: ctx_positions sums each valid lane's causal context
        # (the keys it attends over), sampled_lanes the lanes whose logits
        # a step samples from — with lanes_valid they give a step's model
        # FLOPs without reading its inputs
        self.ctx_positions = 0
        self.sampled_lanes = 0
        # state-arena counters (module docstring, "Recurrent state"): each
        # step reads and writes every slot's state in each Mamba layer
        self.state_slots_moved = 0
        self.state_slots_live = 0
        self.state_bytes = 0

        # prefix sharing: exact-content trie over full-block chunks -> pool
        # block id (PrefixTrie above). The trie holds its own reference on
        # every registered block (fork at registration), so cached prefixes
        # outlive the registering request; index-only blocks (ref == 1) are
        # the eviction candidates, reclaimed LRU-first under pool pressure.
        # decode_sharing additionally registers GENERATED blocks as they fill
        # at the decode frontier (multi-turn reuse) — it rides the same trie,
        # so it implies the prefix-sharing machinery.
        self.decode_sharing = bool(cfg.decode_sharing if decode_sharing is None
                                   else decode_sharing)
        self.prefix_sharing = (bool(cfg.prefix_sharing if prefix_sharing
                                    is None else prefix_sharing)
                               or self.decode_sharing)
        self.trie = PrefixTrie(self.alloc, bs)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prompt_hits = 0            # admissions matching >=1 prompt block
        self.decode_hits = 0            # admissions matching >=1 decode block
        self.prefill_tokens_total = 0
        self.prefill_tokens_skipped = 0
        self.prompt_tokens_skipped = 0  # skip split by matched-block origin
        self.decode_tokens_skipped = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        if cfg.carries_state:
            refused = [name for name, on in (
                ("prefix_sharing", self.prefix_sharing),
                ("decode_sharing", self.decode_sharing),
                ("speculative", self.speculative),
                ("preemption", self._robust and self._adm.preemption),
                ("async_loop", self.async_loop),
                ("packed=False", not self.packed),
                ('kv_quant="int8"', self.quantized),
                (f"decode_kernel={cfg.decode_kernel!r}",
                 cfg.decode_kernel != "none")) if on]
            if refused:
                raise ValueError(
                    f"{cfg.name} carries recurrent state per slot, which "
                    f"{', '.join(refused)} cannot keep: sharing, rollback "
                    "and preemption need state snapshots at block "
                    "boundaries, and the async loop, the lockstep layout, "
                    "int8 KV and the fused kernels are not built for the "
                    "state arena")

        # multi-turn sessions: submit(req, session=sid) prepends the stored
        # history (prompt + generated tokens of every prior turn) to the
        # request's prompt; _finish extends the history with this turn. With
        # decode_sharing the history's KV is still cached in the trie, so a
        # follow-up turn prefix-matches it instead of re-prefilling.
        self._sessions: dict = {}            # session id -> token history
        self._session_busy: set = set()      # sessions with an in-flight turn
        self._req_session: dict[int, object] = {}   # id(req) -> session id
        self._followups: set[int] = set()    # id(req) of follow-up turns
        self.followup_prefill_tokens = 0     # follow-up-turn skip telemetry
        self.followup_tokens_skipped = 0

        # per-slot registration watermark: trie levels already indexed for
        # this request and the INDEXED parent at that depth (which may
        # differ from the slot's own table under first-writer-wins), so
        # frontier-crossing registration only ever walks the newly filled
        # block(s) — O(1) amortized per step instead of re-walking the
        # whole sequence from the root
        self._reg_level = np.zeros(max_batch, np.int32)
        self._reg_parent = np.full(max_batch, -1, np.int64)

        # block tables + host slot table
        self._tables = np.full((max_batch, self._nblk_per_seq), -1, np.int32)
        # dirty-tracked DEVICE MIRRORS of _tables/_lengths: the step used to
        # re-upload both via jnp.asarray(...) every step even when nothing
        # changed (a decode step only crosses a block boundary every
        # block_size tokens). The mirror is invalidated (set to None) at
        # every host-side mutation — all of which go through the handful of
        # methods below (_admit/_grow_tables/_cow_shared/_release_slot/
        # _unwind_allocs and the commit-time length advances) — and rebuilt
        # lazily by _device_tables()/_device_lengths().
        self._tables_dev = None
        self._lengths_dev = None
        self._resv = np.zeros(max_batch, np.int64)   # admission reservations
        self._slots: list[Request | None] = [None] * max_batch
        # the FEED is the token sequence prefill must cover: req.prompt for
        # a first admission, prompt + out_tokens for a request resuming
        # after preemption (the KV rebuilds exactly, mostly skipped via the
        # trie). Every per-step length/position check runs against the feed,
        # never req.prompt, so resume is invisible to the step machinery.
        self._feeds: list[np.ndarray | None] = [None] * max_batch
        self._live = np.zeros(max_batch, bool)
        self._lengths = np.zeros(max_batch, np.int32)
        self._prompt_pos = np.zeros(max_batch, np.int32)  # feed tokens fed
        self._last = np.zeros(max_batch, np.int32)        # next token to feed
        self._temps = np.zeros(max_batch)
        # robustness per-slot metadata: victim policy keys + deadline clocks
        self._prio = np.zeros(max_batch, np.int64)
        self._admit_seq = np.zeros(max_batch, np.int64)
        self._qseq = np.zeros(max_batch, np.int64)   # queue seq (for requeue)
        self._submitted_ts = np.zeros(max_batch, float)
        self._cache = init_paged_cache(cfg, self.num_blocks, bs, max_batch,
                                       cache_dtype)
        self._state_step_bytes = 2 * sum(
            a.size * a.dtype.itemsize for name, a in
            self._cache["layers"].items() if name in ("ssm", "conv"))

        cfg_ = cfg

        # ONE step function, two traced shapes — (B, 1) pure decode and
        # (B, block_size) chunk steps. Only the pool cache is donated (so XLA
        # aliases it in place); the per-step steering arrays (block table,
        # write targets, kv_len) ride in a separate undonated arg
        @functools.partial(jax.jit, donate_argnums=(3,))
        def _step(w, hccs, tokens, cache, extras, t_valid):
            x, cache, _ = M.forward(w, hccs, {"tokens": tokens}, cfg_,
                                    cache=dict(cache, **extras))
            # each slot samples from its LAST VALID position (t_valid - 1):
            # chunk steps are ragged — riding decode slots have t_valid == 1,
            # mid-prompt slots discard their logits entirely
            idx = jnp.maximum(t_valid - 1, 0)
            h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            logits = M.logits_from_hidden(w, h_last, cfg_)
            return logits[:, 0], cache

        self._step_fn = _step

        # packed token step: tokens ride the sequence axis of a batch-of-one
        # forward, steered by slot_ids / per-token positions / per-token
        # kv_len. One traced shape per (chunk width, grid width) pair the
        # traffic actually hits — both ladders are O(1)-sized, so the trace
        # count is bounded (~a dozen worst case), but callers timing steps
        # must warm every shape their workload reaches (see the double
        # warm-up note in benchmarks/serving_throughput.py). lane_idx picks
        # each slot's LAST packed lane for sampling.
        @functools.partial(jax.jit, donate_argnums=(4,))
        def _packed(w, hccs, tokens, positions, cache, extras, lane_idx):
            x, cache, _ = M.forward(
                w, hccs, {"tokens": tokens, "positions": positions}, cfg_,
                cache=dict(cache, **extras))
            h_last = x[0, lane_idx][:, None]             # (B, 1, D)
            logits = M.logits_from_hidden(w, h_last, cfg_)
            return logits[:, 0], cache

        self._packed_fn = _packed

        # speculative verify step: same packed forward, but every slot reads
        # a ROW of verify lanes instead of one sampling lane — lane_grid
        # (B, 1 + draft_len) holds off[s] + i for slot s's i-th verify lane
        # (non-drafting slots repeat their last lane; the duplicate columns
        # are discarded on the host). Kept separate from _packed_fn so
        # non-speculative steps (and anything that instruments _packed_fn)
        # are byte-for-byte untouched.
        @functools.partial(jax.jit, donate_argnums=(4,))
        def _packed_spec(w, hccs, tokens, positions, cache, extras,
                         lane_grid):
            x, cache, _ = M.forward(
                w, hccs, {"tokens": tokens, "positions": positions}, cfg_,
                cache=dict(cache, **extras))
            h = x[0][lane_grid]                          # (B, 1+K, D)
            logits = M.logits_from_hidden(w, h, cfg_)
            return logits, cache

        self._packed_spec_fn = _packed_spec

        # async-loop packed step: identical forward math to _packed_fn plus
        # (a) TOKEN INDIRECTION — decode lanes whose feed token is the
        # PREVIOUS step's still-in-flight sample read it from that step's
        # on-device sampled array (tok_src[lane] = slot id, -1 = host-fed),
        # so the host never blocks on a sample just to re-upload it — and
        # (b) a device-side greedy sample (argmax over the vocab axis,
        # bit-identical to sample_tokens' greedy path, which is
        # np.asarray(jnp.argmax(logits, -1))) returned alongside the logits
        # to feed the NEXT step's indirection. Kept separate from
        # _packed_fn so sync engines are byte-for-byte untouched.
        @functools.partial(jax.jit, donate_argnums=(6,))
        def _packed_async(w, hccs, tokens, tok_src, prev_sampled, positions,
                          cache, extras, lane_idx):
            src = jnp.clip(tok_src, 0, prev_sampled.shape[0] - 1)
            fed = jnp.where(tok_src >= 0, prev_sampled[src], tokens[0])
            x, cache, _ = M.forward(
                w, hccs, {"tokens": fed[None], "positions": positions},
                cfg_, cache=dict(cache, **extras))
            h_last = x[0, lane_idx][:, None]             # (B, 1, D)
            logits = M.logits_from_hidden(w, h_last, cfg_)
            logits = logits[:, 0]
            sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, sampled, cache

        self._packed_async_fn = _packed_async
        # prev_sampled placeholder for steps with no in-flight predecessor
        # (every lane host-fed): a constant device array, uploaded once
        self._no_pending_tokens = jnp.zeros(max_batch, jnp.int32)

    # ----------------------------------------------- device mirrors --

    def _device_tables(self):
        """Device mirror of the host block tables, rebuilt only after a
        host-side mutation (see the dirty-tracking note in __init__)."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    def _device_lengths(self):
        if self._lengths_dev is None:
            self._lengths_dev = jnp.asarray(self._lengths)
        return self._lengths_dev

    # ------------------------------------------------------------- queue --

    def _blocks_for(self, plen: int, max_new: int) -> int:
        return -(-min(plen + max_new, self.max_len) // self.block_size)

    def submit(self, req: Request, session=None):
        """Queue a request. With `session`, the request is one TURN of a
        multi-turn conversation: the session's stored history (prompt +
        generated tokens of every prior turn) is prepended to req.prompt, so
        admission prefix-matches the full prior conversation — with
        decode_sharing on, that skips prefill FLOPs and duplicate KV for
        everything already decoded; with sharing off it degenerates to
        re-feeding the concatenated history (same outputs, full cost). The
        history (and the max_len bound) grows with every turn; a session
        admits one turn at a time.

        With the robustness layer, submission additionally runs the
        bounded-queue backpressure policy: "reject" raises QueueFull before
        ANY engine or session state is touched; "shed-lowest-priority"
        drops the lowest-class newest queued request — possibly this one,
        which then returns marked failed/"shed" instead of queued."""
        prompt = req.prompt
        followup = False
        if session is not None:
            if session in self._session_busy:
                raise ValueError(
                    f"session {session!r} already has an in-flight turn")
            hist = self._sessions.get(session)
            if hist is not None and len(hist):
                prompt = np.concatenate(
                    [hist, np.asarray(prompt, np.int32)])
                followup = True
        validate_prompt(prompt, self.max_len)
        need = self._blocks_for(len(prompt), req.max_new_tokens)
        if need > self.num_blocks - 1:
            raise ValueError(
                f"request needs up to {need} KV blocks but the pool has "
                f"{self.num_blocks - 1} usable")
        # all validation passed: commit the concat + session bookkeeping
        req.prompt = prompt
        if self._robust:
            rc = self.robust_counters
            rc.klass(req.priority)["submitted"] += 1
            try:
                # open-loop drivers stamp the intended arrival time on the
                # request; anchoring the deadline clock there charges a
                # mid-step arrival's wait to queueing, not to the step
                now = (req.arrival_ts if req.arrival_ts is not None
                       else self._clock())
                shed = self._queue.push(req, now=now)
            except QueueFull:
                rc.rejected += 1
                rc.klass(req.priority)["rejected"] += 1
                raise
            for victim in shed:
                rc.shed += 1
                rc.klass(victim.priority)["shed"] += 1
                self._drop_request(victim, "shed")
            if req.failed:
                return                   # shed on arrival: nothing enqueued
        if self.telemetry.enabled:
            self.telemetry.metrics.on_submit(req.uid, len(prompt),
                                             ts=req.arrival_ts)
        if session is not None:
            self._session_busy.add(session)
            self._req_session[id(req)] = session
            if followup:
                self._followups.add(id(req))
        if not self._robust:
            self._queue.append(req)

    def session_history(self, session):
        """Full token history (prompt + generated, every finished turn) of a
        session, or None for an unknown session."""
        hist = self._sessions.get(session)
        return None if hist is None else np.asarray(hist).copy()

    def end_session(self, session):
        """Forget a session's history. A session with an IN-FLIGHT turn has
        that turn cancelled first (the cancel() path: blocks freed
        refcount-aware, the turn writes NO history — it never happened), so
        ending a session is always safe and never orphans queue or slot
        state. Cached KV stays in the trie until evicted under pool
        pressure or clear_prefix_cache()."""
        if session in self._session_busy:
            for req_id, sid in list(self._req_session.items()):
                if sid == session:
                    req = next(
                        (r for r in list(self._queue) + list(self._slots)
                         if r is not None and id(r) == req_id), None)
                    if req is not None:
                        self.cancel(req.uid)
            self._session_busy.discard(session)
        self._sessions.pop(session, None)

    def cancel(self, uid) -> bool:
        """Cancel a queued or running request by uid (public API, works with
        or without the robustness layer). The request is marked failed with
        reason "cancelled", its blocks are freed refcount-aware, and its
        session turn — if any — is aborted with no history written, leaving
        the session immediately reusable. Returns False when no queued or
        running request has this uid."""
        if self._robust:
            req = self._queue.remove(uid)
        else:
            req = next((r for r in self._queue if r.uid == uid), None)
            if req is not None:
                self._queue.remove(req)
        if req is None:
            for slot in np.flatnonzero(self._live):
                if self._slots[slot].uid == uid:
                    req = self._slots[slot]
                    self._release_slot(int(slot))
                    break
        if req is None:
            return False
        self._drop_request(req, "cancelled")
        self.robust_counters.cancelled += 1
        if self._robust:
            self.robust_counters.klass(req.priority)["cancelled"] += 1
        return True

    def _admit(self):
        """FIFO admission into free slots, gated on UNRESERVED free blocks
        covering the request's worst case (deadlock-free: admitted requests
        can always grow to their budget).

        With prefix sharing, the longest run of full-block prompt chunks
        already in the index is forked into the new slot's table and prefill
        starts at the first unmatched token; the reservation shrinks by the
        matched blocks (they need no allocation) and grows by one when the
        WHOLE prompt matched — re-feeding the last prompt token will write
        inside a shared block, and the copy-on-write copy needs a block.
        Index-only cached blocks are evicted on demand when the gate would
        otherwise stall (num_free alone still covers every reservation, so
        eviction can only help, never deadlock).

        With the robustness layer, the queue head is the highest class and
        a stalled gate can PREEMPT instead of waiting: a live victim of a
        STRICTLY lower class (lowest class, most recently admitted) is
        released and re-queued with its generated tokens as resume state,
        then the gate re-evaluates with the reclaimed blocks."""
        while self._queue and not self._live.all():
            entry = None
            if self._robust:
                entry = self._queue.head_entry()
                req = entry.req
            else:
                req = self._queue[0]
            # the feed is what prefill must cover: the prompt, plus — for a
            # preempted request resuming — every token generated before
            # preemption, re-fed so the KV rebuilds exactly (the trie skips
            # whatever stayed cached). need is unchanged: the worst case
            # len(prompt) + max_new equals len(feed) + remaining budget.
            feed = (np.concatenate([np.asarray(req.prompt, np.int32),
                                    np.asarray(req.out_tokens, np.int32)])
                    if req.out_tokens else np.asarray(req.prompt, np.int32))
            matched = (self._match_prefix(feed)
                       if self.prefix_sharing else [])
            start = min(len(matched) * self.block_size, len(feed) - 1)
            need = (self._blocks_for(len(req.prompt), req.max_new_tokens)
                    - len(matched))
            if len(matched) * self.block_size > start:
                need += 1                    # full-feed hit: COW copy block
            resv_other = int(self._resv.sum())
            protect = {blk for _, blk in matched}
            while (self.alloc.num_free - resv_other < need
                   and self._evict_one(protect)):
                pass
            if self.alloc.num_free - resv_other < need:
                if self._robust and self._adm.preemption:
                    victim = choose_victim(
                        np.flatnonzero(self._live), self._prio,
                        self._admit_seq, below=int(req.priority))
                    if victim is not None:
                        self._preempt_slot(int(victim))
                        continue             # gate re-evaluates, pool grew
                break                        # wait for EOS to free blocks
            if self._robust:
                self._queue.pop_head()
            else:
                self._queue.pop(0)
            slot = int(np.argmin(self._live))
            if self.telemetry.enabled:
                self.telemetry.metrics.on_admit(req.uid)
            origins = [self.trie.origin(key) for key, _ in matched]
            for j, (key, blk) in enumerate(matched):
                self._tables[slot, j] = self.alloc.fork(blk)
                self.trie.touch(key)
            if self.prefix_sharing:
                # counted at admission (not per gate retry), so hit_rate is
                # per-request: lookups == requests admitted while sharing
                self.prefix_lookups += 1
                self.prefix_hits += bool(matched)
                self.prompt_hits += any(o == "prompt" for o in origins)
                self.decode_hits += any(o == "decode" for o in origins)
            self.prefill_tokens_total += len(feed)
            self.prefill_tokens_skipped += start
            # split the skip by matched-block origin (the last matched block
            # may contribute < block_size when the whole prompt matched and
            # the final token is re-fed)
            bs = self.block_size
            for j, o in enumerate(origins):
                skipped = max(min(bs, start - j * bs), 0)
                if o == "decode":
                    self.decode_tokens_skipped += skipped
                else:
                    self.prompt_tokens_skipped += skipped
            if id(req) in self._followups:
                self.followup_prefill_tokens += len(feed)
                self.followup_tokens_skipped += start
            if self._robust:
                rc = self.robust_counters
                rc.klass(req.priority)["admitted"] += 1
                if req.out_tokens:           # resumed after preemption
                    rc.reprefill_tokens += len(feed)
                    rc.reprefill_skipped += start
                self._prio[slot] = int(req.priority)
                self._qseq[slot] = entry.seq
                self._submitted_ts[slot] = entry.submit_ts
                self._admit_seq[slot] = self._admit_counter
                self._admit_counter += 1
            self._slots[slot] = req
            self._feeds[slot] = feed
            self._live[slot] = True
            self._lengths[slot] = start
            self._tables_dev = None          # forked blocks joined the table
            self._lengths_dev = None
            self._prompt_pos[slot] = start
            self._resv[slot] = need
            self._temps[slot] = req.temperature
            # matched blocks are already indexed: registration resumes past
            # them, threading the indexed chain tail as the parent
            self._reg_level[slot] = len(matched)
            self._reg_parent[slot] = matched[-1][1] if matched else -1
            if self._state_step_bytes:
                with self.telemetry.profiler.phase("state_reset"):
                    self._cache = dict(self._cache, layers=_zero_slot_state(
                        self._cache["layers"], np.int32(slot)))

    # ------------------------------------------------------------ prefix --

    def _match_prefix(self, prompt) -> list[tuple[tuple, int]]:
        """Longest run of full-block chunks of `prompt` cached in the trie
        (see PrefixTrie.match) — prompt AND decode-origin blocks alike, so a
        session's follow-up turn matches straight through prior replies."""
        return self.trie.match(prompt)

    def _register_blocks(self, slot: int, req: Request,
                         covered: int | None = None):
        """Index every block of this slot now FULLY covered by tokens whose
        values are known (frontier-crossing insertion). Without decode
        sharing that is the prompt-covered prefix; with it, the whole
        written sequence prompt + out_tokens (the KV at positions
        [0, length) holds exactly those tokens — the newest sampled token is
        appended to out_tokens only after this runs, and its KV is written
        next step). Boundary blocks mixing prompt and generated tokens count
        as "decode": they need decode to exist, so reusing one is a
        decode-block hit. The per-slot watermark makes this O(1) amortized:
        only blocks past the already-registered level are hashed and
        inserted, so the per-step cost is zero except on the step a block
        fills. The trie takes its own reference (fork) so the cached KV
        survives the request's EOS; on equal content the first writer wins
        (the walk threads the INDEXED block into the next level's key, so a
        chain stays rooted in index blocks even when this slot's table
        holds a COW copy or a duplicate).

        `covered` overrides the written-token count to register up to: the
        async loop commits one step BEHIND dispatch, so at commit time the
        slot's _lengths/_prompt_pos already include the NEXT in-flight
        step's frontier advance, whose token values are not landed yet —
        the commit passes the pending step's own post-step coverage
        instead. The in-flight step only writes rows at or past that
        coverage, so no registered (hence shared-refcount) block is ever a
        write target of the step racing this registration."""
        bs = self.block_size
        plen = len(req.prompt)
        if covered is None:
            covered = (int(self._lengths[slot]) if self.decode_sharing
                       else min(int(self._prompt_pos[slot]), plen))
        n_levels = covered // bs
        parent = int(self._reg_parent[slot])
        for j in range(int(self._reg_level[slot]), n_levels):
            origin = "prompt" if (j + 1) * bs <= plen else "decode"
            parent = self.trie.insert(
                parent, sequence_chunk(req.prompt, req.out_tokens, j, bs),
                int(self._tables[slot, j]), origin)
        if n_levels > self._reg_level[slot]:
            self._reg_level[slot] = n_levels
            self._reg_parent[slot] = parent

    def _evict_one(self, protect=frozenset()) -> bool:
        """Reclaim one LRU index-only leaf block (PrefixTrie.evict_one);
        returns False when nothing is evictable. Live slots' registration
        watermark PARENTS are always protected: under first-writer-wins a
        slot's cached parent may be another chain's indexed block that the
        slot holds no reference to (ref 1, evictable leaf) — evicting it
        would let the allocator recycle the id while the watermark still
        threads new children under it, silently corrupting the
        parent-id-pins-history invariant."""
        protect = set(protect) | {int(p) for p in
                                  self._reg_parent[self._live] if p >= 0}
        if self.trie.evict_one(protect) is None:
            return False
        self.prefix_evictions += 1
        return True

    def _alloc_block(self) -> int:
        """Pool alloc with eviction fallback: cached prefixes are a best-
        effort use of free space and are reclaimed before exhaustion."""
        if self.alloc.num_free == 0:
            self._evict_one()
        return self.alloc.alloc()

    def _cow_shared(self, t_valid: np.ndarray, journal: list | None = None):
        """Copy-on-write: a slot may only write into a block whose refcount
        is 1. Any shared block in this step's write range [length, length +
        t_valid) is copied to a fresh block first (device-side copy across
        all layers), the table entry is swapped, and the writer's reference
        on the original is dropped — shared KV bytes stay immutable. With
        `journal`, each copy records ("cow", slot, j, old, new, resv_dec)
        AFTER the swap, so _unwind_allocs can re-fork the source and return
        the copy on a mid-phase BlockPoolExhausted."""
        bs = self.block_size
        for slot in np.flatnonzero(t_valid > 0):
            lo = int(self._lengths[slot])
            hi = lo + int(t_valid[slot])
            for j in range(lo // bs, -(-hi // bs)):
                blk = int(self._tables[slot, j])
                if self.alloc.ref(blk) <= 1:
                    continue
                new = self._alloc_block()
                resv_dec = self._resv[slot] > 0
                self._resv[slot] = max(self._resv[slot] - 1, 0)
                self._cache = dict(
                    self._cache,
                    layers=_copy_block_kv(self._cache["layers"],
                                          jnp.int32(blk), jnp.int32(new)))
                self.alloc.free([blk])       # drop this slot's reference
                self._tables[slot, j] = new
                self._tables_dev = None
                self.cow_copies += 1
                if journal is not None:
                    journal.append(("cow", slot, j, blk, new,
                                    bool(resv_dec)))

    def clear_prefix_cache(self):
        """Drop every index reference; blocks with no live holder return to
        the free list immediately. Session histories (host-side token lists)
        survive — a later turn simply re-prefills. Live slots' registration
        watermarks reset to the root: their cached parents just left the
        trie, so the next frontier crossing re-registers the whole covered
        sequence from the slot's own table (the pre-watermark behavior)."""
        self.trie.clear()
        self._reg_level[:] = 0
        self._reg_parent[:] = -1

    def prefix_stats(self) -> dict:
        """Cumulative prefix-sharing telemetry. prefill_tokens counts all
        admitted prompt tokens regardless of the sharing setting (it is the
        skip-rate denominator); every other counter stays zero when sharing
        is disabled. The hit/skip counters are SPLIT by matched-block
        origin: prompt_hits / prompt_tokens_skipped count reuse of blocks
        cached from prompt tokens (system prompts, few-shot headers), while
        decode_hits / decode_tokens_skipped count reuse of blocks cached at
        the decode frontier (multi-turn sessions re-matching prior replies)
        — `hits` stays the per-request union. followup_* restrict the
        token counters to session follow-up turns (the multi-turn acceptance
        metric). pad_lanes_skipped is the OTHER prefill saving — token
        lanes the packed step avoided versus the lockstep layout (zero with
        packed=False) — reported here so the two are distinguishable in the
        same printout: prefix sharing skips real prefill FLOPs, packing
        skips padding FLOPs. The spec_* / *_tokens draft counters cover
        trie-driven speculative decoding (drafted = accepted + rejected per
        verify step; acceptance_rate is None until something was drafted —
        launchers and benchmarks must guard the mid-run/empty case)."""
        cached = self.trie.origin_counts()
        return dict(
            spec_steps=self.spec_steps,
            spec_rollbacks=self.spec_rollbacks,
            tokens_drafted=self.drafted_tokens,
            tokens_accepted=self.accepted_tokens,
            tokens_rejected=self.rejected_tokens,
            acceptance_rate=(self.accepted_tokens / self.drafted_tokens
                             if self.drafted_tokens else None),
            lookups=self.prefix_lookups, hits=self.prefix_hits,
            hit_rate=self.prefix_hits / max(self.prefix_lookups, 1),
            prompt_hits=self.prompt_hits, decode_hits=self.decode_hits,
            prefill_tokens=self.prefill_tokens_total,
            prefill_tokens_skipped=self.prefill_tokens_skipped,
            prompt_tokens_skipped=self.prompt_tokens_skipped,
            decode_tokens_skipped=self.decode_tokens_skipped,
            skip_rate=(self.prefill_tokens_skipped
                       / max(self.prefill_tokens_total, 1)),
            followup_prefill_tokens=self.followup_prefill_tokens,
            followup_tokens_skipped=self.followup_tokens_skipped,
            followup_skip_rate=(self.followup_tokens_skipped
                                / max(self.followup_prefill_tokens, 1)),
            cow_copies=self.cow_copies, evictions=self.prefix_evictions,
            cached_blocks=len(self.trie),
            cached_prompt_blocks=cached["prompt"],
            cached_decode_blocks=cached["decode"],
            pad_lanes_skipped=self.pad_lanes_skipped)

    def padding_stats(self) -> dict:
        """Token-lane telemetry: efficiency = valid lanes / padded lanes over
        every step so far (the packing win the benchmark records), plus the
        estimated lanes the lockstep layout would have burned extra, and the
        state arena's traffic: slot-states read and written (every slot of
        each state-carrying layer kind, per step), those of slots with a
        token in the step, and the bytes of SSM and conv state read plus
        written (all zero without recurrent state)."""
        return dict(lanes_valid=self.lanes_valid,
                    lanes_total=self.lanes_total,
                    efficiency=self.lanes_valid / max(self.lanes_total, 1),
                    pad_lanes_skipped=self.pad_lanes_skipped,
                    ctx_positions=self.ctx_positions,
                    sampled_lanes=self.sampled_lanes,
                    state_slots_moved=self.state_slots_moved,
                    state_slots_live=self.state_slots_live,
                    state_bytes=self.state_bytes)

    # ------------------------------------------------------------- slots --

    def _release_slot(self, slot: int):
        """Free a slot's block references and reset its host state — the
        shared core of finish, preemption, cancellation and deadline
        failure. Refcount-aware: blocks also referenced by the prefix index
        (or shared with other slots) keep those references and stay
        cached."""
        row = self._tables[slot]
        self.alloc.free(row[row >= 0])
        row[:] = -1
        self._tables_dev = None
        self._lengths_dev = None
        self._resv[slot] = 0
        self._slots[slot] = None
        self._feeds[slot] = None
        self._live[slot] = False
        self._lengths[slot] = 0
        self._prompt_pos[slot] = 0
        self._temps[slot] = 0.0
        self._reg_level[slot] = 0
        self._reg_parent[slot] = -1
        # async loop: the slot may have an uncommitted sample in the
        # in-flight step (and the step after it may have written a phantom
        # row into the blocks just freed — harmless: freed blocks always
        # hold stale bytes, and the position-ordered write-before-read
        # discipline plus fresh-block scale zeroing masks them). Mark it
        # dead so _commit_pending skips it: its landed token is discarded,
        # exactly as if the slot had never been scheduled.
        if self._pending is not None:
            self._pending["dead"][slot] = True

    def _finish(self, slot: int) -> Request:
        req = self._slots[slot]
        req.done = True
        if self.telemetry.enabled:
            self.telemetry.metrics.on_finish(req.uid, len(req.out_tokens))
        session = self._req_session.pop(id(req), None)
        if session is not None:
            # the session's next turn prepends this full history (and, with
            # decode sharing, prefix-matches its cached blocks)
            self._sessions[session] = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.out_tokens, np.int32)])
            self._session_busy.discard(session)
        self._followups.discard(id(req))
        if self._robust:
            self.robust_counters.klass(req.priority)["finished"] += 1
        self._release_slot(slot)
        return req

    def _preempt_slot(self, slot: int) -> Request:
        """Preemption by block reclaim (module docstring): free the slot's
        block references and re-queue the request with its generated tokens
        as resume state, keeping its ORIGINAL queue seq and SLA clock. On
        re-admission the feed (prompt + out_tokens) re-prefills — mostly
        skipped via the prefix trie when sharing is on — and sampling keys
        fold (uid, generation index), so the final output is
        token-identical to a never-preempted run."""
        req = self._slots[slot]
        seq = int(self._qseq[slot])
        ts = float(self._submitted_ts[slot])
        self._release_slot(slot)
        req.preemptions += 1
        rc = self.robust_counters
        rc.preemptions += 1
        rc.klass(req.priority)["preempted"] += 1
        self._queue.requeue(req, seq=seq, submit_ts=ts)
        return req

    def _drop_request(self, req: Request, reason: str) -> Request:
        """Terminal failure shared by the shed / deadline / cancel /
        device-error paths: the request ends without completing (done stays
        False), its session turn is aborted with NO history extension (the
        turn never happened), and the session is immediately reusable."""
        req.failed = True
        req.fail_reason = reason
        session = self._req_session.pop(id(req), None)
        if session is not None:
            self._session_busy.discard(session)
        self._followups.discard(id(req))
        if self.telemetry.enabled:
            self.telemetry.metrics.on_drop(req.uid)
        return req

    def _fail_slot(self, slot: int, reason: str) -> Request:
        req = self._slots[slot]
        self._release_slot(slot)
        return self._drop_request(req, reason)

    def _count_deadline(self, req: Request, reason: str):
        rc = self.robust_counters
        if reason == "deadline_ttft":
            rc.deadline_miss_ttft += 1
        else:
            rc.deadline_miss_e2e += 1
        rc.klass(req.priority)["deadline_misses"] += 1

    def _expire_deadlines(self, now: float) -> list[Request]:
        """Deadline enforcement at the step boundary: queued requests past
        TTFT/E2E expire in place (AdmissionQueue.expire); running ones are
        failed and their blocks freed. Misses count per class — the
        fairness signal the overload benchmark gates on."""
        failed = []
        for req, reason in self._queue.expire(now):
            self._count_deadline(req, reason)
            failed.append(self._drop_request(req, reason))
        for slot in np.flatnonzero(self._live):
            req = self._slots[slot]
            age = now - float(self._submitted_ts[slot])
            if (req.deadline_ttft is not None and not req.out_tokens
                    and age > req.deadline_ttft):
                self._count_deadline(req, "deadline_ttft")
                failed.append(self._fail_slot(int(slot), "deadline_ttft"))
            elif req.deadline_e2e is not None and age > req.deadline_e2e:
                self._count_deadline(req, "deadline_e2e")
                failed.append(self._fail_slot(int(slot), "deadline_e2e"))
        return failed

    def _grow_tables(self, t_valid: np.ndarray, journal: list | None = None):
        """Alloc-on-frontier-crossing: extend each slot's table to cover
        lengths + t_valid before the step writes there. With kv_quant, every
        block allocated here is recorded as FRESH: its pool scale may be
        stale from a freed prior owner and is reset to zero inside the next
        step, before the quantizing fold writes into it.

        Returns the allocations as [(slot, table index, block, reservation
        decremented), ...] in allocation order — speculative steps grow in
        two phases (committed coverage first, then draft lanes) and roll the
        second phase's list back in REVERSE on rejection, which restores the
        free list and the reservations exactly (_verify_and_finish).

        With `journal`, every allocation is ALSO appended there as
        ("alloc", slot, j, block, resv_dec) so a mid-phase
        BlockPoolExhausted can be unwound exactly (_unwind_allocs): the
        allocator raises BEFORE mutating, so the journal holds precisely
        the completed allocations and reverse-order frees restore the free
        list byte-identically."""
        allocs = []
        for slot in np.flatnonzero(t_valid > 0):
            needed = -(-int(self._lengths[slot] + t_valid[slot])
                       // self.block_size)
            row = self._tables[slot]
            held = int((row >= 0).sum())
            for j in range(held, needed):
                row[j] = self._alloc_block()
                self._tables_dev = None
                if self.quantized:
                    self._fresh.append(int(row[j]))
                resv_dec = self._resv[slot] > 0
                self._resv[slot] = max(self._resv[slot] - 1, 0)
                allocs.append((slot, j, int(row[j]), bool(resv_dec)))
                if journal is not None:
                    journal.append(("alloc", slot, j, int(row[j]),
                                    bool(resv_dec)))
        return allocs

    def _unwind_allocs(self, journal: list):
        """Roll back a failed alloc/COW phase in REVERSE journal order so
        allocator, tables, reservations and the fresh-block list return to
        their pre-phase state (the free list byte-identically: frees append
        in the reverse of the pops). A COW whose SOURCE block was evicted
        later in the same phase cannot re-fork it — the slot keeps its
        private copy, which is valid (the bytes were copied) though no
        longer shared."""
        if journal:
            self._tables_dev = None
        for op in reversed(journal):
            if op[0] == "alloc":
                _, slot, j, blk, resv_dec = op
                if self.quantized and self._fresh and self._fresh[-1] == blk:
                    self._fresh.pop()
                self.alloc.free([blk])
                self._tables[slot, j] = -1
                if resv_dec:
                    self._resv[slot] += 1
            else:                            # ("cow", slot, j, old, new, dec)
                _, slot, j, old, new, resv_dec = op
                if self.alloc.ref(old):
                    self.alloc.fork(old)
                    self.alloc.free([new])
                    self._tables[slot, j] = old
                    self.cow_copies -= 1
                if resv_dec:
                    self._resv[slot] += 1

    def _take_fresh(self) -> np.ndarray:
        """Drain the fresh-block list into the static-size step array (padded
        with the trash block, whose scale is safely re-zeroed every step)."""
        if len(self._fresh) > self._fresh_cap:
            raise AssertionError(
                f"fresh-block list {len(self._fresh)} exceeds static cap "
                f"{self._fresh_cap} — the per-step allocation bound is wrong")
        out = np.full(self._fresh_cap, TRASH_BLOCK, np.int32)
        out[:len(self._fresh)] = self._fresh
        self._fresh.clear()
        return out

    def _propose_drafts(self, live, remaining) -> dict[int, list[int]]:
        """Draft tokens for every DECODING slot (remaining == 0): continue
        the slot's full sequence (prompt + output) along the prefix trie
        (extend_path), topping up from the n-gram prompt-lookup fallback
        over the slot's own tokens when the trie path runs dry. Caps keep a
        verify step inside never-drafted bounds: at most draft_len lanes,
        never past the request budget's LAST token (the final token's KV is
        never written, so drafting it buys nothing), never past cache-full,
        within the packed chunk cap. Drafts AFTER a draft EOS are dropped —
        a never-drafted engine stops at the EOS, so later lanes could never
        be emitted (the EOS itself stays: accepting it finishes the request
        a step early). Returns {slot: drafts} with only non-empty
        entries."""
        drafts: dict[int, list[int]] = {}
        for slot in np.flatnonzero(np.asarray(live)
                                   & (np.asarray(remaining) == 0)):
            req = self._slots[slot]
            k = min(self.draft_len,
                    req.max_new_tokens - len(req.out_tokens) - 1,
                    self.max_len - 2 - int(self._lengths[slot]),
                    self._chunk_cap - 1)
            if k <= 0:
                continue
            seq = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.out_tokens, np.int32)])
            d = list(self.trie.extend_path(seq, k)
                     if self.prefix_sharing else [])
            while len(d) < k:
                # iterate the n-gram top-up on the hypothetical extended
                # sequence: a single call truncates at the output's loop
                # period (the most recent earlier suffix occurrence is only
                # one period back, so its follow run is period-long), and
                # short-period loops are exactly where drafting pays most
                more = ngram_propose(
                    np.concatenate([seq, np.asarray(d, np.int32)]),
                    k - len(d))
                if not more:
                    break
                d += more
            d = [int(x) for x in d[:k]]
            if self.eos_id is not None and self.eos_id in d:
                d = d[:d.index(self.eos_id) + 1]
            if d:
                drafts[slot] = d
        return drafts

    def _write_positions(self, t_valid: np.ndarray, width: int) -> np.ndarray:
        """Flat pool scatter targets (B, width): token i of slot b lands at
        table[b, (len+i)//bs]*bs + (len+i)%bs while i < t_valid[b]; invalid
        lanes are steered into the trash block (position i of block 0).

        The per-slot length is also the partial-prefill start offset under
        prefix sharing: a slot admitted with `start` matched tokens begins
        with _lengths[slot] == start, so both the write targets here and the
        RoPE positions in attention.py (cache["length"] + arange(t)) resume
        exactly past the shared frontier. _cow_shared ran before this, so no
        target block has refcount > 1."""
        bs = self.block_size
        wp = np.tile(np.arange(width, dtype=np.int64)[None, :],
                     (self.max_batch, 1)) + TRASH_BLOCK * bs
        for slot in np.flatnonzero(t_valid > 0):
            tv = int(t_valid[slot])
            wp[slot, :tv] = _slot_write_targets(
                self._tables[slot], int(self._lengths[slot]), tv, bs)
        return wp.astype(np.int32)

    def _step(self, width: int) -> list[Request]:
        """One lockstep batched step: chunk (width == block_size, some slot
        is mid-prompt) or pure decode (width == 1). Returns newly finished."""
        prof = self.telemetry.profiler
        live = self._live.copy()
        self.occupancy_sum += float(live.mean())
        self.occupancy_steps += 1
        with prof.phase("schedule"):
            t_valid = np.zeros(self.max_batch, np.int32)
            toks = np.zeros((self.max_batch, width), np.int32)
            for slot in np.flatnonzero(live):
                feed = self._feeds[slot]
                pos = int(self._prompt_pos[slot])
                if pos < len(feed):          # chunked prefill
                    tv = min(width, len(feed) - pos)
                    toks[slot, :tv] = feed[pos:pos + tv]
                    t_valid[slot] = tv
                else:                        # decode rides along, t_valid 1
                    toks[slot, 0] = self._last[slot]
                    t_valid[slot] = 1
            self.lanes_valid += int(t_valid.sum())
            self.lanes_total += self.max_batch * width
            tv = t_valid.astype(np.int64)
            self.ctx_positions += int((tv * self._lengths
                                       + tv * (tv + 1) // 2).sum())
        with prof.phase("alloc_cow"):
            journal: list[tuple] = []
            try:
                self._grow_tables(t_valid, journal)
                if self.prefix_sharing:
                    self._cow_shared(t_valid, journal)
            except BlockPoolExhausted:
                self._unwind_allocs(journal)
                raise
        with prof.phase("schedule"):
            # dirty-tracked device mirrors: _tables only changes when a
            # frontier crosses a block boundary (every block_size tokens),
            # so most decode steps re-use the uploaded copy instead of
            # transferring the whole (B, nblk) table again
            # the mirrors must ride in `extras` (undonated): the cache
            # argument is donated, so a mirror passed inside it would have
            # its buffer invalidated after the step. extras merge AFTER the
            # cache inside the jitted fn, so "length" here overrides the
            # stale length the previous step's returned cache carries.
            cache = self._cache
            extras = {"length": self._device_lengths(),
                      "block_table": self._device_tables(),
                      "write_pos": jnp.asarray(
                          self._write_positions(t_valid, width)),
                      "kv_len": jnp.asarray(self._lengths + t_valid)}
            if self.quantized:
                extras["fresh_blocks"] = jnp.asarray(self._take_fresh())
        with prof.phase("device"):
            logits, self._cache = self._call_device(
                self._step_fn, self.w, self.hccs, jnp.asarray(toks), cache,
                extras, jnp.asarray(t_valid))
            if prof.enabled:
                # fence async dispatch so device time lands in THIS phase
                # instead of smearing into the host phases that follow
                jax.block_until_ready(logits)
        return self._sample_and_finish(live, t_valid, logits)

    def _step_packed(self) -> list[Request]:
        """One PACKED engine step — dispatches to the synchronous tail
        (default) or the pipelined async loop (cfg.async_loop; see the
        module docstring's pipeline diagram)."""
        if self.async_loop:
            return self._step_packed_async()
        return self._step_packed_sync()

    def _step_packed_sync(self) -> list[Request]:
        """One PACKED token step: the step's work — a chunk of any length per
        prefilling slot plus one token per decoding slot — flattened into a
        ragged (1, width) token batch with per-token slot ids, positions and
        causal frontiers. width is the smallest rung of the chunk-width
        ladder covering the step's pending work (capped at token_budget);
        pure decode lands on the max_batch rung. Returns newly finished."""
        prof = self.telemetry.profiler
        live = self._live.copy()
        self.occupancy_sum += float(live.mean())
        self.occupancy_steps += 1
        with prof.phase("schedule"):
            remaining = np.zeros(self.max_batch, np.int64)
            for slot in np.flatnonzero(live):
                remaining[slot] = (len(self._feeds[slot])
                                   - int(self._prompt_pos[slot]))
            drafts = (self._propose_drafts(live, remaining)
                      if self.speculative else {})
            n_drafts = np.zeros(self.max_batch, np.int64)
            for slot, d in drafts.items():
                n_drafts[slot] = len(d)
            needed = int(np.where(
                live, np.minimum(np.maximum(remaining, 1) + n_drafts,
                                 self._chunk_cap),
                0).sum())
            needed = min(needed, self.token_budget)
            width = next(w for w in self._widths if w >= needed)
            if drafts:
                # draft-worthwhileness gate: verify lanes are only worth a
                # WIDER traced shape when they could fill at least half the
                # extra lanes the step-up pads in — a step where one slot
                # drafts a few tokens otherwise pays rung-width compute for
                # the whole batch. Drafts riding inside the plain width
                # (width == plain rung) are always kept: their lanes are
                # free. Dropping a step's drafts is just not-drafting —
                # outputs are unchanged (greedy parity holds either way).
                plain = min(int(np.where(
                    live, np.minimum(np.maximum(remaining, 1),
                                     self._chunk_cap), 0).sum()),
                    self.token_budget)
                w_plain = next(w for w in self._widths if w >= plain)
                if 2 * int(n_drafts.sum()) < width - w_plain:
                    drafts = {}
                    n_drafts[:] = 0
                    width = w_plain
            t_valid = schedule_step_tokens(
                live, remaining, width, self._chunk_cap,
                drafts=n_drafts if drafts else None)
            if drafts:
                # the scheduler may truncate drafts to fit the budget
                for slot in list(drafts):
                    d = drafts[slot][:max(int(t_valid[slot]) - 1, 0)]
                    n_drafts[slot] = len(d)
                    if d:
                        drafts[slot] = d
                    else:
                        del drafts[slot]
            sid, off = pack_slot_ids(t_valid, width)
            toks = np.zeros(width, np.int32)
            positions = np.zeros(width, np.int32)
            for slot in np.flatnonzero(t_valid > 0):
                tv = int(t_valid[slot])
                o = int(off[slot])
                if remaining[slot] > 0:      # prefill chunk (budget-sized)
                    pos = int(self._prompt_pos[slot])
                    toks[o:o + tv] = self._feeds[slot][pos:pos + tv]
                else:                        # decode: one lane (+ drafts)
                    toks[o] = self._last[slot]
                    if tv > 1:
                        toks[o + 1:o + tv] = drafts[slot]
                positions[o:o + tv] = (int(self._lengths[slot])
                                       + np.arange(tv))
            self.lanes_valid += int(t_valid.sum())
            self.lanes_total += width
            if self._state_step_bytes:
                self.state_slots_moved += self.max_batch
                self.state_slots_live += int((t_valid > 0).sum())
                self.state_bytes += self._state_step_bytes
            # lanes the lockstep layout would burn for the SAME scheduled
            # work: it caps each slot at block_size tokens per chunk step, so
            # this step's largest per-slot chunk takes ceil(max tv / bs)
            # lockstep steps of max_batch * block_size lanes each. Those
            # extra lockstep steps would ALSO advance every decode rider by
            # one token each — progress this packed step has not made — so
            # credit the riders one future packed decode lane per extra step
            # (decode-only steps themselves save nothing).
            if (remaining > 0).any():
                n_lockstep = -(-int(t_valid.max()) // self.block_size)
                riders = int((live & (remaining == 0)).sum())
                lockstep = n_lockstep * self.max_batch * self.block_size
                self.pad_lanes_skipped += max(
                    lockstep - width - (n_lockstep - 1) * riders, 0)
        with prof.phase("alloc_cow"):
            journal: list[tuple] = []
            try:
                if drafts:
                    # two-phase committed-first growth: the blocks a never-
                    # drafted step would allocate are popped from the free
                    # list FIRST, draft-only blocks after — so rejection's
                    # reverse-order frees restore the free list exactly. COW
                    # runs on the committed coverage only: the single held
                    # block in a decode slot's write range is the one
                    # containing position `length`, which a never-drafted
                    # step COWs identically; draft-reached blocks are
                    # freshly allocated, never shared.
                    t_commit = np.where(
                        remaining > 0, t_valid,
                        np.minimum(t_valid, 1)).astype(np.int32)
                    self._grow_tables(t_commit, journal)
                    if self.prefix_sharing:
                        self._cow_shared(t_commit, journal)
                    draft_allocs = self._grow_tables(t_valid, journal)
                else:
                    draft_allocs = []
                    self._grow_tables(t_valid, journal)
                    if self.prefix_sharing:
                        self._cow_shared(t_valid, journal)
            except BlockPoolExhausted:
                self._unwind_allocs(journal)
                raise
        with prof.phase("schedule"):
            wp = packed_write_positions(t_valid, off, self._tables,
                                        self._lengths, self.block_size, width)
            kv_len = np.where(sid >= 0, positions + 1, 0).astype(np.int32)
            self.ctx_positions += int(kv_len.sum())
            lane_idx = np.maximum(off + t_valid - 1, 0).astype(np.int32)
            # dirty-tracked device mirrors (see _step): skip the per-step
            # _tables/_lengths re-upload when the host copies are unchanged
            # mirrors ride in `extras` (undonated; see _step) — the donated
            # cache arg would invalidate them after the step
            cache = self._cache
            extras = {"length": self._device_lengths(),
                      "block_table": self._device_tables(),
                      "write_pos": jnp.asarray(wp[None]),
                      "kv_len": jnp.asarray(kv_len),
                      "slot_ids": jnp.asarray(sid)}
            fresh_np = None
            if self.quantized:
                fresh_np = self._take_fresh()
                extras["fresh_blocks"] = jnp.asarray(fresh_np)
            snap_blocks = snap = staged = None
            if drafts and self.quantized:
                # pre-step snapshot of every block the drafting slots'
                # verify rows can touch: draft lanes fold with a CLAMPED
                # scale (draft_rows -> paged_quant_scatter), so committed
                # lanes read bit-exact history, and after verification the
                # snapshot is restored and exactly the committed rows are
                # re-folded grow-wise (_restore_and_replay). stage_rows
                # makes each layer emit its raw KV rows for that replay.
                bs = self.block_size
                blks = []
                for slot in sorted(drafts):
                    lo = int(self._lengths[slot])
                    hi = lo + int(t_valid[slot])
                    blks.extend(int(self._tables[slot, j])
                                for j in range(lo // bs, -(-hi // bs)))
                snap_blocks = np.full(self._snap_cap, TRASH_BLOCK, np.int32)
                snap_blocks[:len(blks)] = blks
                extras["stage_rows"] = jnp.zeros((), jnp.int32)
                draft_rows = np.zeros(width, bool)
                for slot in drafts:
                    draft_rows[off[slot] + 1:off[slot]
                               + int(t_valid[slot])] = True
                extras["draft_rows"] = jnp.asarray(draft_rows[None])
            if self._use_grid:
                # XLA attention-grid steering: cell (slot, i) of the (B, Wb)
                # grid is the slot's i-th token this step; grid_pos maps
                # packed lanes to flat cells (pad lanes -> the spill row
                # B*Wb)
                max_tv = max(int(t_valid.max()), 1)
                wb = next(w for w in self._grid_widths if w >= max_tv)
                q_pos_grid = (self._lengths[:, None]
                              + np.arange(wb, dtype=np.int32)[None, :])
                grid_pos = np.full(width, self.max_batch * wb, np.int32)
                valid_lane = sid >= 0
                grid_pos[valid_lane] = (sid[valid_lane] * wb
                                        + (np.flatnonzero(valid_lane)
                                           - off[sid[valid_lane]]))
                extras.update(
                    q_pos_grid=jnp.asarray(q_pos_grid.astype(np.int32)),
                    grid_pos=jnp.asarray(grid_pos),
                    kv_len_slot=jnp.asarray((self._lengths
                                             + t_valid).astype(np.int32)))
        with prof.phase("device"):
            if snap_blocks is not None:
                snap = _gather_block_state(self._cache["layers"],
                                           jnp.asarray(snap_blocks))
            if drafts:
                # verify lanes: row i of a drafting slot is its i-th packed
                # lane (clamped to its last); non-drafting slots repeat
                # their sampling lane across the row
                lane_grid = np.tile(lane_idx[:, None],
                                    (1, self.draft_len + 1))
                for slot in drafts:
                    lane_grid[slot] = off[slot] + np.minimum(
                        np.arange(self.draft_len + 1),
                        int(t_valid[slot]) - 1)
                logits, self._cache = self._call_device(
                    self._packed_spec_fn, self.w, self.hccs,
                    jnp.asarray(toks[None]), jnp.asarray(positions[None]),
                    cache, extras, jnp.asarray(lane_grid.astype(np.int32)))
                if self.quantized:
                    layers = dict(self._cache["layers"])
                    staged = (layers.pop("staged_k"),
                              layers.pop("staged_v"))
                    self._cache = dict(self._cache, layers=layers)
            else:
                logits, self._cache = self._call_device(
                    self._packed_fn, self.w, self.hccs,
                    jnp.asarray(toks[None]), jnp.asarray(positions[None]),
                    cache, extras, jnp.asarray(lane_idx))
            if prof.enabled:
                # fence async dispatch so device time lands in THIS phase
                # instead of smearing into the host phases that follow
                jax.block_until_ready(logits)
        if drafts:
            return self._verify_and_finish(live, t_valid, drafts, off, wp,
                                           logits, draft_allocs,
                                           snap_blocks, snap, staged,
                                           fresh_np)
        return self._sample_and_finish(live, t_valid, logits)

    # ------------------------------------------- pipelined async loop --

    def _step_packed_async(self) -> list[Request]:
        """One engine step of the pipelined loop: dispatch step N+1's packed
        batch, THEN commit step N's (already in-flight) results — so the
        host bookkeeping of step N overlaps step N+1's device execution
        (the donated pool serializes the device side; JAX async dispatch
        makes the second enqueue return immediately).

        Overlap requires that step N+1's schedule not depend on step N's
        landed token VALUES — true exactly when every live slot samples
        greedily (the device argmax in _packed_async_fn is bit-identical to
        sample_tokens' greedy path, and decode lanes read it via on-device
        indirection) and nothing drafts (speculative accept/reject decides
        the next frontier on the host). Otherwise the step degrades to
        commit-then-sync-step — correct, just unpipelined.

        Token-value-independent schedule aside, step N's commit can still
        CHANGE step N+1's live set: a slot at its token budget (or decode
        cache-full bound) finishes at commit. Both are predictable without
        the token value, so those slots are excluded from the dispatch;
        EOS is not predictable — an EOS slot gets one extra in-flight step
        whose writes die with the slot's release (_release_slot dead-marks
        the pending record; freed-block phantom rows are masked by the
        position-ordered write-before-read discipline + int8 fresh-block
        scale zeroing)."""
        live = self._live
        hot = bool((live & (self._temps > 0.0)).any())
        if self.speculative or hot:
            finished = self._commit_pending()
            if self._live.any():
                self.async_sync_fallbacks += 1
                finished.extend(self._step_packed_sync())
            return finished
        p = self._pending
        sched_live = live.copy()
        if p is not None:
            # exclude slots whose pending sample finishes them at commit:
            # scheduling them would grow frontiers past their end
            for slot in np.flatnonzero(p["samples"] & ~p["dead"] & live):
                req = self._slots[slot]
                if req is None:
                    continue
                if (len(req.out_tokens) + 1 >= req.max_new_tokens
                        or (not p["was_prefill"][slot]
                            and p["lengths_after"][slot]
                            >= self.max_len - 1)):
                    sched_live[slot] = False
        if not sched_live.any():
            return self._commit_pending()
        new_pending = self._dispatch_packed_async(sched_live)
        old, self._pending = self._pending, new_pending
        if old is None:
            return []
        self.async_overlapped_steps += 1
        # commits that _finish/_fail a slot dead-mark new_pending via
        # _release_slot — the in-flight step's writes for it become inert
        return self._commit_pending_record(old)

    def _dispatch_packed_async(self, live) -> dict:
        """Schedule + allocate + enqueue one packed step WITHOUT waiting for
        its results: the greedy-sampling clone of _step_packed_sync's front
        half (no drafts by construction — the caller falls back when
        speculation is on). Advances the host frontiers (_lengths /
        _prompt_pos) at dispatch so the NEXT dispatch schedules against the
        post-step state, and returns the pending record _commit_pending
        lands one step later. Raises BlockPoolExhausted (journal unwound,
        state exactly pre-dispatch) like the sync path."""
        prof = self.telemetry.profiler
        self.occupancy_sum += float(live.mean())
        self.occupancy_steps += 1
        p = self._pending
        with prof.phase("schedule"):
            remaining = np.zeros(self.max_batch, np.int64)
            for slot in np.flatnonzero(live):
                remaining[slot] = (len(self._feeds[slot])
                                   - int(self._prompt_pos[slot]))
            needed = int(np.where(
                live, np.minimum(np.maximum(remaining, 1), self._chunk_cap),
                0).sum())
            needed = min(needed, self.token_budget)
            width = next(w for w in self._widths if w >= needed)
            t_valid = schedule_step_tokens(live, remaining, width,
                                           self._chunk_cap)
            sid, off = pack_slot_ids(t_valid, width)
            toks = np.zeros(width, np.int32)
            # decode-lane token indirection: tok_src[lane] = slot id whose
            # token must be read from the in-flight step's device sample
            # (still unlanded on the host); -1 = host-fed from toks
            tok_src = np.full(width, -1, np.int32)
            positions = np.zeros(width, np.int32)
            for slot in np.flatnonzero(t_valid > 0):
                tv = int(t_valid[slot])
                o = int(off[slot])
                if remaining[slot] > 0:      # prefill chunk (host tokens)
                    pos = int(self._prompt_pos[slot])
                    toks[o:o + tv] = self._feeds[slot][pos:pos + tv]
                elif (p is not None and p["samples"][slot]
                        and not p["dead"][slot]):
                    tok_src[o] = slot        # feed step N's device sample
                else:
                    toks[o] = self._last[slot]
                positions[o:o + tv] = (int(self._lengths[slot])
                                       + np.arange(tv))
            self.lanes_valid += int(t_valid.sum())
            self.lanes_total += width
            if (remaining > 0).any():        # see _step_packed_sync
                n_lockstep = -(-int(t_valid.max()) // self.block_size)
                riders = int((live & (remaining == 0)).sum())
                lockstep = n_lockstep * self.max_batch * self.block_size
                self.pad_lanes_skipped += max(
                    lockstep - width - (n_lockstep - 1) * riders, 0)
        with prof.phase("alloc_cow"):
            journal: list[tuple] = []
            try:
                self._grow_tables(t_valid, journal)
                if self.prefix_sharing:
                    self._cow_shared(t_valid, journal)
            except BlockPoolExhausted:
                self._unwind_allocs(journal)
                raise
        with prof.phase("schedule"):
            wp = packed_write_positions(t_valid, off, self._tables,
                                        self._lengths, self.block_size,
                                        width)
            kv_len = np.where(sid >= 0, positions + 1, 0).astype(np.int32)
            self.ctx_positions += int(kv_len.sum())
            lane_idx = np.maximum(off + t_valid - 1, 0).astype(np.int32)
            # mirrors ride in `extras` (undonated; see _step)
            cache = self._cache
            extras = {"length": self._device_lengths(),
                      "block_table": self._device_tables(),
                      "write_pos": jnp.asarray(wp[None]),
                      "kv_len": jnp.asarray(kv_len),
                      "slot_ids": jnp.asarray(sid)}
            if self.quantized:
                extras["fresh_blocks"] = jnp.asarray(self._take_fresh())
            if self._use_grid:
                max_tv = max(int(t_valid.max()), 1)
                wb = next(w for w in self._grid_widths if w >= max_tv)
                q_pos_grid = (self._lengths[:, None]
                              + np.arange(wb, dtype=np.int32)[None, :])
                grid_pos = np.full(width, self.max_batch * wb, np.int32)
                valid_lane = sid >= 0
                grid_pos[valid_lane] = (sid[valid_lane] * wb
                                        + (np.flatnonzero(valid_lane)
                                           - off[sid[valid_lane]]))
                extras.update(
                    q_pos_grid=jnp.asarray(q_pos_grid.astype(np.int32)),
                    grid_pos=jnp.asarray(grid_pos),
                    kv_len_slot=jnp.asarray((self._lengths
                                             + t_valid).astype(np.int32)))
            prev = (p["sampled"] if p is not None
                    else self._no_pending_tokens)
        with prof.phase("device"):
            # NO fence here — landing results is _commit_pending's job, one
            # step later; this enqueue returns as soon as XLA accepts it
            logits, sampled, self._cache = self._call_device(
                self._packed_async_fn, self.w, self.hccs,
                jnp.asarray(toks[None]), jnp.asarray(tok_src), prev,
                jnp.asarray(positions[None]), cache, extras,
                jnp.asarray(lane_idx))
        with prof.phase("sample"):
            # frontier advance AT DISPATCH (the commit reads the record's
            # snapshots, never the advanced arrays)
            feed_len = np.asarray([len(f) if f is not None else 1 << 30
                                   for f in self._feeds])
            samples = live & (self._prompt_pos + t_valid >= feed_len)
            self.sampled_lanes += int(samples.sum())
            was_prefill = live & (self._prompt_pos < feed_len)
            self._lengths_dev = None
            for slot in np.flatnonzero(live):
                tv = int(t_valid[slot])
                self._lengths[slot] += tv
                self._prompt_pos[slot] = min(self._prompt_pos[slot] + tv,
                                             feed_len[slot])
        return {
            "live": live.copy(),
            "samples": samples,
            "was_prefill": was_prefill,
            "lengths_after": self._lengths.copy(),
            "prompt_pos_after": self._prompt_pos.copy(),
            "logits": logits,                # device handle, not landed
            "sampled": sampled,              # device handle, not landed
            "dead": np.zeros(self.max_batch, bool),
        }

    def _commit_pending(self) -> list[Request]:
        """Land and commit the in-flight step, if any (the drain entry
        point: sync fallbacks, empty schedules, and step()'s no-live-work
        branch). Pops the record FIRST so releases triggered inside the
        commit don't dead-mark the record being committed."""
        p, self._pending = self._pending, None
        if p is None:
            return []
        return self._commit_pending_record(p)

    def _commit_pending_record(self, p) -> list[Request]:
        """The host back half of a pipelined step, one step late: fence the
        step's device outputs, then run _sample_and_finish's commit loop
        against the record's SNAPSHOTS (the live arrays already hold the
        next step's frontier advance). Slots released since dispatch
        (preempt / cancel / deadline / EOS at the previous commit) are
        dead-marked in the record and skipped — their landed token is
        discarded exactly as if never scheduled. Prefix registration passes
        the record's own coverage so no block the still-in-flight next step
        writes is ever registered (= refcounted as shared)."""
        prof = self.telemetry.profiler
        with prof.phase("device"):
            if prof.enabled:
                # the profiler's device fence moves HERE from the dispatch
                # (the whole point of the pipeline): device time attributed
                # to the step whose results are being landed
                jax.block_until_ready(p["logits"])
        finished: list[Request] = []
        with prof.phase("sample"):
            live = p["live"] & ~p["dead"]
            samples = p["samples"] & ~p["dead"]
            # np.asarray blocks until the step's outputs land (the
            # unprofiled path's only sync point)
            sampled = np.asarray(p["sampled"])
            if self._robust and self._adm.nan_check:
                bad = ~np.isfinite(np.asarray(p["logits"])).all(axis=-1)
                for slot in np.flatnonzero(samples & bad):
                    finished.append(self._fail_slot(int(slot),
                                                    "nan_logits"))
                    live[slot] = False
                    samples[slot] = False
            for slot in np.flatnonzero(live):
                req = self._slots[slot]
                if req is None:
                    continue
                if self.prefix_sharing and (p["was_prefill"][slot]
                                            or self.decode_sharing):
                    covered = (int(p["lengths_after"][slot])
                               if self.decode_sharing
                               else min(int(p["prompt_pos_after"][slot]),
                                        len(req.prompt)))
                    with prof.phase("register"):
                        self._register_blocks(slot, req, covered=covered)
                if not samples[slot]:
                    continue                     # still mid-prompt at dispatch
                tok = int(sampled[slot])
                req.out_tokens.append(tok)
                if self.telemetry.enabled and len(req.out_tokens) == 1:
                    self.telemetry.metrics.on_first_token(req.uid)
                self._last[slot] = tok
                if (len(req.out_tokens) >= req.max_new_tokens or
                        (self.eos_id is not None and tok == self.eos_id) or
                        (not p["was_prefill"][slot] and
                         p["lengths_after"][slot] >= self.max_len - 1)):
                    finished.append(self._finish(slot))
        return finished

    def _call_device(self, fn, *args):
        """Dispatch one jitted step function. In robust mode transient
        failures are retried up to max_device_retries times — safe because
        the chaos harness's fault wrappers raise BEFORE dispatching to the
        real function, so the donated pool buffer is intact and the call
        repeats bit-identically. Past the retry budget the error propagates
        to step(), which fails every live slot with reason
        "device_error"."""
        if not self._robust:
            return fn(*args)
        retries = self._adm.max_device_retries
        for attempt in range(retries + 1):
            try:
                return fn(*args)
            except Exception:
                self.robust_counters.device_retries += 1
                if attempt == retries:
                    raise

    def _sample_and_finish(self, live, t_valid, logits) -> list[Request]:
        """Shared step tail (lockstep and packed layouts): sample each slot
        that produced a next token, advance frontiers, register prefixes,
        finish slots at budget/EOS/cache-full."""
        prof = self.telemetry.profiler
        # a slot samples this step iff it produced a next token: decoding, or
        # its feed completed within this chunk
        with prof.phase("sample"):
            feed_len = np.asarray([len(f) if f is not None else 1 << 30
                                   for f in self._feeds])
            samples = live & (self._prompt_pos + t_valid >= feed_len)
            finished: list[Request] = []
            if self._robust and self._adm.nan_check:
                # logits are a pure step OUTPUT (the KV write is unaffected),
                # so only rows about to be sampled matter — a non-finite one
                # fails its request with a reason instead of emitting garbage
                bad = ~np.isfinite(np.asarray(logits)).all(axis=-1)
                for slot in np.flatnonzero(samples & bad):
                    finished.append(self._fail_slot(int(slot), "nan_logits"))
                    live[slot] = False
                    samples[slot] = False
            # non-sampling slots go greedy (temp 0): their uid/index rows
            # are placeholders that never reach the categorical path
            nxt = sample_tokens(
                self._key, logits, np.where(samples, self._temps, 0.0),
                [r.uid if r else 0 for r in self._slots],
                [len(r.out_tokens) if r else 0 for r in self._slots])
            self.sampled_lanes += int(samples.sum())
            self._lengths_dev = None         # frontiers advance below
            for slot in np.flatnonzero(live):
                req = self._slots[slot]
                tv = int(t_valid[slot])
                was_prefill = self._prompt_pos[slot] < feed_len[slot]
                self._lengths[slot] += tv
                self._prompt_pos[slot] = min(self._prompt_pos[slot] + tv,
                                             feed_len[slot])
                if self.prefix_sharing and (was_prefill
                                            or self.decode_sharing):
                    # registration precedes any possible _finish below, so
                    # a prompt that completes (or a block that fills at the
                    # decode frontier) on a terminating step still leaves
                    # its full-block KV cached; with decode sharing this
                    # runs every step, so generated blocks enter the trie
                    # the step they fill
                    with prof.phase("register"):
                        self._register_blocks(slot, req)
                if not samples[slot]:
                    continue                     # still mid-prompt
                tok = int(nxt[slot])
                req.out_tokens.append(tok)
                if self.telemetry.enabled and len(req.out_tokens) == 1:
                    self.telemetry.metrics.on_first_token(req.uid)
                self._last[slot] = tok
                # the cache-full guard only applies to decode-written KV —
                # the prefill-completion sample mirrors the continuous
                # engine's admission sample, which is not length-guarded
                if (len(req.out_tokens) >= req.max_new_tokens or
                        (self.eos_id is not None and tok == self.eos_id) or
                        (not was_prefill and
                         self._lengths[slot] >= self.max_len - 1)):
                    finished.append(self._finish(slot))
        return finished

    def _verify_and_finish(self, live, t_valid, drafts, off, wp, logits,
                           draft_allocs, snap_blocks, snap, staged,
                           fresh_np) -> list[Request]:
        """Speculative step tail: sample EVERY verify lane with the owning
        request's per-(uid, position) key — bit-identical to the tokens a
        never-drafted engine samples one step at a time — accept the
        longest draft prefix that matches, emit the accepted run plus the
        model's own token at the first mismatched lane, then roll the
        rejected lanes back so the step leaves no trace of them.

        Rollback, cheapest layer first:
          * host bookkeeping — draft-only block allocations freed in
            REVERSE allocation order (restores the free list exactly),
            table entries back to -1, decremented reservations returned;
          * fp pools — nothing: rejected rows sit beyond the new frontier,
            masked by kv_len and plainly overwritten before any read;
          * int8 pools — snapshot restore + committed-row replay
            (_restore_and_replay) after EVERY verify step, accepted or
            not: the in-step draft folds used a clamped scale (scratch),
            so the committed rows are re-folded grow-wise onto the
            restored pre-step blocks — exactly the never-drafted fold."""
        prof = self.telemetry.profiler
        bs = self.block_size
        width = wp.shape[0]
        kk1 = logits.shape[1]
        with prof.phase("sample"):
            feed_len = np.asarray([len(f) if f is not None else 1 << 30
                                   for f in self._feeds])
            samples = live & (self._prompt_pos + t_valid >= feed_len)
            # one flat sampling batch over (slot, verify lane): lane i of a
            # drafting slot is generation index len(out_tokens) + i, so
            # every token folds exactly the key the never-drafted engine
            # would; all other rows go greedy (temp 0) and are discarded
            n_ver = np.ones(self.max_batch, np.int64)
            for slot, d in drafts.items():
                n_ver[slot] = 1 + len(d)
            col = np.arange(kk1)[None, :]
            do = samples[:, None] & (col < n_ver[:, None])
            uids = np.asarray([r.uid if r else 0 for r in self._slots])
            gen0 = np.asarray([len(r.out_tokens) if r else 0
                               for r in self._slots])
            toks = sample_tokens(
                self._key,
                jnp.reshape(jnp.asarray(logits), (-1, logits.shape[-1])),
                np.where(do, self._temps[:, None], 0.0).reshape(-1),
                np.repeat(uids, kk1),
                (gen0[:, None] + col).reshape(-1),
            ).reshape(self.max_batch, kk1)
            self.sampled_lanes += int(do.sum())
            finished_slots: list[int] = []
            replay = np.zeros(width, bool)       # committed verify lanes
            keep_blocks: dict[int, int] = {}  # slot -> committed blocks
            any_reject = False
            self._lengths_dev = None             # frontiers advance below
            for slot in np.flatnonzero(live):
                req = self._slots[slot]
                tv = int(t_valid[slot])
                was_prefill = self._prompt_pos[slot] < feed_len[slot]
                if slot not in drafts:
                    # identical to the never-drafted tail (_sample_and_finish),
                    # except finishes are deferred until after rollback so EOS
                    # frees append to a free list rollback already restored
                    self._lengths[slot] += tv
                    self._prompt_pos[slot] = min(self._prompt_pos[slot] + tv,
                                                 feed_len[slot])
                    if self.prefix_sharing and (was_prefill
                                                or self.decode_sharing):
                        with prof.phase("register"):
                            self._register_blocks(slot, req)
                    if not samples[slot]:
                        continue                 # still mid-prompt
                    tok = int(toks[slot, 0])
                    req.out_tokens.append(tok)
                    if self.telemetry.enabled and len(req.out_tokens) == 1:
                        self.telemetry.metrics.on_first_token(req.uid)
                    self._last[slot] = tok
                    if (len(req.out_tokens) >= req.max_new_tokens or
                            (self.eos_id is not None and tok == self.eos_id) or
                            (not was_prefill and
                             self._lengths[slot] >= self.max_len - 1)):
                        finished_slots.append(slot)
                    continue
                # drafting decode slot: longest matching prefix wins
                d = drafts[slot]
                k = len(d)
                t_row = [int(toks[slot, i]) for i in range(1 + k)]
                j = 0
                while j < k and d[j] == t_row[j]:
                    j += 1
                # emit t_row[0..j] under never-drafted finish semantics: stop
                # at the first token that would have ended the request (budget,
                # EOS, cache-full) — later accepted tokens must not leak out
                L0 = int(self._lengths[slot])
                emitted: list[int] = []
                fin = False
                for i in range(j + 1):
                    tok = t_row[i]
                    emitted.append(tok)
                    if (len(req.out_tokens) + len(emitted)
                            >= req.max_new_tokens or
                            (self.eos_id is not None and
                             tok == self.eos_id) or
                            L0 + 1 + i >= self.max_len - 1):
                        fin = True
                        break
                m = len(emitted)
                self.spec_steps += 1
                self.drafted_tokens += k
                self.accepted_tokens += m - 1
                self.rejected_tokens += k - (m - 1)
                if m < tv:
                    any_reject = True
                # exactly the rows a never-drafted engine would have written:
                # lanes 0..m-1 (the final emitted token's own KV lands on its
                # NEXT step, or never — same as one-token-per-step decode)
                replay[off[slot]:off[slot] + m] = True
                keep_blocks[slot] = -(-(L0 + m) // bs)
                self._lengths[slot] += m
                req.out_tokens.extend(emitted)
                if self.decode_sharing:
                    with prof.phase("register"):
                        self._register_blocks(slot, req)
                self._last[slot] = emitted[-1]
                if fin:
                    finished_slots.append(slot)
        with prof.phase("rollback"):
            for slot, jdx, blk, resv_dec in reversed(draft_allocs):
                if jdx < keep_blocks[slot]:
                    continue                 # covered by committed rows
                self.alloc.free([blk])
                self._tables[slot, jdx] = -1
                self._tables_dev = None
                if resv_dec:
                    self._resv[slot] += 1
            if any_reject:
                self.spec_rollbacks += 1
            if self.quantized and snap_blocks is not None:
                # the in-step draft folds were scratch (clamped scale);
                # EVERY verify step restores the snapshot and re-folds
                # exactly the committed rows grow-wise, so the pool is
                # what a never-drafted run would hold even when all
                # drafts were accepted. Snapshot blocks freshly
                # allocated this step AND staying live get zeroed
                # scales (the replay fold must see what a real step
                # sees); freed draft blocks keep their restored stale
                # payload+scale — the state a never-drafted run leaves
                # on a never-allocated block
                held = set()
                for slot in drafts:
                    row = self._tables[slot]
                    held.update(int(b) for b in row[row >= 0])
                fresh_live = ((set(int(b) for b in fresh_np) & held)
                              - {TRASH_BLOCK})
                fresh_mask = np.asarray(
                    [int(b) in fresh_live for b in snap_blocks], bool)
                replay_pos = np.where(
                    replay, wp.astype(np.int64),
                    TRASH_BLOCK * bs
                    + np.arange(width, dtype=np.int64) % bs)
                self._cache = dict(
                    self._cache,
                    layers=_restore_and_replay(
                        self._cache["layers"], snap,
                        jnp.asarray(snap_blocks),
                        jnp.asarray(fresh_mask), staged[0], staged[1],
                        jnp.asarray(
                            replay_pos.astype(np.int32)[None])))
        return [self._finish(slot) for slot in finished_slots]

    # --------------------------------------------------------------- run --

    @property
    def busy(self) -> bool:
        """True while the engine has queued or in-flight requests (the
        open-loop driver's loop condition — see telemetry.drive_open_loop).
        An uncommitted pipelined step counts as in-flight work: its tokens
        have not landed in Request.out_tokens yet, so the drain loop must
        keep stepping until the commit catches up."""
        return (bool(self._queue) or bool(self._live.any())
                or self._pending is not None)

    def step(self) -> list[Request]:
        """Admit from the queue and run ONE engine step; returns newly
        finished requests (including, in robust mode, requests ending in
        failure: deadline expiry, NaN logits, device errors — check
        Request.failed / fail_reason). The step-at-a-time API
        arrival-driven serving loops build on (run() is just step() until
        drained); a no-op when the engine is idle.

        With graceful_exhaustion, BlockPoolExhausted never escapes: the
        failing phase unwound its partial allocations (journal), so state
        is exactly pre-step; a victim is preempted (lowest class, most
        recently admitted — possibly the very slot that needed to grow,
        which resumes output-identically once blocks return) and the next
        step retries with the reclaimed blocks."""
        prof = self.telemetry.profiler
        with prof.step():
            finished: list[Request] = []
            with prof.phase("admit"):
                if self._robust:
                    finished.extend(
                        self._expire_deadlines(self._clock()))
                self._admit()
            if self.telemetry.enabled:
                self.telemetry.metrics.sample_queue_depth()
            if not self._live.any():
                if self._pending is not None:
                    # pipeline drain: every live slot finished at the last
                    # commit (or was released), but one dispatched step is
                    # still in flight — land it so its tokens/telemetry
                    # are not lost
                    finished.extend(self._commit_pending())
                    return finished
                # a robust queue may legitimately stall head-of-line (gate
                # blocked with no preemptible lower class); without the
                # layer a stalled queue beside a free pool is a scheduling
                # bug
                assert self._robust or not self._queue, \
                    "admission stalled with free pool"
                return finished
            try:
                if self.packed:
                    finished.extend(self._step_packed())
                else:
                    prefilling = any(
                        self._live[s]
                        and self._prompt_pos[s] < len(self._feeds[s])
                        for s in range(self.max_batch)
                        if self._slots[s] is not None)
                    finished.extend(
                        self._step(self.block_size if prefilling else 1))
            except BlockPoolExhausted:
                if not (self._robust and self._adm.graceful_exhaustion):
                    raise
                self.robust_counters.exhaustion_events += 1
                victim = choose_victim(np.flatnonzero(self._live),
                                       self._prio, self._admit_seq)
                if victim is not None:
                    self._preempt_slot(int(victim))
            except AssertionError:
                raise                        # invariant violations stay loud
            except Exception:
                if not self._robust:
                    raise
                # device failure past max_device_retries: fail every live
                # slot with a reason instead of wedging the engine — blocks
                # freed, queue intact, the engine keeps serving
                for slot in np.flatnonzero(self._live):
                    finished.append(
                        self._fail_slot(int(slot), "device_error"))
            return finished

    def run(self) -> list[Request]:
        """Serve the whole queue; returns finished requests (uid order
        follows completion, not submission)."""
        finished: list[Request] = []
        while self.busy:
            finished.extend(self.step())
        return finished

    def snapshot(self) -> dict:
        """The unified schema-versioned telemetry snapshot (lifecycle
        latency + step phases when telemetry is enabled, merged with the
        engine's cumulative prefix/padding/cache-byte/occupancy counters).
        See telemetry.make_snapshot for the schema contract."""
        return make_snapshot(
            "paged", self.telemetry,
            kv_cache=kv_cache_byte_stats(self._cache, self.cfg, None),
            occupancy=(self.occupancy_sum / self.occupancy_steps
                       if self.occupancy_steps else None),
            prefix=self.prefix_stats(),
            padding=self.padding_stats(),
            robustness=(self.robust_counters.snapshot()
                        if self._robust else None))
