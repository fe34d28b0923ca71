"""Batched serving engine: wave-scheduled prefill + decode.

Requests are grouped into waves by prompt length (static shapes — the
TPU-friendly batching discipline: no dynamic padding, no recompilation).
Each wave batch-prefills together, then decodes lockstep one token/step until
every member finishes; finished slots simply stop sampling (their tokens are
discarded) so shapes never change mid-wave.

HCCS inference runs the same integer-STE path used during QAT, so served
logits match the trained model bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.serve.telemetry import as_telemetry, make_snapshot


def warn_decode_kernel_fallback(cfg):
    """Warn ONCE, at engine construction, when cfg.decode_kernel cannot take
    effect — with the blocking reason (i8 mode, windowed attention, ...) in
    the message. Every engine calls this so programmatic users (benchmarks,
    notebooks) get the same no-effect warning the serve launcher used to
    print, without it repeating on every dispatch."""
    if cfg.decode_kernel == "none":
        return
    from repro.models.attention import decode_kernel_blockers
    blockers = decode_kernel_blockers(cfg)
    if blockers:
        with warnings.catch_warnings():
            # defeat the default once-per-location dedup filter: every engine
            # construction with a blocked config must warn, or the second
            # engine in a process gets silently misattributed timings
            warnings.simplefilter("always", RuntimeWarning)
            warnings.warn(
                f"decode_kernel={cfg.decode_kernel!r} has no effect "
                f"({', '.join(blockers)}); decode runs the XLA STE path",
                RuntimeWarning, stacklevel=3)


def kv_cache_bytes(cache) -> int:
    """Persistently-allocated KV bytes of an engine cache (the slot arena or
    the paged block pool): k/v payload leaves plus per-block scale arrays
    (kv_quant="int8" pools), excluding SSM state."""
    total = 0
    for name in ("k", "v", "hot_k", "hot_v", "k_scale", "v_scale"):
        leaf = cache["layers"].get(name)
        if leaf is not None:
            total += leaf.size * leaf.dtype.itemsize
    return total


def kv_cache_byte_stats(cache, cfg, max_len: int | None = None) -> dict:
    """Padded (as-allocated) vs LOGICAL KV bytes of an engine cache.

    When the fused decode kernel is active, the arena is allocated
    lane-padded (head_dim -> 128 lanes, slot arenas additionally round seq
    to the kernel block — attention.kv_store_geometry), so raw kv_cache_bytes
    reports up to 4x the bytes the model semantically uses for the SAME
    logical cache. `logical` counts only the true head_dim lanes and (for
    slot arenas, when max_len is given) the first max_len rows; `padded` is
    the real allocation. Benchmarks report both so kernel and non-kernel
    rows stay comparable.

    The payload math is dtype-driven (leaf.dtype.itemsize), so int8 paged
    pools (cfg.kv_quant="int8") report 1-byte rows under the SAME
    lane-padding rules as fp pools; their per-block scale arrays (k_scale/
    v_scale, (L, N, Hkv) f32 — metadata with no lane padding) are counted in
    full on both sides, so the occupancy telemetry reflects the true
    quantized footprint rather than pretending scales are free."""
    padded = kv_cache_bytes(cache)
    logical = 0
    for name in ("k", "v", "hot_k", "hot_v"):
        leaf = cache["layers"].get(name)
        if leaf is None:
            continue
        if leaf.ndim == 4:       # token-major paged pool (L, N, bs, Hkv*hd):
            logical += leaf.size * leaf.dtype.itemsize    # never padded
            continue
        rows_c, hd_c = leaf.shape[-2], leaf.shape[-1]
        rows = rows_c
        if name in ("k", "v") and max_len is not None:
            rows = min(rows_c, max_len)      # paged pools pass None: their
            # rows axis is block_size, which kv_store_geometry never pads
        logical += (leaf.size // (rows_c * hd_c) * rows
                    * min(hd_c, cfg.head_dim) * leaf.dtype.itemsize)
    for name in ("k_scale", "v_scale"):      # quantization metadata: logical
        leaf = cache["layers"].get(name)     # == padded (never lane-padded)
        if leaf is not None:
            logical += leaf.size * leaf.dtype.itemsize
    return dict(cache_bytes_logical=logical, cache_bytes_padded=padded)


@dataclasses.dataclass
class Request:
    """One serving request. The robustness fields (serve/admission.py) are
    strictly opt-in: with the defaults every engine treats the request
    exactly as before they existed. `done` means completed normally;
    `failed` is the OTHER terminal state (shed / deadline miss / cancel /
    device error, reason in `fail_reason`) — blocks are freed and sessions
    stay reusable either way. A preempted request is neither: it re-queues
    with `out_tokens` as resume state and `preemptions` bumped, and its
    final output is token-identical to an uncontended run (sampling keys
    fold (uid, generation index), not batch position)."""
    uid: int
    prompt: np.ndarray            # (t,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    priority: int = 0             # SLA class: higher admits (and evicts) first
    deadline_ttft: float | None = None   # seconds, submit -> first token
    deadline_e2e: float | None = None    # seconds, submit -> finish
    failed: bool = False
    fail_reason: str | None = None
    preemptions: int = 0
    # intended arrival time (absolute seconds on the serving clock), stamped
    # by open-loop drivers BEFORE submit. Engines anchor the telemetry
    # submit timestamp and the admission queue's deadline clock here, so an
    # arrival that came due during a long device step is measured from when
    # it arrived, not from the post-step submit. None = "arrived at submit".
    arrival_ts: float | None = None


def validate_prompt(prompt, max_len: int):
    """Shared admission bound: the prompt must fit the cache with room for at
    least one generated token. Both engines enforce the same limit so a
    request is never accepted by one scheduler and rejected by the other."""
    if len(prompt) < 1 or len(prompt) > max_len - 1:
        raise ValueError(
            f"prompt length {len(prompt)} not in [1, {max_len - 1}]")


def sample_tokens(key, logits, temps: np.ndarray, uids, gen_idx):
    """Per-row sampling: greedy where temps == 0, categorical otherwise.
    Returns tokens (B,) np.int64.

    Each sampled row derives its own key by folding the request uid and the
    token's generation index into the engine's base key, so a request's
    sampled output is a pure function of (request, position) — independent
    of batch composition, scheduler, and step layout. (The old scheme split
    one key per STEP shared across the batch, coupling every sampled request
    to its co-batched neighbors; speculative verification additionally needs
    several positions of ONE request sampled in one step.) Greedy rows never
    enter the categorical path, so they neither consume randomness nor see
    the inf-scaled logits a near-zero temperature divisor would produce."""
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    temps = np.asarray(temps, np.float64)
    hot = np.flatnonzero(temps > 0)
    if hot.size == 0:
        return greedy
    uids = np.asarray(uids)
    gen_idx = np.asarray(gen_idx)
    # np.uint32 wraps negative uids (e.g. warmup requests) into fold_in range
    keys = jnp.stack([jax.random.fold_in(
        jax.random.fold_in(key, np.uint32(int(uids[i]))),
        np.uint32(int(gen_idx[i]))) for i in hot])
    sampled = np.asarray(jax.vmap(jax.random.categorical)(
        keys, jnp.asarray(logits)[hot] / jnp.asarray(temps[hot, None],
                                                     logits.dtype)))
    out = greedy.copy()
    out[hot] = sampled
    return out


class ServeEngine:
    def __init__(self, params, cfg, *, max_batch: int = 8,
                 max_len: int = 512, eos_id: int | None = None,
                 cache_dtype=None, telemetry=None):
        if cfg.kv_quant != "none":
            raise ValueError(
                f"kv_quant={cfg.kv_quant!r} quantizes the paged block pool; "
                "the wave engine's slot arena is fp-only (use PagedEngine)")
        if cache_dtype is None:
            cache_dtype = jnp.dtype(cfg.cache_dtype)
        self.w = params["weights"]
        self.hccs = params["hccs"]
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self._queue: list[Request] = []
        self._key = jax.random.PRNGKey(0)
        # request-lifecycle tracing + step-phase profiling (telemetry.py);
        # disabled by default — every hook below is a no-op flag check then
        self.telemetry = as_telemetry(telemetry)
        warn_decode_kernel_fallback(cfg)
        cfg_ = cfg

        @jax.jit
        def _decode(w, hccs, tokens, cache):
            return M.decode_step(w, hccs, tokens, cache, cfg_)

        self._decode = _decode

    def submit(self, req: Request):
        validate_prompt(req.prompt, self.max_len)
        if self.telemetry.enabled:
            self.telemetry.metrics.on_submit(req.uid, len(req.prompt),
                                             ts=req.arrival_ts)
        self._queue.append(req)

    def _sample(self, logits, temps: np.ndarray, wave):
        return sample_tokens(self._key, logits, temps,
                             [r.uid for r in wave],
                             [len(r.out_tokens) for r in wave])

    def _next_wave(self) -> list[Request]:
        if not self._queue:
            return []
        by_len: dict[int, list[Request]] = defaultdict(list)
        for r in self._queue:
            by_len[len(r.prompt)].append(r)
        # largest group first; cap at max_batch
        length = max(by_len, key=lambda k: len(by_len[k]))
        wave = by_len[length][: self.max_batch]
        for r in wave:
            self._queue.remove(r)
        return wave

    def _run_wave(self, wave: list[Request]):
        tel = self.telemetry
        prof = tel.profiler
        b = len(wave)
        temps = np.asarray([r.temperature for r in wave])
        with prof.step("prefill"):
            if tel.enabled:
                # wave admission IS wave start: members leave the queue here
                for r in wave:
                    tel.metrics.on_admit(r.uid)
                tel.metrics.sample_queue_depth()
            with prof.phase("device"):
                toks = jnp.asarray(np.stack([r.prompt for r in wave]),
                                   jnp.int32)
                logits, cache = M.prefill(self.w, self.hccs,
                                          {"tokens": toks}, self.cfg,
                                          max_len=self.max_len,
                                          cache_dtype=self.cache_dtype)
                if prof.enabled:
                    jax.block_until_ready(logits)
            with prof.phase("sample"):
                nxt = self._sample(logits, temps, wave)
        live = np.ones(b, bool)
        # the prefill-sampled token counts against the budget and may be EOS,
        # exactly as in the continuous engine's admission — scheduling must
        # never change what is generated
        for i, r in enumerate(wave):
            tok = int(nxt[i])
            r.out_tokens.append(tok)
            if tel.enabled:
                tel.metrics.on_first_token(r.uid)
            if (len(r.out_tokens) >= r.max_new_tokens or
                    (self.eos_id is not None and tok == self.eos_id)):
                r.done = True
                live[i] = False
                if tel.enabled:
                    tel.metrics.on_finish(r.uid, len(r.out_tokens))
        max_steps = max(r.max_new_tokens for r in wave) - 1
        for _ in range(max(max_steps, 0)):
            if not live.any():
                break
            with prof.step("decode"):
                with prof.phase("device"):
                    last = jnp.asarray(nxt[:, None].astype(np.int32))
                    logits, cache = self._decode(self.w, self.hccs, last,
                                                 cache)
                    if prof.enabled:
                        # fence async dispatch so device time lands in THIS
                        # phase instead of smearing into the host phases
                        jax.block_until_ready(logits)
                with prof.phase("sample"):
                    # finished rows sample greedily (free): keeps the
                    # categorical branch from running for discarded outputs,
                    # same as the continuous engine's dead-slot handling
                    nxt = self._sample(logits, np.where(live, temps, 0.0),
                                       wave)
            for i, r in enumerate(wave):
                if not live[i]:
                    continue
                tok = int(nxt[i])
                r.out_tokens.append(tok)
                if (len(r.out_tokens) >= r.max_new_tokens or
                        (self.eos_id is not None and tok == self.eos_id)):
                    r.done = True
                    live[i] = False
                    if tel.enabled:
                        tel.metrics.on_finish(r.uid, len(r.out_tokens))
            if not live.any() or int(cache["length"]) >= self.max_len - 1:
                break
        for r in wave:
            r.done = True
            if tel.enabled:
                # budget/cache-full exits that never hit an in-loop finish
                tel.metrics.on_finish(r.uid, len(r.out_tokens))

    def run(self) -> list[Request]:
        """Serve the whole queue; returns finished requests."""
        finished: list[Request] = []
        while self._queue:
            wave = self._next_wave()
            if not wave:
                break
            self._run_wave(wave)
            finished.extend(wave)
        return finished

    def snapshot(self) -> dict:
        """The unified schema-versioned telemetry snapshot. The wave engine
        allocates a fresh slot cache per wave rather than holding one, so
        kv_cache reports that per-wave reservation; prefix/padding counters
        don't exist here and are None. See telemetry.make_snapshot."""
        cache = M.init_cache(self.cfg, self.max_batch, self.max_len,
                             self.cache_dtype)
        return make_snapshot(
            "wave", self.telemetry,
            kv_cache=kv_cache_byte_stats(cache, self.cfg, self.max_len))
