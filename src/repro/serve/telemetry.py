"""Serving telemetry: request-lifecycle tracing, latency percentiles, and a
step-phase profiler shared by all three engines.

Three layers, composable and individually cheap:

* **RequestTrace / MetricsRegistry** — one trace per request recording the
  lifecycle timestamps ``submit -> admit -> first_token -> finish``. The
  registry derives the serving SLO metrics from finished traces:

      TTFT  = first_token - submit        (time to first token; includes
                                           queue wait, so open-loop arrival
                                           benchmarks measure it honestly)
      TPOT  = (finish - first_token)      (time per output token AFTER the
              / (n_tokens - 1)             first; single-token requests are
                                           excluded)
      E2E   = finish - submit
      queue_wait = admit - submit         (admission-wait histogram)

  reported as p50/p95/p99/mean over finished requests (percentile math is
  numpy-equivalent linear interpolation, pinned by tests/test_telemetry.py).

* **StepProfiler** — wraps each engine step and the named PHASES of its
  body in profiler spans: the step in a
  ``jax.profiler.StepTraceAnnotation("serve.step", step_num=n)``, each
  phase in a ``jax.profiler.TraceAnnotation("serve.<phase>")``. The spans
  are emitted whether or not telemetry is enabled; JAX records them only
  while a profiler session is open (``jax.profiler.trace``,
  ``launch/serve.py --trace-out DIR``), so they land in the same trace as
  every device op, on its clock. The phase taxonomy (paged engine; the
  other engines use the applicable subset):

      admit      queue -> slot admission: prefix match, reservation gate,
                 table fork (continuous: includes the admission prefill)
      schedule   host-side step scheduling + token packing (t_valid, slot
                 ids, steering arrays) and their upload
      alloc_cow  block-pool bookkeeping: alloc-on-frontier-crossing growth
                 plus copy-on-write copies of shared blocks
      device     the ENQUEUE of the jitted model step: JAX dispatches
                 asynchronously, so this span ends when XLA accepts the
                 program, not when the device finishes it
      sample     the step tail: the wait for the step's result, the
                 argmax/categorical sampling, the host transfer and the
                 finish loop (frontier advance, EOS/budget checks). In the
                 async loop, the dispatch-time frontier advance and the
                 one-step-late commit
      register   prefix-trie registration of newly filled blocks (nested
                 inside sample)
      rollback   speculative decoding: freeing rejected draft blocks and
                 the int8 snapshot restore

  With telemetry enabled the profiler also attributes wall time on the
  serving clock: ``summary()`` gives per-phase self time, share-of-step and
  ``coverage`` = attributed/step wall time (the acceptance gate keeps this
  >= 0.9). That accounting fences the ``device`` phase with
  ``jax.block_until_ready`` so device time is not smeared into later host
  phases; with telemetry disabled nothing is fenced, timed or allocated
  beyond the span objects, and async dispatch keeps its overlap.

* **Telemetry** — the per-engine facade bundling one registry + one
  profiler behind a single ``enabled`` flag. Engines hold a Telemetry
  instance unconditionally; when disabled the lifecycle hooks return
  immediately and ``phase()``/``step()`` return the bare profiler span, so
  telemetry-off serving pays one attribute test per hook plus the spans —
  greedy outputs are asserted token-identical with
  telemetry on vs off for all three engines.

``make_snapshot`` merges the lifecycle/phase metrics with the engines'
existing counters (``prefix_stats``/``padding_stats``/
``kv_cache_byte_stats``/occupancy) into ONE schema-versioned dict — the
thing ``launch/serve.py`` prints and ``benchmarks/serving_throughput.py``
writes — so every consumer reads the same shape regardless of engine.

``drive_open_loop`` is the arrival-driven serving loop used by the Poisson
latency benchmark and ``launch/serve.py --arrival-rate``: requests are
submitted at pre-drawn arrival offsets (open loop — arrivals do not wait
for the system, so queueing shows up in TTFT instead of being hidden by
batch-drain submission).
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

# v2 added the `robustness` section (admission/preemption/deadline counters
# from serve/admission.py's RobustnessCounters; None for engines without the
# opt-in layer) and RequestTrace.dropped / MetricsRegistry.on_drop for
# requests ending in failure (shed, cancelled, deadline-expired); v3 added
# the `padding` section's ctx_positions / sampled_lanes work counters; v4
# its state_slots_moved / state_slots_live / state_bytes state-arena
# counters (zero without recurrent state)
SNAPSHOT_SCHEMA_VERSION = 4

# admission-wait histogram bucket edges (milliseconds, log-spaced); the last
# bucket is open-ended
QUEUE_WAIT_EDGES_MS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                       500.0, 1000.0, 2000.0, 5000.0)

# THE serving clock. Every time-reading component of the serving stack —
# deadline/admission decisions (serve/admission.py), lifecycle latencies
# (MetricsRegistry), the step profiler, and the open-loop driver — defaults
# to this one callable, so a request can never miss its SLA on one clock
# while telemetry reports it in-SLO on another. Inject a replacement by
# passing `clock=` to Telemetry (the engines resolve deadlines off the same
# instance unless AdmissionConfig.clock is explicitly overridden).
SERVING_CLOCK = time.perf_counter


def percentile(values, q: float):
    """q-th percentile (0..100) with linear interpolation — the same
    definition as numpy's default method, reimplemented so the registry has
    no numpy-version coupling; pinned against np.percentile in tests."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def _dist(values) -> dict:
    """p50/p95/p99/mean/count summary of a latency sample (seconds)."""
    vals = [v for v in values if v is not None]
    return dict(
        count=len(vals),
        mean=float(np.mean(vals)) if vals else None,
        p50=percentile(vals, 50), p95=percentile(vals, 95),
        p99=percentile(vals, 99))


@dataclasses.dataclass
class RequestTrace:
    """Lifecycle timestamps of one request (seconds on the registry clock).

    Invariants (asserted in tests/test_telemetry.py):
    submit_ts <= admit_ts <= first_token_ts <= finish_ts for a finished
    trace, and every derived latency is non-negative."""
    uid: int
    prompt_len: int
    submit_ts: float
    admit_ts: float | None = None
    first_token_ts: float | None = None
    finish_ts: float | None = None
    n_tokens: int = 0
    dropped: bool = False         # ended in failure (shed/cancel/deadline)

    @property
    def queue_wait(self):
        if self.admit_ts is None:
            return None
        return self.admit_ts - self.submit_ts

    @property
    def ttft(self):
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.submit_ts

    @property
    def tpot(self):
        """Per-token decode latency after the first token; None for
        single-token requests (no decode interval to measure)."""
        if self.finish_ts is None or self.first_token_ts is None \
                or self.n_tokens < 2:
            return None
        return (self.finish_ts - self.first_token_ts) / (self.n_tokens - 1)

    @property
    def e2e(self):
        if self.finish_ts is None:
            return None
        return self.finish_ts - self.submit_ts


class MetricsRegistry:
    """Collects RequestTraces and derives the latency summary.

    Keyed by request uid; re-submitting a uid starts a fresh trace (the old
    one stays in the finished list if it completed). The engine hooks are
    called with the engine's own notion of the lifecycle:
    on_submit at queue entry, on_admit at slot assignment, on_first_token
    when out_tokens goes 0 -> 1, on_finish when the request completes."""

    def __init__(self, clock=None):
        self.clock = clock if clock is not None else SERVING_CLOCK
        self.traces: dict[int, RequestTrace] = {}
        self.finished: list[RequestTrace] = []
        self.queue_depth = 0          # currently submitted, not yet admitted
        self.queue_depth_peak = 0
        self._depth_sum = 0           # sampled per step for the mean
        self._depth_samples = 0

    def on_submit(self, uid: int, prompt_len: int, ts=None):
        """`ts` is an optional explicit submit timestamp (seconds on the
        registry clock). Open-loop drivers pass the request's INTENDED
        arrival time here: an arrival that came due while a multi-ms device
        step was in flight is only submitted after the step returns, and
        without the override its queue wait / TTFT would silently absorb
        that step-granularity jitter instead of charging it to queueing."""
        self.traces[uid] = RequestTrace(
            uid, int(prompt_len), self.clock() if ts is None else float(ts))
        self.queue_depth += 1
        self.queue_depth_peak = max(self.queue_depth_peak, self.queue_depth)

    def on_admit(self, uid: int):
        t = self.traces.get(uid)
        if t is not None and t.admit_ts is None:
            t.admit_ts = self.clock()
            self.queue_depth -= 1

    def on_first_token(self, uid: int):
        t = self.traces.get(uid)
        if t is not None and t.first_token_ts is None:
            t.first_token_ts = self.clock()

    def on_finish(self, uid: int, n_tokens: int):
        t = self.traces.get(uid)
        if t is None or t.finish_ts is not None:
            return
        t.finish_ts = self.clock()
        t.n_tokens = int(n_tokens)
        self.finished.append(t)

    def on_drop(self, uid: int):
        """A request ended in failure (shed / cancelled / deadline-expired /
        device error): mark its trace dropped and rebalance queue_depth if
        it was never admitted. Dropped traces never join the finished list,
        so the latency percentiles summarize completed work only."""
        t = self.traces.get(uid)
        if t is None or t.dropped or t.finish_ts is not None:
            return
        t.dropped = True
        if t.admit_ts is None:
            self.queue_depth -= 1

    def sample_queue_depth(self):
        """Per-step queue-depth sample (drives the mean in the summary)."""
        self._depth_sum += self.queue_depth
        self._depth_samples += 1

    def latency_summary(self) -> dict:
        """TTFT/TPOT/E2E p50/p95/p99 + queue telemetry over finished
        requests. The schema (key set) is pinned by
        tests/test_telemetry.py::test_snapshot_schema_stability."""
        done = self.finished
        waits = [t.queue_wait for t in done if t.queue_wait is not None]
        edges = QUEUE_WAIT_EDGES_MS
        counts = [0] * (len(edges) + 1)
        for w in waits:
            ms = w * 1e3
            counts[np.searchsorted(edges, ms, side="right")] += 1
        return dict(
            requests=len(done),
            ttft=_dist(t.ttft for t in done),
            tpot=_dist(t.tpot for t in done),
            e2e=_dist(t.e2e for t in done),
            queue_wait=_dist(waits),
            queue_wait_hist=dict(edges_ms=list(edges), counts=counts),
            queue_depth_peak=self.queue_depth_peak,
            queue_depth_mean=(self._depth_sum / self._depth_samples
                              if self._depth_samples else None))


class _Span:
    """One open step or phase: enters its profiler annotation and, when the
    profiler is enabled, times itself on the serving clock. Allocated per
    __enter__ site so re-entrant and nested phases are safe."""

    def __init__(self, prof, name: str, annotation, is_step: bool):
        self.prof = prof
        self.name = name
        self.annotation = annotation
        self.is_step = is_step

    def __enter__(self):
        prof = self.prof
        self.annotation.__enter__()
        if self.is_step:
            prof._step_depth += 1
        else:
            self.child = 0.0
            prof._open.append(self)
        self.t0 = prof.clock()
        return self

    def __exit__(self, *exc):
        prof = self.prof
        dur = prof.clock() - self.t0
        if self.is_step:
            prof._step_depth -= 1
            prof.step_total += dur
            prof.step_count += 1
        else:
            prof._open.pop()
            # phases hold their SELF time: a nested phase (register inside
            # sample) is charged to itself, not twice
            prof.phase_seconds[self.name] = (
                prof.phase_seconds.get(self.name, 0.0) + dur - self.child)
            prof.phase_counts[self.name] = (
                prof.phase_counts.get(self.name, 0) + 1)
            if prof._open:
                prof._open[-1].child += dur
            elif prof._step_depth > 0:
                prof.in_step_seconds += dur
        self.annotation.__exit__(*exc)
        return False


class StepProfiler:
    """Engine steps and their named phases, as profiler spans and (when
    enabled) as host-clock wall-time attribution.

    ``step(name)`` wraps one engine step in a
    ``jax.profiler.StepTraceAnnotation("serve.<name>", step_num=n)``;
    ``phase(name)`` wraps a region of its body in a
    ``jax.profiler.TraceAnnotation("serve.<name>")``. The annotations are
    entered whether or not the profiler is enabled: JAX records them only
    while a profiler session is open, so outside one they cost an object and
    a flag test, and inside one they share the device trace's clock.

    A phase may be opened several times per step (it accumulates) and may
    nest inside another (``register`` inside ``sample``); ``phase_seconds``
    holds each phase's self time. ``coverage`` is the fraction of step wall
    time attributed to phases — the observability gate keeps it >= 0.9, so a
    new chunk of per-step host work can't silently hide outside the
    breakdown. The host-clock accounting runs only when enabled."""

    def __init__(self, enabled: bool = True, clock=None):
        self.enabled = enabled
        self.clock = clock if clock is not None else SERVING_CLOCK
        self._names: dict[str, str] = {}
        self.step_num = 0             # numbers the step annotations
        self.reset()

    def reset(self):
        self.phase_seconds: dict[str, float] = {}
        self.phase_counts: dict[str, int] = {}
        self.in_step_seconds = 0.0
        self.step_total = 0.0
        self.step_count = 0
        self._step_depth = 0
        self._open: list[_Span] = []

    def _name(self, name: str) -> str:
        full = self._names.get(name)
        if full is None:
            full = self._names[name] = "serve." + name
        return full

    def step(self, name: str = "step"):
        ann = StepTraceAnnotation(self._name(name), step_num=self.step_num)
        self.step_num += 1
        if not self.enabled:
            return ann
        return _Span(self, name, ann, is_step=True)

    def phase(self, name: str):
        ann = TraceAnnotation(self._name(name))
        if not self.enabled:
            return ann
        return _Span(self, name, ann, is_step=False)

    @property
    def coverage(self):
        """Fraction of step wall time attributed to in-step phases."""
        if not self.step_total:
            return None
        return self.in_step_seconds / self.step_total

    def summary(self) -> dict:
        return dict(
            steps=self.step_count,
            step_seconds=self.step_total,
            coverage=self.coverage,
            phases={name: dict(count=self.phase_counts[name],
                               seconds=secs,
                               share_of_step=(secs / self.step_total
                                              if self.step_total else None))
                    for name, secs in sorted(self.phase_seconds.items())})


class Telemetry:
    """Per-engine facade: one MetricsRegistry + one StepProfiler behind a
    single `enabled` flag. Engines construct a disabled instance by default,
    so every hook site stays a plain attribute check when telemetry is off
    (no Optional plumbing, no behavioral branches)."""

    def __init__(self, enabled: bool = True, clock=None):
        self.enabled = enabled
        self.clock = clock if clock is not None else SERVING_CLOCK
        self.metrics = MetricsRegistry(self.clock)
        self.profiler = StepProfiler(enabled, self.clock)

    def reset(self):
        """Drop accumulated traces and profile data (e.g. after a warm-up
        segment, so a timed segment reports only its own requests)."""
        self.metrics = MetricsRegistry(self.clock)
        self.profiler.reset()


def as_telemetry(telemetry) -> Telemetry:
    """Normalize an engine's `telemetry=` constructor argument: a Telemetry
    instance passes through, truthy builds an enabled one, falsy/None builds
    the disabled default."""
    if isinstance(telemetry, Telemetry):
        return telemetry
    return Telemetry(enabled=bool(telemetry))


def make_snapshot(engine: str, telemetry: Telemetry, *, kv_cache=None,
                  occupancy=None, prefix=None, padding=None,
                  robustness=None) -> dict:
    """The unified, schema-versioned telemetry snapshot every engine's
    ``snapshot()`` returns, ``launch/serve.py`` prints, and the serving
    benchmark writes. Counter sections an engine doesn't have (and the
    latency/phase sections when telemetry is disabled) are None rather than
    absent, so the key set is STABLE across engines and settings — pinned
    by tests/test_telemetry.py::test_snapshot_schema_stability.
    `robustness` (schema v2) is RobustnessCounters.snapshot() for engines
    running the opt-in admission layer, None otherwise."""
    enabled = telemetry.enabled
    return dict(
        schema_version=SNAPSHOT_SCHEMA_VERSION,
        engine=engine,
        latency=telemetry.metrics.latency_summary() if enabled else None,
        phases=telemetry.profiler.summary() if enabled else None,
        kv_cache=kv_cache,
        occupancy=occupancy,
        prefix=prefix,
        padding=padding,
        robustness=robustness)


def format_snapshot(snap: dict) -> str:
    """Human-readable rendering of a snapshot's latency + phase sections
    (the counter sections have their own printouts in launch/serve.py)."""
    lines = [f"telemetry snapshot (schema v{snap['schema_version']}, "
             f"engine={snap['engine']})"]
    lat = snap.get("latency")
    if lat:
        for name in ("ttft", "tpot", "e2e", "queue_wait"):
            d = lat[name]
            if not d["count"]:
                continue
            lines.append(
                "  %-10s p50 %7.2f ms  p95 %7.2f ms  p99 %7.2f ms  "
                "(n=%d)" % (name, d["p50"] * 1e3, d["p95"] * 1e3,
                            d["p99"] * 1e3, d["count"]))
        lines.append(f"  queue depth: peak {lat['queue_depth_peak']}")
    prof = snap.get("phases")
    if prof and prof["steps"]:
        lines.append(
            "  %d steps, %.3f s total, %.0f%% attributed to phases:"
            % (prof["steps"], prof["step_seconds"],
               100 * (prof["coverage"] or 0)))
        for name, p in sorted(prof["phases"].items(),
                              key=lambda kv: -kv[1]["seconds"]):
            share = p["share_of_step"]
            lines.append("    %-10s %8.3f s  %5.1f%%  (n=%d)" % (
                name, p["seconds"],
                100 * share if share is not None else 0.0, p["count"]))
    return "\n".join(lines)


def drive_open_loop(eng, reqs, arrivals, *, clock=None, sleep=time.sleep):
    """Open-loop serving: submit reqs[i] once `arrivals[i]` seconds have
    elapsed (arrival offsets must be sorted ascending) and step the engine
    whenever it has work; idle gaps sleep until the next arrival. Arrivals
    do NOT wait for the system — the load generator of every latency-SLO
    benchmark — so admission queueing lands in TTFT where it belongs.
    The engine needs the step-at-a-time API (`step()` + `busy`): paged or
    continuous. Returns the requests the ENGINE returned (finished OR
    failed); requests that never entered it — rejected by backpressure or
    shed straight from the queue — are marked failed in place on `reqs`,
    so per-request outcomes are always read off the input list.

    Each request is stamped with its INTENDED arrival time
    (``req.arrival_ts = t0 + arrivals[i]``, absolute on `clock`) before
    submission; the engines forward that to ``MetricsRegistry.on_submit``
    and to the admission queue's deadline anchor, so an arrival that came
    due mid-step is measured from when it ARRIVED, not from when the step
    loop got around to submitting it. `clock` defaults to SERVING_CLOCK —
    inject a custom clock into the engine's Telemetry as well, or the
    stamped arrivals land on a different timebase."""
    from repro.serve.admission import QueueFull
    clock = clock if clock is not None else SERVING_CLOCK
    arrivals = np.asarray(arrivals, float)
    if len(arrivals) != len(reqs):
        raise ValueError(f"{len(reqs)} requests but {len(arrivals)} arrivals")
    if (np.diff(arrivals) < 0).any():
        raise ValueError("arrival offsets must be sorted ascending")
    done = []
    i = 0
    t0 = clock()
    while i < len(reqs) or eng.busy:
        now = clock() - t0
        while i < len(reqs) and arrivals[i] <= now:
            reqs[i].arrival_ts = t0 + float(arrivals[i])
            try:
                eng.submit(reqs[i])
            except QueueFull:
                # backpressure="reject": the overload analogue of HTTP 429.
                # The request never entered the engine, so mark it here —
                # an open-loop load test must keep generating load, and the
                # caller reads per-request outcomes off the reqs list.
                reqs[i].failed = True
                reqs[i].fail_reason = "rejected"
            i += 1
        if eng.busy:
            done.extend(eng.step())
        elif i < len(reqs):
            sleep(max(arrivals[i] - (clock() - t0), 0.0))
    return done
