"""What a metric reader is given, and the arithmetic readers share.

Client-side numbers come from the records drivers.Run holds: a
request's TTFT runs from when it was due (open loop) or sent (closed loop)
to its first stamped token; a request due in the window that has no first
token by the end of the run, or failed, is counted at the run's end, so
it ranks above every real TTFT. Percentiles are nearest-rank.
"""
from __future__ import annotations

import dataclasses
import math

from bench import trace as T


@dataclasses.dataclass
class Context:
    run: object                 # drivers.Run
    config: dict                # the configuration file
    mix: dict                   # the traffic mix file
    setup_s: float
    end: float                  # clock at the end of the run
    trace: object = None        # trace.Trace of the traced sub-window
    counters: dict = None       # engine counters at the sub-window's ends
    flops: float = None         # model FLOPs the sub-window's steps require
    peak: dict = None           # the device's row of peaks.json


def nearest_rank(values, pct: float) -> float | None:
    v = sorted(values)
    if not v:
        return None
    return v[max(math.ceil(pct / 100 * len(v)) - 1, 0)]


def ttft_s(ctx: Context) -> list:
    out = []
    for r in ctx.run.due_in_window():
        first = r.stamps[0] if r.stamps and not r.failed else ctx.end
        out.append(first - r.due)
    return out


def itl_s(ctx: Context) -> list:
    w0, w1 = ctx.run.window
    return [b - a for r in ctx.run.records
            for a, b in zip(r.stamps, r.stamps[1:]) if w0 <= b <= w1]


def tokens_in_window(ctx: Context) -> int:
    w0, w1 = ctx.run.window
    return sum(n for _, end, n in ctx.run.steps if w0 <= end <= w1)


def counter_delta(ctx: Context, name: str):
    if not ctx.counters:
        return None
    return ctx.counters["end"][name] - ctx.counters["start"][name]


def mfu_percent(ctx: Context) -> float | None:
    """Model FLOPs the traced steps require over device-busy seconds times
    the device's bf16 peak, in percent."""
    if ctx.trace is None or not ctx.flops:
        return None
    busy_s = T.busy_ns(ctx.trace) / 1e9
    if busy_s <= 0:
        return None
    return 100.0 * ctx.flops / (busy_s * ctx.peak["bf16_flops_per_s"])
