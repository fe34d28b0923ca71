"""Open- and closed-loop drivers with a measured window and client-side
token stamps.

The open loop is a copy of `repro.serve.telemetry.drive_open_loop` (submit
each request once its intended arrival has passed, step while there is
work, sleep to the next arrival when idle), kept here so that no change to
the program can move the yardstick, with three additions: a window of
fixed length after a lead-in, a stamp on every output token taken when
`eng.step()` returns it, and a drain after the window closes that steps on
until every request due in the window has its first token or has failed
(at most `drain_s`). No request is submitted after the window closes.

The closed loop gives each of `clients` callers one request at a time: a
caller sends its next request when its last one ends.

Both work against anything with `submit(req)`, `step() -> finished` and
`busy`, and take the clock (and the open loop its sleep) as arguments, so
tests drive them with a fake engine and a fake clock. `on_open()` is called once when the
window opens, and each `(offset, fn)` of `marks` once the window has been
open `offset` seconds, between steps.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time


@dataclasses.dataclass
class Record:
    """One request as the client saw it. `due` is the intended arrival (open
    loop) or the send time (closed loop); `sent` when submit() returned;
    `stamps` the clock when each output token came back."""
    req: object
    due: float
    sent: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)
    failed: bool = False
    finished: bool = False
    refused: str = ""           # why submit() refused it, if it did


@dataclasses.dataclass
class Run:
    records: list
    window: tuple               # (open, close), absolute clock seconds
    steps: list                 # (start, end, tokens returned), per step
    drain_s: float              # seconds stepped after the close
    late_s: list                # open loop: sent - due, per request sent

    def due_in_window(self):
        w0, w1 = self.window
        return [r for r in self.records if w0 <= r.due < w1]


def _nospan(_name):
    return contextlib.nullcontext()


class _Loop:
    def __init__(self, eng, make_req, clock, span, marks, on_open):
        self.eng, self.make_req = eng, make_req
        self.clock, self.span = clock, span
        self.marks = sorted(marks or [], key=lambda m: m[0])
        self.on_open = on_open
        self.records, self.live, self.steps = [], [], []

    def start(self, lead_in_s, seconds):
        t0 = self.clock()
        self.w0, self.w1 = t0 + lead_in_s, t0 + lead_in_s + seconds
        self.marks = [(self.w0 + off, fn) for off, fn in self.marks]
        return t0

    def submit(self, item, due):
        rec = Record(req=self.make_req(item), due=due)
        self.records.append(rec)
        try:
            self.eng.submit(rec.req)
        except Exception as e:      # refused at the door: a failed request
            rec.failed, rec.refused = True, f"{type(e).__name__}: {e}"
            return rec
        rec.sent = self.clock()
        self.live.append(rec)
        return rec

    def fire_marks(self, now):
        if self.on_open is not None and now >= self.w0:
            self.on_open()
            self.on_open = None
        while self.marks and self.marks[0][0] <= now:
            self.marks.pop(0)[1]()

    def next_event(self):
        """When the loop must next wake up if idle: the window's opening or
        close, or the next mark."""
        times = [self.w1] + [m[0] for m in self.marks[:1]]
        if self.on_open is not None:
            times.append(self.w0)
        return min(times)

    def step(self):
        """One engine step; stamps every token it returned. Returns the
        records that ended in it."""
        t0 = self.clock()
        with self.span("bench.step"):
            self.eng.step()
        t1 = self.clock()
        ended, n = [], 0
        with self.span("bench.client"):
            keep = []
            for rec in self.live:
                out = rec.req.out_tokens
                k = len(out) - len(rec.stamps)
                if k > 0:
                    rec.stamps.extend([t1] * k)
                    n += k
                if getattr(rec.req, "failed", False):
                    rec.failed = True
                    ended.append(rec)
                elif getattr(rec.req, "done", False):
                    rec.finished = True
                    ended.append(rec)
                else:
                    keep.append(rec)
            self.live = keep
        self.steps.append((t0, t1, n))
        return ended

    def drained(self, w0, w1):
        return all(r.stamps or r.failed or r.finished
                   for r in self.records if w0 <= r.due < w1)


def drive_open(eng, items, make_req, *, lead_in_s, seconds, drain_s=60.0,
               clock=time.perf_counter, sleep=time.sleep, span=_nospan,
               marks=None, on_open=None) -> Run:
    """items carry `.arrival` offsets, sorted ascending."""
    loop = _Loop(eng, make_req, clock, span, marks, on_open)
    t0 = loop.start(lead_in_s, seconds)
    w0, w1 = loop.w0, loop.w1
    i, late = 0, []
    while True:
        now = clock()
        loop.fire_marks(now)
        if now >= w1 and (loop.drained(w0, w1) or now >= w1 + drain_s):
            break
        with span("bench.client"):
            while (i < len(items) and t0 + items[i].arrival <= now
                   and t0 + items[i].arrival < w1):
                rec = loop.submit(items[i], t0 + items[i].arrival)
                if not rec.failed:
                    late.append(rec.sent - rec.due)
                i += 1
        if eng.busy:
            loop.step()
        elif now < w1:
            wake = loop.next_event()
            if i < len(items) and t0 + items[i].arrival < w1:
                wake = min(wake, t0 + items[i].arrival)
            with span("bench.wait_arrival"):
                sleep(max(wake - clock(), 0.0))
        else:
            break
    return Run(records=loop.records, window=(w0, w1), steps=loop.steps,
               drain_s=max(clock() - w1, 0.0), late_s=late)


def drive_closed(eng, items, make_req, *, clients, lead_in_s, seconds,
                 drain_s=60.0, clock=time.perf_counter, span=_nospan,
                 marks=None, on_open=None) -> Run:
    """`clients` callers take items in order; each sends its next one when
    its last one ends, until the window closes. Caller i sends its first
    item before the engine step numbered by that item's `.start_step`, or
    at once when the engine has nothing to do. Every send follows a step,
    never the clock, so a seed's requests meet the same steps on every
    run. A request the engine refuses at submit is failed, and its caller
    sends the next one."""
    loop = _Loop(eng, make_req, clock, span, marks, on_open)
    loop.start(lead_in_s, seconds)
    w0, w1 = loop.w0, loop.w1
    starts = sorted(it.start_step for it in items[:clients])
    nxt = iter(items)

    def send():
        while True:
            item = next(nxt, None)
            if item is None:
                refused = [r.refused for r in loop.records if r.refused]
                raise RuntimeError("closed loop: the traffic ran out of "
                                   "requests before the window closed"
                                   + (f"; refused: {refused[0]}"
                                      if refused else ""))
            if not loop.submit(item, clock()).failed:
                return

    while True:
        now = clock()
        loop.fire_marks(now)
        if now >= w1 and (loop.drained(w0, w1) or now >= w1 + drain_s):
            break
        with span("bench.client"):
            while starts and now < w1 and (starts[0] <= len(loop.steps)
                                           or not eng.busy):
                starts.pop(0)
                send()
        if not eng.busy:
            if now >= w1:
                break
            raise RuntimeError("closed loop: engine idle with no caller "
                               "left to send")
        ended = loop.step()
        with span("bench.client"):
            for _ in ended:
                if clock() < w1:
                    send()
    return Run(records=loop.records, window=(w0, w1), steps=loop.steps,
               drain_s=max(clock() - w1, 0.0), late_s=[])
