"""The open- and closed-loop drivers against a fake engine and a fake
clock: the window's cut, the token stamps, failures, the drain, marks."""
import dataclasses

import numpy as np
import pytest

from bench import drivers, readings
from bench.traffic.generator import Item


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


@dataclasses.dataclass
class Req:
    uid: int
    max_new: int
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    failed: bool = False


class Engine:
    """Each step takes `dt` seconds and gives every live request one token;
    up to `slots` requests are live, the rest wait in order. A request
    whose uid is in `fail` fails at its first step; one in `refuse` is
    refused at submit."""

    def __init__(self, clock, dt=0.1, slots=2, fail=(), refuse=()):
        self.clock, self.dt, self.slots = clock, dt, slots
        self.fail, self.refuse = set(fail), set(refuse)
        self.queue = []

    @property
    def busy(self):
        return bool(self.queue)

    def submit(self, r):
        if r.uid in self.refuse:
            raise ValueError("refused")
        self.queue.append(r)

    def step(self):
        self.clock.t += self.dt
        for r in self.queue[:self.slots]:
            if r.uid in self.fail:
                r.failed = True
                continue
            r.out_tokens.append(7)
            r.done = len(r.out_tokens) >= r.max_new
        self.queue = [r for r in self.queue if not (r.done or r.failed)]
        return []


def _items(arrivals, max_new=3):
    return [Item(uid=i, prompt=np.zeros(4, np.int32), max_new=max_new,
                 arrival=a) for i, a in enumerate(arrivals)]


def _req(it):
    return Req(uid=it.uid, max_new=it.max_new)


def test_open_loop_window_stamps_and_ttft():
    clk = Clock()
    eng = Engine(clk, dt=0.1, slots=4)
    fired = []
    run = drivers.drive_open(
        eng, _items([0.0, 0.5, 1.05, 1.5, 2.0, 2.95, 3.5]), _req,
        lead_in_s=1.0, seconds=2.0, clock=clk, sleep=clk.sleep,
        marks=[(0.5, lambda: fired.append(clk.t))],
        on_open=lambda: fired.append(("open", clk.t)))
    w0, w1 = run.window
    assert (w0, w1) == (101.0, 103.0)
    assert fired[0][0] == "open" and fired[0][1] >= w0
    assert fired[1] >= w0 + 0.5
    # arrivals at 1.05 .. 2.95 are due in the window; 3.5 is never sent
    assert [r.req.uid for r in run.due_in_window()] == [2, 3, 4, 5]
    assert len(run.records) == 6
    for r in run.records[:5]:
        assert len(r.stamps) == 3 and r.finished
        assert r.stamps[0] >= r.due and r.stamps == sorted(r.stamps)
    # the request due at 102.95 gets its first token in the drain, which
    # ends there
    last = run.records[5]
    assert run.drain_s > 0 and len(last.stamps) == 1 > 0
    assert last.stamps[0] > w1 and not last.finished
    ctx = readings.Context(run=run, config={}, mix={}, setup_s=0,
                           end=clk.t)
    ttft = readings.ttft_s(ctx)
    assert len(ttft) == 4 and all(0 < v <= 0.15 for v in ttft)
    n = readings.tokens_in_window(ctx)
    assert n == sum(1 for r in run.records for s in r.stamps
                    if w0 <= s <= w1)
    gaps = readings.itl_s(ctx)
    assert gaps and all(g == pytest.approx(0.1) for g in gaps)
    assert all(late >= 0 for late in run.late_s)


def test_open_loop_counts_failed_and_refused_requests():
    clk = Clock()
    eng = Engine(clk, fail={1}, refuse={2})
    run = drivers.drive_open(eng, _items([0.1, 0.2, 0.3]), _req,
                             lead_in_s=0.0, seconds=1.0, clock=clk,
                             sleep=clk.sleep)
    by = {r.req.uid: r for r in run.records}
    assert by[0].finished and not by[0].failed
    assert by[1].failed and not by[1].stamps
    assert by[2].failed and by[2].refused.startswith("ValueError")
    ctx = readings.Context(run=run, config={}, mix={}, setup_s=0,
                           end=clk.t + 5)
    ttft = sorted(readings.ttft_s(ctx))
    # the two failures count at the run's end, above every real TTFT
    assert ttft[0] < 1 and min(ttft[1:]) > 5


def test_open_loop_drain_is_capped():
    clk = Clock()
    eng = Engine(clk, dt=0.5, slots=1)
    run = drivers.drive_open(eng, _items([0.0] * 40, max_new=10), _req,
                             lead_in_s=0.0, seconds=1.0, drain_s=3.0,
                             clock=clk, sleep=clk.sleep)
    assert 3.0 <= run.drain_s < 3.6
    assert any(not r.stamps for r in run.due_in_window())


def test_closed_loop_keeps_every_caller_busy():
    clk = Clock()
    eng = Engine(clk, dt=0.1, slots=8)
    items = _items([None] * 200, max_new=4)
    run = drivers.drive_closed(eng, items, _req, clients=3, lead_in_s=0.5,
                               seconds=2.0, clock=clk)
    w0, w1 = run.window
    # at most 3 requests in flight at any time, each sent when one ended
    for t in np.arange(100.0, w1, 0.05):
        live = [r for r in run.records
                if r.due <= t and (not r.stamps or r.stamps[-1] > t)]
        assert len(live) <= 3
    sends = sorted(r.due for r in run.records)
    assert sends[:3] == [100.0] * 3 and all(s < w1 for s in sends)
    # 3 callers, 4 steps a request, 0.1 s a step: 3 tokens every 0.1 s
    ctx = readings.Context(run=run, config={}, mix={}, setup_s=0,
                           end=clk.t)
    assert readings.tokens_in_window(ctx) == pytest.approx(60, abs=3)
    assert readings.nearest_rank(readings.ttft_s(ctx), 50) == \
        pytest.approx(0.1)


def test_closed_loop_refused_request_sends_the_next():
    clk = Clock()
    eng = Engine(clk, refuse={0})
    run = drivers.drive_closed(eng, _items([None] * 50), _req, clients=1,
                               lead_in_s=0.0, seconds=1.0, clock=clk)
    assert run.records[0].failed and run.records[1].finished


def test_closed_loop_ramp_counts_steps():
    clk = Clock()
    eng = Engine(clk, dt=0.1, slots=8)
    items = _items([None] * 200, max_new=50)
    for it, st in zip(items, (0, 2, 5)):
        it.start_step = st
    run = drivers.drive_closed(eng, items, _req, clients=3, lead_in_s=0.0,
                               seconds=2.0, clock=clk)
    # each caller's first send comes right before its step, whatever the
    # clock reads
    assert [r.due for r in run.records[:3]] == pytest.approx(
        [100.0, 100.2, 100.5])
    n = [len(r.stamps) for r in run.records[:3]]
    assert n[0] >= 20 and (n[0] - n[1], n[0] - n[2]) == (2, 5)


def test_nearest_rank():
    assert readings.nearest_rank([], 50) is None
    assert readings.nearest_rank([3, 1, 2], 50) == 2
    assert readings.nearest_rank(list(range(1, 101)), 95) == 95
    assert readings.nearest_rank(list(range(1, 101)), 90) == 90
