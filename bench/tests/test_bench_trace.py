"""Trace reduction pinned on a small synthetic trace: busy union, idle
share, idle gaps by harness span, gaps between step programs, top ops."""
import pytest

from bench import trace as T

MS = 1_000_000


def _trace():
    # host: step spans 0-10 and 12-22 ms, client 10-12 ms, a wait for an
    # arrival 22-30 ms, then a step 30-40 ms
    spans = [("bench.step", 0, 10 * MS), ("bench.client", 10 * MS, 12 * MS),
             ("bench.step", 12 * MS, 22 * MS),
             ("bench.wait_arrival", 22 * MS, 30 * MS),
             ("bench.step", 30 * MS, 40 * MS)]
    # device: three step programs with ops inside, one small op between
    modules = [("jit__packed", 1 * MS, 8 * MS), ("jit__packed", 13 * MS,
                                                   20 * MS),
               ("jit__packed", 31 * MS, 38 * MS)]
    ops = [("fusion.1", 1 * MS, 5 * MS), ("dot.2", 5 * MS, 8 * MS),
           ("argmax", 9 * MS, 10 * MS),
           ("fusion.1", 13 * MS, 20 * MS), ("fusion.1", 31 * MS, 38 * MS)]
    return T.Trace(ops=ops, modules=modules, spans=spans, devices=1)


def test_union_and_clip():
    assert T.union([(5, 8), (1, 3), (2, 4), (8, 9), (10, 10)]) == \
        [(1, 4), (5, 9)]
    assert T.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_busy_idle_and_window():
    tr = _trace()
    assert T.window(tr) == (0, 40 * MS)
    # busy: 1-8, 9-10, 13-20, 31-38 = 7 + 1 + 7 + 7 ms
    assert T.busy_ns(tr) == 22 * MS
    assert T.idle_share(tr) == pytest.approx(1 - 22 / 40)


def test_idle_gaps_by_span():
    gaps = T.idle_gaps(_trace())
    assert [(s // MS, e // MS, n) for s, e, n in gaps] == [
        (0, 1, "bench.step"), (8, 9, "bench.step"),
        (10, 13, "bench.client"), (20, 31, "bench.wait_arrival"),
        (38, 40, "bench.step")]
    top = T.top_gaps(_trace(), n=2)
    assert top == [["bench.wait_arrival", 0.011], ["bench.client", 0.003]]


def test_step_gaps_skip_waits_and_other_ops():
    gaps = T.step_gaps_ns(_trace(), lambda n: "packed" in n)
    # 8 -> 13 ms holds the 1 ms argmax: 4 ms idle; 20 -> 31 ms waited
    assert gaps == [4 * MS]


def test_top_ops():
    assert T.top_ops(_trace()) == [["fusion.1", 0.018], ["dot.2", 0.003],
                                   ["argmax", 0.001]]


def test_top_ops_count_self_time_of_nested_ops():
    ops = [("while.1 = (f32[8]{0:T(8)}) while(...)", 0, 10 * MS),
           ("fusion.2 = f32[8]{0} fusion(...)", 1 * MS, 4 * MS),
           ("fusion.2 = f32[8]{0} fusion(...)", 5 * MS, 9 * MS),
           ("copy.3", 12 * MS, 13 * MS)]
    tr = T.Trace(ops=ops, modules=[], spans=[("bench.step", 0, 14 * MS)],
                 devices=1)
    assert T.top_ops(tr) == [["fusion.2 = f32[8] fusion(...)", 0.007],
                             ["while.1 = (f32[8]) while(...)", 0.003],
                             ["copy.3", 0.001]]
    assert T.busy_ns(tr) == 11 * MS


def test_no_span_is_an_error():
    with pytest.raises(ValueError):
        T.window(T.Trace(ops=[], modules=[], spans=[], devices=1))
