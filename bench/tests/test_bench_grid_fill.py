"""The reader of `attn.grid_fill`: valid lanes over the attention grids'
query rows, from the engine counters at the traced sub-window's ends."""
from bench import readings, registry


def _ctx(start, end):
    return readings.Context(run=None, config={}, mix={}, setup_s=1.0, end=0,
                            counters={"start": start, "end": end})


def test_grid_fill_reads_the_counter_deltas():
    read = registry.reader("attn.grid_fill")
    ctx = _ctx({"lanes_valid": 100, "grid_rows": 400},
               {"lanes_valid": 1100, "grid_rows": 2400})
    assert read(ctx) == 100.0 * 1000 / 2000
    # no packed XLA step in the sub-window: nothing to read
    assert read(_ctx(ctx.counters["start"], ctx.counters["start"])) is None


def test_grid_fill_reads_nothing_without_the_counter():
    # a program that keeps no grid_rows counter, or a run without a trace:
    # nothing, and no error
    read = registry.reader("attn.grid_fill")
    old = {"lanes_valid": 1, "lanes_total": 2}
    assert read(_ctx(old, old)) is None
    assert read(readings.Context(run=None, config={}, mix={}, setup_s=1.0,
                                 end=0, counters=None)) is None
