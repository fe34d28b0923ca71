"""Valid token lanes over the query rows of the attention grids in the
packed steps of the traced sub-window (the engine's padding_stats()
counters `lanes_valid` and `grid_rows`), in percent: how much of the XLA
attention core's work serves a token. Nothing where the program keeps no
`grid_rows` counter."""
from bench.readings import counter_delta


def read(ctx):
    if not ctx.counters or "grid_rows" not in ctx.counters["start"]:
        return None
    rows = counter_delta(ctx, "grid_rows")
    if not rows:
        return None
    return 100.0 * counter_delta(ctx, "lanes_valid") / rows
