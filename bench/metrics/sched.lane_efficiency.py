"""Valid token lanes over all lanes of the packed steps in the traced
sub-window (the engine's padding_stats() counters), in percent."""
from bench.readings import counter_delta


def read(ctx):
    total = counter_delta(ctx, "lanes_total")
    if not total:
        return None
    return 100.0 * counter_delta(ctx, "lanes_valid") / total
