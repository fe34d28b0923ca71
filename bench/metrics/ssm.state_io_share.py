"""Bytes of recurrent state the traced sub-window's steps read and wrote
(the engine's padding_stats() counter `state_bytes`: every slot's SSM and
conv state in every Mamba-2 layer, each step) over the bytes the HBM
moves at its peak bandwidth in the device's busy seconds, in percent: the
state arena's share of busy time at the HBM roofline. Nothing where the
program keeps no such counter."""
from bench import trace as T
from bench.readings import counter_delta


def read(ctx):
    if (ctx.trace is None or not ctx.counters
            or "state_bytes" not in ctx.counters["start"]):
        return None
    busy_s = T.busy_ns(ctx.trace) / 1e9
    moved = counter_delta(ctx, "state_bytes")
    if busy_s <= 0 or not moved:
        return None
    return 100.0 * moved / (busy_s * ctx.peak["hbm_bytes_per_s"])
