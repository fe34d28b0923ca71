"""Slot-states of slots with a token in the step over all slot-states the
traced sub-window's steps read and wrote (the engine's padding_stats()
counters `state_slots_live` and `state_slots_moved`), in percent: how
much of the state arena's traffic serves a sequence. Nothing where the
program keeps no such counters."""
from bench.readings import counter_delta


def read(ctx):
    if not ctx.counters or "state_slots_moved" not in ctx.counters["start"]:
        return None
    moved = counter_delta(ctx, "state_slots_moved")
    if not moved:
        return None
    return 100.0 * counter_delta(ctx, "state_slots_live") / moved
