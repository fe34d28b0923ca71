"""Mean device-idle time between consecutive step programs while the
engine had work (no bench.wait_arrival span in the gap), from the trace."""
from bench import trace as T


def _is_step(name):
    return "packed" in name


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = T.step_gaps_ns(ctx.trace, _is_step)
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
