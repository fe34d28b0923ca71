"""Median time to first token over the requests due in the window, from
the send (closed loop) or the intended arrival (open loop) to the first
stamped token; a request with none by the run's end counts there."""
from bench.readings import nearest_rank, ttft_s


def read(ctx):
    v = nearest_rank(ttft_s(ctx), 50)
    return None if v is None else 1e3 * v
