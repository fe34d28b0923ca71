"""Share of the traced sub-window with no device op running, in percent."""
from bench import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * T.idle_share(ctx.trace)
