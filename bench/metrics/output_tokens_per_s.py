"""Output tokens returned by steps that ended inside the window, over the
window's seconds."""
from bench.readings import tokens_in_window


def read(ctx):
    w0, w1 = ctx.run.window
    return tokens_in_window(ctx) / (w1 - w0)
