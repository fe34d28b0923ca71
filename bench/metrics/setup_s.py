"""Seconds from the start of the process to the window's opening: imports,
weights, engine, compilation or cache loads, warm-up, cache fill and the
lead-in traffic."""


def read(ctx):
    return ctx.setup_s
