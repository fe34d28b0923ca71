"""The model FLOPs the traced sub-window's steps require (valid tokens'
projections and MLP, each token's attention over its causal context, the
head at sampled positions) over device-busy seconds times the bf16 peak,
in percent."""
from bench.readings import mfu_percent


def read(ctx):
    return mfu_percent(ctx)
