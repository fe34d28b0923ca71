"""95th percentile gap between consecutive output tokens of one request,
over the gaps whose later token came back inside the window."""
from bench.readings import itl_s, nearest_rank


def read(ctx):
    v = nearest_rank(itl_s(ctx), 95)
    return None if v is None else 1e3 * v
