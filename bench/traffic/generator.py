"""The one traffic generator: a mix file of parameters -> requests.

A mix (`bench/traffic/<mix>.json`) sets the loop and its load, the prompt
and output length distributions, and optional prefix sharing:

    loop        "open" (Poisson arrivals at rate_rps) or "closed"
                (`clients` callers, each sending its next request when its
                last one ends; caller i sends its first before engine step
                i * ramp_steps // clients, and the first round's outputs
                are cut to evenly spread shares of their drawn lengths, so
                the callers do not finish in step: the lead-in starts near
                steady state. Counted in steps, not seconds, the ramp
                sends the same requests into the same steps on every run
                of a seed.)
    lead_in_s   seconds of traffic before the measured window opens
    prompt      a length distribution; with `shared`, the unique suffix
    output      a length distribution for max_new_tokens
    shared      optional: `groups` contexts with lengths from `context`,
                each request picks a group by Zipf(`zipf`) and prepends
                its context; `warm` sends each group's context once in
                set-up

    block       requests per stratum (below); all of them if absent

A length distribution is {"dist": "uniform", "min", "max"} or
{"dist": "lognormal", "median", "sigma", "min", "max"} (clipped).

Every seed gets the same work in the same order; the seed draws the
token ids alone. Prompt and output lengths and inter-arrival gaps are
drawn at the fixed quantiles (i + 1/2) / k of each consecutive stratum of
k = `block` requests, and group picks at fixed Zipf counts over the whole
run, all put in an order drawn once from a generator of fixed seed
(ORDER_SEED), not from the run's. So each stretch of `block` requests
holds the distribution's quantiles, and a run on one seed meets the same
sizes at the same steps as a run on another: the engine's schedule, and
with it the timing, does not change with the seed, while what it
computes does.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from statistics import NormalDist

ORDER_SEED = 0x5EED      # the order of sizes, gaps and picks, for every run


@dataclasses.dataclass
class Item:
    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    arrival: float | None       # seconds after the start (open loop)
    group: int = -1
    start_step: int = 0         # closed loop: the caller's first send


@dataclasses.dataclass
class Traffic:
    items: list
    warm: list                  # prompts sent once in set-up
    loop: str
    clients: int
    lead_in_s: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _strata(n: int, block: int | None):
    """Sizes of consecutive strata covering n requests."""
    block = block or n
    return [min(block, n - i) for i in range(0, n, block)]


def lengths(spec: dict, n: int, rng, block: int | None = None) -> np.ndarray:
    """n lengths at the distribution's fixed quantiles of each stratum of
    `block`, in an order from `rng` within it."""
    if len(_strata(n, block)) > 1:
        return np.concatenate([lengths(spec, k, rng)
                               for k in _strata(n, block)])
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + np.floor(q * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in q])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng.permutation(np.clip(x, lo, hi).astype(np.int64))


def zipf_picks(groups: int, s: float, n: int, rng) -> np.ndarray:
    """n group ids in an order from `rng`, each group's count fixed by
    Zipf(s) and rounded by largest remainder."""
    p = np.arange(1, groups + 1, dtype=np.float64) ** -s
    p /= p.sum()
    want = p * n
    counts = np.floor(want).astype(np.int64)
    for g in np.argsort(-(want - counts))[:n - counts.sum()]:
        counts[g] += 1
    return rng.permutation(np.repeat(np.arange(groups), counts))


def poisson_arrivals(rate: float, n: int, rng,
                     block: int | None = None) -> np.ndarray:
    """Arrival offsets whose gaps are the exponential's quantiles of each
    stratum of `block`, in an order from `rng` within it."""
    gaps = np.concatenate([rng.permutation(-np.log1p(-_quantiles(k)) / rate)
                           for k in _strata(n, block)])
    return np.cumsum(gaps)


def request_count(mix: dict, seconds: float) -> int:
    """Requests generated: an open loop's arrivals up to the window's close;
    for a closed loop more than its clients can finish in that time."""
    horizon = mix["lead_in_s"] + seconds
    if mix["loop"] == "open":
        return math.ceil(mix["rate_rps"] * horizon) + 1
    return mix["clients"] * mix.get("per_client", 64)


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    rng = np.random.default_rng(seed)               # token ids
    order = np.random.default_rng(ORDER_SEED)       # sizes, gaps, picks
    n = request_count(mix, seconds)
    tokens = lambda k: rng.integers(0, vocab, k, dtype=np.int64).astype(
        np.int32)
    block = mix.get("block")
    own = lengths(mix["prompt"], n, order, block)
    out = lengths(mix["output"], n, order, block)
    if mix["loop"] == "open":
        arrivals = poisson_arrivals(mix["rate_rps"], n, order, block)
    else:
        k = mix["clients"]
        arrivals = [None] * n
        starts = [i * mix.get("ramp_steps", 0) // k for i in range(k)]
        share = order.permutation(_quantiles(k))
        out[:k] = np.maximum(np.ceil(share * out[:k]), 1).astype(np.int64)
    shared = mix.get("shared")
    warm, groups = [], np.full(n, -1)
    if shared:
        ctx_len = lengths(shared["context"], shared["groups"], order)
        contexts = [tokens(int(k)) for k in ctx_len]
        groups = zipf_picks(shared["groups"], shared["zipf"], n, order)
        if shared.get("warm"):
            warm = list(contexts)
    items = []
    for i in range(n):
        prompt = tokens(int(own[i]))
        g = int(groups[i])
        if g >= 0:
            prompt = np.concatenate([contexts[g], prompt])
        items.append(Item(uid=i, prompt=prompt, max_new=int(out[i]),
                          arrival=None if arrivals[i] is None
                          else float(arrivals[i]), group=g))
    if mix["loop"] == "closed":
        for it, st in zip(items, starts):
            it.start_step = st
    return Traffic(items=items, warm=warm, loop=mix["loop"],
                   clients=int(mix.get("clients", 0)),
                   lead_in_s=float(mix["lead_in_s"]))
