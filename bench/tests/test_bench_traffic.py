"""The traffic generator: deterministic in the seed, the same work for
every seed in another order, Zipf picks and Poisson gaps as stated."""

import numpy as np
import pytest

from bench import registry
from bench.traffic import generator as G

# an open loop with shared prefixes, beside the closed rag-4k mix
OPEN = {"loop": "open", "rate_rps": 1.2, "lead_in_s": 10,
        "prompt": {"dist": "uniform", "min": 128, "max": 768},
        "output": {"dist": "uniform", "min": 16, "max": 128},
        "shared": {"groups": 8, "zipf": 1.1, "warm": True,
                   "context": {"dist": "uniform", "min": 1536, "max": 3072}},
        "block": 16}
MIXES = ["rag-4k", "open-shared"]


def _mix(name):
    return dict(OPEN) if name == "open-shared" else registry.mix(name)


def _sig(tr):
    return [(it.uid, it.prompt.tobytes(), it.max_new, it.arrival, it.group)
            for it in tr.items], [w.tobytes() for w in tr.warm]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    mix = _mix(name)
    a = G.generate(mix, 49152, 2 ** 31 + 5, 30)
    b = G.generate(mix, 49152, 2 ** 31 + 5, 30)
    assert _sig(a) == _sig(b)
    c = G.generate(mix, 49152, 2 ** 31 + 6, 30)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_multiset_of_sizes(name):
    # every seed gets the same sizes, gaps and picks in the same order;
    # only the token ids differ
    mix = _mix(name)
    a = G.generate(mix, 49152, 1, 30)
    b = G.generate(mix, 49152, 2, 30)
    shape = lambda tr: [(len(i.prompt), i.max_new, i.arrival, i.group,
                         i.start_step) for i in tr.items]
    assert shape(a) == shape(b)
    assert [len(w) for w in a.warm] == [len(w) for w in b.warm]
    assert not np.array_equal(a.items[0].prompt, b.items[0].prompt)
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    if mix["loop"] == "closed":     # the first round's outputs are cut
        first = a.items[:mix["clients"]]
        assert all(1 <= i.max_new <= hi for i in first)
        starts = [i.start_step for i in first]
        assert starts == sorted(starts) and starts[0] == 0
        assert starts[-1] < mix["ramp_steps"]
    else:
        assert all(np.diff([i.arrival for i in a.items]) > 0)
    for it in a.items[mix.get("clients", 0):]:
        assert 0 <= it.prompt.min() and it.prompt.max() < 49152
        assert lo <= it.max_new <= hi
    # each stratum of `block` requests holds the distribution's quantiles
    k = mix["block"]
    own = [len(i.prompt) - (len(a.warm[i.group]) if i.group >= 0 else 0)
           for i in a.items]
    want = sorted(G.lengths(mix["prompt"], k, np.random.default_rng(0)))
    assert sorted(own[k:2 * k]) == want


def test_lengths_stay_in_range_and_follow_the_median():
    spec = {"dist": "lognormal", "median": 2048, "sigma": 0.45,
            "min": 1024, "max": 3840}
    x = G.lengths(spec, 1001, np.random.default_rng(0))
    assert x.min() >= 1024 and x.max() <= 3840
    assert np.median(x) == 2048


def test_zipf_counts_and_shared_contexts():
    picks = G.zipf_picks(8, 1.1, 1000, np.random.default_rng(0))
    counts = np.bincount(picks, minlength=8)
    p = np.arange(1, 9) ** -1.1
    assert np.abs(counts - 1000 * p / p.sum()).max() <= 1
    tr = G.generate(OPEN, 49152, 3, 30)
    assert len(tr.warm) == 8
    for it in tr.items:
        ctx = tr.warm[it.group]
        assert np.array_equal(it.prompt[:len(ctx)], ctx)
    cold = G.generate(dict(OPEN, shared=dict(OPEN["shared"], warm=False)),
                      49152, 3, 30)
    assert cold.warm == []


def test_poisson_gaps_have_the_rate():
    a = G.poisson_arrivals(4.0, 4000, np.random.default_rng(0))
    assert a[-1] / 4000 == pytest.approx(0.25, rel=0.01)


def test_every_stratum_holds_the_same_sizes():
    spec = {"dist": "uniform", "min": 32, "max": 256}
    a = G.lengths(spec, 40, np.random.default_rng(1), block=16)
    b = G.lengths(spec, 40, np.random.default_rng(2), block=16)
    for lo, hi in ((0, 16), (16, 32), (32, 40)):
        assert sorted(a[lo:hi]) == sorted(b[lo:hi])
    assert list(a[:16]) != list(b[:16])
    x = G.poisson_arrivals(2.0, 32, np.random.default_rng(1), block=16)
    y = G.poisson_arrivals(2.0, 32, np.random.default_rng(2), block=16)
    assert x[15] == pytest.approx(y[15]) and x[31] == pytest.approx(y[31])
