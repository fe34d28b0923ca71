"""Seeded chaos smoke (serve/chaos.py): the fault injector drives a robust
paged engine through arrival bursts, hand-driven allocator exhaustion,
mid-flight cancels, preemption storms, device-step failures and NaN logits
— asserting the global block-accounting invariants after every step and a
fully reclaimed pool at the end.

Two tiers:
* fixed legs (below) run in the tier-1 hypothesis CI step — deterministic
  from (seed, leg), no wall-clock dependence (no deadlines);
* the option-driven leg (slow) rides the cache-layouts matrix chaos job,
  inheriting --prefix-sharing/--kv-quant/--decode-sharing/--async-loop.
"""
import jax
import numpy as np
import pytest

from repro.models import model as M
from repro.serve import (AdmissionConfig, ChaosMonkey, PagedEngine, Request,
                         assert_drained, check_invariants)


def _maker(seed=7, vocab=256):
    rng = np.random.default_rng(seed)

    def mk(i):
        plen = int(rng.integers(4, 24))
        return Request(uid=i,
                       prompt=rng.integers(0, vocab, plen).astype(np.int32),
                       max_new_tokens=int(rng.integers(2, 10)),
                       priority=int(rng.integers(0, 3)))

    return mk


def _params(tiny_cfg, **cfg_kw):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div", **cfg_kw)
    return M.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.mark.parametrize("sharing,quant", [
    (True, "none"),                  # the default serving leg
    (False, "none"),                 # no trie
    (False, "int8"),                 # quantized pool
    (True, "int8"),                  # quantized + sharing (COW on int8)
])
def test_chaos_smoke_fixed_legs(tiny_cfg, sharing, quant):
    """Every seeded chaos run passes the invariant checker at every step
    and drains the pool to empty, across sharing x int8 legs."""
    params, cfg = _params(tiny_cfg,
                          **({"kv_quant": quant} if quant != "none" else {}))
    eng = PagedEngine(params, cfg, max_batch=3, max_len=64, block_size=8,
                      num_blocks=14, prefix_sharing=sharing,
                      admission=AdmissionConfig(
                          max_queue=8,
                          backpressure="shed-lowest-priority",
                          preemption=True))
    report = ChaosMonkey(eng, seed=0, make_request=_maker(),
                         n_requests=12, max_steps=1500).run()
    assert report["submitted"] == 12
    assert sum(report["faults"].values()) > 0, "no fault ever injected"
    assert report["finished"], "chaos killed every single request"
    # the run ends drained; the report's robustness counters are consistent
    rb = report["robustness"]
    assert rb["cancelled"] == len(
        [r for r in report["failed"] if r.fail_reason == "cancelled"])


def test_chaos_seed_reproducible(tiny_cfg):
    """Same (seed, engine config) => same fault schedule and the same
    terminal outcome for every request — the debugging contract."""
    outcomes = []
    for _ in range(2):
        params, cfg = _params(tiny_cfg)
        eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                          num_blocks=12, prefix_sharing=True,
                          admission=AdmissionConfig(preemption=True))
        rep = ChaosMonkey(eng, seed=3, make_request=_maker(),
                          n_requests=10, max_steps=1500).run()
        outcomes.append((rep["steps"], rep["faults"],
                         sorted((r.uid, tuple(int(t) for t in r.out_tokens))
                                for r in rep["finished"]),
                         sorted((r.uid, r.fail_reason)
                                for r in rep["failed"])))
    assert outcomes[0] == outcomes[1]


def test_chaos_requires_robust_engine(tiny_cfg):
    params, cfg = _params(tiny_cfg)
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8)
    with pytest.raises(ValueError, match="robust"):
        ChaosMonkey(eng, seed=0, make_request=_maker())


def test_chaos_restores_step_fns(tiny_cfg):
    """After run() the engine's step functions are unwrapped — a later
    clean run sees no injected faults."""
    params, cfg = _params(tiny_cfg)
    eng = PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=8,
                      num_blocks=12, prefix_sharing=True,
                      admission=AdmissionConfig(preemption=True))
    monkey = ChaosMonkey(eng, seed=1, make_request=_maker(), n_requests=6,
                         max_steps=1500)
    wrapped = eng._packed_fn
    monkey.run()
    assert eng._packed_fn is not wrapped
    rng = np.random.default_rng(9)
    req = Request(uid=99, prompt=rng.integers(0, 256, 9).astype(np.int32),
                  max_new_tokens=4)
    eng.submit(req)
    done = eng.run()
    assert len(done) == 1 and done[0].done and not done[0].failed
    assert_drained(eng)


@pytest.mark.slow
def test_chaos_option_leg(tiny_cfg, make_engine, cache_layout, kv_quant,
                          speculative):
    """The option-driven chaos leg for the CI cache-layouts matrix: same
    harness, engine shape taken from the session options."""
    if cache_layout != "paged":
        pytest.skip("chaos harness targets the paged engine")
    if speculative:
        pytest.skip("chaos legs are non-speculative")
    params, cfg = _params(tiny_cfg)
    for seed in (0, 1):
        eng = make_engine(params, cfg, max_batch=3, max_len=64, block_size=8,
                          num_blocks=14,
                          admission=AdmissionConfig(
                              max_queue=8,
                              backpressure="shed-lowest-priority",
                              preemption=True))
        report = ChaosMonkey(eng, seed=seed, make_request=_maker(seed + 20),
                             n_requests=12, max_steps=1500).run()
        assert report["submitted"] == 12
        check_invariants(eng)
