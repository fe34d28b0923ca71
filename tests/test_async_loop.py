"""Pipelined async engine loop (paged packed step, serve/paged.py
"PIPELINED ASYNC LOOP"): greedy token parity async-on vs async-off across
the packed x sharing x int8 x speculative matrix, EOS-one-step-late
rollback, chaos-harness invariants at commit boundaries, profiler coverage
under overlap, and sync-point hygiene — the unprofiled step paths must
issue no explicit device fence."""
import jax
import numpy as np
import pytest

from repro.models import model as M
from repro.serve import (AdmissionConfig, ChaosMonkey, ContinuousEngine,
                         PagedEngine, Request, Telemetry)


@pytest.fixture
def served(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def served_int8(tiny_cfg):
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div",
                   kv_quant="int8")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _requests(seed=7, n=10, shared_len=32, temps=None):
    """Mixed traffic: odd uids share a 2-block prompt prefix (so the
    sharing legs actually hit the trie), prompt/budget lengths seeded."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 256, shared_len).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, 256, int(rng.integers(3, 24))).astype(np.int32)
        prompt = np.concatenate([shared, tail]) if i % 2 else tail
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(2, 12)),
                            temperature=(0.0 if temps is None
                                         else float(temps[i % len(temps)]))))
    return reqs


def _serve(params, cfg, async_loop, *, eos_id=5, temps=None, **kw):
    eng = PagedEngine(params, cfg, max_batch=4, max_len=128, block_size=16,
                      async_loop=async_loop, eos_id=eos_id,
                      **kw)
    for req in _requests(temps=temps):
        eng.submit(req)
    done = eng.run()
    return {r.uid: [int(t) for t in r.out_tokens] for r in done}, eng


LEGS = {
    "packed": {},
    "prefix": dict(prefix_sharing=True),
    "decode_sharing": dict(prefix_sharing=True, decode_sharing=True),
    "speculative": dict(speculative=True, prefix_sharing=True),
}


@pytest.mark.parametrize("leg", sorted(LEGS))
def test_async_greedy_parity(served, leg):
    """Greedy outputs are token-identical with the async loop on or off;
    non-speculative legs genuinely overlap, speculative degrades to the
    sync fallback (accept/reject is host-side control flow)."""
    cfg, params = served
    sync_out, _ = _serve(params, cfg, False, **LEGS[leg])
    async_out, eng = _serve(params, cfg, True, **LEGS[leg])
    assert async_out == sync_out
    if LEGS[leg].get("speculative"):
        assert eng.async_overlapped_steps == 0
        assert eng.async_sync_fallbacks > 0
    else:
        assert eng.async_overlapped_steps > 0
        assert eng.async_sync_fallbacks == 0


@pytest.mark.parametrize("leg", ["packed", "prefix", "speculative"])
def test_async_greedy_parity_int8(served_int8, leg):
    """The same parity on the int8-quantized block pool: per-block scale
    growth (and the speculative restore-then-replay) must commute with the
    one-step-late commit."""
    cfg, params = served_int8
    sync_out, _ = _serve(params, cfg, False, **LEGS[leg])
    async_out, eng = _serve(params, cfg, True, **LEGS[leg])
    assert async_out == sync_out
    if not LEGS[leg].get("speculative"):
        assert eng.async_overlapped_steps > 0


def test_async_hot_sampling_falls_back(served):
    """Sampled (temperature > 0) slots need landed logits on the host —
    those steps must degrade to commit-then-sync-step, and outputs stay
    identical to the synchronous loop (sampling keys are deterministic
    per (uid, generation index))."""
    cfg, params = served
    sync_out, _ = _serve(params, cfg, False, temps=(0.7, 1.0))
    async_out, eng = _serve(params, cfg, True, temps=(0.7, 1.0))
    assert async_out == sync_out
    assert eng.async_overlapped_steps == 0
    assert eng.async_sync_fallbacks > 0


def test_async_eos_one_step_late(served):
    """EOS cannot be predicted at dispatch: the async loop runs one extra
    in-flight step for an EOS slot and discards its writes at commit.
    Pin parity with an eos_id picked from the middle of a sync run's
    output, so the late-EOS path actually fires."""
    cfg, params = served
    sync_out, _ = _serve(params, cfg, False, eos_id=None)
    # a token some request emits mid-output: stopping there exercises the
    # discard-the-extra-step path on every request that emits it
    eos = next(toks[len(toks) // 2] for toks in sync_out.values()
               if len(toks) >= 3)
    sync_eos, _ = _serve(params, cfg, False, eos_id=eos)
    async_eos, eng = _serve(params, cfg, True, eos_id=eos)
    assert async_eos == sync_eos
    assert eng.async_overlapped_steps > 0
    assert any(len(t) < len(sync_out[u]) for u, t in sync_eos.items()), \
        "chosen eos_id never cut a request short — test is vacuous"


def test_async_multi_turn_sessions(served):
    """Session follow-up turns (decode-block sharing) are token-identical
    under the pipelined loop — commit-time trie registration with the
    record's own coverage must index exactly the blocks the sync loop
    registers."""
    cfg, params = served

    def serve_turns(async_loop):
        eng = PagedEngine(params, cfg, max_batch=3, max_len=192,
                          block_size=16, prefix_sharing=True,
                          decode_sharing=True, async_loop=async_loop)
        rng = np.random.default_rng(11)
        out = {}
        for turn in range(3):
            for s in range(3):
                msg = rng.integers(0, 256, 12).astype(np.int32)
                eng.submit(Request(uid=10 * turn + s, prompt=msg,
                                   max_new_tokens=6),
                           session=f"chat-{s}")
            for r in eng.run():
                out[r.uid] = [int(t) for t in r.out_tokens]
        return out, eng

    sync_out, _ = serve_turns(False)
    async_out, eng = serve_turns(True)
    assert async_out == sync_out
    assert eng.async_overlapped_steps > 0


def test_async_requires_packed(served):
    """packed=False is refused outright, the async loop included: the
    lockstep layout it asked for was removed."""
    cfg, params = served
    with pytest.raises(ValueError, match="lockstep step layout was removed"):
        PagedEngine(params, cfg, max_batch=2, max_len=64, block_size=16,
                    packed=False, async_loop=True)


def test_cfg_async_loop_requires_paged(tiny_cfg):
    with pytest.raises(ValueError, match="paged"):
        tiny_cfg(async_loop=True)          # default cache_layout is slot
    cfg = tiny_cfg(async_loop=True, cache_layout="paged")
    assert cfg.async_loop


def test_async_engine_reads_cfg_flag(served):
    cfg, params = served
    eng = PagedEngine(params, cfg.replace(cache_layout="paged",
                                          async_loop=True),
                      max_batch=2, max_len=64, block_size=16)
    assert eng.async_loop


# ------------------------------------------------------- chaos harness --


def _chaos_maker(seed=5):
    rng = np.random.default_rng(seed)

    def mk(i):
        plen = int(rng.integers(4, 24))
        return Request(uid=i,
                       prompt=rng.integers(0, 256, plen).astype(np.int32),
                       max_new_tokens=int(rng.integers(2, 8)),
                       priority=int(rng.integers(0, 3)),
                       deadline_e2e=30.0)

    return mk


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_async_chaos_invariants(tiny_cfg, quant):
    """The block-accounting invariants hold at every commit boundary under
    fault injection with the pipeline on: preemption, cancellation and
    device faults mid-pipeline dead-mark the in-flight record and drain
    cleanly to a fully reclaimed pool."""
    cfg = tiny_cfg(attention_prob="hccs", hccs_mode="i16_div",
                   **({"kv_quant": quant} if quant != "none" else {}))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedEngine(params, cfg, max_batch=3, max_len=64, block_size=8,
                      num_blocks=14, async_loop=True, eos_id=5,
                      admission=AdmissionConfig(
                          max_queue=8,
                          backpressure="shed-lowest-priority",
                          preemption=True))
    report = ChaosMonkey(eng, seed=0, make_request=_chaos_maker(),
                         n_requests=12, max_steps=1500).run()
    assert report["submitted"] == 12
    assert sum(report["faults"].values()) > 0, "no fault ever injected"
    assert report["finished"], "chaos killed every single request"
    assert eng.async_overlapped_steps > 0, "pipeline never engaged"


# ----------------------------------------------------------- telemetry --


def test_async_profiler_coverage(served):
    """The phase taxonomy still covers >= 90% of wall-clock inside steps
    when the loop pipelines — the device fence moved to the commit, it
    must not open an unattributed gap."""
    cfg, params = served
    tel = Telemetry(enabled=True)
    eng = PagedEngine(params, cfg, max_batch=4, max_len=128, block_size=16,
                      async_loop=True, telemetry=tel)
    for req in _requests():
        eng.submit(req)
    eng.run()
    assert eng.async_overlapped_steps > 0
    snap = eng.snapshot()
    assert snap["phases"]["coverage"] >= 0.9


def _count_fences(monkeypatch):
    fences = []
    real = jax.block_until_ready

    def counting(x):
        fences.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    return fences


@pytest.mark.parametrize("speculative,async_loop", [
    (False, False), (True, False), (False, True)])
def test_paged_unprofiled_steps_issue_no_fence(served, monkeypatch,
                                               speculative, async_loop):
    """With telemetry off, no paged step path — synchronous, speculative
    verify or pipelined — calls jax.block_until_ready: the profiler's
    phase-attribution fence is strictly gated on prof.enabled (host syncs
    happen only through the data dependency on sampled tokens). Guards
    against re-introducing a per-step forced sync on the hot path."""
    cfg, params = served
    eng = PagedEngine(params, cfg, max_batch=4, max_len=128, block_size=16,
                      speculative=speculative, async_loop=async_loop)
    for req in _requests(n=6):
        eng.submit(req)
    fences = _count_fences(monkeypatch)
    eng.run()
    assert not fences, f"unprofiled path issued {len(fences)} device fences"
    assert not speculative or eng.spec_steps > 0


def test_continuous_unprofiled_steps_issue_no_fence(served, monkeypatch):
    cfg, params = served
    eng = ContinuousEngine(params, cfg, max_batch=4, max_len=64)
    for req in _requests(n=6):
        eng.submit(req)
    fences = _count_fences(monkeypatch)
    eng.run()
    assert not fences, f"unprofiled path issued {len(fences)} device fences"
