"""Shared test substrate: CPU pin, tiny-config factory, seeded RNGs, markers.

Markers:
  slow   — heavyweight integration cases (multi-minute compiles / subprocess
           dry-runs). Skipped by default; run with ``--runslow`` (CI has a
           separate non-blocking job for them).
  kernel — Pallas kernel parity tests (interpret mode on CPU, Mosaic on TPU).
"""
import jax
import numpy as np
import pytest

# one process-wide pin instead of per-module jax.config calls: kernels are
# validated in interpret mode and every numeric test is platform-deterministic
jax.config.update("jax_platform_name", "cpu")


def require_hypothesis():
    """Module-level guard shared by the property-test files: skips the whole
    module cleanly when `hypothesis` is not installed (bare environments run
    the deterministic suites only). Use as the first executable statement,
    BEFORE any `import hypothesis...`:

        from conftest import require_hypothesis
        hypothesis = require_hypothesis()

    Centralized here so new property-test modules don't copy the
    importorskip boilerplate (and can't typo the distribution name)."""
    return pytest.importorskip("hypothesis")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavyweight integration test (needs --runslow)")
    config.addinivalue_line(
        "markers", "kernel: Pallas kernel parity test (interpret mode on CPU)")


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow")
    parser.addoption("--cache-layout", default="slot",
                     choices=("slot", "paged"),
                     help="KV-cache layout the engine-level decode-kernel "
                          "parity suite runs against (CI runs both)")
    parser.addoption("--prefix-sharing", default="off", choices=("on", "off"),
                     help="run the engine-level suites with paged prompt-"
                          "prefix sharing (refcounted COW blocks) enabled; "
                          "only meaningful with --cache-layout paged "
                          "(CI runs paged under both settings)")
    parser.addoption("--decode-sharing", default="off", choices=("on", "off"),
                     help="run the engine-level suites with paged DECODE-"
                          "block sharing (generated blocks enter the prefix "
                          "trie as they fill; implies prefix sharing); only "
                          "meaningful with --cache-layout paged (CI runs a "
                          "decode-sharing leg)")
    parser.addoption("--kv-quant", default="none", choices=("none", "int8"),
                     help="run the engine-level suites with int8-quantized "
                          "paged KV blocks (per-block per-kv-head scales); "
                          "only meaningful with --cache-layout paged "
                          "(CI runs int8 legs)")
    parser.addoption("--speculative", default="off", choices=("on", "off"),
                     help="run the engine-level suites with trie-driven "
                          "speculative decoding (draft/verify/rollback); "
                          "only meaningful with --cache-layout paged "
                          "(CI runs speculative legs)")
    parser.addoption("--async-loop", default="off", choices=("on", "off"),
                     help="run the engine-level suites with the paged "
                          "engine's pipelined async step loop (dispatch "
                          "step N+1 before committing step N); only "
                          "meaningful with --cache-layout paged "
                          "(CI runs async legs)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow: pass --runslow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture
def rng():
    """Seeded numpy Generator — deterministic across runs and platforms."""
    return np.random.default_rng(42)


@pytest.fixture
def seeded_key():
    """Seeded jax PRNG key."""
    return jax.random.PRNGKey(0)


@pytest.fixture
def cache_layout(request):
    """The --cache-layout option: which KV layout engine-level suites use."""
    return request.config.getoption("--cache-layout")


@pytest.fixture
def prefix_sharing(request):
    """The --prefix-sharing option as a bool (paged engines only)."""
    return request.config.getoption("--prefix-sharing") == "on"


@pytest.fixture
def decode_sharing(request):
    """The --decode-sharing option as a bool (paged engines only)."""
    return request.config.getoption("--decode-sharing") == "on"


@pytest.fixture
def kv_quant(request):
    """The --kv-quant option: paged KV pool quantization (none | int8)."""
    return request.config.getoption("--kv-quant")


@pytest.fixture
def speculative(request):
    """The --speculative option as a bool (paged engines only)."""
    return request.config.getoption("--speculative") == "on"


@pytest.fixture
def async_loop(request):
    """The --async-loop option as a bool (paged engines only)."""
    return request.config.getoption("--async-loop") == "on"


@pytest.fixture
def make_engine(cache_layout, prefix_sharing, decode_sharing, kv_quant,
                speculative, async_loop):
    """Factory building the continuous-batching engine for the selected
    cache layout: ContinuousEngine (slot arena) or PagedEngine (block pool,
    optionally with --prefix-sharing prompt-prefix reuse, --decode-sharing
    generated-block reuse, --kv-quant int8 block quantization,
    --speculative decoding and/or the --async-loop). Both schedule mixed-length
    traffic step-by-step, so engine-level tests are layout-agnostic through
    this fixture. kv_quant rides on cfg (the single source the engine and
    cache init read), so it only applies to the paged layout — the slot
    arena is fp-only and its engines reject a quantized cfg."""
    def make(params, cfg, **kw):
        if cache_layout == "paged":
            from repro.serve import PagedEngine
            if kv_quant != "none" and cfg.kv_quant != kv_quant:
                cfg = cfg.replace(kv_quant=kv_quant)
            kw.setdefault("block_size", 16)
            kw.setdefault("prefix_sharing", prefix_sharing)
            kw.setdefault("decode_sharing", decode_sharing)
            kw.setdefault("speculative", speculative)
            kw.setdefault("async_loop", async_loop)
            return PagedEngine(params, cfg, **kw)
        from repro.serve import ContinuousEngine
        return ContinuousEngine(params, cfg, **kw)

    return make


@pytest.fixture
def tiny_cfg():
    """Factory for a tiny dense HCCS model config; override fields via kwargs."""
    from repro.configs.base import ModelConfig

    def make(**kw):
        base = dict(name="t", family="dense", num_layers=2, d_model=64,
                    num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                    vocab_pad_multiple=1)
        base.update(kw)
        return ModelConfig(**base)

    return make
