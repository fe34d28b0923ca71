"""The architecture modules: a configuration names its own, and the dense
one serves and checks exactly the numbers the benchmark had before the
architectures moved into modules of their own.

The digests below were taken from the benchmark as it stood before that
move (bench/model.py and bench/reference.py holding the dense code), on
XLA's CPU backend with its default flags, and are pinned: the weights'
bytes and the reference's logit statistics (float32 and the float8
control) of the tiny harness configuration, and of the same at
starcoder2's GELU and layer norm.
"""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import model as BM
from bench import reference as R
from bench import registry

SEED = 2 ** 31 + 11
PINNED = {
    "granite": ("f19438d018d2d5249d8b98a5cb6a7532843df4200cc59a8e599d7fb00d6e811f",
                "813fedc6c8c20d223fd2a15654b379b664f390d133ca4016d6a7d7c0acb31330"),
    "starcoder2": ("d9d5279b23264cf0dbe5981a9962101104da32ed4df3d48339c5eec9dffe3455",
                   "dbf36dcacf17d157dbbcc31a7cb64804920f296ba685a64407fff929d2e81467"),
}


def _tiny(kind):
    """test_bench_harness.py's tiny configuration; with "starcoder2" its
    activation, norm and RoPE base."""
    c = registry.config("granite-3-2b")
    c["name"] = "tiny"
    c["model"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, d_ff=128, vocab_size=256)
    if kind == "starcoder2":
        c["model"].update(activation="gelu", norm="layernorm",
                          rope_theta=999999.4)
    c["serving"].update(max_batch=4, max_len=128, block_size=8,
                        num_blocks=80, prefix_sharing=True)
    return c


def _sequences():
    rng = np.random.default_rng(0)
    return [(rng.integers(0, 256, p).astype(np.int32),
             rng.integers(0, 256, o).astype(np.int32))
            for p, o in ((20, 5), (33, 9), (7, 3))]


@pytest.mark.parametrize("kind", sorted(PINNED))
def test_dense_module_reproduces_the_pinned_bytes(kind):
    c = _tiny(kind)
    arch = registry.arch(c["arch"])
    params = BM.make_params(c, SEED, arch.model_config(c), arch)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    weights = h.hexdigest()
    h = hashlib.sha256()
    for precision in ("f32", "fp8"):
        for mx, at, am in R.logit_stats(c, SEED, _sequences(), t=64, k=12,
                                        n=4, precision=precision):
            h.update(mx.tobytes())
            h.update(at.tobytes())
            h.update(am.tobytes())
    assert (weights, h.hexdigest()) == PINNED[kind]


def test_a_configuration_without_an_arch_names_what_is_missing(tmp_path):
    c = registry.config("granite-3-2b")
    del c["arch"]
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "no-arch.json").write_text(json.dumps(c))
    with pytest.raises(KeyError, match='"arch"'):
        registry.config("no-arch", tmp_path)
    with pytest.raises(FileNotFoundError, match="no architecture 'no-such'"):
        registry.arch("no-such", tmp_path)


def test_every_configuration_names_a_whole_architecture():
    needed = ("model_config", "leaf_table", "hccs_params", "step_flops",
              "head_flops", "hidden")
    for entry in registry.load_benchmark()["configs"]:
        arch = registry.arch(registry.config(entry["name"])["arch"])
        assert all(callable(getattr(arch, f, None)) for f in needed)
