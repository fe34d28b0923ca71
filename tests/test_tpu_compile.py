"""Compile the serving path's Pallas kernels for a TPU v5e, without a chip.

Interpret-mode tests show what a kernel computes, not whether Mosaic accepts
it: a tile that breaks the (8, 128) layout, or an operand that outgrows SMEM
or VMEM, only fails when the kernel is compiled for the chip. These tests
compile with `interpret=False` against a described `v5e:2x2` topology, one
device, at granite-3-2b head geometry (H 32, Hkv 8, head_dim 64 lane-padded
to 128) and at pool sizes a real deployment holds. The XLA attention
path's packed step is compiled the same way, to check what it does with
its KV pool.

The TPU library may be loaded by one process at a time, so the topology is
described inside a module fixture and never at import: every xdist worker
collects the same tests, and only the worker given this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import decode as D
from repro.kernels import ops

H, HKV, HD = 32, 8, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    keep it out of JAX's persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _spec(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


POOLS = [  # (pool dtype, N blocks, block_size, static_max)
    pytest.param(jnp.bfloat16, 512, 16, False, id="bf16-N512-bs16"),
    pytest.param(jnp.bfloat16, 4096, 32, False, id="bf16-N4096-bs32"),
    pytest.param(jnp.float32, 512, 32, True, id="f32-N512-static_max"),
    pytest.param(jnp.int8, 512, 16, False, id="int8-N512-bs16"),
    pytest.param(jnp.int8, 4096, 32, False, id="int8-N4096-bs32"),
    pytest.param(jnp.int8, 4096, 32, True, id="int8-N4096-static_max"),
]


@pytest.mark.parametrize("dtype,n,bs,static_max", POOLS)
def test_packed_prefill_compiles(one_chip, dtype, n, bs, static_max):
    """The packed step's kernel: 256 ragged tokens over 8 slots, 2048-token
    block tables. int8 at N=4096 overflowed SMEM when its (N, Hkv) scales
    were scalar-prefetched."""
    s = _spec(one_chip)
    quant = dtype == jnp.int8
    t, b, nblk = 256, 8, 2048 // bs

    def f(q, kp, vp, tbl, sid, ln, sc, th, ks, vs):
        return D.hccs_packed_prefill(q, kp, vp, tbl, sid, ln, sc, th,
                                     static_max=static_max, interpret=False,
                                     k_scales=ks, v_scales=vs)

    pool = s((n, HKV, bs, 128), dtype)
    scales = s((n, HKV), jnp.float32) if quant else None
    _compile(f, s((t, H, HD), jnp.float32), pool, pool, s((b, nblk), jnp.int32),
             s((t,), jnp.int32), s((t,), jnp.int32), s((H,), jnp.float32),
             s((H, 3), jnp.int32), scales, scales)


@pytest.mark.parametrize("dtype,n,bs,static_max", POOLS)
def test_paged_decode_compiles(one_chip, dtype, n, bs, static_max):
    """The lockstep decode kernel with 32k-token block tables, whose (B, nblk)
    table is scalar-prefetched into SMEM."""
    s = _spec(one_chip)
    quant = dtype == jnp.int8
    b, nblk = 8, 32768 // bs

    def f(q, kp, vp, tbl, ln, sc, th, ks, vs):
        return D.hccs_paged_decode(q, kp, vp, tbl, ln, sc, th,
                                   static_max=static_max, interpret=False,
                                   k_scales=ks, v_scales=vs)

    pool = s((n, HKV, bs, 128), dtype)
    scales = s((n, HKV), jnp.float32) if quant else None
    _compile(f, s((b, H, HD), jnp.float32), pool, pool, s((b, nblk), jnp.int32),
             s((b,), jnp.int32), s((H,), jnp.float32), s((H, 3), jnp.int32),
             scales, scales)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("static_max", [False, True])
def test_slot_decode_compiles(one_chip, dtype, static_max):
    """The slot-arena decode kernel over lane-padded (B, Hkv, 2048, 128)."""
    s = _spec(one_chip)
    b, tmax = 8, 2048

    def f(q, k, v, ln, sc, th):
        return D.hccs_decode(q, k, v, ln, sc, th, static_max=static_max,
                             interpret=False)

    cache = s((b, HKV, tmax, 128), dtype)
    _compile(f, s((b, H, HD), jnp.float32), cache, cache, s((b,), jnp.int32),
             s((H,), jnp.float32), s((H, 3), jnp.int32))


def test_full_width_int8_packed_step_compiles(one_chip, monkeypatch):
    """The engine's own packed step at granite-3-2b full width (40 layers,
    d_model 2048) with the fused kernel and an int8 pool of 1024 blocks:
    before the scales moved out of SMEM this step needed 1.02M of its 1.00M.
    Shapes come from jax.eval_shape; the step's kernels must lower to Mosaic
    although this process runs on the CPU, so the interpret switch is
    steered here."""
    from repro.models import model as M
    from repro.serve import PagedEngine
    from repro.serve.paged import init_paged_cache

    monkeypatch.setattr(ops, "_interp", lambda: False)
    cfg = get_config("granite-3-2b").replace(
        cache_layout="paged", decode_kernel="fused", cache_dtype="bfloat16",
        kv_quant="int8")
    b, bs, max_len, n, width = 8, 32, 2048, 1024, 256
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    # a small real pool: the step is compiled at n blocks from shapes alone
    eng = PagedEngine(params, cfg, max_batch=b, max_len=max_len,
                      block_size=bs, num_blocks=max_len // bs + 2)
    assert not eng._use_grid
    s = _spec(one_chip)
    place = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    cache = place(jax.eval_shape(lambda: init_paged_cache(cfg, n, bs, b)))
    extras = {"length": s((b,), jnp.int32),
              "block_table": s((b, max_len // bs), jnp.int32),
              "write_pos": s((1, width), jnp.int32),
              "kv_len": s((width,), jnp.int32),
              "slot_ids": s((width,), jnp.int32),
              "fresh_blocks": s((eng._fresh_cap,), jnp.int32)}
    compiled = eng._packed_fn.lower(
        place(params["weights"]), place(params["hccs"]),
        s((1, width), jnp.int32), s((1, width), jnp.int32), cache, extras,
        s((b,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 16e9, used        # v5e: 16 GB of HBM


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4}
_MOVES = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
                    r"(copy|copy-start|dynamic-slice|dynamic-update-slice)\(")


def _pool_moves(hlo_text, n_blocks, at_least):
    """(name, op, bytes) of every copy, slice or update-slice in the
    compiled text with a result (or an element of a tuple result) that can
    hold a whole layer's pool: at least `at_least` bytes, with an axis of
    the pool's `n_blocks`. The attention core's gathered per-slot K/V,
    which may be as large but has no block axis, is not a pool move."""
    out = []
    for line in hlo_text.splitlines():
        m = _MOVES.match(line)
        if not m:
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(2)):
            dims = [int(d) for d in dims.split(",") if d]
            size = _BYTES.get(dtype, 4)
            for d in dims:
                size *= d
            if size >= at_least and n_blocks in dims:
                out.append((m.group(1), m.group(3), size))
    return out


def test_xla_packed_step_moves_no_pool(one_chip):
    """The engine's packed step on the XLA attention path at the serving
    benchmark's rag-4k shapes: granite-3-2b, a bf16 pool of 1792 blocks of
    32, 16 slots, 512 lanes, a 128-wide attention grid. Its token-major pool
    rides the layer scan's carry and is written in place, so no op copies,
    slices or writes back a layer's pool, and the step's temp stays far
    below one pool (4.7 GB; with the pool as scan xs/ys it read 5.86 GB)."""
    from repro.models import model as M
    from repro.serve import PagedEngine
    from repro.serve.paged import init_paged_cache

    cfg = get_config("granite-3-2b").replace(
        cache_layout="paged", cache_dtype="bfloat16")
    b, bs, max_len, n, width, wb = 16, 32, 4096, 1792, 512, 128
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    eng = PagedEngine(params, cfg, max_batch=b, max_len=max_len,
                      block_size=bs, num_blocks=max_len // bs + 2)
    assert eng._use_grid
    s = _spec(one_chip)
    place = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    cache = place(jax.eval_shape(lambda: init_paged_cache(cfg, n, bs, b)))
    layer_pool = n * bs * HKV * HD * 2
    assert cache["layers"]["k"].shape == (cfg.num_layers, n, bs, HKV * HD)
    extras = {"length": s((b,), jnp.int32),
              "block_table": s((b, max_len // bs), jnp.int32),
              "write_pos": s((1, width), jnp.int32),
              "kv_len": s((width,), jnp.int32),
              "slot_ids": s((width,), jnp.int32),
              "q_pos_grid": s((b, wb), jnp.int32),
              "grid_pos": s((width,), jnp.int32),
              "kv_len_slot": s((b,), jnp.int32)}
    compiled = eng._packed_fn.lower(
        place(params["weights"]), place(params["hccs"]),
        s((1, width), jnp.int32), s((1, width), jnp.int32), cache, extras,
        s((b,), jnp.int32)).compile()
    assert _pool_moves(compiled.as_text(), n, layer_pool) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1e9, temp


def _whole_moves(hlo_text, dims, ops):
    """Names of the ops among `ops` whose result has exactly `dims`."""
    want = ",".join(str(d) for d in dims)
    return [m.group(1) for m in map(_MOVES.match, hlo_text.splitlines())
            if m and m.group(3) in ops
            and re.match(rf"\w+\[{want}\]", m.group(2))]


@pytest.mark.parametrize("width,wb", [(32, 1), (1024, 128)],
                         ids=["decode", "chunk"])
def test_hybrid_packed_step_moves_no_state_arena(one_chip, width, wb):
    """granite-4.0-h-micro's packed step at the rag-4k-32 cell's shapes: 32
    slots, a bf16 pool of 4096 blocks of 32 for the 4 attention layers and
    the state arena of the 36 Mamba-2 layers (2.4 GB of f32 SSM state), for
    a step where every slot decodes one token and for the widest chunk
    step. The arena rides the layer scan's carry and each layer's rows are
    written back in place, so no op copies or slices the whole arena, no op
    moves a layer's pool, and the step's temp stays far below the arena."""
    from repro.models import model as M
    from repro.serve import PagedEngine
    from repro.serve.paged import init_paged_cache

    cfg = get_config("granite-4.0-h-micro").replace(
        cache_layout="paged", cache_dtype="bfloat16")
    b, bs, max_len, n = 32, 32, 4096, 4096
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    # one slot's arena is real; the step is compiled at b slots from shapes
    eng = PagedEngine(params, cfg, max_batch=1, max_len=max_len,
                      block_size=bs, num_blocks=max_len // bs + 2)
    s = _spec(one_chip)
    place = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    cache = place(jax.eval_shape(lambda: init_paged_cache(cfg, n, bs, b)))
    arena = cache["layers"]["ssm"].shape
    assert arena == (36, b, 64, 64, 128)
    assert cache["layers"]["k"].shape == (4, n, bs, HKV * HD)
    extras = {"length": s((b,), jnp.int32),
              "block_table": s((b, max_len // bs), jnp.int32),
              "write_pos": s((1, width), jnp.int32),
              "kv_len": s((width,), jnp.int32),
              "slot_ids": s((width,), jnp.int32),
              "q_pos_grid": s((b, wb), jnp.int32),
              "grid_pos": s((width,), jnp.int32),
              "kv_len_slot": s((b,), jnp.int32)}
    compiled = eng._packed_fn.lower(
        place(params["weights"]), place(params["hccs"]),
        s((1, width), jnp.int32), s((1, width), jnp.int32), cache, extras,
        s((b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _whole_moves(text, arena, ("copy", "copy-start",
                                      "dynamic-slice")) == []
    moves = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
    for pool in ((4, n, bs, HKV * HD), (1, n, bs, HKV * HD),
                 (n, bs, HKV * HD)):
        assert _whole_moves(text, pool, moves) == [], pool
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1e9, temp


@pytest.mark.parametrize("backend,interp", [("cpu", True), ("tpu", False)])
def test_interp_follows_backend(monkeypatch, backend, interp):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interp() is interp


def test_interp_refuses_other_backends(monkeypatch):
    """No silent interpret mode on an accelerator the kernels do not target."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interp()
