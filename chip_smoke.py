#!/usr/bin/env python3
"""Bring-up smoke test of the paged HCCS serving path on one TPU.

    python3 chip_smoke.py [--seed N]

One process, one device, no fallback: without a TPU it exits non-zero
before doing any work. Phases, in order:

  1. device: the first device must be a TPU;
  2. compile cache: repro.launch.compile_cache, before the first compile;
  3. kernels: hccs_packed_prefill and hccs_paged_decode at granite-3-2b head
     geometry (H 32, Hkv 8, head_dim 64, block_size 32) on a bf16 and an int8
     pool of 2048 blocks, against kernels/ref.py at highest matmul precision;
  4. engine, fused kernel: granite-3-2b at full width (40 layers, d_model
     2048, vocab 49155, random bf16 weights from --seed) in PagedEngine's
     packed step, serving 8 requests of 200-1500 prompt tokens and 32 new
     tokens each, with a bf16 pool of 512 blocks;
  5. engine, int8 pool of 1024 blocks, otherwise as phase 4;
  6. engine, the default XLA attention path (decode_kernel="none"); its
     greedy agreement with phase 4 is printed as information.

The engine phases print warm-up and compile seconds, steps, tokens and the
device's live and peak bytes: a record of the run, not a benchmark. Any
failed phase raises. The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

# H, Hkv, head_dim of granite-3-2b, and the serving geometry of phases 4-6
H, HKV, HD = 32, 8, 64
BLOCK, MAX_LEN, MAX_BATCH = 32, 2048, 8
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (200, 1500), 32
# worst |kernel - oracle| over outputs of magnitude up to ~1.5. On a v5e the
# worst seen was 5.9e-3 (int8 pool, packed kernel): Mosaic's f32 dot rounds
# differently from XLA's, and a logit that lands across an int8 bin edge
# moves one key's HCCS score by S
KERNEL_ATOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_bytes(dev) -> str:
    """Live and peak device bytes; the peak is the process's, not a phase's."""
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def check_device():
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: no TPU: JAX found no backend ({e})")
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    return dev, len(devices)


def kernel_case(rng, quant: bool, n_blocks: int = 2048):
    """A bf16 or int8 lane-padded pool of n_blocks with 8 slots' block tables
    over MAX_LEN tokens, 256 packed tokens (16 of them pad lanes) and one
    decode query per slot. Pad lanes of head_dim stay zero, as the engine
    allocates them."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.constraints import default_params

    nblk = MAX_LEN // BLOCK
    shape = (n_blocks, HKV, BLOCK, HD)
    if quant:
        k = rng.integers(-127, 128, shape, dtype=np.int8)
        v = rng.integers(-127, 128, shape, dtype=np.int8)
        ks = rng.uniform(2e-3, 2e-2, (n_blocks, HKV)).astype(np.float32)
        vs = rng.uniform(2e-3, 2e-2, (n_blocks, HKV)).astype(np.float32)
    else:
        k = rng.normal(0, 1, shape).astype(jnp.bfloat16)
        v = rng.normal(0, 1, shape).astype(jnp.bfloat16)
        ks = vs = None
    pad = ((0, 0),) * 3 + ((0, 128 - HD),)
    slot_len = rng.integers(1, MAX_LEN + 1, MAX_BATCH)
    ids = rng.permutation(n_blocks)[:MAX_BATCH * nblk].reshape(MAX_BATCH, nblk)
    live = np.arange(nblk)[None] * BLOCK < slot_len[:, None]
    table = np.where(live, ids, -1).astype(np.int32)
    t, n_pad = 256, 16
    sid = np.full(t, -1, np.int32)
    sid[:t - n_pad] = np.sort(rng.integers(0, MAX_BATCH, t - n_pad))
    tok_len = np.where(sid >= 0, rng.integers(1, MAX_LEN + 1, t), 0)
    tok_len = np.minimum(tok_len, slot_len[np.maximum(sid, 0)]).astype(np.int32)
    B, S, D = default_params(128)
    return dict(
        k=jnp.asarray(np.pad(k, pad)), v=jnp.asarray(np.pad(v, pad)),
        ks=None if ks is None else jnp.asarray(ks),
        vs=None if vs is None else jnp.asarray(vs),
        table=jnp.asarray(table), slot_len=jnp.asarray(slot_len, jnp.int32),
        sid=jnp.asarray(sid), tok_len=jnp.asarray(tok_len),
        q_tok=jnp.asarray(rng.normal(0, 1, (t, H, HD)), jnp.float32),
        q_slot=jnp.asarray(rng.normal(0, 1, (MAX_BATCH, H, HD)), jnp.float32),
        scale=jnp.full((H,), 0.05, jnp.float32),
        theta=jnp.asarray(np.tile([[B, S, D]], (H, 1)), jnp.int32))


def phase_kernels(seed: int) -> None:
    import jax
    import numpy as np
    from repro.kernels import ops
    from repro.kernels import ref as REF

    rng = np.random.default_rng(seed)
    for quant in (False, True):
        c = kernel_case(rng, quant)
        pool = "int8" if quant else "bf16"
        k_ref, v_ref = c["k"][..., :HD], c["v"][..., :HD]
        sc = dict(k_scales=c["ks"], v_scales=c["vs"])
        runs = {
            "hccs_packed_prefill": (
                lambda: ops.hccs_packed_prefill(
                    c["q_tok"], c["k"], c["v"], c["table"], c["sid"],
                    c["tok_len"], c["scale"], c["theta"], **sc),
                lambda: REF.hccs_packed_prefill_ref(
                    c["q_tok"], k_ref, v_ref, c["table"], c["sid"],
                    c["tok_len"], c["scale"], c["theta"], **sc)),
            "hccs_paged_decode": (
                lambda: ops.hccs_paged_decode(
                    c["q_slot"], c["k"], c["v"], c["table"], c["slot_len"],
                    c["scale"], c["theta"], **sc),
                lambda: REF.hccs_paged_decode_ref(
                    c["q_slot"], k_ref, v_ref, c["table"], c["slot_len"],
                    c["scale"], c["theta"], **sc)),
        }
        for name, (kernel, oracle) in runs.items():
            got = np.asarray(jax.block_until_ready(kernel()))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(oracle())
            if not (np.isfinite(got).all() and got.shape == want.shape):
                raise AssertionError(f"{name} {pool}: bad output")
            err = np.abs(got - want)
            log(f"[kernels] {name} pool={pool} N={c['k'].shape[0]} "
                f"max_err={err.max():.3e} mean_err={err.mean():.3e} "
                f"atol={KERNEL_ATOL:.0e} max|want|={np.abs(want).max():.3f}")
            if err.max() > KERNEL_ATOL:
                raise AssertionError(
                    f"{name} {pool}: max error {err.max():.3e} > "
                    f"{KERNEL_ATOL:.0e}")
        del c, runs
    gc.collect()


class CompileClock:
    """Seconds the backend spent compiling, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def make_requests(seed: int, vocab: int):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(uid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=NEW_TOKENS) for i, n in enumerate(lens)]


def phase_engine(name, params, cfg, num_blocks, seed, dev, clock,
                 kernel: bool):
    import jax
    from repro.models.attention import decode_kernel_blockers
    from repro.serve import PagedEngine

    blockers = decode_kernel_blockers(cfg)
    if kernel and (cfg.decode_kernel == "none" or blockers):
        raise AssertionError(f"{name}: the kernel cannot run: {blockers}")
    eng = PagedEngine(params, cfg, max_batch=MAX_BATCH,
                      max_len=MAX_LEN, block_size=BLOCK,
                      num_blocks=num_blocks, admission=None)
    if eng._use_grid == kernel:
        raise AssertionError(f"{name}: engine attention path is not the "
                             f"{'kernel' if kernel else 'XLA'} path")
    first_call = []
    step_fn = eng._packed_fn

    def recorded(*args):
        if not first_call:
            first_call.extend(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args))
        return step_fn(*args)

    eng._packed_fn = recorded
    reqs = make_requests(seed, cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    compile0, t0 = clock.seconds, time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - compile0
    if sorted(r.uid for r in done) != list(range(N_REQUESTS)):
        raise AssertionError(f"{name}: finished {len(done)} of {N_REQUESTS}")
    for r in reqs:
        if r.failed or len(r.out_tokens) != NEW_TOKENS or not all(
                0 <= t < cfg.vocab_size for t in r.out_tokens):
            raise AssertionError(f"{name}: request {r.uid} ended with "
                                 f"{len(r.out_tokens)} tokens "
                                 f"(failed={r.failed}, {r.fail_reason})")
    hlo = step_fn.lower(*first_call).compile().as_text()
    if ("tpu_custom_call" in hlo) != kernel:
        raise AssertionError(f"{name}: tpu_custom_call "
                             f"{'missing from' if kernel else 'in'} the step")
    log(f"[{name}] warmup_s={wall:.1f} compile_s={compile_s:.1f}"
        f" steps={eng.occupancy_steps} tokens="
        f"{sum(len(r.out_tokens) for r in reqs)} prompt_tokens="
        f"{sum(len(r.prompt) for r in reqs)} kernel_in_step={kernel} "
        f"{device_bytes(dev)}")
    outs = [list(r.out_tokens) for r in reqs]
    del eng, reqs, done
    gc.collect()
    return outs


def agreement(a, b) -> str:
    same = sum(x == y for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    total = sum(len(r) for r in a)
    whole = sum(ra == rb for ra, rb in zip(a, b))
    return f"{same}/{total} tokens, {whole}/{len(a)} requests identical"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev, count = check_device()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    from repro.configs import get_config
    from repro.models import model as M

    clock = CompileClock()
    t0 = time.perf_counter()
    phase_kernels(args.seed)
    log(f"[kernels] ok in {time.perf_counter() - t0:.1f}s")

    base = get_config("granite-3-2b").replace(
        cache_layout="paged", decode_kernel="fused", cache_dtype="bfloat16")
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), base))
    n_params = sum(x.size for x in jax.tree.leaves(params["weights"]))
    log(f"[params] {base.name} layers={base.num_layers} "
        f"d_model={base.d_model} vocab={base.vocab_size} params={n_params} "
        f"dtype={base.dtype} init_s={time.perf_counter() - t0:.1f} "
        f"{device_bytes(dev)}")

    fused = phase_engine("engine-fused-bf16", params, base, 512, args.seed,
                         dev, clock, kernel=True)
    int8 = phase_engine("engine-fused-int8", params,
                        base.replace(kv_quant="int8"), 1024, args.seed, dev,
                        clock, kernel=True)
    xla = phase_engine("engine-xla-bf16", params,
                       base.replace(decode_kernel="none"), 512, args.seed,
                       dev, clock, kernel=False)
    log(f"[agreement] int8 vs fused bf16: {agreement(int8, fused)}")
    log(f"[agreement] xla vs fused bf16: {agreement(xla, fused)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
