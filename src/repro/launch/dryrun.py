"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract roofline terms. Zero device allocation (ShapeDtypeStruct inputs).

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all --mesh both
Records JSON per cell under experiments/dryrun/.
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax locks
# the device count on first init, so this MUST precede every other import.
# (REPRO_DRYRUN_DEVICES overrides for the mini dry-run integration test.)
import os
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count="
    f"{os.environ.get('REPRO_DRYRUN_DEVICES', '512')} "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, input_specs, iter_cells
from repro.configs.base import TrainConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.parallel import sharding as SH
from repro.train import steps as TS


def _tree_shardings(tree, mesh, stacked_token="layers"):
    """NamedSharding tree via the path-regex param rules (works for the whole
    train state: opt moments mirror weight paths)."""
    def one(path, leaf):
        name = SH._path_str(path)
        return NamedSharding(mesh, _leaf_spec(name, leaf, stacked_token))
    return jax.tree_util.tree_map_with_path(one, tree)


def _leaf_spec(name, leaf, stacked_token="layers"):
    import re
    stacked = f"{stacked_token}/" in name
    for pat, logical in SH._PARAM_RULES:
        if re.search(pat, name):
            rank = leaf.ndim - (1 if stacked else 0)
            ax = list(logical)[:rank]
            ax += [None] * (rank - len(ax))
            return SH.spec(*([None] if stacked else []) + ax)
    return SH.spec(*([None] * leaf.ndim))


def _batch_shardings(batch_specs, mesh, batch_divisible):
    def one(path, leaf):
        name = SH._path_str(path)
        if name == "mrope_positions":
            ax = [None, "batch" if batch_divisible else None] + \
                 [None] * (leaf.ndim - 2)
        else:
            ax = ["batch" if batch_divisible else None] + \
                 [None] * (leaf.ndim - 1)
        return NamedSharding(mesh, SH.spec(*ax))
    return jax.tree_util.tree_map_with_path(one, batch_specs)


def _cache_shardings(cache_shapes, mesh, batch_divisible):
    """(L,B,hkv,T,hd) attn caches / (L,B,nh,N,P) ssm states / length scalar."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(path, leaf):
        name = SH._path_str(path)
        if "hot_" in name:   # replicated hot buffer (small, static writes)
            ax = (None, "batch") + (None,) * (leaf.ndim - 2)
        elif leaf.ndim == 5 and ("/k" in name or "/v" in name):
            if batch_divisible:
                ax = (None, "batch", None, "cache_seq", None)
            else:
                ax = (None, None, None, "seq_kv_joint", None)
        elif leaf.ndim == 5:  # ssm state: heads over model iff divisible
            if batch_divisible:
                ax = (None, "batch", None, None, None)
            elif leaf.shape[2] % sizes.get("model", 1) == 0:
                ax = (None, None, "ssm_heads", None, None)
            else:  # e.g. hymba's 50 SSM heads on a 16-way TP axis: replicate
                ax = (None, None, None, None, None)
        else:
            ax = (None,) * leaf.ndim
        return NamedSharding(mesh, SH.spec(*ax))
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


# extra logical axes used only by the cache layouts above
_EXTRA_RULES = {
    "seq_kv_joint": ("data", "model"),   # long-context: shard cache T jointly
    "cache_seq": "model",                # KV-cache seq dim (survives profiles
                                         # that unmap "model" for weights)
    "ssm_heads": "model",
}

# sharding profiles (hillclimb levers; see EXPERIMENTS.md §Perf):
#   default  — TP(model) + FSDP(data) weights, SP residual: the training layout
#   serve_sp — inference layout: weights REPLICATED (no FSDP/TP gathers per
#              token), activations sequence-sharded over the model axis; the
#              only per-layer collective left is the GQA KV all-gather, which
#              is H_kv/H smaller than the residual stream. Experts stay
#              EP-sharded (MoE weights don't fit replicated).
PROFILES = {
    "default": {},
    "serve_sp": {"fsdp": None, "model": None, "ffn": None, "vocab": None,
                 "kv_model": None, "seq_act": "model", "attn_seq": "model",
                 "seq_kv_joint": "model"},
    # training with sequence-sharded q inside attention instead of
    # head-sharded scores: avoids score replication when the head count is
    # not TP-divisible (hymba: 25 heads on a 16-way axis)
    "train_sp_attn": {"attn_seq": "model", "kv_model": None},
    # inference for models too big to replicate (yi-34b): keep TP on the
    # weights, drop only the FSDP-over-data sharding (no per-token gathers;
    # weights resident, replicated across the data axis)
    "serve_tp": {"fsdp": None},
}


def _mesh_batch(mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)


def build_cell(arch: str, shape_name: str, mesh, prob: str | None = None,
               hccs_router: bool = False, remat: str | None = None,
               num_layers: int | None = None, seq_parallel: bool = True,
               extra_rules: dict | None = None, scan_unroll: int = 1,
               hot_buffer: int = 0):
    """Returns (lower_fn, meta) — lower_fn() does the jit lowering."""
    cfg = get_config(arch)
    if hot_buffer:
        cfg = cfg.replace(hot_buffer=hot_buffer)
    if prob and cfg.num_heads:
        cfg = cfg.replace(attention_prob=prob)
    if hccs_router and cfg.is_moe:
        cfg = cfg.replace(hccs_router=True)
    cfg = cfg.replace(remat=remat or "full", scan_unroll=scan_unroll)
    if num_layers:
        cfg = cfg.replace(num_layers=num_layers)
    shape = SHAPES[shape_name]
    tcfg = TrainConfig()
    nb = _mesh_batch(mesh)
    batch_div = shape.global_batch % nb == 0
    rules = dict(_EXTRA_RULES)
    if seq_parallel and shape.kind in ("train", "prefill"):
        # sequence parallelism on the residual stream AND seq-sharded q
        # inside attention (train_sp_attn; measured strictly better than
        # head-sharded scores on every train cell — see §Perf A4/B2)
        rules["seq_act"] = "model"
        rules["attn_seq"] = "model"
        rules["kv_model"] = None
    if not batch_div:
        rules["batch"] = None
    if extra_rules:
        rules.update(extra_rules)
    if shape.kind == "decode":
        rules["seq_act"] = None     # decode steps have t=1

    batch_specs = input_specs(cfg, shape)

    def lower():
        with SH.use_rules(mesh, rules):
            bsh = _batch_shardings(batch_specs, mesh, batch_div)
            if shape.kind == "train":
                state_shapes = jax.eval_shape(
                    lambda: TS.make_train_state(jax.random.PRNGKey(0), cfg, tcfg))
                ssh = _tree_shardings(state_shapes, mesh)
                step = TS.make_train_step(cfg, tcfg)
                fn = jax.jit(step, in_shardings=(ssh, bsh),
                             donate_argnums=0)
                return fn.lower(state_shapes, batch_specs), state_shapes

            weights_shapes = jax.eval_shape(
                lambda: M.init_params(jax.random.PRNGKey(0), cfg))
            wsh = _tree_shardings(weights_shapes, mesh)
            if shape.kind == "prefill":
                cache_shapes = jax.eval_shape(
                    lambda: M.init_cache(cfg, shape.global_batch,
                                         shape.seq_len))
                csh = _cache_shardings(cache_shapes, mesh, batch_div)

                def prefill_step(params, batch):
                    return M.prefill(params["weights"], params["hccs"],
                                     batch, cfg, max_len=shape.seq_len)
                fn = jax.jit(prefill_step, in_shardings=(wsh, bsh),
                             out_shardings=(None, csh))
                return fn.lower(weights_shapes, batch_specs), weights_shapes

            # decode: one new token against a seq_len cache
            cache_shapes = jax.eval_shape(
                lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
            csh = _cache_shardings(cache_shapes, mesh, batch_div)

            if cfg.input_mode == "embeddings":
                def decode(params, batch, cache):
                    return M.decode_step(params["weights"], params["hccs"],
                                         None, cache, cfg,
                                         embeddings=batch["embeddings"])
            else:
                def decode(params, batch, cache):
                    return M.decode_step(params["weights"], params["hccs"],
                                         batch["tokens"], cache, cfg)
            fn = jax.jit(decode, in_shardings=(wsh, bsh, csh),
                         out_shardings=(None, csh), donate_argnums=2)
            return fn.lower(weights_shapes, batch_specs,
                            cache_shapes), weights_shapes

    return lower, dict(cfg=cfg, shape=shape, tcfg=tcfg)


def _measure(lowered) -> dict:
    compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = RL.collective_bytes(compiled.as_text())
    return dict(flops=float(ca.get("flops", 0.0)),
                bytes=float(ca.get("bytes accessed", 0.0)),
                coll=float(coll["total_bytes"]),
                coll_detail=coll,
                compiled=compiled)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             prob: str | None = None, tag: str = "", remat: str | None = None,
             hccs_router: bool = False, seq_parallel: bool = True,
             extra_rules: dict | None = None, extrapolate: bool = True,
             profile: str = "default", hot_buffer: int = 0) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = int(np.prod(mesh.devices.shape))
    rules = dict(PROFILES[profile], **(extra_rules or {}))
    kw = dict(prob=prob, remat=remat, hccs_router=hccs_router,
              seq_parallel=seq_parallel, extra_rules=rules,
              hot_buffer=hot_buffer)
    lower_fn, meta = build_cell(arch, shape_name, mesh, **kw)
    cfg, shape = meta["cfg"], meta["shape"]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "prob": prob or cfg.attention_prob,
           "remat": cfg.remat, "tag": tag, "profile": profile, "ok": False}
    t0 = time.time()
    try:
        with mesh:
            lowered, param_shapes = lower_fn()
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            m_full = _measure(lowered)
            compiled = m_full["compiled"]
            rec["compile_s"] = round(time.time() - t1, 1)

            mem = compiled.memory_analysis()
            print(mem)    # proves it fits

            # --- scan-body correction -------------------------------------
            # XLA cost_analysis counts a while-loop body ONCE regardless of
            # trip count; with scan-over-layers the L-layer totals must be
            # extrapolated from 1- and 2-layer compiles of the same cell:
            #   body = m(2) - m(1);   total = m(1) + (L-1) * body
            # (the L=2 compile is force-unrolled: XLA's cost analysis counts a
            # while body once, so both extrapolation points must be loop-free)
            L = get_config(arch).num_layers
            if extrapolate and L > 1:
                l1, _ = build_cell(arch, shape_name, mesh, num_layers=1, **kw)
                l2, _ = build_cell(arch, shape_name, mesh, num_layers=2,
                                   scan_unroll=2, **kw)
                m1 = _measure(l1()[0])
                m2 = _measure(l2()[0])
                def tot(key):
                    body = max(m2[key] - m1[key], 0.0)
                    return m1[key] + (L - 1) * body
                flops_dev = tot("flops")
                bytes_dev = tot("bytes")
                coll_dev = tot("coll")
                rec["scan_once"] = {k: m_full[k] for k in ("flops", "bytes", "coll")}
                rec["body_per_layer"] = {k: m2[k] - m1[k]
                                         for k in ("flops", "bytes", "coll")}
            else:
                flops_dev, bytes_dev, coll_dev = (m_full["flops"],
                                                  m_full["bytes"],
                                                  m_full["coll"])
            print({"flops/dev": flops_dev, "bytes/dev": bytes_dev,
                   "coll/dev": coll_dev})
            coll = m_full["coll_detail"]
            terms = RL.roofline_terms(flops_dev, bytes_dev, coll_dev)

            if shape.kind == "train":
                wshapes = param_shapes["params"]["weights"]
            else:
                wshapes = param_shapes["weights"]
            n_params = RL.count_params(wshapes)
            n_active = RL.count_active_params(cfg, wshapes)
            tokens = shape.global_batch * (
                shape.seq_len if shape.kind != "decode" else 1)
            mflops = RL.model_flops(cfg, n_params, n_active, tokens, shape.kind)

            rec.update(
                ok=True,
                flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
                collectives={k: v for k, v in coll.items()},
                roofline=terms,
                n_params=n_params, n_active=n_active, tokens=tokens,
                model_flops=mflops,
                useful_flops_ratio=(mflops / (flops_dev * chips)
                                    if flops_dev else 0.0),
                memory=dict(
                    argument_bytes=mem.argument_size_in_bytes,
                    output_bytes=mem.output_size_in_bytes,
                    temp_bytes=mem.temp_size_in_bytes,
                    alias_bytes=mem.alias_size_in_bytes,
                ),
            )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(out_dir,
                        f"{arch}_{shape_name}_{mesh_kind}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '?')[:120]})"
    print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: {status} "
          f"({rec['total_s']}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--prob", default=None, choices=[None, "hccs", "softmax"])
    ap.add_argument("--remat", default=None)
    ap.add_argument("--hccs-router", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--profile", default="default", choices=list(PROFILES))
    ap.add_argument("--hot-buffer", type=int, default=0)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch, shape, ok in iter_cells(include_skipped=False):
            for mk in meshes:
                run_cell(arch, shape.name, mk, args.out, prob=args.prob,
                         tag=args.tag, remat=args.remat,
                         hccs_router=args.hccs_router, profile=args.profile,
                         hot_buffer=args.hot_buffer)
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        for mk in meshes:
            run_cell(args.arch, args.shape, mk, args.out, prob=args.prob,
                     tag=args.tag, remat=args.remat,
                     hccs_router=args.hccs_router, profile=args.profile,
                     hot_buffer=args.hot_buffer)


if __name__ == "__main__":
    main()
