"""Training launcher: real training on the local device(s), or a sharded run
when launched under a multi-device environment.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --steps 200 --batch 8 --seq 256 --reduced

--reduced uses the smoke-scale config (CPU-friendly); without it the full
config is used (requires a real TPU slice).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prob", default=None, choices=[None, "hccs", "softmax"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data", default="data")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced_config
    from repro.configs.base import TrainConfig
    from repro.data import LMStream, LMStreamConfig, make_embedding_batch
    from repro.train import make_train_state, make_train_step, train_loop

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.prob and cfg.num_heads:
        cfg = cfg.replace(attention_prob=args.prob)
    tcfg = TrainConfig(total_steps=args.steps, learning_rate=args.lr,
                       warmup_steps=max(args.steps // 10, 1),
                       grad_compression=args.grad_compression)

    state = make_train_state(jax.random.PRNGKey(tcfg.seed), cfg, tcfg)
    step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=0)

    if cfg.input_mode == "embeddings":
        import numpy as np

        def batch_fn(s):
            rng = np.random.default_rng(1000 + s)
            b = make_embedding_batch(rng, args.batch, args.seq, cfg.d_model,
                                     cfg.vocab_size)
            return {k: jnp.asarray(v) for k, v in b.items()}
    else:
        stream = LMStream(LMStreamConfig(vocab_size=cfg.vocab_size,
                                         seq_len=args.seq,
                                         global_batch=args.batch,
                                         seed=tcfg.seed))

        def batch_fn(s):
            return {k: jnp.asarray(v) for k, v in stream.batch_at(s).items()}

    state, history = train_loop(
        state, step, batch_fn, total_steps=args.steps,
        ckpt_dir=args.ckpt_dir, cfg=cfg, log_every=10,
        install_signal_handlers=True)
    print(f"final loss {history[-1]['loss']:.4f} "
          f"(start {history[0]['loss']:.4f}) over {len(history)} steps")


if __name__ == "__main__":
    main()
