"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
the full production substrate — deterministic data pipeline, optional IHTC
instance selection, AdamW+ZeRO, fault-tolerant loop, async checkpoints.

    python examples/train_lm.py --arch mamba2-370m --steps 200 --width 256

(`--width` scales d_model down so a few hundred steps fit a CPU session;
drop it on real hardware to train the full config.)
"""
import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

import jax
import numpy as np


def main():
    from repro.configs import ARCHS, SHAPES, smoke_config
    from repro.data import make_batch
    from repro.models import build
    from repro.train import (CheckpointManager, OptConfig, init_opt_state,
                             make_train_step)
    from repro.train.fault_tolerance import run_training

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--width", type=int, default=0,
                    help="override d_model (0 = full config)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    if args.width:
        cfg = smoke_config(cfg)
        kw = dict(d_model=args.width)
        if cfg.n_heads:
            kw["head_dim"] = max(args.width // max(cfg.n_heads, 1), 8)
        if args.layers:
            kw["n_layers"] = args.layers
        cfg = dataclasses.replace(cfg, **kw)
    bundle = build(cfg)

    params = bundle.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch}x{args.seq}")

    opt = init_opt_state(params)
    step = jax.jit(make_train_step(bundle, OptConfig(
        peak_lr=args.lr, warmup_steps=20, decay_steps=args.steps)))
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    losses = []

    def on_metrics(s, m):
        losses.append(float(m["loss"]))
        if s % 20 == 0 or s == args.steps - 1:
            print(f"step {s:>5}  loss {losses[-1]:.4f}  "
                  f"lr {float(m['lr']):.2e}  gnorm {float(m['grad_norm']):.2f}")

    params, opt, stats = run_training(
        train_step=step,
        init_state=(params, opt),
        batch_for_step=lambda s: make_batch(
            cfg, SHAPES["train_4k"], s, batch_override=args.batch,
            seq_override=args.seq),
        n_steps=args.steps,
        ckpt=ckpt, ckpt_every=50,
        on_metrics=on_metrics,
    )
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"step-time p50 {stats.quantiles().get('p50', 0):.3f}s; "
          f"checkpoints at {args.ckpt_dir}: {ckpt.all_steps()}")


if __name__ == "__main__":
    main()
