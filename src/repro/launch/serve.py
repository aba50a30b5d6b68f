"""Serving launcher: batched generation with optional IHTC KV compression.

    python -m repro.launch.serve --arch gemma2-2b --batch 4 --prompt-len 64 \
        --new-tokens 32 --compress
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.models import build
from repro.serve import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--compress-t", type=int, default=2)
    ap.add_argument("--compress-m", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = smoke_config(ARCHS[args.arch]) if args.smoke else ARCHS[args.arch]
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32)

    eng = ServeEngine(bundle, params, ServeConfig(
        max_new_tokens=args.new_tokens, temperature=args.temperature,
        compress=args.compress, compress_t=args.compress_t,
        compress_m=args.compress_m))
    import time

    t0 = time.perf_counter()
    out = eng.generate({"tokens": prompts})
    sec = time.perf_counter() - t0
    toks = args.batch * out["n_steps"]
    print(f"generated {out['tokens'].shape} in {sec:.2f}s "
          f"({toks / sec:.1f} tok/s, {out['compressions']} recompressions)")


if __name__ == "__main__":
    main()
