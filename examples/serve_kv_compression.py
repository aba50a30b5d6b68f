"""Serve a small model with batched requests and IHTC KV-cache compression —
the paper's instance selection applied to long-context inference.

Shows: batched prefill → greedy decode, cache compressed by (t*)^m with
log-mass bias correction, periodic recompression as the fresh tail fills,
and the logit agreement between compressed and exact decoding.

    python examples/serve_kv_compression.py --prompt-len 96 --new-tokens 32
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.configs import ARCHS, smoke_config
    from repro.models import build
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.kv_compression import compress_model_caches

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--m", type=int, default=1)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = smoke_config(ARCHS[args.arch])
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # repetitive prompts -> clusterable KV sets (the regime IHTC exploits)
    prompts = jnp.asarray(
        rng.integers(0, 12, size=(args.batch, args.prompt_len)), jnp.int32)

    # --- exact vs compressed single-step logit agreement ---
    caches = bundle.init_caches(args.batch, args.prompt_len + args.new_tokens)
    lg, caches = bundle.prefill(params, caches, {"tokens": prompts})
    comp = compress_model_caches(caches, args.t, args.m, tail=16, impl="ref")
    nxt = jnp.argmax(lg[:, -1], -1)[:, None]
    l_exact, _ = bundle.decode_step(params, caches, {"tokens": nxt})
    l_comp, _ = bundle.decode_step(params, comp, {"tokens": nxt})
    p1 = jax.nn.softmax(l_exact[:, -1].astype(jnp.float32), -1)
    p2 = jax.nn.softmax(l_comp[:, -1].astype(jnp.float32), -1)
    tv = 0.5 * float(jnp.mean(jnp.sum(jnp.abs(p1 - p2), -1)))
    agree = float(jnp.mean(jnp.argmax(p1, -1) == jnp.argmax(p2, -1)))
    full_slots = caches["prefix"][0]["k"].shape[2] if caches["prefix"] else \
        caches["stack"][0]["k"].shape[3]
    comp_slots = comp["prefix"][0]["k"].shape[2] if comp["prefix"] else \
        comp["stack"][0]["k"].shape[3]
    print(f"cache slots {full_slots} -> {comp_slots} "
          f"({args.t}^{args.m} compression + tail)")
    print(f"decode agreement: TV={tv:.3f}, top-1 match={agree:.2f}")

    # --- full generation with periodic recompression ---
    eng = ServeEngine(bundle, params, ServeConfig(
        max_new_tokens=args.new_tokens, compress=True,
        compress_t=args.t, compress_m=args.m, compress_tail=16))
    out = eng.generate({"tokens": prompts})
    print(f"generated {out['tokens'].shape} tokens with "
          f"{out['compressions']} in-flight recompressions")
    print("sample:", np.asarray(out["tokens"][0][:16]))


if __name__ == "__main__":
    main()
