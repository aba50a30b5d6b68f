import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract roofline terms. The two lines above MUST run before any jax import —
jax locks the device count at first init. This is the ONLY entry point that
requests 512 host devices (tests/benches see 1).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/results/dryrun
  python -m repro.launch.dryrun --arch granite-20b --shape long_500k \
      --variant ihtc-kv   # paper-technique-compressed long context
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config  # noqa: E402
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    batch_specs,
    data_axes,
    make_plan,
    make_production_mesh,
)
from repro.models import build  # noqa: E402
from repro.models.frontends import VISION_PREFIX_TOKENS  # noqa: E402
from repro.train.optimizer import OptConfig, init_opt_state, zero_opt_specs  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402
from repro.utils import hlo as hlo_utils  # noqa: E402
from repro.utils.roofline import build_report, model_flops_for  # noqa: E402
from repro.utils.tree import tree_size  # noqa: E402

# the chip the host-device dry-run stands in for (its roofline peaks)
TARGET_DEVICE_KIND = "TPU v5 lite"

# long_500k baseline needs sub-quadratic sequence mixing: only ssm/hybrid
# qualify (DESIGN.md §6). Dense/MoE/enc-dec archs run it only under the
# --variant ihtc-kv paper-technique compression.
LONG_OK_FAMILIES = ("ssm", "hybrid")


def cell_is_baseline_runnable(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    if shape.name == "long_500k" and cfg.family not in LONG_OK_FAMILIES:
        return False
    return True


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shard_tree(mesh, abstract, specs):
    return jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, NamedSharding(mesh, s)),
        abstract,
        specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )


def _active_params(cfg: ModelConfig, abstract_params) -> int:
    total = tree_size(abstract_params)
    if not cfg.tie_embeddings:
        total -= cfg.vocab_size * cfg.d_model  # gather table is not matmul flops
    if cfg.n_experts:
        per_expert = 3 * cfg.d_model * cfg.d_ff
        n_moe = sum(cfg.layer_is_moe(l) for l in range(cfg.n_layers))
        total -= n_moe * (cfg.n_experts - cfg.n_experts_per_tok) * per_expert
    return int(total)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, *, kind: str,
                variant: str = "baseline"):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bx = dp if (b % dp_size == 0 and b >= dp_size) else None
    sh = lambda spec: NamedSharding(mesh, spec)

    if kind == "train":
        batch = {
            "tokens": _sds((b, s), jnp.int32, sh(P(bx, None))),
            "labels": _sds((b, s), jnp.int32, sh(P(bx, None))),
        }
        if cfg.frontend == "vision":
            batch["patch_embeds"] = _sds(
                (b, VISION_PREFIX_TOKENS, cfg.d_model), jnp.bfloat16,
                sh(P(bx, None, None)))
        if cfg.frontend == "audio":
            batch["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16,
                                   sh(P(bx, None, None)))
        return batch
    if kind == "prefill":
        batch = {"tokens": _sds((b, s), jnp.int32, sh(P(bx, None)))}
        if cfg.frontend == "vision":
            batch["patch_embeds"] = _sds(
                (b, VISION_PREFIX_TOKENS, cfg.d_model), jnp.bfloat16,
                sh(P(bx, None, None)))
        if cfg.frontend == "audio":
            batch["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16,
                                   sh(P(bx, None, None)))
        return batch
    # decode: one new token against a seq_len KV cache
    return {"tokens": _sds((b, 1), jnp.int32, sh(P(bx, None)))}


def _add_ihtc_bias(c, s):
    """Recursively add prototype bias/mass entries to attention caches
    (shape = k.shape minus head_dim), mirrored in the spec tree."""
    if isinstance(c, dict):
        if "k" in c and "pos" in c:
            kshape = c["k"].shape
            kspec = tuple(s["k"]) + (None,) * (len(kshape) - len(tuple(s["k"])))
            bias_spec = P(*kspec[:-1])
            c, s = dict(c), dict(s)
            c["bias"] = _sds(kshape[:-1], jnp.float32)
            c["mass"] = _sds(kshape[:-1], jnp.float32)
            s["bias"] = bias_spec
            s["mass"] = bias_spec
            return c, s
        cc, ss = {}, {}
        for k2 in c:
            cc[k2], ss[k2] = _add_ihtc_bias(c[k2], s[k2])
        return cc, ss
    if isinstance(c, (list, tuple)):
        pairs = [_add_ihtc_bias(a, b) for a, b in zip(c, s, strict=True)]
        return [p[0] for p in pairs], [p[1] for p in pairs]
    return c, s


def cache_abstract(cfg: ModelConfig, shape: ShapeConfig, mesh, bundle,
                   plan, variant: str):
    """(abstract caches, cache sharding tree) for prefill/decode cells."""
    b, s = shape.global_batch, shape.seq_len
    kw = {}
    if cfg.family == "encdec-audio":
        kw["enc_len"] = s
    if variant == "ihtc-kv":
        t, m, tail = 2, 2, 1024  # 4× compression + fresh tail
        s_c = s // (t**m) + tail
        caches = jax.eval_shape(lambda: bundle.init_caches(b, s_c, **kw))
    else:
        caches = jax.eval_shape(lambda: bundle.init_caches(b, s, **kw))

    tp_size = mesh.shape["model"]
    spec_tree = bundle.cache_specs(plan=plan, tp_size=tp_size)
    if variant == "ihtc-kv":
        caches, spec_tree = _add_ihtc_bias(caches, spec_tree)

    sharded = jax.tree_util.tree_map(
        lambda a, sp: _sds(a.shape, a.dtype, NamedSharding(mesh, sp)),
        caches, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    return sharded, spec_tree


def _lower_and_compile(cfg, shape, mesh, *, variant, parallel, kind,
                       heads_mode="auto", param_dtype="float32"):
    """Lower + compile one step for (possibly layer-reduced) cfg; return raw
    per-chip cost artifacts."""
    bundle = build(cfg)
    plan = make_plan(cfg, shape, mesh, heads_mode=heads_mode)
    tp_size = mesh.shape["model"]
    dp = data_axes(mesh)
    master = param_dtype == "bfloat16"

    abstract_params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    if master:  # bf16 working params; fp32 master lives in the opt state
        abstract_params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape,
                jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
            abstract_params,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
    pspecs = bundle.param_specs(tp="model", tp_size=tp_size)
    params_in = _shard_tree(mesh, abstract_params, pspecs)
    t0 = time.time()

    with mesh:
        if kind == "train":
            opt_abstract = jax.eval_shape(
                lambda p: init_opt_state(p, master=master), abstract_params)
            ospecs = zero_opt_specs(
                pspecs, abstract_params, dp, dict(mesh.shape),
                zero_stage=parallel.zero_stage, master=master,
            )
            opt_in = _shard_tree(mesh, opt_abstract, ospecs)
            batch_in = input_specs(cfg, shape, mesh, kind="train")
            step = make_train_step(bundle, OptConfig(), parallel, plan)
            jitted = jax.jit(
                step,
                out_shardings=(
                    jax.tree_util.tree_map(
                        lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P)),
                    jax.tree_util.tree_map(
                        lambda s: NamedSharding(mesh, s), ospecs,
                        is_leaf=lambda x: isinstance(x, P)),
                    None,
                ),
            )
            lowered = jitted.lower(params_in, opt_in, batch_in)
        elif kind == "prefill":
            caches_in, _ = cache_abstract(cfg, shape, mesh, bundle, plan, variant)
            batch_in = input_specs(cfg, shape, mesh, kind="prefill")

            def prefill_fn(params, caches, batch):
                return bundle.prefill(params, caches, batch, plan=plan)

            lowered = jax.jit(prefill_fn).lower(params_in, caches_in, batch_in)
        else:  # decode
            caches_in, _ = cache_abstract(cfg, shape, mesh, bundle, plan, variant)
            batch_in = input_specs(cfg, shape, mesh, kind="decode",
                                   variant=variant)

            def decode_fn(params, caches, batch):
                return bundle.decode_step(params, caches, batch, plan=plan)

            lowered = jax.jit(decode_fn).lower(params_in, caches_in, batch_in)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo_text = compiled.as_text()
    return {
        "abstract_params": abstract_params,
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": hlo_utils.collective_bytes(hlo_text),
        "coll_counts": hlo_utils.collective_op_counts(hlo_text),
        "mem": mem,
        "t_lower": t_lower,
        "t_compile": t_compile,
    }


def run_cell(
    arch: str,
    shape_name: str,
    mesh_name: str,
    *,
    variant: str = "baseline",
    parallel: Optional[ParallelConfig] = None,
    verbose: bool = True,
    cfg_override: Optional[ModelConfig] = None,
    heads_mode: str = "auto",
    param_dtype: str = "float32",
    force: bool = False,  # bypass the long_500k full-attention skip policy
) -> dict:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    chips = int(np.prod(tuple(mesh.shape.values())))
    if parallel is None:
        # train: grad-accumulation microbatches bound activation memory; the
        # per-step cost accounting is unchanged (same total tokens/step).
        micro = 8 if shape.kind == "train" else 1
        parallel = ParallelConfig(
            remat="block" if shape.kind == "train" else "none",
            microbatches=micro,
        )

    if variant == "baseline" and not force \
            and not cell_is_baseline_runnable(cfg, shape):
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "variant": variant, "status": "skip",
            "reason": "full-attention arch at 500k context (DESIGN.md §6); "
                      "runnable under --variant ihtc-kv",
        }

    from repro.models.transformer import stack_plan

    # full-config compile: THE deliverable (proves lower+compile succeeds and
    # yields the real memory analysis)
    full = _lower_and_compile(cfg, shape, mesh, variant=variant,
                              parallel=parallel, kind=shape.kind,
                              heads_mode=heads_mode, param_dtype=param_dtype)

    # HloCostAnalysis counts a while(scan) body ONCE, so the scanned layer
    # stack under-counts by ~n_repeats. Everything *inside* a layer is fully
    # visible (the flash-attention chunk loop is deliberately unrolled — see
    # attention.py), so cost(L) = a + L·b is exact; solve it from two
    # UNROLLED shallow probes at L=1, 2 and extrapolate to the full depth.
    n_prefix, period, rep = stack_plan(cfg)
    enc_stacked = cfg.n_enc_layers >= 2  # enc-dec stacks scale with n_layers too
    if rep >= 3 or enc_stacked:
        def mk(r):
            kw = dict(scan_layers=False)
            if enc_stacked:
                kw.update(n_layers=r, n_enc_layers=r)
            else:
                kw.update(n_layers=n_prefix + period * r)
            return dataclasses.replace(cfg, **kw)

        L_full = cfg.n_layers if enc_stacked else rep
        # probes run without microbatching (a grad-accumulation scan body is
        # also invisible to HloCostAnalysis); per-step totals are identical
        probe_par = dataclasses.replace(parallel, microbatches=1)
        f1 = _lower_and_compile(mk(1), shape, mesh, variant=variant,
                                parallel=probe_par, kind=shape.kind,
                                heads_mode=heads_mode, param_dtype=param_dtype)
        f2 = _lower_and_compile(mk(2), shape, mesh, variant=variant,
                                parallel=probe_par, kind=shape.kind,
                                heads_mode=heads_mode, param_dtype=param_dtype)

        def extrap(get):
            b = get(f2) - get(f1)
            a = get(f1) - b
            return max(a + L_full * b, 0.0)

        flops_per_chip = extrap(lambda r: r["flops"])
        bytes_per_chip_accessed = extrap(lambda r: r["bytes"])
        keys = set(f1["coll"]) | set(f2["coll"])
        coll = {k: extrap(lambda r: r["coll"].get(k, 0.0)) for k in keys}
        cost_method = (
            f"two-point extrapolation over unrolled layer probes (L=1,2 → "
            f"{L_full}); attention chunk loop is unrolled so per-layer costs "
            f"are exact"
        )
    else:
        flops_per_chip = full["flops"]
        bytes_per_chip_accessed = full["bytes"]
        coll = full["coll"]
        cost_method = "direct (stack unrolled or shallow)"

    abstract_params = full["abstract_params"]
    mem = full["mem"]
    coll_counts = full["coll_counts"]
    t_lower, t_compile = full["t_lower"], full["t_compile"]

    peak_bytes = float(
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
    )
    n_active = _active_params(cfg, abstract_params)
    mf = model_flops_for(cfg, shape, n_active=n_active)
    report = build_report(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        flops=flops_per_chip * chips, hbm_bytes=bytes_per_chip_accessed * chips,
        collective_per_chip_bytes=float(coll.get("total", 0.0)),
        model_flops=mf, bytes_per_chip=peak_bytes,
        device_kind=TARGET_DEVICE_KIND,
    )
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "variant": variant,
        "status": "ok",
        "chips": chips,
        "n_params": tree_size(abstract_params),
        "n_active_params": n_active,
        "cost_method": cost_method,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
            "output_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
            "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
            "peak_gb": peak_bytes / 1e9,
        },
        "cost": {"flops_per_chip": flops_per_chip,
                 "bytes_per_chip": bytes_per_chip_accessed},
        "collectives": {"bytes_per_chip": coll, "op_counts": coll_counts},
        "roofline": dataclasses.asdict(report),
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name} × {variant}] "
              f"compile={t_compile:.0f}s chips={chips}")
        print(f"  memory_analysis: peak {peak_bytes/1e9:.2f} GB/chip "
              f"(args {out['memory']['argument_gb']:.2f} + temp "
              f"{out['memory']['temp_gb']:.2f})")
        print(f"  cost_analysis: {flops_per_chip/1e9:.1f} GFLOP/chip, "
              f"{bytes_per_chip_accessed/1e9:.2f} GB/chip accessed")
        print(f"  collectives/chip: { {k: f'{v/1e6:.1f}MB' for k, v in coll.items()} }")
        r = report
        print(f"  roofline: compute {r.compute_term_s:.2e}s | memory "
              f"{r.memory_term_s:.2e}s | collective {r.collective_term_s:.2e}s "
              f"→ {r.dominant}-bound; useful-FLOP ratio {r.useful_ratio:.2f}; "
              f"MFU bound {r.mfu_bound*100:.1f}%")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="pod1", choices=("pod1", "pod2", "both"))
    ap.add_argument("--variant", default="baseline",
                    choices=("baseline", "ihtc-kv"))
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    args = ap.parse_args()

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    failures = 0
    for a, s, m in cells:
        try:
            res = run_cell(a, s, m, variant=args.variant)
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            res = {"arch": a, "shape": s, "mesh": m, "variant": args.variant,
                   "status": "error", "error": str(e)}
            failures += 1
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            fn = f"{a}__{s}__{m}__{args.variant}.json"
            with open(os.path.join(args.out, fn), "w") as f:
                json.dump(res, f, indent=1)
        if res["status"] == "skip":
            print(f"[{a} × {s} × {m}] SKIP: {res['reason']}")
    print(f"\ndry-run finished: {len(cells)} cells, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
