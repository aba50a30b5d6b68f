"""Production training launcher.

    python -m repro.launch.train --arch qwen2.5-32b --shape train_4k \
        --steps 100 [--mesh pod1|pod2|debug|single] [--select-instances]

On real TPU pods this launches under `jax.distributed`; on the CPU container
use --mesh single (1 device) or debug (8 host devices) for a real sharded
run. XLA latency-hiding-scheduler flags are set for collective overlap.
"""
import os

_LHS_FLAGS = (
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_enable_async_collective_fusion=true"
)
if "--mesh debug" in " ".join(os.sys.argv):  # 8 host devices before jax init
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
elif os.environ.get("JAX_PLATFORMS", "") not in ("", "cpu"):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _LHS_FLAGS

import argparse  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES  # noqa: E402
from repro.configs.base import ParallelConfig  # noqa: E402
from repro.data import make_batch  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    data_axes,
    make_debug_mesh,
    make_plan,
    make_production_mesh,
)
from repro.models import build  # noqa: E402
from repro.train import (  # noqa: E402
    CheckpointManager,
    OptConfig,
    init_opt_state,
    make_train_step,
)
from repro.train.fault_tolerance import run_training  # noqa: E402
from repro.train.optimizer import zero_opt_specs  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=("single", "debug", "pod1", "pod2"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0, help="override batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="block", choices=("none", "block", "dots"))
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    bundle = build(cfg)
    parallel = ParallelConfig(remat=args.remat, microbatches=args.microbatches)

    if args.mesh == "single":
        from repro.models.transformer import ShardingPlan

        mesh = None
        plan = ShardingPlan()
    else:
        mesh = (make_debug_mesh(2, 4) if args.mesh == "debug"
                else make_production_mesh(multi_pod=(args.mesh == "pod2")))
        plan = make_plan(cfg, shape, mesh)

    params = bundle.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    if mesh is not None:
        tp_size = mesh.shape["model"]
        pspecs = bundle.param_specs(tp="model", tp_size=tp_size)
        ospecs = zero_opt_specs(pspecs, params, data_axes(mesh),
                                dict(mesh.shape))
        put = lambda tree, specs: jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs,
            is_leaf=lambda x: hasattr(x, "shape"))
        params = put(params, pspecs)
        opt = put(opt, ospecs)

    step = jax.jit(make_train_step(bundle, OptConfig(
        decay_steps=max(args.steps, 100)), parallel, plan))

    b = args.batch or min(shape.global_batch, 8)
    s = args.seq or min(shape.seq_len, 256)
    bfs = lambda st: make_batch(cfg, shape, st, batch_override=b,
                                seq_override=s)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest_step():
        start = ckpt.latest_step()
        state = ckpt.restore(start, {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        print(f"resumed from step {start}")

    def on_metrics(st, m):
        if st % 10 == 0:
            print(f"step {st:>6} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.2f}")

    ctx = mesh if mesh is not None else _null_ctx()
    with ctx:
        params, opt, stats = run_training(
            train_step=step, init_state=(params, opt), batch_for_step=bfs,
            n_steps=args.steps, start_step=start,
            ckpt=ckpt, ckpt_every=args.ckpt_every, on_metrics=on_metrics)
    q = stats.quantiles()
    print(f"done: {args.steps - start} steps, p50 {q.get('p50', 0):.3f}s, "
          f"p99 {q.get('p99', 0):.3f}s, stragglers {stats.stragglers()}")


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
