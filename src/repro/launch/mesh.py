"""Production mesh + per-(arch × shape) sharding plans.

Mesh axes: 'pod' (cross-pod DP, slow DCN links), 'data' (in-pod DP / ZeRO /
sequence), 'model' (TP/EP). Defined as functions so importing this module
never touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.transformer import ShardingPlan


def _auto_mesh(shape, axes):
    """Mesh whose axes are all ``Auto``: the model stack places activations
    with ``with_sharding_constraint`` and lets the partitioner choose gather
    and matmul output shardings, which ``jax.make_mesh``'s default of
    ``Explicit`` axes refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU distribution tests (device count set by the test)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_data_mesh(n_data: Optional[int] = None):
    """1-D ``("data",)`` mesh for the sharded clustering pipeline.

    The distributed ITIS/IHTC drivers (repro.core.distributed) shard points,
    kNN graphs and prototype buffers over this single axis; model-parallel
    axes are irrelevant to clustering, so the full device set goes to data.
    """
    from repro.core.distributed import make_data_mesh as _mk

    return _mk(n_data)


def data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def make_plan(cfg: ModelConfig, shape: ShapeConfig, mesh,
              *, heads_mode: str = "auto") -> ShardingPlan:
    """Activation-sharding plan for one (arch × shape × mesh) cell.

    heads_mode (for archs whose head counts don't divide TP):
      auto — leave attention sharding to SPMD propagation;
      seq  — context parallelism: q sequence-sharded over 'model', k/v
             replicated once per layer (one small all-gather)."""
    dp = data_axes(mesh)
    dp_size = axis_size(mesh, dp)
    tp_size = mesh.shape["model"]
    b = shape.global_batch

    batch_axes = dp if (b % dp_size == 0 and b >= dp_size) else None
    heads_ok = cfg.n_heads % tp_size == 0 if cfg.n_heads else False
    kv_ok = cfg.n_kv_heads % tp_size == 0 if cfg.n_kv_heads else False
    # heads not divisible by TP (qwen/llama4 40H, gemma2 8H at tp=16): leave
    # attention sharding to SPMD propagation — XLA partially tiles the kv
    # heads (e.g. 8-of-16 with replication), which beats both forced
    # replication (q all-gather) and forced q-seq sharding (per-chunk
    # resharding thrash). Measured in EXPERIMENTS.md §Perf.
    kv_spec = None
    if heads_ok:
        heads_spec = P(batch_axes, "model", None, None)
    elif heads_mode == "seq" and shape.kind in ("train", "prefill"):
        heads_spec = P(batch_axes, None, "model", None)
        kv_spec = P(batch_axes, None, None, None)  # replicate k/v once
    else:
        heads_spec = None

    if cfg.ssm_state:
        from repro.models.mamba2 import _dims

        _, h_m, _, _ = _dims(cfg)
        mamba_ok = h_m % tp_size == 0
    else:
        mamba_ok = False

    # decode KV cache: batch over dp when possible; kv-heads over model when
    # divisible, else sequence over model (flash-decoding style partial
    # softmax — XLA partitions the softmax reduction); batch=1 long-context
    # shards the sequence over everything available.
    if shape.kind == "decode":
        if b == 1:
            seq_axes = dp + ("model",) if not kv_ok else dp
            cache = P(None, "model" if kv_ok else None, seq_axes, None)
        elif kv_ok:
            cache = P(batch_axes, "model", None, None)
        else:
            cache = P(batch_axes, None, "model", None)
    else:
        cache = P(batch_axes, "model" if kv_ok else None, None, None)

    if cfg.n_experts and cfg.n_experts % tp_size == 0:
        ep = (P(batch_axes, "model", None, None) if cfg.moe_groups > 1
              else P("model", None, None))
    elif cfg.n_experts:
        ep = P(None, None, None)
    else:
        ep = None
    return ShardingPlan(
        resid=P(batch_axes, None, None),
        heads=heads_spec,
        kv=kv_spec,
        mamba_heads=P(batch_axes, None, "model" if mamba_ok else None, None),
        ep=ep,
        cache=cache,
        logits=P(batch_axes, None, "model"),
    )


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, *, kind: str):
    """PartitionSpec pytree for the input batch dict of this cell."""
    dp = data_axes(mesh)
    dp_size = axis_size(mesh, dp)
    b = shape.global_batch if kind != "decode" else shape.global_batch
    bx = dp if (b % dp_size == 0 and b >= dp_size) else None
    specs = {"tokens": P(bx, None)}
    if kind == "train":
        specs["labels"] = P(bx, None)
    if cfg.frontend == "vision" and kind != "decode":
        specs["patch_embeds"] = P(bx, None, None)
    if cfg.frontend == "audio" and kind != "decode":
        specs["frames"] = P(bx, None, None)
    return specs
