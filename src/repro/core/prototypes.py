"""Prototype (cluster centre) construction and back-out label composition."""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import runtime
from repro.kernels import ops

# Canonical reduction-tree width for prototype/centroid accumulations.
# Pinning the block count (instead of letting it follow the device count or
# XLA's scatter order) makes reductions device-layout-invariant, which is
# what lets the sharded pipeline in repro.core.distributed match the
# single-device driver bit-for-bit (DESIGN.md §4.3). This is also the
# default of RuntimeConfig.n_blocks — the runtime config is the live knob;
# this constant documents the canonical parity value.
REDUCE_BLOCKS = 8


class PrototypeSet(NamedTuple):
    x: jax.Array        # (n_max, d) prototype coordinates (padded)
    mass: jax.Array     # (n_max,) total original-unit mass per prototype
    valid: jax.Array    # (n_max,) bool — real prototype vs padding
    tiles: Optional[jax.Array] = None  # () int32 segment-sum tile matmuls run


def reduce_to_prototypes(
    x: jax.Array,
    labels: jax.Array,
    n_max: int,
    *,
    weights: Optional[jax.Array] = None,
    weighted: bool = True,
    impl: Optional[str] = None,
    n_blocks: Optional[int] = None,
) -> PrototypeSet:
    """Collapse clusters to centroid prototypes.

    ``labels`` in [0, n_max) (use -1 / out-of-range for masked rows — they are
    dropped). ``weighted=False`` reproduces the paper exactly (plain centroid
    of the points at this level); ``weighted=True`` carries original-unit mass
    through ITIS levels (mass-correct centroids — the beyond-paper fix).
    ``mass`` always accumulates true unit counts for the size guarantee and
    for weighted clustering of the prototypes downstream. ``n_blocks`` pins
    the accumulation order (see ``ops.blocked_segment_sum``); it and ``impl``
    default to the active runtime config, resolved before the jit boundary.
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    return _reduce_to_prototypes(x, labels, n_max, weights=weights,
                                 weighted=weighted, impl=impl,
                                 n_blocks=n_blocks,
                                 _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit,
    static_argnames=("n_max", "weighted", "impl", "n_blocks", "_dispatch"),
)
def _reduce_to_prototypes(
    x: jax.Array,
    labels: jax.Array,
    n_max: int,
    *,
    weights: Optional[jax.Array],
    weighted: bool,
    impl: str,
    n_blocks: int,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
) -> PrototypeSet:
    n = x.shape[0]
    w = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    safe_labels = jnp.where(labels >= 0, labels, n_max).astype(jnp.int32)

    if weighted:
        sums, denom, tiles = ops.blocked_segment_sum(
            x, safe_labels, n_max, weights=w, n_blocks=n_blocks, impl=impl)
        mass = denom
    else:
        ones = jnp.where(labels >= 0, 1.0, 0.0).astype(jnp.float32)
        sums, denom, tiles = ops.blocked_segment_sum(
            x, safe_labels, n_max, weights=ones, n_blocks=n_blocks, impl=impl)
        _, mass, mass_tiles = ops.blocked_segment_sum(
            jnp.zeros((n, 1), x.dtype), safe_labels, n_max, weights=w,
            n_blocks=n_blocks, impl=impl,
        )
        tiles = tiles + mass_tiles
    protos = sums / jnp.maximum(denom, 1e-12)[:, None]
    valid = denom > 0
    protos = jnp.where(valid[:, None], protos, 0.0).astype(x.dtype)
    return PrototypeSet(protos, mass, valid, tiles)


def compose_assignments(levels: Sequence[jax.Array], final: jax.Array) -> jax.Array:
    """Back out labels to the original units.

    ``levels[l]`` maps level-l points to level-(l+1) prototype ids; ``final``
    maps the last level's prototypes to backend cluster labels. -1 entries
    (padding) propagate as -1.
    """
    lab = levels[0]
    for nxt in list(levels[1:]) + [final]:
        ok = lab >= 0
        lab = jnp.where(ok, nxt[jnp.where(ok, lab, 0)], -1)
    return lab


def standardize(x: jax.Array, valid: Optional[jax.Array] = None) -> jax.Array:
    """Standardized-Euclidean preprocessing (the paper's recommended metric)."""
    if valid is None:
        mu = jnp.mean(x, axis=0)
        sd = jnp.std(x, axis=0)
    else:
        w = valid.astype(x.dtype)[:, None]
        denom = jnp.maximum(jnp.sum(w), 1.0)
        mu = jnp.sum(x * w, axis=0) / denom
        sd = jnp.sqrt(jnp.sum(jnp.square(x - mu) * w, axis=0) / denom)
    return (x - mu) / jnp.maximum(sd, 1e-12)
