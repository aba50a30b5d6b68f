"""Weighted k-means (Lloyd + k-means++) in pure JAX, mask-aware.

This is the paper's primary "sophisticated" backend. Supports sample weights
(prototype masses from ITIS) so that k-means on prototypes optimizes the same
objective as k-means on the original units would (the mass-correct variant);
with unit weights it reproduces the paper's plain k-means-on-prototypes.

Lloyd statistics are accumulated with ``ops.blocked_segment_sum`` — a fixed
``n_blocks``-wide reduction tree — so the mesh-aware twin in
:mod:`repro.core.distributed` (replicated centroids, sharded rows, ordered
fold of all-gathered per-shard partials) produces bit-identical centers and
labels (DESIGN.md §4.3).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import runtime
from repro.cluster.registry import register_backend
from repro.kernels import ops

STAT_BLOCKS = 8  # canonical reduction width; must match the distributed twin


class KMeansResult(NamedTuple):
    centers: jax.Array   # (k, d)
    labels: jax.Array    # (n,) int32, -1 for invalid rows
    inertia: jax.Array   # () weighted within-cluster sum of squares
    iters: jax.Array     # () iterations until convergence


def _plus_plus_init(x, w, valid, k, key, impl):
    """k-means++ seeding with weighted D² sampling."""
    n = x.shape[0]
    wv = jnp.where(valid, w, 0.0)
    key0, key_loop = jax.random.split(key)
    first = jax.random.categorical(key0, jnp.log(jnp.maximum(wv, 1e-30)))
    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    def body(i, carry):
        centers, key = carry
        key, sub = jax.random.split(key)
        d = ops.pairwise_sq_l2(x, centers, impl=impl)  # (n, k)
        # distance to nearest chosen center (ignore not-yet-filled slots)
        slot_ok = jnp.arange(k)[None, :] < i
        dmin = jnp.min(jnp.where(slot_ok, d, jnp.inf), axis=1)
        logits = jnp.log(jnp.maximum(wv * dmin, 1e-30))
        nxt = jax.random.categorical(sub, logits)
        return centers.at[i].set(x[nxt]), key

    centers, _ = jax.lax.fori_loop(1, k, body, (centers0, key_loop))
    return centers


def kmeans(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    iters: int = 100,
    tol: float = 1e-6,
    impl: Optional[str] = None,
    n_blocks: Optional[int] = None,
) -> KMeansResult:
    """Weighted k-means; ``impl``/``n_blocks`` default to the runtime config
    (resolved before the jit boundary — DESIGN.md §10)."""
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    return _kmeans(x, k, valid=valid, weights=weights, key=key, iters=iters,
                   tol=tol, impl=impl, n_blocks=n_blocks,
                   _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit,
    static_argnames=("k", "iters", "impl", "n_blocks", "_dispatch"),
)
def _kmeans(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array],
    weights: Optional[jax.Array],
    key: Optional[jax.Array],
    iters: int,
    tol: float,
    impl: str,
    n_blocks: int,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
) -> KMeansResult:
    n, d = x.shape
    if valid is None:
        valid = jnp.ones((n,), bool)
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(0)
    w = jnp.where(valid, weights.astype(jnp.float32), 0.0)

    # scope name: a contract with the benchmark (DESIGN.md §20)
    with jax.named_scope("kmeans.seed"):
        centers = _plus_plus_init(x, w, valid, k, key, impl)

    def assign(centers):
        dist = ops.pairwise_sq_l2(x, centers, impl=impl)  # (n, k)
        lab = jnp.argmin(dist, axis=1).astype(jnp.int32)
        dmin = jnp.min(dist, axis=1)
        return lab, dmin

    def cond(state):
        _, _, delta, it = state
        return (delta > tol) & (it < iters)

    def body(state):
        centers, _, _, it = state
        lab, _ = assign(centers)
        lab_safe = jnp.where(valid, lab, k)  # dropped by segment_sum
        sums, mass, _ = ops.blocked_segment_sum(
            x, lab_safe, k, weights=w, n_blocks=n_blocks, impl=impl)
        new = jnp.where(
            (mass > 0)[:, None], sums / jnp.maximum(mass, 1e-30)[:, None], centers
        ).astype(x.dtype)
        delta = jnp.max(jnp.sum(jnp.square(new - centers), axis=1))
        return new, lab, delta, it + 1

    lab0, _ = assign(centers)
    state = (centers, lab0, jnp.asarray(jnp.inf, jnp.float32), jnp.asarray(0))
    centers, labels, _, it = jax.lax.while_loop(cond, body, state)
    labels, dmin = assign(centers)
    inertia = jnp.sum(jnp.where(valid, w * dmin, 0.0))
    labels = jnp.where(valid, labels, -1)
    return KMeansResult(centers, labels.astype(jnp.int32), inertia,
                        it.astype(jnp.int32))


@register_backend("kmeans")
def kmeans_masked(
    x: jax.Array,
    *,
    k: int = 3,
    valid: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    iters: int = 100,
    **_: object,
) -> KMeansResult:
    """IHTC backend adapter: the whole result, so the planner keeps the
    Lloyd iteration count beside the labels."""
    return kmeans(
        x, k, valid=valid, weights=weights, key=key, iters=iters, impl=impl
    )
