"""kNN-graph construction — the computational bottleneck of TC.

The paper uses kd-trees (serial, pointer-chasing). The TPU-native strategy is
brute force on the MXU, organized three ways by scale:

  * ``knn_graph``          — one-shot, n ≲ 32k (full tile set in one call).
  * ``knn_graph_blocked``  — query blocks × key blocks with a running top-k
    merge; HBM traffic O(n·d + n·k), never materializes (n, n).
  * ``ring_knn``           — multi-device: keys rotate around the ``data``
    mesh axis via ``lax.ppermute`` (ring all-gather overlap pattern), each
    shard folds the visiting block into its running top-k. Weak-scales to
    arbitrary pod counts.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import runtime
from repro.kernels import ops
from repro.kernels.ref import merge_topk as _ref_merge_topk

# what knn_block == 0 ("auto") means for every blocked-kNN entry point:
# one-shot below this row count, blocks of this size above (the O(n²) HBM
# threshold of the one-shot path). With the tuning policy active
# (RuntimeConfig.tune, DESIGN.md §14) the measured winner for this
# hardware + shape bucket replaces the constant — see resolve_auto_block.
AUTO_KNN_BLOCK = 8192


def resolve_auto_block(n: int, d: int = 0, k: int = 0,
                       dtype: str = "float32") -> int:
    """What ``knn_block == 0`` ("auto") resolves to for an (n, d) problem:
    the tuning cache's measured winner when the policy is active and has
    one for this bucket, else the hand-picked ``AUTO_KNN_BLOCK``.

    ``dtype`` must be the data's element type so this lookup and
    ``plan_fit``'s (which freezes the same cell into the FitPlan) key the
    cache identically — a mismatch would make execution dispatch diverge
    from the plan. Safe at trace time: callers are jitted drivers whose
    static ``_dispatch`` key carries the tune mode + cache epoch, so a
    changed winner always retraces (§10/§14).
    """
    if runtime.active().tune != "off":
        from repro import tune  # lazy: no import cycle through core

        tuned = tune.tuned_params("knn_block", dtype=dtype, n=n, d=d, k=k)
        if tuned.get("knn_block"):
            return int(tuned["knn_block"])
    return AUTO_KNN_BLOCK


def knn_graph(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact (dists, idx) of the k nearest valid neighbours of each row.

    ``k`` may exceed the number of *valid* rows — unfillable slots come back
    with ``inf`` distance and index ``-1`` — but not the buffer size ``n``
    (XLA's top_k would fail with an opaque shape error deep in the trace).
    """
    if k > x.shape[0]:
        raise ValueError(
            f"knn_graph: k={k} exceeds the number of rows n={x.shape[0]}; "
            f"slots beyond the valid count are padded with -1, but k itself "
            f"must be <= n")
    return ops.knn(x, k, valid=valid, exclude_self=True, impl=impl)


# canonical streaming top-k merge — now shared with the fused assign kernel,
# so its single home is the kernels package (core keeps the old name alive
# for the blocked/ring drivers and external importers)
_merge_topk = _ref_merge_topk


def knn_graph_blocked(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array] = None,
    block: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Blocked exact kNN for n beyond one-tile range.

    Streams key blocks against each query block and keeps a (block, k)
    running best list, so peak memory is O(block² + n·k). ``block`` defaults
    to the runtime config's ``knn_block`` (``resolve_auto_block`` when that
    is 0 = auto — the same resolution threshold_clustering uses).
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    if block is None:
        block = cfg.knn_block or resolve_auto_block(
            x.shape[0], x.shape[1], k, dtype=str(x.dtype))
    return _knn_graph_blocked(x, k, valid=valid, block=block, impl=impl,
                              _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit, static_argnames=("k", "block", "impl", "_dispatch")
)
def _knn_graph_blocked(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array],
    block: int,
    impl: str,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
) -> Tuple[jax.Array, jax.Array]:
    n, _ = x.shape
    if valid is None:
        valid = jnp.ones((n,), bool)
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    vp = jnp.pad(valid, (0, pad))
    npad = xp.shape[0]
    nq = npad // block

    xq = xp.reshape(nq, block, -1)
    # "auto" takes the fused path on TPU, as ops.knn does for one block
    fused = ops._resolve(impl, fused=True) in ops._FUSED_IMPLS

    def per_query_block(qi):
        q = xq[qi]
        q_gidx = qi * block + jnp.arange(block)

        if fused:
            # fused inner loop: the kernel streams key blocks itself and
            # takes the self-exclusion as a traced global-index array, so
            # the (block, block) distance tile never exists outside VMEM
            return ops.nearest_topk(
                q, xp, k, key_valid=vp, q_gidx=q_gidx.astype(jnp.int32),
                impl="fused")

        def body(kb, carry):
            bd, bi = carry
            keys = jax.lax.dynamic_slice_in_dim(xp, kb * block, block, axis=0)
            kval = jax.lax.dynamic_slice_in_dim(vp, kb * block, block, axis=0)
            d = ops.pairwise_sq_l2(q, keys, y_valid=kval, impl=impl)
            k_gidx = kb * block + jnp.arange(block)
            d = jnp.where(q_gidx[:, None] == k_gidx[None, :], jnp.inf, d)
            return _merge_topk(bd, bi, d, jnp.broadcast_to(k_gidx, d.shape), k)

        init = (
            jnp.full((block, k), jnp.inf, jnp.float32),
            jnp.full((block, k), -1, jnp.int32),
        )
        return jax.lax.fori_loop(0, nq, body, init)

    bd, bi = jax.lax.map(per_query_block, jnp.arange(nq))
    return bd.reshape(npad, k)[:n], bi.reshape(npad, k)[:n]


def ring_knn(
    x_local: jax.Array,
    k: int,
    *,
    axis_name: str,
    valid: Optional[jax.Array] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded exact kNN inside ``shard_map``: keys rotate around the ring.

    Each of P shards holds ``x_local`` (n_local, d). At step s the shard
    computes distances of its queries against the visiting key block (which
    originated on shard ``(my_id + s) % P``), folds them into its running
    top-k with *global* indices, then forwards the block to the next shard.
    Communication: P-1 permutes of the key block = one all-gather's bytes,
    but overlapped with compute and never materialized on one device.
    """
    n_local = x_local.shape[0]
    if valid is None:
        valid = jax.lax.pcast(jnp.ones((n_local,), bool), axis_name,
                              to="varying")
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i - 1) % p) for i in range(p)]  # block travels to lower rank

    def body(s, carry):
        bd, bi, keys, kval = carry
        src = (me + s) % p  # owner of the visiting block
        d = ops.pairwise_sq_l2(x_local, keys, y_valid=kval, impl=impl)
        q_gidx = me * n_local + jnp.arange(n_local)
        k_gidx = src * n_local + jnp.arange(n_local)
        d = jnp.where(q_gidx[:, None] == k_gidx[None, :], jnp.inf, d)
        bd, bi = _merge_topk(bd, bi, d, jnp.broadcast_to(k_gidx, d.shape), k)
        keys = jax.lax.ppermute(keys, axis_name, perm)
        kval = jax.lax.ppermute(kval, axis_name, perm)
        return bd, bi, keys, kval

    init = (
        jax.lax.pcast(jnp.full((n_local, k), jnp.inf, jnp.float32),
                      axis_name, to="varying"),
        jax.lax.pcast(jnp.full((n_local, k), -1, jnp.int32), axis_name,
                      to="varying"),
        x_local,
        valid,
    )
    bd, bi, _, _ = jax.lax.fori_loop(0, p, body, init)
    return bd, bi
