"""Fused distance + streaming top-k: the TPU-native kNN-graph builder.

This is the kernel that replaces the paper's kd-tree. Instead of
materializing the (n, n) distance matrix in HBM (the memory wall of
brute-force kNN), each program computes one (Bq, Bk) distance tile on the
MXU and folds it into a running (Bq, k) best-list kept in VMEM, so HBM
traffic is O(n·d + n·k) instead of O(n²).

The self-kNN graph is the fused nearest/top-k kernel
(:func:`repro.kernels.fused_assign.fused_topk`) with the points as both
queries and keys and the self-match excluded through the global query
index, so there is one kernel body, one tiling rule and one merge.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_assign import fused_topk


def knn_topk(
    x: jax.Array,
    k: int,
    valid: jax.Array | None = None,
    *,
    exclude_self: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
):
    """k nearest neighbours of each row of x within x.

    Returns (dists (n,k) ascending sq-L2, idx (n,k); unfilled slots inf/-1).
    ``block_q``/``block_k`` default to the active runtime config's tile
    sizes.
    """
    gidx = jnp.arange(x.shape[0], dtype=jnp.int32) if exclude_self else None
    return fused_topk(x, x, k, valid, q_gidx=gidx, block_q=block_q,
                      block_k=block_k, interpret=interpret)
