"""Tiled squared-L2 pairwise distance — the inner primitive of kNN-graph
construction (the computational bottleneck of Threshold Clustering).

TPU mapping: the (Bq × Bk) distance tile is dominated by a (Bq, d) × (d, Bk)
matmul that runs on the MXU; the rank-1 norm corrections ride the VPU. With
128-aligned tiles the kernel is compute-bound at arithmetic intensity ≈ d.

Grid: (n/Bq, m/Bk). Each program owns one output tile in VMEM:
  x block  (Bq, d)  — revisited across the j axis (stays resident),
  y block  (Bk, d),
  out tile (Bq, Bk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_assign import _lane_pad, _tile


def _pairwise_kernel(x_ref, y_ref, yv_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (bq, d)
    y = y_ref[...].astype(jnp.float32)  # (bk, d)
    xn = jnp.sum(x * x, axis=1, keepdims=True)  # (bq, 1)
    yn = jnp.sum(y * y, axis=-1)[None, :]  # (1, bk)
    cross = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (bq, bk) — MXU
    d = jnp.maximum(xn + yn - 2.0 * cross, 0.0)
    o_ref[...] = jnp.where(yv_ref[...] > 0.0, d, jnp.inf)  # (1, bk) validity


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def pairwise_sq_l2(
    x: jax.Array,
    y: jax.Array,
    y_valid: jax.Array | None = None,
    *,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Pallas pairwise squared-L2: (n, d) × (m, d) → (n, m) float32."""
    n, d = x.shape
    m = y.shape[0]
    if y_valid is None:
        y_valid = jnp.ones((m,), jnp.float32)
    else:
        y_valid = y_valid.astype(jnp.float32)

    # the key axis is the lane axis of the output tile and of the (1, bk)
    # validity row, so compiled blocks are 128-lane multiples (see _tile)
    rows = -(-max(n, 8) // 8) * 8
    cols = -(-max(m, 8) // 8) * 8
    bq = _tile(block_q, rows, 8)
    bk = _tile(block_k, cols, 1 if interpret else 128)
    n_pad = -(-rows // bq) * bq - n
    m_pad = -(-cols // bk) * bk - m
    d_pad = _lane_pad(d)
    xp = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    yp = jnp.pad(y, ((0, m_pad), (0, d_pad)))
    vp = jnp.pad(y_valid, (0, m_pad))[None, :]  # padded keys invalid -> +inf

    grid = (xp.shape[0] // bq, yp.shape[0] // bk)
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, xp.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, yp.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], yp.shape[0]), jnp.float32),
        interpret=interpret,
    )(xp, yp, vp)
    return out[:n, :m]
