"""Prototype aggregation (weighted segment-sum) as a one-hot MXU matmul.

Cluster-centroid computation is a scatter-add, which is slow on TPU (serialized
DMA). Instead each program builds the (Bn, Bs) one-hot membership tile for its
segment range on the VPU and contracts it against the (Bn, d) data tile on the
MXU: ``sums[s] += onehot.T @ (w * x)``. Mass (cluster size) falls out of the
same contraction against a column of ones.

Grid: (S/Bs, n/Bn), point axis innermost (accumulation pattern). Ids and
weights travel as lane-dense (1, Bn) rows and the one-hot tile is built
segment-major, (Bs, Bn), so its contraction with the (Bn, d) data tile is a
plain matmul; mass comes out as a (Bs, 1) column. Mosaic refuses the 1-D
layouts XLA picks for per-row vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_assign import _tile


def _segsum_kernel(ids_ref, w_ref, x_ref, sums_ref, mass_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        mass_ref[...] = jnp.zeros_like(mass_ref)

    bs = sums_ref.shape[0]
    bn = x_ref.shape[0]
    # global segment id of each row of the tile; ids outside it drop out
    seg = jax.lax.broadcasted_iota(jnp.int32, (bs, bn), 0) + pl.program_id(0) * bs
    onehot = jnp.where(seg == ids_ref[...], w_ref[...], 0.0)  # (bs, bn)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (bs, d) — MXU
    mass_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)  # (bs, 1)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_s", "block_n", "interpret")
)
def segment_sum(
    x: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    weights: jax.Array | None = None,
    *,
    block_s: int = 512,
    block_n: int = 1024,
    interpret: bool = False,
):
    """Weighted segment sum; ids outside [0, num_segments) are dropped.

    Returns (sums (num_segments, d) f32, masses (num_segments,) f32).
    """
    n, d = x.shape
    w = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)

    segs = -(-max(num_segments, 8) // 8) * 8
    rows = -(-max(n, 8) // 8) * 8
    bs = _tile(block_s, segs, 8)
    bn = _tile(block_n, rows, 1 if interpret else 128)  # lane axis of ids/w
    S = -(-segs // bs) * bs
    n_pad = -(-rows // bn) * bn - n
    xp = jnp.pad(x, ((0, n_pad), (0, 0)))
    wp = jnp.pad(w, (0, n_pad))[None, :]  # zero weight -> no contribution
    idp = jnp.pad(segment_ids.astype(jnp.int32), (0, n_pad),
                  constant_values=-1)[None, :]

    grid = (S // bs, xp.shape[0] // bn)
    sums, mass = pl.pallas_call(
        _segsum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda s, j: (0, j)),
            pl.BlockSpec((1, bn), lambda s, j: (0, j)),
            pl.BlockSpec((bn, d), lambda s, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, d), lambda s, j: (s, 0)),
            pl.BlockSpec((bs, 1), lambda s, j: (s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, d), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(idp, wp, xp)
    return sums[:num_segments], mass[:num_segments, 0]
