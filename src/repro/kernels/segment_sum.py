"""Prototype aggregation (weighted segment-sum) as one-hot MXU matmuls over a
sorted, banded tile schedule.

Cluster-centroid computation is a scatter-add, which is slow on TPU (serialized
DMA). Instead each program builds the (Bs, Bn) one-hot membership tile of a
segment tile against a row tile on the VPU and contracts it with the (Bn, d)
data tile on the MXU at ``Precision.HIGHEST``: ``sums[s] += onehot @ (w * x)``.
Mass (cluster size) falls out of the same one-hot, summed over its rows.

Only the (segment tile, row tile) pairs that share a member do useful work,
so the rows are first put in segment order: a *stable* sort by segment id
(ids outside ``[0, num_segments)`` sort to the end and are never visited),
with ``x`` and ``w`` gathered into that order. Stability keeps each
segment's members in their original row order. Each segment tile then owns
one contiguous run of sorted rows, and the tiles' runs form a monotone
staircase over the row tiles: segment tile ``t`` visits the row tiles its
run touches, and a tile with no members gets one visit that writes zeros.
Consecutive tiles meet in at most one row tile, so a block takes at most
``S_tiles + J_tiles - 1`` visits: that bound is the static grid. The visit
list (:func:`tile_schedule`) travels as scalar-prefetch operands (the
grouped-matmul pattern of ``megablox``); a visit zeroes its output tile
when it is the tile's first, accumulates when the tile has members, and the
grid steps past the list's end repeat its last visit's block indices and do
nothing. The work is linear in n: O((S_tiles + J_tiles) * Bs * Bn * d)
against the dense grid's O(S_tiles * J_tiles * Bs * Bn * d).

When the segment axis is one tile, banding saves nothing and the dense
(n/Bn,) grid runs on the rows as given, with no sort.

Ids and weights travel as lane-dense (1, Bn) rows and the one-hot tile is
built segment-major, (Bs, Bn), so its contraction with the (Bn, d) data
tile is a plain matmul; mass comes out as a (Bs, 1) column. Mosaic refuses
the 1-D layouts XLA picks for per-row vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_assign import _tile

_FIRST, _WORK = 1, 2  # visit flags: zero the output tile; accumulate into it


def _accumulate(t, ids_ref, w_ref, x_ref, sums_ref, mass_ref):
    """Add one row tile into segment tile ``t``'s sums and masses."""
    bs = sums_ref.shape[0]
    bn = x_ref.shape[0]
    # global segment id of each row of the tile; ids outside it drop out
    seg = jax.lax.broadcasted_iota(jnp.int32, (bs, bn), 0) + t * bs
    onehot = jnp.where(seg == ids_ref[...], w_ref[...], 0.0)  # (bs, bn)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (bs, d) — MXU
    mass_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)  # (bs, 1)


def _dense_kernel(ids_ref, w_ref, x_ref, sums_ref, mass_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        mass_ref[...] = jnp.zeros_like(mass_ref)

    _accumulate(0, ids_ref, w_ref, x_ref, sums_ref, mass_ref)


def _banded_kernel(seg_ref, row_ref, flag_ref, ids_ref, w_ref, x_ref,
                   sums_ref, mass_ref):
    del row_ref  # read by the index maps only
    v = pl.program_id(0)
    flag = flag_ref[v]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        mass_ref[...] = jnp.zeros_like(mass_ref)

    @pl.when((flag & _WORK) != 0)
    def _work():
        _accumulate(seg_ref[v], ids_ref, w_ref, x_ref, sums_ref, mass_ref)


def tile_shape(n: int, num_segments: int, block_s: int, block_n: int,
               interpret: bool):
    """``(bs, bn, S, R)``: the segment and row tiles ``segment_sum`` runs
    for ``n`` rows, and the segment and row axes padded to them."""
    segs = -(-max(num_segments, 8) // 8) * 8
    rows = -(-max(n, 8) // 8) * 8
    bs = _tile(block_s, segs, 8)
    bn = _tile(block_n, rows, 1 if interpret else 128)  # lane axis of ids/w
    return bs, bn, -(-segs // bs) * bs, -(-rows // bn) * bn


def tile_schedule(sorted_ids: jax.Array, num_tiles: int, block_s: int,
                  block_n: int):
    """The banded visit list over rows sorted by segment id.

    ``sorted_ids`` is (rows,) ascending, rows a multiple of ``block_n``;
    ids at or above ``num_tiles * block_s`` are dropped. Returns
    ``(seg_tile, row_tile, flags, tiles)``: three int32 visit arrays, one
    entry per grid step (``num_tiles + row_tiles - 1`` of them) — the
    output tile, the row tile and the ``_FIRST``/``_WORK`` flags — and
    ``tiles``, the () int32 count of visits that run the matmul (those
    whose segment tile has members).
    """
    rows = sorted_ids.shape[0]
    row_tiles = rows // block_n
    steps = num_tiles + row_tiles - 1
    bounds = jnp.searchsorted(
        sorted_ids, jnp.arange(num_tiles + 1, dtype=jnp.int32) * block_s,
        side="left").astype(jnp.int32)
    start, end = bounds[:-1], bounds[1:]
    full = end > start
    first = jnp.minimum(start // block_n, row_tiles - 1)
    last = jnp.where(full, (end - 1) // block_n, first)
    count = last - first + 1                  # >= 1: an empty tile's one visit
    ends = jnp.cumsum(count).astype(jnp.int32)
    v = jnp.arange(steps, dtype=jnp.int32)
    seg = jnp.minimum(jnp.searchsorted(ends, v, side="right"),
                      num_tiles - 1).astype(jnp.int32)
    k = v - (ends[seg] - count[seg])          # the visit's place in its tile
    row = jnp.minimum(first[seg] + k, last[seg])  # past the end: stay put
    live = v < ends[-1]
    flags = (jnp.where(live & (k == 0), _FIRST, 0)
             | jnp.where(live & full[seg], _WORK, 0)).astype(jnp.int32)
    tiles = jnp.sum(jnp.where(full, count, 0)).astype(jnp.int32)
    return seg, row, flags, tiles


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

    Returns (sums (num_segments, d) f32, masses (num_segments,) f32, tiles
    () int32), ``tiles`` being the (segment tile, row tile) matmuls the
    kernel ran.
    """
    n, d = x.shape
    w = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    ids = segment_ids.astype(jnp.int32)
    bs, bn, S, R = tile_shape(n, num_segments, block_s, block_n, interpret)
    out_shape = [jax.ShapeDtypeStruct((S, d), jnp.float32),
                 jax.ShapeDtypeStruct((S, 1), jnp.float32)]

    if S == bs:  # one segment tile: the dense grid, rows as given
        n_pad = R - n
        xp = jnp.pad(x, ((0, n_pad), (0, 0)))
        wp = jnp.pad(w, (0, n_pad))[None, :]  # zero weight -> no contribution
        idp = jnp.pad(ids, (0, n_pad), constant_values=-1)[None, :]
        sums, mass = pl.pallas_call(
            _dense_kernel,
            grid=(R // bn,),
            in_specs=[
                pl.BlockSpec((1, bn), lambda j: (0, j)),
                pl.BlockSpec((1, bn), lambda j: (0, j)),
                pl.BlockSpec((bn, d), lambda j: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bs, d), lambda j: (0, 0)),
                pl.BlockSpec((bs, 1), lambda j: (0, 0)),
            ],
            out_shape=out_shape,
            interpret=interpret,
        )(idp, wp, xp)
        return sums[:num_segments], mass[:num_segments, 0], jnp.int32(R // bn)

    # stable sort by segment id; dropped ids (and the padding rows) go last
    keys = jnp.where((ids >= 0) & (ids < num_segments), ids, S)
    keys = jnp.pad(keys, (0, R - n), constant_values=S)
    keys, order = jax.lax.sort(
        (keys, jnp.arange(R, dtype=jnp.int32)), num_keys=1, is_stable=True)
    order = jnp.minimum(order, n - 1)  # padding rows read a real row at w = 0
    xs = jnp.take(x, order, axis=0)
    ws = jnp.where(keys < S, jnp.take(w, order), 0.0)[None, :]
    seg, row, flags, tiles = tile_schedule(keys, S // bs, bs, bn)

    sums, mass = pl.pallas_call(
        _banded_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(seg.shape[0],),
            in_specs=[
                pl.BlockSpec((1, bn), lambda v, s, r, f: (0, r[v])),
                pl.BlockSpec((1, bn), lambda v, s, r, f: (0, r[v])),
                pl.BlockSpec((bn, d), lambda v, s, r, f: (r[v], 0)),
            ],
            out_specs=[
                pl.BlockSpec((bs, d), lambda v, s, r, f: (s[v], 0)),
                pl.BlockSpec((bs, 1), lambda v, s, r, f: (s[v], 0)),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(seg, row, flags, keys[None, :], ws, xs)
    return sums[:num_segments], mass[:num_segments, 0], tiles
