"""Fused nearest-prototype / streaming top-k — the assign/TC hot path.

The serving assign path and the TC inner loop both reduce to: distances of
a query block against a big key set, keep the k best. Composing
``pairwise_sq_l2 -> top-k merge`` through XLA materializes the full
(query x key) distance block in HBM; this kernel streams key blocks
flash-attention-style instead — each program computes one (Bq, Bk)
distance tile on the MXU and folds it into a running (Bq, k) best list
carried in VMEM, so the distance tile is never written to HBM and traffic
is O(nq·d + p·d + nq·k).

Three entry points, one merge semantics (bit-compatible with the composed
``ref.pairwise_sq_l2 + ref.merge_topk`` path — DESIGN.md §16):

  * :func:`fused_topk`      — the Pallas kernel (TPU; interpret mode on CPU
    for the parity tests). Serves query != key sets and the self-kNN case
    (``knn_topk`` is this kernel with ``q_gidx = arange(n)``), takes
    self-exclusion as a *traced* global-query-index array (so blocked
    drivers can call it under ``lax.map`` with a dynamic block offset), and
    dequantizes int8 key tiles in-register.
  * :func:`fused_topk_xla`  — the same streaming fold expressed as a jnp
    ``fori_loop`` over key blocks: the production fused path on CPU/GPU
    (XLA compiles it well; Pallas-interpret would be orders slower). Peak
    memory O(nq·block_k), never (nq, p).
  * :func:`quantize_keys` / :func:`rescore_top1` — freeze-time per-feature
    int8 scale/zero-point quantization and the exact-f32 shortlist rescore
    the quantized ``impl`` variants use (``fused_bf16`` / ``fused_int8``
    shortlist with cheap distances, then rescore the shortlist against the
    full-precision buffer so labels match the exact path on separated
    data).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import runtime
from repro.kernels import ref

#: shortlist length the quantized assign variants rescore in exact f32
RESCORE_K = 8


def _sublane(dtype) -> int:
    """Minimum second-to-minor tile multiple for ``dtype`` on TPU
    (f32: 8, bf16: 16, int8: 32 — see the Pallas guide)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {1: 32, 2: 16}.get(itemsize, 8)


def _lane_pad(d: int) -> int:
    """Zero-pad width taking a feature dim to a 128-lane multiple. Padding
    features with 0.0 is bitwise-safe for sq-L2: each per-feature term of
    the norm/cross reductions is independent and x + 0.0 == x in f32."""
    return (-d) % 128 if d > 128 else (128 - d)


def _tile(block: int, rows: int, align: int) -> int:
    """Block size for an axis of ``rows`` (already a sublane multiple):
    the requested ``block`` clamped to the axis, rounded up to ``align``
    unless one block covers the whole axis. Mosaic requires a block's last
    dim to be a 128-lane multiple or the full array dim, which is why the
    key axis (the lane axis of the ``(1, bk)`` validity row and of every
    distance tile) passes ``align=128`` when compiling; interpret mode
    passes 1 so the parity tests can still drive multi-block grids at
    tiny n."""
    b = min(block, rows)
    return rows if b >= rows else -(-b // align) * align


def _merge_tile(run_d, run_i, d, j, k):
    """Fold one (bq, bk) distance tile into the running (bq, k) best list.

    k rounds of row-min selection with the tie semantics of
    ``ref.merge_topk``: the running list (earlier in concat order) wins a
    tie against the tile, and within either part the lowest column wins,
    which within the tile is the lowest global key index. The two parts
    are reduced separately instead of concatenated, and every value stays
    2-D, because Mosaic lays out neither an unaligned lane concat nor a
    1-D per-row vector."""
    bq, bk = d.shape
    big = jnp.iinfo(jnp.int32).max
    rcols = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    tcols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    new_d = jnp.full((bq, k), jnp.inf, jnp.float32)
    new_i = jnp.full((bq, k), -1, jnp.int32)
    for r in range(k):
        mr = jnp.min(run_d, axis=1, keepdims=True)
        mt = jnp.min(d, axis=1, keepdims=True)
        from_run = mr <= mt
        cr = jnp.min(jnp.where(run_d == mr, rcols, big), axis=1, keepdims=True)
        ct = jnp.min(jnp.where(d == mt, tcols, big), axis=1, keepdims=True)
        ir = jnp.sum(jnp.where(rcols == cr, run_i, 0), axis=1, keepdims=True)
        md = jnp.where(from_run, mr, mt)
        mi = jnp.where(from_run, ir, j * bk + ct)
        new_d = jnp.where(rcols == r, md, new_d)
        new_i = jnp.where(rcols == r, jnp.where(jnp.isfinite(md), mi, -1),
                          new_i)
        run_d = jnp.where(from_run & (rcols == cr), jnp.inf, run_d)
        d = jnp.where(~from_run & (tcols == ct), jnp.inf, d)
    return new_d, new_i


def _fused_kernel(*refs, k, has_qg, quantized):
    it = iter(refs)
    q_ref = next(it)
    y_ref = next(it)
    yv_ref = next(it)
    qg_ref = next(it) if has_qg else None
    if quantized:
        scale_ref = next(it)
        zero_ref = next(it)
    bd_ref = next(it)
    bi_ref = next(it)

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bd_ref[...] = jnp.full(bd_ref.shape, jnp.inf, jnp.float32)
        bi_ref[...] = jnp.full(bi_ref.shape, -1, jnp.int32)

    x = q_ref[...].astype(jnp.float32)  # (bq, d)
    if quantized:
        # dequantize the int8 key tile in-register: padded features carry
        # scale == zero == 0 so they contribute exact 0.0 to the distance
        y = y_ref[...].astype(jnp.float32) * scale_ref[...] + zero_ref[...]
    else:
        y = y_ref[...].astype(jnp.float32)  # (bk, d)
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    yn = jnp.sum(y * y, axis=-1)[None, :]
    cross = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d = jnp.maximum(xn + yn - 2.0 * cross, 0.0)  # (bq, bk)
    d = jnp.where(yv_ref[...] > 0.0, d, jnp.inf)  # (1, bk) key validity
    if has_qg:
        # self-exclusion against *global* key indices; qg is a traced
        # (bq, 1) column so blocked drivers can pass `offset + iota`
        kcols = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
                 + j * d.shape[1])
        d = jnp.where(qg_ref[...] == kcols, jnp.inf, d)
    bd_ref[...], bi_ref[...] = _merge_tile(bd_ref[...], bi_ref[...], d, j, k)


def fused_topk(
    q: jax.Array,
    keys: jax.Array,
    k: int,
    key_valid: Optional[jax.Array] = None,
    *,
    q_gidx: Optional[jax.Array] = None,
    keys_scale: Optional[jax.Array] = None,
    keys_zero: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """k nearest valid keys of each query row — fused Pallas kernel.

    Args:
      q: (nq, d) queries (any float dtype; distances fold in f32).
      keys: (p, d) keys — float, or int8 with ``keys_scale``/``keys_zero``
        per-feature dequantization parameters (see :func:`quantize_keys`).
      k: best-list length (static; small — 1 for assign, t*-1 for TC).
      key_valid: optional (p,) mask; invalid keys get distance ``+inf``.
      q_gidx: optional (nq,) int32 *global* index of each query row among
        the keys — matching key columns are excluded (the blocked-kNN
        self-match mask). May be traced (dynamic block offsets).

    Returns:
      (dists (nq, k) ascending sq-L2 f32, idx (nq, k) int32; unfilled
      slots inf/-1). Bit-identical to
      ``ref.pairwise_sq_l2 + ref.merge_topk`` (DESIGN.md §16).
    """
    cfg = runtime.active()
    block_q = cfg.block_q if block_q is None else block_q
    block_k = cfg.block_k if block_k is None else block_k
    return _fused_topk(q, keys, k, key_valid, q_gidx, keys_scale, keys_zero,
                       block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_k", "interpret")
)
def _fused_topk(
    q: jax.Array,
    keys: jax.Array,
    k: int,
    key_valid: Optional[jax.Array] = None,
    q_gidx: Optional[jax.Array] = None,
    keys_scale: Optional[jax.Array] = None,
    keys_zero: Optional[jax.Array] = None,
    *,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
):
    nq, d = q.shape
    p = keys.shape[0]
    quantized = keys_scale is not None
    if key_valid is None:
        key_valid = jnp.ones((p,), jnp.float32)
    else:
        key_valid = key_valid.astype(jnp.float32)

    # Tiling: rows round up to the dtype's sublane multiple, each axis
    # then pads to its own block multiple so both grid axes tile with zero
    # remainder. Per-key and per-query vectors travel as 2-D blocks, the
    # validity row as (1, bk) and the self-exclusion column as (bq, 1):
    # Mosaic refuses the 1-D layouts XLA picks for them.
    qa = _sublane(q.dtype)
    qrows = -(-max(nq, qa) // qa) * qa
    bq = _tile(block_q, qrows, qa)
    nqp = -(-qrows // bq) * bq

    ka = _sublane(keys.dtype)
    krows = -(-max(p, ka) // ka) * ka
    bk = _tile(block_k, krows, 1 if interpret else 128)
    pp = -(-krows // bk) * bk

    d_pad = _lane_pad(d)
    qp = jnp.pad(q, ((0, nqp - nq), (0, d_pad)))
    yp = jnp.pad(keys, ((0, pp - p), (0, d_pad)))
    vp = jnp.pad(key_valid, (0, pp - p))[None, :]

    grid = (nqp // bq, pp // bk)
    dd = qp.shape[1]
    inputs = [qp, yp, vp]
    in_specs = [
        pl.BlockSpec((bq, dd), lambda i, j: (i, 0)),
        pl.BlockSpec((bk, dd), lambda i, j: (j, 0)),
        pl.BlockSpec((1, bk), lambda i, j: (0, j)),
    ]
    if q_gidx is not None:
        # padded query rows get -2: never matches a real key column
        inputs.append(jnp.pad(q_gidx.astype(jnp.int32), (0, nqp - nq),
                              constant_values=-2)[:, None])
        in_specs.append(pl.BlockSpec((bq, 1), lambda i, j: (i, 0)))
    if quantized:
        for v in (keys_scale, keys_zero):
            inputs.append(jnp.pad(v.astype(jnp.float32), (0, d_pad))[None, :])
            in_specs.append(pl.BlockSpec((1, dd), lambda i, j: (0, 0)))

    kernel = functools.partial(
        _fused_kernel, k=k, has_qg=q_gidx is not None, quantized=quantized)
    bd, bi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nqp, k), jnp.float32),
            jax.ShapeDtypeStruct((nqp, k), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    return bd[:nq], bi[:nq]


def fused_topk_xla(
    q: jax.Array,
    keys: jax.Array,
    k: int,
    key_valid: Optional[jax.Array] = None,
    *,
    q_gidx: Optional[jax.Array] = None,
    keys_scale: Optional[jax.Array] = None,
    keys_zero: Optional[jax.Array] = None,
    block_k: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The fused streaming fold as plain jnp — the production fused path on
    non-TPU backends. Identical signature/semantics to :func:`fused_topk`
    (minus ``block_q``: XLA fuses the query axis itself); peak live
    distance memory is O(nq·block_k) instead of O(nq·p)."""
    cfg = runtime.active()
    block_k = cfg.block_k if block_k is None else block_k
    return _fused_topk_xla(q, keys, k, key_valid, q_gidx, keys_scale,
                           keys_zero, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("k", "block_k"))
def _fused_topk_xla(
    q: jax.Array,
    keys: jax.Array,
    k: int,
    key_valid: Optional[jax.Array] = None,
    q_gidx: Optional[jax.Array] = None,
    keys_scale: Optional[jax.Array] = None,
    keys_zero: Optional[jax.Array] = None,
    *,
    block_k: int = 512,
):
    nq = q.shape[0]
    p = keys.shape[0]
    if key_valid is None:
        key_valid = jnp.ones((p,), bool)
    bk = min(block_k, max(p, 1))
    pad = (-p) % bk
    yp = jnp.pad(keys, ((0, pad), (0, 0)))
    vp = jnp.pad(key_valid.astype(bool), (0, pad))
    nb = (p + pad) // bk

    def body(b, carry):
        bd, bi = carry
        y = jax.lax.dynamic_slice_in_dim(yp, b * bk, bk, axis=0)
        if keys_scale is not None:
            y = (y.astype(jnp.float32) * keys_scale[None, :]
                 + keys_zero[None, :])
        v = jax.lax.dynamic_slice_in_dim(vp, b * bk, bk, axis=0)
        d = ref.pairwise_sq_l2(q, y, y_valid=v)
        gidx = b * bk + jnp.arange(bk, dtype=jnp.int32)
        if q_gidx is not None:
            d = jnp.where(q_gidx[:, None] == gidx[None, :], jnp.inf, d)
        return ref.merge_topk(bd, bi, d, jnp.broadcast_to(gidx, d.shape), k)

    init = (
        jnp.full((nq, k), jnp.inf, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )
    return jax.lax.fori_loop(0, nb, body, init)


# ---------------------------------------------------------------------------
# quantization (freeze time) + exact-f32 shortlist rescore (serve time)
# ---------------------------------------------------------------------------


def quantize_keys(
    keys: jax.Array, valid: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-feature symmetric-range int8 quantization of a key/prototype set.

    Scale/zero-point are computed over the *valid* rows only (padding rows
    carry arbitrary values and must not widen the range). Constant features
    (hi == lo) get a floor scale so dequantization reproduces them exactly
    via the zero-point.

    Returns ``(q8 (p, d) int8, scale (d,) f32, zero (d,) f32)`` with
    dequantization ``q8 * scale + zero``.
    """
    k32 = keys.astype(jnp.float32)
    if valid is None:
        v = jnp.ones((keys.shape[0],), bool)
    else:
        v = valid.astype(bool)
    any_valid = jnp.any(v)
    lo = jnp.min(jnp.where(v[:, None], k32, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(v[:, None], k32, -jnp.inf), axis=0)
    lo = jnp.where(any_valid, lo, 0.0)
    hi = jnp.where(any_valid, hi, 0.0)
    zero = 0.5 * (hi + lo)
    scale = jnp.maximum((hi - lo) / 254.0, 1e-12)
    q8 = jnp.clip(jnp.round((k32 - zero) / scale), -127.0, 127.0)
    return q8.astype(jnp.int8), scale, zero


def rescore_top1(
    queries: jax.Array,
    keys: jax.Array,
    valid: jax.Array,
    cand_idx: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Exact-f32 rescore of a quantized shortlist: gather the candidate
    rows of the *full-precision* key buffer and return the true nearest.

    Args:
      queries: (nq, d); keys: (p, d) full-precision buffer.
      valid: (p,) mask; cand_idx: (nq, r) shortlist (int32, -1 = empty).

    Returns:
      (dist (nq,), idx (nq,)) — exact sq-L2 to the winner, -1 if the
      shortlist holds no valid candidate.
    """
    q32 = queries.astype(jnp.float32)
    safe = jnp.where(cand_idx >= 0, cand_idx, 0)
    cp = keys.astype(jnp.float32)[safe]  # (nq, r, d)
    d = jnp.sum(jnp.square(q32[:, None, :] - cp), axis=-1)
    ok = (cand_idx >= 0) & valid.astype(bool)[safe]
    d = jnp.where(ok, d, jnp.inf)
    j = jnp.argmin(d, axis=1)
    dist = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
    idx = jnp.take_along_axis(cand_idx, j[:, None], axis=1)[:, 0]
    return dist, jnp.where(jnp.isfinite(dist), idx, -1)
