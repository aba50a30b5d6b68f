"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy (``impl=``):
  * ``None``     — pull the policy from the active runtime config
    (:func:`repro.runtime.active`); this is the default everywhere, so one
    ``runtime.configure(impl=...)`` switches the whole pipeline.
  * ``"auto"``   — Pallas on TPU, jnp reference elsewhere (XLA:CPU/GPU compile
    the references well; Pallas-interpret would be orders slower).
  * ``"pallas"`` — force the kernel; on non-TPU backends runs ``interpret=True``
    (that is exactly what the correctness tests do) unless the runtime config
    pins ``interpret`` explicitly.
  * ``"ref"``    — force the pure-jnp oracle.

The dry-run/roofline path always uses ``"ref"`` so that
``compiled.cost_analysis()`` sees real FLOPs (a Pallas custom-call is opaque
to HLO cost analysis — see DESIGN.md §7).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import runtime

from . import flash_attention as _fa
from . import fused_assign as _fused
from . import knn_topk as _knn
from . import pairwise_l2 as _pw
from . import ref
from . import segment_sum as _ss


from repro.runtime.config import _IMPLS as IMPLS  # single impl registry

#: the fused nearest/top-k dispatch family (DESIGN.md §16). Ops without a
#: fused path degrade these to "auto" so a process-wide impl="fused" only
#: changes the assign/kNN hot path.
_FUSED_IMPLS = ("fused", "fused_bf16", "fused_int8")


def _resolve(impl: Optional[str] = None, *, tuned: Optional[str] = None,
             fused: bool = False) -> str:
    """Dispatch policy → concrete impl name, rejecting unknown strings.

    ``tuned`` is the measured winner from the tuning cache (if any): it
    only decides the ``"auto"`` case — an explicit ``impl=`` kwarg or a
    configured non-auto policy always wins over the autotuner.

    ``fused=True`` marks entry points with a fused streaming path
    (nearest_topk / knn): they may resolve to the fused family, and their
    ``"auto"`` prefers it on TPU. Everywhere else the fused family (from a
    global config or a tuned winner) degrades to the ``"auto"`` resolution
    instead of raising.
    """
    if impl is None:
        impl = runtime.active().impl
    if impl in _FUSED_IMPLS and not fused:
        impl = "auto"
    if impl == "auto":
        tpu = jax.default_backend() == "tpu"
        impl = tuned or (("fused" if fused else "pallas") if tpu else "ref")
        if impl in _FUSED_IMPLS:
            # a tuned fused winner leaking into a non-fused op degrades the
            # same way a configured one does
            impl = impl if fused else ("pallas" if tpu else "ref")
    allowed = ("pallas", "ref") + (_FUSED_IMPLS if fused else ())
    if impl not in allowed:
        # an unknown string used to fall through silently to the XLA path —
        # a typo'd impl="palas" would quietly benchmark the wrong kernel
        raise ValueError(
            f"unknown impl {impl!r}; registered impls: {list(IMPLS)}")
    return impl


def _interpret() -> bool:
    pinned = runtime.active().interpret
    if pinned is not None:
        return bool(pinned)
    return jax.default_backend() != "tpu"


def _tuned(kernel: str, dtype, **dims: int) -> dict:
    """Measured winners for this call's shape bucket (``{}`` unless the
    tuning policy is active and has/measures an entry — DESIGN.md §14).

    Called at trace time from inside jitted drivers; sound because every
    inner jit takes ``dispatch_key()`` as a static argument and the key
    carries the cache epoch whenever tuning is on, so changed winners
    always retrace.
    """
    if runtime.active().tune == "off":
        return {}
    from repro import tune  # lazy: keeps kernels importable without tune

    return tune.tuned_params(kernel, dtype=str(dtype), **dims)


def pairwise_sq_l2(
    x: jax.Array,
    y: jax.Array,
    *,
    y_valid: Optional[jax.Array] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    tp = _tuned("pairwise_sq_l2", x.dtype,
                n=x.shape[0], m=y.shape[0], d=x.shape[1])
    if _resolve(impl, tuned=tp.get("impl")) == "pallas":
        kw = {a: tp[a] for a in ("block_q", "block_k") if a in tp}
        return _pw.pairwise_sq_l2(x, y, y_valid, interpret=_interpret(), **kw)
    return ref.pairwise_sq_l2(x, y, y_valid=y_valid)


def knn(
    x: jax.Array,
    k: int,
    *,
    valid: Optional[jax.Array] = None,
    exclude_self: bool = True,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    tp = _tuned("knn", x.dtype, n=x.shape[0], d=x.shape[1], k=k)
    r = _resolve(impl, tuned=tp.get("impl"), fused=True)
    if r in _FUSED_IMPLS:
        # the self-kNN Pallas kernel (knn_topk) IS the fused kernel for the
        # x-vs-x case; off-TPU the fused path is the XLA streaming fold
        # (never materializes (n, n)) — quantized variants have no frozen
        # buffer here, so they degrade to the f32 fused path
        if _use_pallas_fused():
            r = "pallas"
        else:
            gidx = (jnp.arange(x.shape[0], dtype=jnp.int32)
                    if exclude_self else None)
            return _fused.fused_topk_xla(x, x, k, valid, q_gidx=gidx,
                                         block_k=tp.get("block_k"))
    if r == "pallas":
        return _knn.knn_topk(
            x, k, valid, exclude_self=exclude_self, interpret=_interpret(),
            block_q=tp.get("block_q"), block_k=tp.get("block_k"),
        )
    return ref.knn(x, k, valid=valid, exclude_self=exclude_self)


def _use_pallas_fused() -> bool:
    """Whether the fused family dispatches to the Pallas kernel (real TPU,
    or interpret explicitly pinned on — what the parity tests do) rather
    than the XLA streaming fold (the off-TPU production fused path)."""
    return (jax.default_backend() == "tpu"
            or runtime.active().interpret is True)


def resolve_nearest(impl: Optional[str], *, dtype, nq: int, p: int, d: int,
                    k: int = 1) -> Tuple[str, dict]:
    """Resolve the nearest/top-k dispatch family through the ``"assign"``
    tuning cell. Returns ``(resolved impl, tuned params)`` — the tuned tile
    sizes apply only when the caller passed none explicitly."""
    tp = _tuned("assign", dtype, nq=nq, p=p, d=d, k=k)
    return _resolve(impl, tuned=tp.get("impl"), fused=True), tp


def nearest_topk(
    q: jax.Array,
    keys: jax.Array,
    k: int,
    *,
    key_valid: Optional[jax.Array] = None,
    q_gidx: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """k nearest valid keys of each query row (dists ascending, idx; -1 for
    unfillable slots) — the assign/TC hot-path entry point (DESIGN.md §16).

    The fused family streams key blocks against a running best list
    (Pallas kernel on TPU, XLA fold elsewhere; the distance block is never
    materialized); ``"pallas"``/``"ref"`` compose a dense distance matrix
    with the same merge, bit-identical by the shared tie semantics of
    :func:`repro.kernels.ref.merge_topk`. The quantized variants degrade
    to ``"fused"`` here — frozen low-precision buffers live on
    :class:`repro.core.index.ClusterIndex`, not at this stateless layer.
    """
    r, tp = resolve_nearest(impl, dtype=q.dtype, nq=q.shape[0],
                            p=keys.shape[0], d=q.shape[1], k=k)
    bq = block_q if block_q is not None else tp.get("block_q")
    bk = block_k if block_k is not None else tp.get("block_k")
    if r in _FUSED_IMPLS:
        if _use_pallas_fused():
            return _fused.fused_topk(
                q, keys, k, key_valid, q_gidx=q_gidx,
                block_q=bq, block_k=bk, interpret=_interpret())
        return _fused.fused_topk_xla(q, keys, k, key_valid, q_gidx=q_gidx,
                                     block_k=bk)
    if r == "pallas":
        d = _pw.pairwise_sq_l2(q, keys, key_valid, interpret=_interpret())
    else:
        d = ref.pairwise_sq_l2(q, keys, y_valid=key_valid)
    if q_gidx is not None:
        kcols = jnp.arange(keys.shape[0], dtype=jnp.int32)
        d = jnp.where(q_gidx[:, None] == kcols[None, :], jnp.inf, d)
    nq = q.shape[0]
    init_d = jnp.full((nq, k), jnp.inf, jnp.float32)
    init_i = jnp.full((nq, k), -1, jnp.int32)
    gidx = jnp.arange(keys.shape[0], dtype=jnp.int32)
    return ref.merge_topk(init_d, init_i, d, jnp.broadcast_to(gidx, d.shape),
                          k)


def segment_sum(
    x: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    *,
    weights: Optional[jax.Array] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Weighted segment sum; ids outside [0, num_segments) are dropped.

    Returns ``(sums, masses, tiles)``: ``tiles`` is the () int32 count of
    (segment tile, row tile) matmuls the Pallas kernel ran, 0 on the
    reference path.
    """
    tp = _tuned("segment_sum", x.dtype,
                n=x.shape[0], d=x.shape[1], s=num_segments)
    if _resolve(impl, tuned=tp.get("impl")) == "pallas":
        kw = {a: tp[a] for a in ("block_s", "block_n") if a in tp}
        return _ss.segment_sum(
            x, segment_ids, num_segments, weights, interpret=_interpret(),
            **kw
        )
    return (*ref.segment_sum(x, segment_ids, num_segments, weights=weights),
            jnp.int32(0))


def blocked_segment_sum(
    x: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    *,
    weights: Optional[jax.Array] = None,
    n_blocks: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Segment sum with a *fixed* reduction tree (DESIGN.md §4.3).

    Rows are split into ``n_blocks`` equal blocks (right-padded with dropped
    ids), per-block partials are computed independently, and the partials are
    accumulated left-to-right in block order. Because the summation order is
    pinned by ``n_blocks`` — not by how rows happen to be laid out across
    devices — a sharded execution whose P shards each compute their
    ``n_blocks/P`` local partials and fold the all-gathered stack in the same
    block order reproduces this result bit-for-bit. This is what makes the
    distributed ITIS/IHTC pipeline label-identical to the single-device one.

    ``n_blocks`` defaults to the active runtime config's reduction width;
    ``n_blocks <= 1`` falls back to the plain one-shot ``segment_sum``.
    Returns ``(sums, masses, tiles)``, ``tiles`` summed over the blocks.
    """
    if n_blocks is None:
        n_blocks = runtime.active().n_blocks
    n = x.shape[0]
    if n_blocks <= 1:
        return segment_sum(x, segment_ids, num_segments, weights=weights,
                           impl=impl)
    pad = (-n) % n_blocks
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    # padded rows get id == num_segments, which segment_sum drops
    ip = jnp.pad(segment_ids, (0, pad), constant_values=num_segments)
    wp = None if weights is None else jnp.pad(weights, (0, pad))
    nb = (n + pad) // n_blocks
    sums = masses = tiles = None
    for b in range(n_blocks):  # static unroll: left fold in block order
        sl = slice(b * nb, (b + 1) * nb)
        s_b, m_b, t_b = segment_sum(
            xp[sl], ip[sl], num_segments,
            weights=None if wp is None else wp[sl], impl=impl,
        )
        sums = s_b if sums is None else sums + s_b
        masses = m_b if masses is None else masses + m_b
        tiles = t_b if tiles is None else tiles + t_b
    return sums, masses, tiles


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_bias: Optional[jax.Array] = None,
    logit_softcap: float = 0.0,
    impl: Optional[str] = None,
) -> jax.Array:
    """GQA-aware attention entry point: q (b, hq, lq, dh); k/v (b, hkv, lk, dh)."""
    hq, hkv = q.shape[1], k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        if kv_bias is not None and kv_bias.shape[1] != hq:
            kv_bias = jnp.repeat(kv_bias, rep, axis=1)
    if _resolve(impl) == "pallas":
        return _fa.flash_attention(
            q, k, v, kv_bias, causal=causal, scale=scale,
            logit_softcap=float(logit_softcap), interpret=_interpret(),
        )
    return ref.flash_attention(
        q, k, v, causal=causal, scale=scale, kv_bias=kv_bias,
        logit_softcap=float(logit_softcap),
    )
