"""ITIS — Iterated Threshold Instance Selection (the paper's §3.1).

Repeat {TC at threshold t* → collapse clusters to prototypes} m times.
Each iteration shrinks the point set by ≥ t*, so ITIS level l lives in a
*static* padded buffer of size n₀ // (t*)^l — fully jit-compatible fixed
shapes with validity masks (one XLA program per level shape; the geometric
shrink means total compile+run cost is dominated by level 0). See
DESIGN.md §3 for the padding scheme and DESIGN.md §4 for the multi-device
twin of this driver (:func:`repro.core.distributed.itis_sharded`), which
shares :func:`level_sizes` so both drivers agree on every buffer shape.

The host-level driver (`itis`) orchestrates the per-level jitted step and
keeps the level assignment maps needed for IHTC back-out.

:func:`level_sizes` and :func:`validate_reduction_params` are the single
sources every fit executor shares — the planner (:mod:`repro.core.plan`,
DESIGN.md §13) wraps them as ``FitPlan.schedule`` and validates once at
plan time, so no executor re-implements level scheduling or t/m rules.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core.prototypes import PrototypeSet, reduce_to_prototypes
from repro.core.tc import TCResult, threshold_clustering


def round_up(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is ≥ ``n``."""
    if multiple <= 1:
        return n
    return ((n + multiple - 1) // multiple) * multiple


def validate_reduction_params(
    t: int, m: int, *, n: Optional[int] = None, min_m: int = 0,
    driver: str = "itis",
) -> None:
    """Reject t/m values every ITIS-family driver would silently mishandle.

    ``t < 2`` never shrinks the point set, so ``t = 1`` would run ``m``
    full-size levels (the original silent-acceptance bug); ``m`` below
    ``min_m`` is meaningless for the driver; and with any reduction level to
    run, TC needs a ``k = t - 1``-NN graph, which requires ``t - 1 < n``.
    """
    if int(t) != t or t < 2:
        raise ValueError(
            f"{driver}: threshold t must be an integer >= 2 (t={t!r} would "
            f"never shrink the point set, so every level stays full-size)")
    if int(m) != m or m < min_m:
        raise ValueError(
            f"{driver}: iteration count m must be an integer >= {min_m}, "
            f"got {m!r}")
    if n is not None and m >= 1 and t - 1 >= n:
        raise ValueError(
            f"{driver}: TC builds a k = t-1 = {t - 1} nearest-neighbour "
            f"graph, which needs t - 1 < n points; got n={n}")


def level_sizes(n0: int, t: int, m: int, *, multiple: int = 1) -> List[int]:
    """Static buffer size of every ITIS level, levels 0..m inclusive.

    ``multiple`` pads each level to a multiple (1 = the paper-exact sizes;
    the sharded driver uses the reduction-block count so every level splits
    evenly across devices). Both the single-device and the distributed
    drivers derive their shapes from this one function: when the unpadded
    sizes already satisfy the multiple, the two compute in identical buffers
    and their results agree bit-for-bit (DESIGN.md §4.3).
    """
    validate_reduction_params(t, m, driver="level_sizes")
    sizes = [round_up(n0, multiple)]
    for _ in range(m):
        sizes.append(round_up(max(sizes[-1] // t, 1), multiple))
    return sizes


class ITISLevelOut(NamedTuple):
    protos: jax.Array      # (n_out_max, d)
    mass: jax.Array        # (n_out_max,)
    valid: jax.Array       # (n_out_max,) bool
    assignment: jax.Array  # (n_in,) int32 → [0, n_out_max), -1 for padding
    n_clusters: jax.Array  # () int32
    mis_rounds: Optional[jax.Array] = None  # () int32 Luby rounds of the level
    reduce_tiles: Optional[jax.Array] = None  # () int32 reduce tile matmuls


class ITISResult(NamedTuple):
    protos: jax.Array               # final level prototypes (padded)
    mass: jax.Array
    valid: jax.Array
    assignments: Sequence[jax.Array]  # one per level, for back-out
    n_prototypes: jax.Array           # () int32 — valid count at final level
    mis_rounds: Sequence[jax.Array] = ()  # () int32 per level run
    reduce_tiles: Sequence[jax.Array] = ()  # () int32 per level run


def itis_step(
    x: jax.Array,
    mass: jax.Array,
    valid: jax.Array,
    t: int,
    *,
    key: jax.Array,
    weighted: bool = False,
    impl: Optional[str] = None,
    knn_block: Optional[int] = None,
    n_out: Optional[int] = None,
    n_blocks: Optional[int] = None,
) -> ITISLevelOut:
    """One ITIS level: TC on the valid points, reduce to ≤ n//t prototypes.

    ``n_out`` overrides the output buffer size (default ``max(n // t, 1)``;
    the sharded driver passes a device-padded size from ``level_sizes``).
    ``impl``/``knn_block``/``n_blocks`` default to the active runtime config,
    resolved before the jit boundary (DESIGN.md §10).
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    knn_block = cfg.knn_block if knn_block is None else knn_block
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    return _itis_step(x, mass, valid, t, key=key, weighted=weighted,
                      impl=impl, knn_block=knn_block, n_out=n_out,
                      n_blocks=n_blocks, _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit,
    static_argnames=("t", "weighted", "impl", "knn_block", "n_out",
                     "n_blocks", "_dispatch"),
)
def _itis_step(
    x: jax.Array,
    mass: jax.Array,
    valid: jax.Array,
    t: int,
    *,
    key: jax.Array,
    weighted: bool,
    impl: str,
    knn_block: int,
    n_out: Optional[int],
    n_blocks: int,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
) -> ITISLevelOut:
    n = x.shape[0]
    if n_out is None:
        n_out = max(n // t, 1)
    tc: TCResult = threshold_clustering(
        x, t, valid=valid, key=key, impl=impl, knn_block=knn_block
    )
    with jax.named_scope("itis.reduce"):
        ps: PrototypeSet = reduce_to_prototypes(
            x, tc.labels, n_out, weights=mass, weighted=weighted, impl=impl,
            n_blocks=n_blocks,
        )
    return ITISLevelOut(ps.x, ps.mass, ps.valid, tc.labels, tc.n_clusters,
                        tc.mis_rounds, ps.tiles)


def itis(
    x: jax.Array,
    t: int,
    m: int,
    *,
    weights: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    weighted: bool = False,
    impl: Optional[str] = None,
    knn_block: Optional[int] = None,
    min_points: int = 4,
    pad_multiple: int = 1,
    n_blocks: Optional[int] = None,
) -> ITISResult:
    """Run m ITIS iterations (host driver).

    Stops early if fewer than ``max(min_points, 2*t)`` valid points remain
    (further reduction would collapse everything into one cluster).
    ``pad_multiple`` > 1 pads every level buffer to that multiple (used to
    shape-match the sharded driver; results are unchanged semantically but
    padding alters TC's random seed-priority draw, so only shape-identical
    runs are bit-comparable — see DESIGN.md §4.3). ``impl``/``knn_block``/
    ``n_blocks`` default to the active runtime config.
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    knn_block = cfg.knn_block if knn_block is None else knn_block
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    validate_reduction_params(t, m, n=x.shape[0], driver="itis")
    if key is None:
        key = jax.random.PRNGKey(0)
    n = x.shape[0]
    mass = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    valid = jnp.ones((n,), bool)

    sizes = level_sizes(n, t, m, multiple=pad_multiple)
    if sizes[0] != n:
        pad = sizes[0] - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        mass = jnp.pad(mass, (0, pad))
        valid = jnp.pad(valid, (0, pad))

    assignments, mis_rounds, reduce_tiles = [], [], []
    cur_x, cur_m, cur_v = x, mass, valid
    n_protos = jnp.sum(cur_v).astype(jnp.int32)
    for level in range(m):
        # host span names are a contract with the benchmark (DESIGN.md §20)
        with jax.profiler.TraceAnnotation("repro.itis.sync"):
            # repro: allow[HS202]: deliberate per-level sync — the early-exit floor is a host decision, m times per fit
            n_valid = int(jnp.sum(cur_v))
        if n_valid < max(min_points, 2 * t):
            break
        with jax.profiler.TraceAnnotation("repro.itis.level"):
            key, sub = jax.random.split(key)
            out = itis_step(
                cur_x, cur_m, cur_v, t,
                key=sub, weighted=weighted, impl=impl, knn_block=knn_block,
                n_out=sizes[level + 1], n_blocks=n_blocks,
            )
        assignments.append(out.assignment)
        mis_rounds.append(out.mis_rounds)
        reduce_tiles.append(out.reduce_tiles)
        cur_x, cur_m, cur_v = out.protos, out.mass, out.valid
        n_protos = out.n_clusters
    return ITISResult(cur_x, cur_m, cur_v, assignments, n_protos, mis_rounds,
                      reduce_tiles)
