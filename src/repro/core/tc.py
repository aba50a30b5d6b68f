"""Threshold Clustering (TC) — Higgins et al. (2016), TPU-native.

TC partitions n points into clusters of size ≥ t* while 4-approximating the
bottleneck (max within-cluster dissimilarity) objective:

  1. build the (t*−1)-NN graph ``NG``;
  2. pick seeds ``S``: a maximal independent set of ``NG²`` (no two seeds
     within graph distance 2; every non-seed within distance 2 of a seed);
  3. grow: cluster(seed) = seed + its NG-neighbours;
  4. assign each remaining unit (distance exactly 2 from ≥1 seed) to the seed
     with the smallest *direct* dissimilarity.

Hardware adaptation (see DESIGN.md §2): the paper's greedy sequential seed
scan is replaced by a **deterministic Luby/Blelloch parallel MIS** — every
active vertex draws a fixed random priority (rank of a hashed permutation);
a vertex becomes a seed iff its priority is the maximum over its *closed
2-hop* neighbourhood of active vertices; selected seeds deactivate their
2-hop neighbourhood; repeat until no vertex is active. O(log n) rounds w.h.p.
and every round is dense vectorized gather/scatter over a fixed-shape (n, k)
adjacency — exactly what a TPU wants. The 4-approximation proof only needs
*maximality + independence* of the seed set, both of which are invariants of
any MIS, so the bound is preserved (property-tested in
tests/test_tc_properties.py).

The undirected kNN graph is stored as the directed (n, k) index array plus
implicit reverse edges, handled by the gather (out) + scatter (in) pair in
``_push_max``. All ops are mask-aware so TC composes with padded/masked ITIS
iterations under fixed XLA shapes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core.knn import knn_graph, knn_graph_blocked, resolve_auto_block

_NEG = jnp.int32(-1)  # priorities are ranks in [0, n); -1 == "-inf"


class TCResult(NamedTuple):
    labels: jax.Array       # (n,) int32 cluster id in [0, n_clusters), -1 invalid
    seed_of: jax.Array      # (n,) int32 vertex index of the owning seed, -1 invalid
    is_seed: jax.Array      # (n,) bool
    n_clusters: jax.Array   # () int32
    mis_rounds: Optional[jax.Array] = None  # () int32 Luby rounds run


def _push_max(p: jax.Array, idx: jax.Array, idx_ok: jax.Array) -> jax.Array:
    """max over *undirected* neighbours of p (edges = directed idx ∪ reverse).

    p: (n,) int32 with -1 as -inf; idx: (n, k) int32 (-1 = no edge);
    idx_ok: (n, k) bool.
    """
    n = p.shape[0]
    safe = jnp.where(idx_ok, idx, 0)
    out_vals = jnp.where(idx_ok, p[safe], _NEG)          # gather: i <- p[nbr]
    out_max = jnp.max(out_vals, axis=1, initial=_NEG)
    src_vals = jnp.where(idx_ok, p[:, None], _NEG)       # scatter: nbr <- p[i]
    in_max = jnp.full((n,), _NEG).at[safe.ravel()].max(src_vals.ravel())
    return jnp.maximum(out_max, in_max)


def _closed2_max(p: jax.Array, idx: jax.Array, idx_ok: jax.Array) -> jax.Array:
    """max of p over the closed ≤2-hop neighbourhood of each vertex."""
    q1 = jnp.maximum(p, _push_max(p, idx, idx_ok))
    return jnp.maximum(q1, _push_max(q1, idx, idx_ok))


def luby_mis_rounds(
    priorities: jax.Array, active0: jax.Array, closed2_max
) -> Tuple[jax.Array, jax.Array]:
    """Maximal independent set via parallel local-maxima rounds; returns
    ``(is_seed, rounds)``, ``rounds`` the () int32 count of rounds run.

    ``closed2_max(p)`` must return, per vertex, the max of ``p`` over that
    vertex's closed ≤2-hop neighbourhood. The single-device path passes
    :func:`_closed2_max` over the local (n, k) adjacency; the sharded path
    (repro.core.distributed) passes a cross-shard pmax-combining operator —
    both run the *same* round structure here, which is what keeps the two
    executions seed-set-identical (DESIGN.md §4.2).
    """

    def cond(state):
        active, _, _ = state
        return jnp.any(active)

    def body(state):
        active, seed, rounds = state
        p_eff = jnp.where(active, priorities, _NEG)
        m2 = closed2_max(p_eff)
        newly = active & (p_eff == m2)
        seed = seed | newly
        # deactivate the closed 2-hop neighbourhood of the new seeds
        b = jnp.where(newly, jnp.int32(1), jnp.int32(0))
        covered = closed2_max(b) > 0
        active = active & ~covered & ~newly
        return active, seed, rounds + 1

    # derive from active0 (not a fresh constant) so the carry keeps the same
    # varying-manual-axes type under shard_map
    seed0 = active0 & False
    _, seed, rounds = jax.lax.while_loop(cond, body,
                                         (active0, seed0, jnp.int32(0)))
    return seed, rounds


def _luby_mis_sq(
    priorities: jax.Array, idx: jax.Array, idx_ok: jax.Array, active0: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """MIS of NG² on one device: local-adjacency ``closed2`` plug-in."""
    return luby_mis_rounds(
        priorities, active0, lambda p: _closed2_max(p, idx, idx_ok)
    )


def seed_priorities(key: jax.Array, n: int) -> jax.Array:
    """Fixed random priorities: ranks of a hashed permutation (deterministic).

    Shared by the single-device and sharded TC paths — identical keys and
    buffer sizes give identical priorities, hence identical MIS seed sets.
    """
    u = jax.random.uniform(key, (n,))
    order = jnp.argsort(u)
    return jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))


def _sq_dist_rows(x: jax.Array, i_rows: jax.Array, j_rows: jax.Array) -> jax.Array:
    """||x[i] - x[j]||² for index arrays of equal shape (computed in f32)."""
    a = x[i_rows].astype(jnp.float32)
    b = x[j_rows].astype(jnp.float32)
    return jnp.sum(jnp.square(a - b), axis=-1)


def threshold_clustering(
    x: jax.Array,
    t: int,
    *,
    valid: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    knn_block: Optional[int] = None,
) -> TCResult:
    """Run TC with minimum cluster size ``t`` on (n, d) points.

    ``valid`` masks padded rows (ITIS levels); invalid rows get label -1 and
    transmit no graph edges. ``knn_block`` > 0 selects the blocked kNN path.
    ``impl``/``knn_block`` default to the active runtime config (DESIGN.md
    §10) — resolved *before* the jit boundary so the compiled-cache key
    always carries the concrete values. Deterministic given ``key``
    (default: PRNGKey(0)).
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    knn_block = cfg.knn_block if knn_block is None else knn_block
    return _threshold_clustering(x, t, valid=valid, key=key, impl=impl,
                                 knn_block=knn_block,
                                 _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit, static_argnames=("t", "impl", "knn_block", "_dispatch")
)
def _threshold_clustering(
    x: jax.Array,
    t: int,
    *,
    valid: Optional[jax.Array],
    key: Optional[jax.Array],
    impl: str,
    knn_block: int,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
) -> TCResult:
    n = x.shape[0]
    if valid is None:
        # derived from x (not a fresh constant) so TC composes with shard_map
        # (keeps the varying-manual-axes type); x==x is all-true for finite x
        valid = x[:, 0] == x[:, 0]
    if key is None:
        key = jax.random.PRNGKey(0)

    if t <= 1:  # degenerate: singletons
        labels = jnp.where(valid, jnp.cumsum(valid) - 1, -1).astype(jnp.int32)
        seed_of = jnp.where(valid, jnp.arange(n), -1).astype(jnp.int32)
        return TCResult(labels, seed_of, valid, jnp.sum(valid).astype(jnp.int32),
                        jnp.int32(0))

    k = t - 1
    # auto: avoid O(n²) HBM at scale (tuned winner when the policy is on;
    # trace-time read, pinned by the _dispatch static above)
    block = knn_block or resolve_auto_block(n, x.shape[1], k,
                                            dtype=str(x.dtype))
    # the scope names below are a contract with the benchmark (DESIGN.md §20)
    with jax.named_scope("tc.knn"):
        if n > block:
            _, idx = knn_graph_blocked(x, k, valid=valid, block=block,
                                       impl=impl)
        else:
            _, idx = knn_graph(x, k, valid=valid, impl=impl)
        idx = jnp.where(valid[:, None], idx, -1)       # invalid rows: no out-edges
        idx_ok = idx >= 0                               # kNN never returns invalid keys

    with jax.named_scope("tc.mis"):
        priorities = seed_priorities(key, n)
        is_seed, mis_rounds = _luby_mis_sq(priorities, idx, idx_ok, valid)

    with jax.named_scope("tc.grow"):
        labels, seed_of, n_clusters = _grow(x, idx, idx_ok, is_seed, valid)
    return TCResult(labels, seed_of, is_seed, n_clusters, mis_rounds)


def _grow(x, idx, idx_ok, is_seed, valid):
    """Steps 3–4 of TC: grow each seed over its graph neighbours, hand the
    leftovers to the nearest seed, and compact cluster ids; returns
    ``(labels, seed_of, n_clusters)``."""
    n = x.shape[0]
    # ---- grow: each vertex adjacent to a seed joins that seed (unique by MIS) ----
    n_arange = jnp.arange(n, dtype=jnp.int32)
    safe = jnp.where(idx_ok, idx, 0)
    out_lab = jnp.max(
        jnp.where(idx_ok & is_seed[safe], safe, -1), axis=1, initial=_NEG
    )  # i's out-neighbour that is a seed
    src = jnp.where(idx_ok & is_seed[:, None], n_arange[:, None], -1)
    in_lab = jnp.full((n,), _NEG).at[safe.ravel()].max(src.ravel())
    seed_of = jnp.maximum(out_lab, in_lab)
    seed_of = jnp.where(is_seed, n_arange, seed_of)

    # ---- assign leftovers (graph distance exactly 2) to nearest seed ----
    labeled = seed_of >= 0
    # out-direction candidates: s = seed_of[out-neighbour]
    cand_out = jnp.where(idx_ok, seed_of[safe], -1)                   # (n, k)
    cand_ok = cand_out >= 0
    d_out = jnp.where(
        cand_ok,
        _sq_dist_rows(x, jnp.broadcast_to(n_arange[:, None], cand_out.shape),
                      jnp.where(cand_ok, cand_out, 0)),
        jnp.inf,
    )
    best_out_d = jnp.min(d_out, axis=1)
    best_out_s = jnp.where(
        jnp.isfinite(best_out_d),
        jnp.take_along_axis(cand_out, jnp.argmin(d_out, axis=1)[:, None], axis=1)[:, 0],
        -1,
    )
    # in-direction: edge (v -> i): candidate seed_of[v] at distance ||x_i - x_seed||
    s_v = jnp.broadcast_to(seed_of[:, None], idx.shape)               # (n, k)
    edge_ok = idx_ok & (s_v >= 0)
    d_edge = jnp.where(
        edge_ok, _sq_dist_rows(x, safe, jnp.where(edge_ok, s_v, 0)), jnp.inf
    )
    tgt = safe.ravel()
    d_in = jnp.full((n,), jnp.inf).at[tgt].min(
        jnp.where(edge_ok, d_edge, jnp.inf).ravel()
    )
    winners = edge_ok & (d_edge <= d_in[safe])
    s_in = jnp.full((n,), _NEG).at[tgt].max(jnp.where(winners, s_v, -1).ravel())

    use_out = best_out_d <= d_in
    fallback = jnp.where(use_out, best_out_s, s_in)
    seed_of = jnp.where(labeled, seed_of, fallback)
    seed_of = jnp.where(valid, seed_of, -1)

    # ---- compact cluster ids: rank among seeds ----
    seed_rank = (jnp.cumsum(is_seed.astype(jnp.int32)) - 1).astype(jnp.int32)
    labels = jnp.where(seed_of >= 0, seed_rank[jnp.where(seed_of >= 0, seed_of, 0)], -1)
    n_clusters = jnp.sum(is_seed).astype(jnp.int32)
    return labels.astype(jnp.int32), seed_of.astype(jnp.int32), n_clusters
