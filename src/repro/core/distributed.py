"""End-to-end sharded ITIS / IHTC over the ``data`` mesh axis.

Every ITIS level runs inside one ``shard_map`` program per level shape:

  1. **TC** — the kNN graph is built with :func:`repro.core.knn.ring_knn`
     (keys rotate around the ring, global neighbour indices), the
     Luby/Blelloch MIS runs the *same* round structure as the single-device
     path (:func:`repro.core.tc.luby_mis_rounds`) with a cross-shard
     ``closed2`` operator: each shard computes its local gather/scatter
     contribution over its (n_local, k) adjacency slice and the per-vertex
     max is combined with ``lax.pmax`` (ints — exact, order-free). Leftover
     units are assigned to their nearest seed using a replicated
     seed-coordinate table (built by exact psum-scatter of each shard's seed
     rows) plus a second ring pass that carries each shard's point block past
     every shard so in-edge distances ``||x_i − x_seed||²`` are evaluated
     where the edge lives. The large O(n·(d+k)) state — points, kNN graph,
     distance blocks — stays sharded; only O(n)-bit label/priority vectors
     and the O(n/t · d) seed table (= the *next* level's point set) are
     replicated.
  2. **Prototype reduce + rebalance** — per-shard blocked segment-sums are
     all-gathered and folded left-to-right in canonical block order
     (mirroring ``ops.blocked_segment_sum`` exactly), then each shard keeps
     its contiguous slice of the level-(l+1) buffer, so the next level stays
     evenly sharded in its static padded buffer.
  3. **Backend** — a mesh-aware weighted k-means: centroids (k, d) are
     replicated, rows stay sharded, assignment statistics are combined with
     the same ordered all-gather fold, and k-means++ draws from all-gathered
     global logits. The point set is never gathered to one device.

Determinism contract (DESIGN.md §4.3): every cross-shard combination is
either an exact operation (int/bool ``pmax``/``pmin``, float ``min``/``max``,
psum of disjoint one-hot contributions) or a float accumulation folded in the
canonical ``n_blocks`` order that the single-device path also uses. When the
level buffer sizes of :func:`repro.core.itis.level_sizes` already divide
evenly by the shard count (so no extra padding changes TC's priority draw),
``ihtc_sharded`` is **bit-identical** to single-device ``ihtc`` — asserted on
an 8-device CPU mesh in tests/test_distribution.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import runtime
from repro.cluster.registry import BackendFn
from repro.core.itis import ITISResult, level_sizes, validate_reduction_params
from repro.core.knn import ring_knn
from repro.core.plan import (
    FitPlan,
    FitResult,
    Reduction,
    fit,
    register_executor,
)
from repro.core.tc import _NEG, luby_mis_rounds, seed_priorities
from repro.kernels import ops


def make_data_mesh(n_data: Optional[int] = None):
    """1-D ``("data",)`` mesh over the first ``n_data`` (default all) devices."""
    devices = jax.devices()
    n = n_data or len(devices)
    return jax.sharding.Mesh(devices[:n], ("data",))


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off: the per-level
    programs mix replicated and per-shard values in while-loop carries, and
    their correctness is pinned by the parity tests instead."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# sharded TC (runs inside shard_map)
# ---------------------------------------------------------------------------


def _gather1d(x_local: jax.Array, axis_name: str) -> jax.Array:
    """(n_local, ...) → replicated (n, ...) in shard order (exact copy)."""
    return jax.lax.all_gather(x_local, axis_name, tiled=True)


def _local_rows(vec: jax.Array, row0: jax.Array, n_local: int) -> jax.Array:
    """My shard's contiguous slice of a replicated per-vertex vector."""
    return jax.lax.dynamic_slice_in_dim(vec, row0, n_local, axis=0)


def tc_sharded(
    x_local: jax.Array,
    valid_local: jax.Array,
    t: int,
    key: jax.Array,
    *,
    axis_name: str,
    impl: Optional[str] = None,
):
    """Global TC on row-sharded points; returns (labels (n,) replicated,
    is_seed (n,) replicated, n_clusters ()).

    Computes the same function as single-device ``threshold_clustering`` on
    the concatenated rows — same kNN graph (ring pass), same MIS rounds,
    same leftover tie-breaking — with only per-vertex vectors replicated.
    """
    n_local, d = x_local.shape
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    n = n_local * p
    row0 = me * n_local
    rows = row0 + jnp.arange(n_local, dtype=jnp.int32)

    valid = _gather1d(valid_local, axis_name)  # (n,) replicated

    if t <= 1:  # degenerate: singletons (replicated compute on (n,) bools)
        labels = jnp.where(valid, jnp.cumsum(valid) - 1, -1).astype(jnp.int32)
        is_seed = valid
        return labels, is_seed, jnp.sum(valid).astype(jnp.int32)

    k = t - 1
    _, idx = ring_knn(x_local, k, axis_name=axis_name, valid=valid_local,
                      impl=impl)
    idx = jnp.where(valid_local[:, None], idx, -1)  # invalid rows: no out-edges
    idx_ok = idx >= 0
    safe = jnp.where(idx_ok, idx, 0)

    def push_max(pvec):
        # max over undirected neighbours, assembled from this shard's directed
        # edge slice and combined across shards with an exact integer pmax.
        out_max = jnp.max(jnp.where(idx_ok, pvec[safe], _NEG), axis=1,
                          initial=_NEG)                      # (n_local,)
        part = jnp.full((n,), _NEG).at[rows].set(out_max)
        src = jnp.where(idx_ok, _local_rows(pvec, row0, n_local)[:, None], _NEG)
        part = part.at[safe.ravel()].max(src.ravel())
        return jax.lax.pmax(part, axis_name)

    def closed2(pvec):
        q1 = jnp.maximum(pvec, push_max(pvec))
        return jnp.maximum(q1, push_max(q1))

    priorities = seed_priorities(key, n)  # replicated; identical to 1-device
    is_seed, _ = luby_mis_rounds(priorities, valid, closed2)

    # ---- grow: each vertex adjacent to a seed joins that seed ----
    n_arange = jnp.arange(n, dtype=jnp.int32)
    out_lab = jnp.max(jnp.where(idx_ok & is_seed[safe], safe, -1), axis=1,
                      initial=_NEG)
    part = jnp.full((n,), _NEG).at[rows].set(out_lab)
    src = jnp.where(idx_ok & is_seed[rows][:, None], rows[:, None], -1)
    part = part.at[safe.ravel()].max(src.ravel())
    seed_of = jax.lax.pmax(part, axis_name)
    seed_of = jnp.where(is_seed, n_arange, seed_of)

    # ---- leftover assignment: nearest seed at graph distance 2 ----
    labeled = seed_of >= 0
    seed_rank = (jnp.cumsum(is_seed.astype(jnp.int32)) - 1).astype(jnp.int32)
    n_seed_max = max(n // t, 1)  # TC guarantee: ≤ n/t disjoint size-≥t clusters

    # replicated seed-coordinate table: exact psum of disjoint one-hot rows
    slot = jnp.where(is_seed[rows], seed_rank[rows], n_seed_max)
    stbl = jnp.zeros((n_seed_max + 1, d), jnp.float32)
    stbl = stbl.at[slot].set(x_local.astype(jnp.float32))
    stbl = jax.lax.psum(stbl.at[n_seed_max].set(0.0), axis_name)

    def seed_coord(seed_vertex, ok):
        r = jnp.where(ok, seed_rank[jnp.where(ok, seed_vertex, 0)], n_seed_max)
        return stbl[r]

    # out-direction: my rows against their out-neighbours' seeds
    cand_out = jnp.where(idx_ok, seed_of[safe], -1)                 # (nl, k)
    cand_ok = cand_out >= 0
    a = x_local.astype(jnp.float32)[:, None, :]
    d_out = jnp.where(
        cand_ok,
        jnp.sum(jnp.square(a - seed_coord(cand_out, cand_ok)), axis=-1),
        jnp.inf,
    )
    best_out_d = jnp.min(d_out, axis=1)                             # (nl,)
    best_out_s = jnp.where(
        jnp.isfinite(best_out_d),
        jnp.take_along_axis(cand_out, jnp.argmin(d_out, axis=1)[:, None],
                            axis=1)[:, 0],
        -1,
    )

    # in-direction: edge (v -> i) carries candidate seed_of[v]; the distance
    # ||x_i - x_seed||² needs x_i, which lives on i's shard — a second ring
    # pass rotates every point block past every shard so each edge is
    # evaluated exactly once, where the edge (not the point) lives.
    s_v = jnp.broadcast_to(seed_of[rows][:, None], idx.shape)       # (nl, k)
    edge_ok = idx_ok & (s_v >= 0)
    c_coord = seed_coord(s_v, edge_ok)                              # (nl, k, d)
    perm = [(i, (i - 1) % p) for i in range(p)]

    def ring_body(s, carry):
        d_edge, xblk = carry
        blk = (me + s) % p  # owner of the visiting block
        in_blk = edge_ok & (safe // n_local == blk)
        pos = jnp.where(in_blk, safe - blk * n_local, 0)
        tgt_coord = xblk[pos].astype(jnp.float32)                   # (nl, k, d)
        de = jnp.sum(jnp.square(tgt_coord - c_coord), axis=-1)
        d_edge = jnp.where(in_blk, de, d_edge)
        return d_edge, jax.lax.ppermute(xblk, axis_name, perm)

    d_edge0 = jnp.full(idx.shape, jnp.inf, jnp.float32)
    d_edge, _ = jax.lax.fori_loop(0, p, ring_body, (d_edge0, x_local))

    part_d = jnp.full((n,), jnp.inf).at[safe.ravel()].min(
        jnp.where(edge_ok, d_edge, jnp.inf).ravel())
    d_in = jax.lax.pmin(part_d, axis_name)                          # exact
    winners = edge_ok & (d_edge <= d_in[safe])
    part_s = jnp.full((n,), _NEG).at[safe.ravel()].max(
        jnp.where(winners, s_v, -1).ravel())
    s_in = jax.lax.pmax(part_s, axis_name)

    # assemble the per-row out-direction winners into replicated vectors
    pd = jax.lax.pmin(jnp.full((n,), jnp.inf).at[rows].set(best_out_d),
                      axis_name)
    ps_ = jax.lax.pmax(jnp.full((n,), _NEG).at[rows].set(best_out_s),
                       axis_name)
    use_out = pd <= d_in
    fallback = jnp.where(use_out, ps_, s_in)
    seed_of = jnp.where(labeled, seed_of, fallback)
    seed_of = jnp.where(valid, seed_of, -1)

    labels = jnp.where(seed_of >= 0,
                       seed_rank[jnp.where(seed_of >= 0, seed_of, 0)], -1)
    return labels.astype(jnp.int32), is_seed, jnp.sum(is_seed).astype(jnp.int32)


# ---------------------------------------------------------------------------
# sharded prototype reduce (ordered-fold twin of ops.blocked_segment_sum)
# ---------------------------------------------------------------------------


def _folded_segment_sum(x_local, ids_local, n_out, weights_local, *,
                        axis_name, n_blocks, impl):
    """Cross-shard segment sum in the canonical ``n_blocks`` fold order.

    Each of P shards computes its ``n_blocks / P`` per-block partials; the
    all-gathered (n_blocks, ...) stack is folded left-to-right — bitwise the
    same accumulation as ``ops.blocked_segment_sum(n_blocks=...)`` over the
    concatenated rows (requires P | n_blocks and n_blocks | n, which the
    driver's level padding guarantees).
    """
    p = jax.lax.axis_size(axis_name)
    sub = n_blocks // p
    nl = x_local.shape[0]
    pad = (-nl) % sub
    if pad:  # right-pad with dropped ids, like ops.blocked_segment_sum
        x_local = jnp.pad(x_local, ((0, pad), (0, 0)))
        ids_local = jnp.pad(ids_local, (0, pad), constant_values=n_out)
        if weights_local is not None:
            weights_local = jnp.pad(weights_local, (0, pad))
    nb = (nl + pad) // sub
    parts = []
    for b in range(sub):
        sl = slice(b * nb, (b + 1) * nb)
        parts.append(ops.segment_sum(
            x_local[sl], ids_local[sl], n_out,
            weights=None if weights_local is None else weights_local[sl],
            impl=impl))
    sums = jnp.stack([s for s, _, _ in parts])       # (sub, n_out, d)
    masses = jnp.stack([m for _, m, _ in parts])     # (sub, n_out)
    sums = _gather1d(sums, axis_name)                # (n_blocks, n_out, d)
    masses = _gather1d(masses, axis_name)
    acc_s, acc_m = sums[0], masses[0]
    for b in range(1, n_blocks):                     # left fold in block order
        acc_s = acc_s + sums[b]
        acc_m = acc_m + masses[b]
    return acc_s, acc_m


def _reduce_sharded(x_local, labels_local, n_out, *, weights_local, weighted,
                    axis_name, n_blocks, impl):
    """Sharded twin of ``reduce_to_prototypes``: replicated (n_out, d) result."""
    safe_labels = jnp.where(labels_local >= 0, labels_local, n_out).astype(jnp.int32)
    w = weights_local.astype(jnp.float32)
    if weighted:
        sums, denom = _folded_segment_sum(
            x_local, safe_labels, n_out, w,
            axis_name=axis_name, n_blocks=n_blocks, impl=impl)
        mass = denom
    else:
        ones = jnp.where(labels_local >= 0, 1.0, 0.0).astype(jnp.float32)
        sums, denom = _folded_segment_sum(
            x_local, safe_labels, n_out, ones,
            axis_name=axis_name, n_blocks=n_blocks, impl=impl)
        _, mass = _folded_segment_sum(
            jnp.zeros((x_local.shape[0], 1), x_local.dtype), safe_labels,
            n_out, w, axis_name=axis_name, n_blocks=n_blocks, impl=impl)
    protos = sums / jnp.maximum(denom, 1e-12)[:, None]
    valid = denom > 0
    protos = jnp.where(valid[:, None], protos, 0.0).astype(x_local.dtype)
    return protos, mass, valid


# ---------------------------------------------------------------------------
# per-level shard_map program
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("t", "n_out", "weighted", "impl", "n_blocks",
                     "axis_name", "mesh", "_dispatch"),
)
def _itis_level_sharded(x, mass, valid, key, *, t, n_out, weighted, impl,
                        n_blocks, axis_name, mesh, _dispatch=()):
    def level(x_local, mass_local, valid_local, key):
        n_local = x_local.shape[0]
        p = jax.lax.axis_size(axis_name)
        me = jax.lax.axis_index(axis_name)
        labels, _, n_clusters = tc_sharded(
            x_local, valid_local, t, key, axis_name=axis_name, impl=impl)
        labels_local = _local_rows(labels, me * n_local, n_local)
        protos, pmass, pvalid = _reduce_sharded(
            x_local, labels_local, n_out, weights_local=mass_local,
            weighted=weighted, axis_name=axis_name, n_blocks=n_blocks,
            impl=impl)
        # rebalance: level l+1 stays evenly sharded — every shard keeps its
        # contiguous slice of the replicated fold result (an exact copy)
        npl = n_out // p
        sl = me * npl
        return (
            jax.lax.dynamic_slice_in_dim(protos, sl, npl, axis=0),
            jax.lax.dynamic_slice_in_dim(pmass, sl, npl, axis=0),
            jax.lax.dynamic_slice_in_dim(pvalid, sl, npl, axis=0),
            labels_local,
            n_clusters.reshape(1),
        )

    return _shard_map(
        level, mesh,
        in_specs=(P(axis_name, None), P(axis_name), P(axis_name), P()),
        out_specs=(P(axis_name, None), P(axis_name), P(axis_name),
                   P(axis_name), P(axis_name)),
    )(x, mass, valid, key)


# ---------------------------------------------------------------------------
# mesh-aware weighted k-means (replicated centroids, psum'd statistics)
# ---------------------------------------------------------------------------


def kmeans_sharded(
    x,
    k: int,
    *,
    valid,
    weights,
    key,
    mesh,
    axis_name: Optional[str] = None,
    iters: int = 100,
    tol: float = 1e-6,
    impl: Optional[str] = None,
    n_blocks: Optional[int] = None,
):
    """Sharded twin of ``repro.cluster.kmeans.kmeans`` (labels only).

    Rows stay sharded; the (k, d) centroids are replicated; Lloyd statistics
    are combined with the canonical ordered fold; k-means++ samples from
    all-gathered global logits. Bit-identical to the single-device k-means
    when the row count divides evenly into the canonical blocks.
    ``impl``/``axis_name``/``n_blocks`` default to the runtime config.
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    axis_name = cfg.axis_name if axis_name is None else axis_name
    n_blocks = cfg.n_blocks if n_blocks is None else n_blocks
    return _kmeans_sharded(x, k, valid=valid, weights=weights, key=key,
                           mesh=mesh, axis_name=axis_name, iters=iters,
                           tol=tol, impl=impl, n_blocks=n_blocks,
                           _dispatch=cfg.dispatch_key())


@functools.partial(
    jax.jit,
    static_argnames=("k", "iters", "impl", "n_blocks", "axis_name", "mesh",
                     "_dispatch"),
)
def _kmeans_sharded(
    x,
    k: int,
    *,
    valid,
    weights,
    key,
    mesh,
    axis_name: str,
    iters: int,
    tol: float,
    impl: str,
    n_blocks: int,
    _dispatch: tuple = (),  # cache-key pin for trace-time config reads (§10)
):

    def body_fn(x_local, valid_local, w_local, key):
        n_local, d = x_local.shape
        me = jax.lax.axis_index(axis_name)
        rows = me * n_local + jnp.arange(n_local, dtype=jnp.int32)
        w = jnp.where(valid_local, w_local.astype(jnp.float32), 0.0)

        def global_pick(key, logits_local):
            return jax.random.categorical(key, _gather1d(logits_local,
                                                         axis_name))

        def gather_row(i):
            hit = (rows == i)[:, None]
            return jax.lax.psum(
                jnp.sum(jnp.where(hit, x_local, 0), axis=0), axis_name)

        # ---- k-means++ (mirrors _plus_plus_init) ----
        key0, key_loop = jax.random.split(key)
        first = global_pick(key0, jnp.log(jnp.maximum(w, 1e-30)))
        centers0 = jnp.zeros((k, d), x_local.dtype).at[0].set(gather_row(first))

        def ppbody(i, carry):
            centers, key = carry
            key, sub = jax.random.split(key)
            dist = ops.pairwise_sq_l2(x_local, centers, impl=impl)
            slot_ok = jnp.arange(k)[None, :] < i
            dmin = jnp.min(jnp.where(slot_ok, dist, jnp.inf), axis=1)
            nxt = global_pick(sub, jnp.log(jnp.maximum(w * dmin, 1e-30)))
            return centers.at[i].set(gather_row(nxt)), key

        centers, _ = jax.lax.fori_loop(1, k, ppbody, (centers0, key_loop))

        # ---- Lloyd (mirrors kmeans.body with folded statistics) ----
        def assign(centers):
            dist = ops.pairwise_sq_l2(x_local, centers, impl=impl)
            return (jnp.argmin(dist, axis=1).astype(jnp.int32),
                    jnp.min(dist, axis=1))

        def cond(state):
            _, _, delta, it = state
            return (delta > tol) & (it < iters)

        def body(state):
            centers, _, _, it = state
            lab, _ = assign(centers)
            lab_safe = jnp.where(valid_local, lab, k)
            sums, mass = _folded_segment_sum(
                x_local, lab_safe, k, w,
                axis_name=axis_name, n_blocks=n_blocks, impl=impl)
            new = jnp.where(
                (mass > 0)[:, None], sums / jnp.maximum(mass, 1e-30)[:, None],
                centers).astype(x_local.dtype)
            delta = jnp.max(jnp.sum(jnp.square(new - centers), axis=1))
            return new, lab, delta, it + 1

        lab0, _ = assign(centers)
        state = (centers, lab0, jnp.asarray(jnp.inf, jnp.float32),
                 jnp.asarray(0))
        centers, _, _, _ = jax.lax.while_loop(cond, body, state)
        labels, _ = assign(centers)
        return jnp.where(valid_local, labels, -1).astype(jnp.int32)

    return _shard_map(
        body_fn, mesh,
        in_specs=(P(axis_name, None), P(axis_name), P(axis_name), P()),
        out_specs=P(axis_name),
    )(x, valid, weights, key)


# ---------------------------------------------------------------------------
# host drivers (mirror itis()/ihtc() including their key sequences)
# ---------------------------------------------------------------------------


def _place(arr, mesh, axis_name, spec):
    return jax.device_put(arr, NamedSharding(mesh, spec))


def itis_sharded(
    x: jax.Array,
    t: int,
    m: int,
    *,
    mesh=None,
    axis_name: Optional[str] = None,
    weights: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    weighted: bool = False,
    impl: Optional[str] = None,
    min_points: int = 4,
    n_blocks: Optional[int] = None,
) -> ITISResult:
    """Multi-device twin of :func:`repro.core.itis.itis`.

    Level buffers are padded (validity-masked) to a multiple of the canonical
    reduction block count so every level splits evenly across shards; the key
    sequence and early-stop rule match the single-device driver exactly.
    ``impl``/``axis_name``/``mesh`` default to the active runtime config.

    ``valid`` marks pre-padded inputs (e.g. from ``data.stream_to_mesh``,
    which pads to the same multiple) — rows marked False never transmit graph
    edges or mass.
    """
    cfg = runtime.active()
    impl = cfg.impl if impl is None else impl
    axis_name = cfg.axis_name if axis_name is None else axis_name
    validate_reduction_params(t, m, n=x.shape[0], driver="itis_sharded")
    if mesh is None:
        mesh = cfg.mesh if cfg.mesh is not None else make_data_mesh()
    if key is None:
        key = jax.random.PRNGKey(0)
    p = mesh.shape[axis_name]
    if n_blocks is None:
        # smallest multiple of p that is >= the configured reduction width
        # (default: the canonical REDUCE_BLOCKS), so defaults work on any
        # device count; parity with the single-device path needs the widths
        # equal, which holds whenever p divides the configured width
        n_blocks = -(-max(cfg.n_blocks, p) // p) * p
    if n_blocks % p:
        raise ValueError(f"n_blocks={n_blocks} must be a multiple of the "
                         f"'{axis_name}' axis size {p}")

    n = x.shape[0]
    mass = jnp.ones((n,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    if valid is None:
        valid = jnp.ones((n,), bool)
    mass = jnp.where(valid, mass, 0.0)
    sizes = level_sizes(n, t, m, multiple=n_blocks)
    if sizes[0] != n:
        pad = sizes[0] - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        mass = jnp.pad(mass, (0, pad))
        valid = jnp.pad(valid, (0, pad))

    cur_x = _place(x, mesh, axis_name, P(axis_name, None))
    cur_m = _place(mass, mesh, axis_name, P(axis_name))
    cur_v = _place(valid, mesh, axis_name, P(axis_name))

    assignments = []
    n_protos = jnp.sum(cur_v).astype(jnp.int32)
    for level in range(m):
        # repro: allow[HS202]: deliberate per-level sync — the early-exit floor is a host decision, m times per fit
        n_valid = int(jnp.sum(cur_v))
        if n_valid < max(min_points, 2 * t):
            break
        key, sub = jax.random.split(key)
        cur_x, cur_m, cur_v, assignment, ncs = _itis_level_sharded(
            cur_x, cur_m, cur_v, sub, t=t, n_out=sizes[level + 1],
            weighted=weighted, impl=impl, n_blocks=n_blocks,
            axis_name=axis_name, mesh=mesh, _dispatch=cfg.dispatch_key())
        assignments.append(assignment)
        n_protos = ncs[0]
    return ITISResult(cur_x, cur_m, cur_v, assignments, n_protos)


@register_executor("sharded")
def _execute_sharded(plan: FitPlan, x: jax.Array) -> Reduction:
    """Mesh data-movement strategy: every level buffer is padded to the
    plan's shard multiple and row-sharded over ``axis_name``; the points
    are never gathered to one device. The planner's epilogue keeps the
    ``kmeans`` backend on the mesh (:func:`kmeans_sharded`) and runs any
    other backend single-device on the already-reduced prototype set."""
    key_itis, _ = plan.split_keys()
    r = itis_sharded(
        x, plan.t, plan.m, mesh=plan.mesh, axis_name=plan.axis_name,
        weights=plan.weights, valid=plan.valid, key=key_itis,
        weighted=plan.weighted, impl=plan.impl,
        min_points=plan.min_points, n_blocks=plan.shard_multiple(),
    )
    return Reduction(
        protos=r.protos, mass=r.mass, valid=r.valid,
        n_prototypes=r.n_prototypes, assignments=r.assignments,
        n0=x.shape[0],
    )


def ihtc_sharded(
    x: jax.Array,
    t: int,
    m: int,
    backend: Union[str, BackendFn] = "kmeans",
    *,
    mesh=None,
    axis_name: Optional[str] = None,
    weights: Optional[jax.Array] = None,
    valid: Optional[jax.Array] = None,
    weighted: bool = False,
    use_mass_in_backend: bool = True,
    key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    n_blocks: Optional[int] = None,
    **backend_kwargs,
) -> FitResult:
    """Multi-device twin of :func:`repro.core.ihtc.ihtc` (deprecated alias
    of ``repro.fit(..., executor="sharded")``).

    ``backend="kmeans"`` runs the mesh-aware k-means (prototypes stay
    sharded). Other backends resolve through the registry and fall back to
    the single-device implementation on the final prototype set — which is
    n/(t*)^m-sized, i.e. already reduced by ITIS; the raw points are still
    never gathered. ``impl``/``axis_name``/``mesh`` default to the active
    runtime config.
    """
    return fit(
        x, t, m, backend, executor="sharded",
        mesh=mesh, axis_name=axis_name, weights=weights, valid=valid,
        weighted=weighted, use_mass_in_backend=use_mass_in_backend, key=key,
        impl=impl, n_blocks=n_blocks, driver="ihtc_sharded",
        **backend_kwargs,
    )
