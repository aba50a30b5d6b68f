"""Plain references for the checks that decide ``correct``.

Nothing here imports the program (``repro``): each reference follows the
algorithm's definition in straightforward NumPy (float64, on the host) or
``jax.numpy`` (float32, distances in the difference form
``Σ (a - b)²``, which has no cancellation and no matmul precision to pick).

The kNN graph of every row comes from :func:`knn_all`, float32 distances
at ``Precision.HIGHEST`` in the expansion form; where a decision is close,
:func:`kth_nn_dist` and :func:`pair_dist` settle it in the difference form
and in float64. :func:`kmeans_moves` does the same on the host, float64
distances in the expansion form a block of rows at a time and the close
rows again in the difference form, so that it holds O(block·k) for any
number of prototypes p and centres k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: a distance decision closer than this share of the squared norms involved
#: is a tie to float32 rounding (2^-20: sixteen float32 epsilons)
ROUNDING = 2.0 ** -20
#: prototype rows that :func:`kmeans_moves` holds against every centre
MOVES_BLOCK = 4096


# ---------------------------------------------------------------------------
# the fit: ITIS prototype chain, the k-means fixed point, the label back-out


def segment_mean(x, seg, n_out: int, weights=None):
    """Mean (or ``weights``-weighted mean) of the rows of ``x`` in each
    segment ``0..n_out-1``; ``seg < 0`` rows are dropped. float64."""
    ok = seg >= 0
    s = np.where(ok, seg, n_out)
    w = ok.astype(np.float64) if weights is None else np.where(ok, weights, 0)
    den = np.bincount(s, weights=w, minlength=n_out + 1)[:n_out]
    out = np.empty((n_out, x.shape[1]), np.float64)
    for j in range(x.shape[1]):
        out[:, j] = np.bincount(s, weights=w * x[:, j],
                                minlength=n_out + 1)[:n_out]
    return out / np.maximum(den, 1e-300)[:, None], den


def prototype_chain(x, assignments, n_final: int, *, weighted: bool = False):
    """Prototypes, masses and member counts of the last ITIS level, from
    the points and the fit's own level maps: level l+1's prototype j is
    the mean of the level-l rows mapped to j (mass-weighted when
    ``weighted``), and its mass the sum of theirs."""
    cur = np.asarray(x, np.float64)
    mass = np.ones(cur.shape[0], np.float64)
    count = None
    for level, a in enumerate(assignments):
        a = np.asarray(a)
        n_out = (int(np.asarray(assignments[level + 1]).shape[0])
                 if level + 1 < len(assignments) else n_final)
        cur, count = segment_mean(cur, a, n_out,
                                  weights=mass if weighted else None)
        if weighted:
            count = np.bincount(np.where(a >= 0, a, n_out),
                                minlength=n_out + 1)[:n_out]
        mass = np.bincount(np.where(a >= 0, a, n_out), weights=np.where(
            a >= 0, mass[: a.shape[0]], 0), minlength=n_out + 1)[:n_out]
    return cur, mass, count


def compose(assignments, final):
    """Each point's entry of ``final`` through the level maps (-1 kept)."""
    lab = np.asarray(assignments[0])
    for nxt in [np.asarray(a) for a in assignments[1:]] + [np.asarray(final)]:
        ok = lab >= 0
        lab = np.where(ok, nxt[np.where(ok, lab, 0)], -1)
    return lab


def kmeans_moves(protos, mass, valid, labels, k: int) -> int:
    """Lloyd's fixed point: with each centre the mass-weighted mean of its
    members, every prototype's label is its nearest centre. The number of
    valid prototypes whose label's centre lies farther than the nearest
    centre by more than float32 rounding (:data:`ROUNDING` of the squared
    norms of the prototype and both centres), the distances taken in the
    difference form ``Σ (p - c)²``. float64.

    The host holds O(:data:`MOVES_BLOCK`·k), not O(p·k·d): distances are
    first taken that many rows at a time in the expansion form
    ``|p|² + |c|² - 2 p·c``, and a row is decided there only where neither
    its margin against the slack nor its nearest centre against the second
    nearest lies within twice :func:`_expansion_error` of it. Every other
    row is settled again in the difference form
    (:func:`_moves_by_difference`), so the count is the difference form's,
    row for row."""
    v = np.asarray(valid, bool) & (np.asarray(labels) >= 0)
    p = np.asarray(protos, np.float64)[v]
    lab = np.asarray(labels)[v]
    if p.shape[0] == 0:
        return 1
    centres, den = segment_mean(p, lab, k, weights=np.asarray(mass)[v])
    present = den > 0
    cp = centres[present]
    col = (np.cumsum(present) - 1)[lab]
    pn, cn = (p * p).sum(-1), (cp * cp).sum(-1)
    err = _expansion_error(p.shape[1], pn, cn)
    block, moves = MOVES_BLOCK, 0
    for lo in range(0, p.shape[0], block):
        s = slice(lo, lo + block)
        rows = np.arange(p[s].shape[0])
        d = p[s] @ cp.T
        d *= -2
        d += pn[s, None]
        d += cn[None, :]
        near = d.argmin(axis=1)
        nearest = d[rows, near]
        margin = d[rows, col[s]] - nearest
        slack = ROUNDING * (2 * pn[s] + cn[col[s]] + cn[near])
        d[rows, near] = np.inf
        # NaN compares false: a row with a non-finite distance is close
        decided = ((d.min(axis=1) - nearest > 2 * err[s])
                   & (np.abs(margin - slack) > 2 * err[s]))
        del d
        moves += int((decided & (margin > slack)).sum())
        close = lo + np.flatnonzero(~decided)
        moves += _moves_by_difference(p[close], cp, col[close], pn[close],
                                      cn, max(1, block // p.shape[1]))
    return moves


def _expansion_error(dim: int, pn, cn):
    """Per row, a bound on how far a float64 distance to any centre in the
    expansion form may lie from the same distance in the difference form:
    each is within ~(2·dim + 5) units of rounding of ``|p|² + |c|²``
    (dot products and sums of ``dim`` terms), and this allows four times
    their sum."""
    return 8 * (dim + 4) * np.finfo(np.float64).eps * (pn + cn.max())


def _moves_by_difference(p, cp, col, pn, cn, rows: int) -> int:
    """:func:`kmeans_moves`'s count over the rows ``p`` with their label
    columns ``col``, every distance in the difference form, ``rows`` rows
    against every centre at a time."""
    moves = 0
    for lo in range(0, p.shape[0], rows):
        s = slice(lo, lo + rows)
        d = p[s, None, :] - cp[None, :, :]
        np.square(d, out=d)
        d = d.sum(-1)
        near = d.argmin(axis=1)
        r = np.arange(d.shape[0])
        slack = ROUNDING * (2 * pn[s] + cn[col[s]] + cn[near])
        moves += int((d[r, col[s]] - d[r, near] > slack).sum())
    return moves


def bss_tss(x, labels, k: int) -> float:
    """Between-cluster over total sum of squares (the arithmetic of
    ``repro.cluster.metrics.bss_tss``, unweighted), in float32 on the
    device; rows labelled < 0 are left out."""
    return float(_bss_tss(jnp.asarray(x), jnp.asarray(labels), k))


@functools.partial(jax.jit, static_argnames=("k",))
def _bss_tss(x, labels, k):
    ok = labels >= 0
    w = ok.astype(jnp.float32)
    tot_w = jnp.maximum(jnp.sum(w), 1e-30)
    mu = jnp.sum(x * w[:, None], axis=0) / tot_w
    tss = jnp.sum(w * jnp.sum(jnp.square(x - mu), axis=1))
    lab = jnp.where(ok, labels, k)
    sums = jax.ops.segment_sum(x * w[:, None], lab, num_segments=k + 1)[:k]
    mass = jax.ops.segment_sum(w, lab, num_segments=k + 1)[:k]
    cent = sums / jnp.maximum(mass, 1e-30)[:, None]
    wss = jnp.sum(w * jnp.sum(jnp.square(x - cent[jnp.where(ok, labels, 0)]),
                              axis=1))
    return (tss - wss) / jnp.maximum(tss, 1e-30)


# ---------------------------------------------------------------------------
# distances on the device, in blocks


@jax.jit
def _sq_dist(a, b):
    return jnp.sum(jnp.square(a[:, None, :] - b[None, :, :]), axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _kth_nn_block(x, rows, self_idx, *, k, block):
    """k-th smallest exact distance from each row to the rows of ``x``,
    leaving out the row's own index. ``x`` is padded to ``block``."""
    n = x.shape[0]
    nb = n // block

    def body(b, best):
        keys = jax.lax.dynamic_slice_in_dim(x, b * block, block, 0)
        d = _sq_dist(rows, keys)
        gidx = b * block + jnp.arange(block)
        d = jnp.where(gidx[None, :] == self_idx[:, None], jnp.inf, d)
        both = jnp.concatenate([best, d], axis=1)
        return -jax.lax.top_k(-both, k)[0]

    best = jnp.full((rows.shape[0], k), jnp.inf, jnp.float32)
    return jax.lax.fori_loop(0, nb, body, best)[:, k - 1]


def kth_nn_dist(x, idx, k: int, *, row_block: int = 64,
                key_block: int = 8192) -> np.ndarray:
    """For each index in ``idx``, the exact squared distance to its k-th
    nearest other point of ``x`` (a device array)."""
    key_block = min(key_block, x.shape[0])
    xp = _padded_keys(x, key_block)
    idx = np.asarray(idx, np.int64)
    out = []
    for lo in range(0, idx.shape[0], row_block):
        sel = idx[lo:lo + row_block]
        sel_p = np.pad(sel, (0, row_block - sel.shape[0]), mode="edge")
        rows = x[jnp.asarray(sel_p)]
        r = _kth_nn_block(xp, rows, jnp.asarray(sel_p), k=k, block=key_block)
        out.append(np.asarray(r)[: sel.shape[0]])
    return np.concatenate(out) if out else np.zeros(0)


def _padded_keys(x, key_block: int):
    """``x`` padded to a multiple of ``key_block`` rows, the padding far
    away, so that it is never among the nearest."""
    n = x.shape[0]
    pad = (-n) % key_block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    if pad:
        far = jnp.max(jnp.abs(x)) * 1e3 + 1e3
        xp = xp.at[n:].set(far)
    return xp


@functools.partial(jax.jit, static_argnames=("k", "row_block", "key_block"))
def _knn_all(xp, *, k, row_block, key_block):
    """Indices of the k nearest other rows of every row of ``xp`` (padded
    to a multiple of ``key_block``, itself a multiple of ``row_block``)."""
    sq = jnp.sum(xp * xp, axis=1)
    col = jnp.arange(key_block)

    def rows_of(r0):
        rows = jax.lax.dynamic_slice_in_dim(xp, r0, row_block, 0)
        rn = jax.lax.dynamic_slice_in_dim(sq, r0, row_block, 0)[:, None]
        ridx = r0 + jnp.arange(row_block)

        def body(b, best):
            k0 = b * key_block
            keys = jax.lax.dynamic_slice_in_dim(xp, k0, key_block, 0)
            kn = jax.lax.dynamic_slice_in_dim(sq, k0, key_block, 0)[None, :]
            d = rn + kn - 2.0 * jnp.dot(rows, keys.T,
                                        precision=jax.lax.Precision.HIGHEST)
            d = jnp.where(col[None, :] + k0 == ridx[:, None], jnp.inf, d)
            cand_d, cand_i = [best[0]], [best[1]]
            for r in range(k):
                a = jnp.argmin(d, axis=1)
                cand_d.append(jnp.take_along_axis(d, a[:, None], axis=1))
                cand_i.append((a + k0)[:, None])
                if r + 1 < k:
                    d = jnp.where(col[None, :] == a[:, None], jnp.inf, d)
            both_d = jnp.concatenate(cand_d, axis=1)
            both_i = jnp.concatenate(cand_i, axis=1)
            neg, pos = jax.lax.top_k(-both_d, k)
            return -neg, jnp.take_along_axis(both_i, pos, axis=1)

        best = (jnp.full((row_block, k), jnp.inf, jnp.float32),
                jnp.full((row_block, k), -1, jnp.int32))
        return jax.lax.fori_loop(0, xp.shape[0] // key_block, body, best)[1]

    starts = jnp.arange(0, xp.shape[0], row_block)
    return jax.lax.map(rows_of, starts).reshape(xp.shape[0], k)


def knn_all(x, k: int, *, row_block: int = 2048,
            key_block: int = 16384) -> np.ndarray:
    """The kNN graph of ``x`` (a device array): for every row the indices
    of its k nearest other rows, (n, k) on the host, by float32 distances
    ``|a|² + |b|² - 2 a·b`` at Precision.HIGHEST."""
    n = int(x.shape[0])
    rb = min(row_block, -(-n // 8) * 8)
    kb = min(key_block, -(-n // rb) * rb)
    nbr = _knn_all(_padded_keys(x, kb), k=k, row_block=rb, key_block=kb)
    return np.asarray(nbr)[:n]


def pair_dist(x, i, j) -> np.ndarray:
    """Exact squared distances ``|x[i] - x[j]|²`` (float64 on the host)."""
    a = np.asarray(x[jnp.asarray(i)], np.float64)
    b = np.asarray(x[jnp.asarray(j)], np.float64)
    return ((a - b) ** 2).sum(-1)
