"""The fit path's spans and counters (DESIGN.md §20): the scope names reach
the compiled programs, the counters count what they say, and tracing
changes no result."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.cluster.kmeans import _kmeans, kmeans, kmeans_masked
from repro.core.itis import _itis_step
from repro.core.plan import plan_fit
from repro.core.tc import _luby_mis_sq, seed_priorities, threshold_clustering
from repro.kernels.segment_sum import tile_schedule, tile_shape


def _points(n=256, d=4, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


def _scope_components(hlo: str) -> set:
    """Every component of every ``op_name`` in compiled HLO metadata: the
    path the device trace carries for each operation."""
    return {c for name in re.findall(r'op_name="([^"]*)"', hlo)
            for c in name.split("/")}


@pytest.fixture(scope="module")
def compiled_scopes():
    x = _points(64)
    itis = _itis_step.lower(
        x, jnp.ones((64,)), jnp.ones((64,), bool), 2,
        key=jax.random.PRNGKey(0), weighted=False, impl="ref", knn_block=0,
        n_out=32, n_blocks=8).compile().as_text()
    km = _kmeans.lower(x, 3, valid=None, weights=None, key=None, iters=10,
                       tol=1e-6, impl="ref", n_blocks=8).compile().as_text()
    return {"_itis_step": _scope_components(itis),
            "_kmeans": _scope_components(km)}


@pytest.mark.parametrize("program,scope", [
    ("_itis_step", "tc.knn"), ("_itis_step", "tc.mis"),
    ("_itis_step", "tc.grow"), ("_itis_step", "itis.reduce"),
    ("_kmeans", "kmeans.seed")])
def test_scope_names_reach_the_compiled_program(compiled_scopes, program,
                                                scope):
    assert scope in compiled_scopes[program]


def _np_closed2_max(p, idx, ok):
    """max of p over each vertex's closed 2-hop neighbourhood, by loops."""
    def push(v):
        out = v.copy()
        for i in range(len(v)):
            for j, e in zip(idx[i], ok[i]):
                if e:
                    out[i] = max(out[i], v[j])
                    out[j] = max(out[j], v[i])
        return out
    return push(push(p))


def _np_luby(pri, idx, ok, active):
    """The Luby rounds of DESIGN.md §2 in plain NumPy: (seeds, rounds)."""
    active, seed, rounds = active.copy(), np.zeros_like(active), 0
    while active.any():
        p = np.where(active, pri, -1)
        newly = active & (p == _np_closed2_max(p, idx, ok))
        seed |= newly
        covered = _np_closed2_max(newly.astype(np.int64), idx, ok) > 0
        active &= ~covered & ~newly
        rounds += 1
    return seed, rounds


@pytest.mark.parametrize("n,k", [(40, 1), (64, 3)])
def test_mis_rounds_match_a_numpy_rerun(n, k):
    rng = np.random.default_rng(n + k)
    idx = rng.integers(0, n, size=(n, k)).astype(np.int32)
    idx[rng.random((n, k)) < 0.2] = -1
    ok = idx >= 0
    active = rng.random(n) < 0.9
    pri = seed_priorities(jax.random.PRNGKey(k), n)
    seed, rounds = _luby_mis_sq(pri, jnp.asarray(idx), jnp.asarray(ok),
                                jnp.asarray(active))
    want_seed, want_rounds = _np_luby(np.asarray(pri), idx, ok, active)
    assert int(rounds) == want_rounds >= 1
    np.testing.assert_array_equal(np.asarray(seed), want_seed)


def test_tc_mis_rounds_match_a_numpy_rerun_on_its_knn_graph():
    x = _points(48, 3, seed=5)
    key = jax.random.PRNGKey(7)
    r = threshold_clustering(x, 3, key=key, impl="ref")
    xn = np.asarray(x, np.float64)
    d = ((xn[:, None] - xn[None]) ** 2).sum(-1)
    np.fill_diagonal(d, np.inf)
    idx = np.argsort(d, axis=1)[:, :2]
    seed, rounds = _np_luby(np.asarray(seed_priorities(key, 48)), idx,
                            np.ones_like(idx, bool), np.ones(48, bool))
    assert int(r.mis_rounds) == rounds
    np.testing.assert_array_equal(np.asarray(r.is_seed), seed)


def test_fit_counts_mis_rounds_per_level_run():
    res = repro.fit(_points(512), 2, 3, "kmeans", k=4,
                    key=jax.random.PRNGKey(2))
    rounds = np.asarray(res.counters["mis_rounds"])
    assert rounds.dtype == np.int32
    assert rounds.shape == (len(res.assignments),) and (rounds >= 1).all()


def test_lloyd_iters_equal_kmeans_iters():
    x, key = _points(300, 3, seed=1), jax.random.PRNGKey(4)
    plan = plan_fit(x, 2, 2, "kmeans", k=5, key=key)
    res = repro.fit(x, 2, 2, "kmeans", k=5, key=key)
    _, key_backend = plan.split_keys()
    want = kmeans(res.protos, 5, valid=res.proto_valid,
                  weights=res.proto_mass, key=key_backend)
    got = res.counters["lloyd_iters"]
    assert got.dtype == jnp.int32 and int(got) == int(want.iters) >= 1
    masked = kmeans_masked(x, k=5, key=key)
    assert int(masked.iters) == int(kmeans(x, 5, key=key).iters)


def _schedule_tiles(labels, n_out, n_blocks=8):
    """The reduce's tile matmuls at one level, from the schedule helper:
    each block of the fixed fold, for the sums and the masses."""
    ids = np.where(labels >= 0, labels, n_out).astype(np.int32)
    ids = np.pad(ids, (0, (-len(ids)) % n_blocks), constant_values=n_out)
    total = 0
    for block in np.split(ids, n_blocks):
        bs, bn, S, R = tile_shape(len(block), n_out, 512, 1024,
                                  interpret=True)
        if S == bs:  # one segment tile: the dense grid
            tiles = R // bn
        else:
            keys = np.sort(np.where(block < n_out, block, S))
            keys = np.pad(keys, (0, R - len(block)), constant_values=S)
            tiles = int(tile_schedule(jnp.asarray(keys), S // bs, bs, bn)[3])
        assert tiles <= S // bs + R // bn - 1
        total += 2 * tiles  # unweighted: the sums' call and the masses'
    return total


def test_fit_counts_reduce_tiles_from_the_schedule():
    x = _points(2048)
    res = repro.fit(x, 2, 2, "kmeans", k=4, key=jax.random.PRNGKey(2),
                    impl="pallas")
    got = np.asarray(res.counters["reduce_tiles"])
    sizes = [a.shape[0] for a in res.assignments] + [res.protos.shape[0]]
    want = [_schedule_tiles(np.asarray(a), n_out)
            for a, n_out in zip(res.assignments, sizes[1:])]
    assert got.dtype == np.int32 and got.tolist() == want
    assert min(want) > 0
    # the reference path runs no tiles
    ref = repro.fit(x, 2, 2, "kmeans", k=4, key=jax.random.PRNGKey(2))
    assert np.asarray(ref.counters["reduce_tiles"]).tolist() == [0, 0]


def test_other_backends_leave_lloyd_iters_out():
    res = repro.fit(_points(300), 2, 2, "hac", k=3)
    assert set(res.counters) == {"mis_rounds", "reduce_tiles"}


def test_tracing_perturbs_no_result(tmp_path):
    x, key = _points(600, 5, seed=3), jax.random.PRNGKey(9)
    plain = repro.fit(x, 2, 3, "kmeans", k=4, key=key)
    with jax.profiler.trace(str(tmp_path)):
        traced = repro.fit(x, 2, 3, "kmeans", k=4, key=key)
        jax.block_until_ready(traced.labels)
    for name in ("labels", "protos", "proto_mass", "proto_valid",
                 "proto_labels"):
        a = np.asarray(getattr(plain, name))
        b = np.asarray(getattr(traced, name))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    for name in plain.counters:
        np.testing.assert_array_equal(np.asarray(plain.counters[name]),
                                      np.asarray(traced.counters[name]))
