"""Fused assign path wiring (DESIGN.md §16): ClusterIndex freeze-time
low-precision buffers, fused/quantized impl dispatch through assign and
the blocked kNN inner loop, impl-registry growth, and servability checks
for the packed buffers."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import runtime
from repro.core.index import ClusterIndex, nearest_valid_prototype
from repro.core.knn import knn_graph_blocked
from repro.kernels import ops


def _index(rng, p=48, d=5, c=7, spread=20.0):
    protos = jnp.asarray(rng.normal(size=(p, d)) * spread, jnp.float32)
    return ClusterIndex(
        protos=protos,
        proto_mass=jnp.ones((p,), jnp.float32),
        proto_valid=jnp.asarray(rng.random(p) > 0.2),
        proto_labels=jnp.asarray(rng.integers(0, c, size=p), jnp.int32),
        n_prototypes=jnp.asarray(p, jnp.int32),
    )


# ----------------------------------------------- freeze-time packed buffers


def test_from_result_packs_low_precision_buffers(rng):
    """Freezing a fit precomputes the bf16 + int8 prototype buffers, so
    per-request assign work only touches the queries (satellite: no more
    per-call re-cast inside jit)."""
    x = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
    idx = ClusterIndex.build(x, 2, 1, "kmeans", k=3)
    assert idx.protos_bf16 is not None
    assert idx.protos_bf16.dtype == jnp.bfloat16
    assert idx.protos_q8 is not None and idx.protos_q8.dtype == jnp.int8
    assert idx.q8_scale.shape == (idx.dim,)
    assert idx.q8_zero.shape == (idx.dim,)
    # the bf16 buffer is exactly the in-jit cast the old path did per call
    np.testing.assert_array_equal(
        np.asarray(idx.protos_bf16, dtype=np.float32),
        np.asarray(idx.protos.astype(jnp.bfloat16), dtype=np.float32))
    idx.check_servable()


def test_hand_built_index_defaults_and_on_the_fly_quantization(rng):
    """Five-field construction keeps working (packed fields default None)
    and the quantized impls pack on the fly, matching the packed index."""
    idx = _index(rng)
    assert idx.protos_bf16 is None and idx.protos_q8 is None
    q = jnp.asarray(rng.normal(size=(17, 5)) * 20.0, jnp.float32)
    packed = ClusterIndex.build(idx)
    for impl in ("fused_bf16", "fused_int8"):
        np.testing.assert_array_equal(
            np.asarray(idx.assign(q, impl=impl)),
            np.asarray(packed.assign(q, impl=impl)))


def test_bfloat16_precision_uses_packed_buffer_bitwise(rng):
    """precision="bfloat16" serves from the frozen bf16 buffer when
    present — bitwise identical to the old per-call in-jit cast (which
    the unpacked index still exercises)."""
    idx = _index(rng)
    q = jnp.asarray(rng.normal(size=(9, 5)), jnp.float32)
    with runtime.configure(precision="bfloat16"):
        want = idx.assign(q)                       # in-jit cast fallback
        got = ClusterIndex.build(idx).assign(q)    # frozen buffer
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_check_servable_rejects_mismatched_packed_buffers(rng):
    idx = ClusterIndex.build(_index(rng))
    bad = idx._replace(protos_bf16=idx.protos_bf16[:-1])
    with pytest.raises(ValueError, match="protos_bf16"):
        bad.check_servable()
    bad = idx._replace(q8_scale=None)
    with pytest.raises(ValueError, match="q8_scale"):
        bad.check_servable()
    bad = idx._replace(protos_q8=idx.protos_q8[:, :-1])
    with pytest.raises(ValueError, match="protos_q8"):
        bad.check_servable()


# -------------------------------------------------------- fused dispatch


def test_assign_fused_matches_ref_bitwise(rng):
    idx = _index(rng)
    q = jnp.asarray(rng.normal(size=(23, 5)) * 20.0, jnp.float32)
    want = idx.assign(q, impl="ref")
    np.testing.assert_array_equal(np.asarray(idx.assign(q, impl="fused")),
                                  np.asarray(want))
    # default (auto) stays bit-for-bit the composed path off-TPU
    np.testing.assert_array_equal(np.asarray(idx.assign(q)),
                                  np.asarray(want))
    # blocked composed streaming also unchanged
    np.testing.assert_array_equal(np.asarray(idx.assign(q, block=16)),
                                  np.asarray(want))


def test_nearest_valid_prototype_fused_branch(rng):
    # exact dyadic-grid inputs (DESIGN.md §16): every distance is exact in
    # f32 under any summation order, so bit-equality across the separately
    # compiled branches is guaranteed, and ties exercise index tie-breaks
    def grid(shape):
        return jnp.asarray(rng.integers(-16, 17, size=shape) * 0.25,
                           jnp.float32)

    q = grid((11, 4))
    protos = grid((37, 4))
    valid = jnp.asarray(rng.random(37) > 0.3)
    wd, wi = nearest_valid_prototype(q, protos, valid, impl="ref")
    gd, gi = nearest_valid_prototype(q, protos, valid, impl="fused",
                                     block_k=16)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_zero_valid_index_fused_variants(rng):
    idx = _index(rng)._replace(proto_valid=jnp.zeros((48,), bool))
    q = jnp.asarray(rng.normal(size=(5, 5)), jnp.float32)
    for impl in ("fused", "fused_bf16", "fused_int8"):
        assert (np.asarray(idx.assign(q, impl=impl)) == -1).all()


def test_blocked_knn_fused_inner_loop_bitwise(rng):
    """The TC inner loop (blocked kNN) through the fused path reproduces
    the composed driver bit-for-bit, including the self-exclusion mask
    carried as a traced global-index array."""
    x = jnp.asarray(rng.normal(size=(130, 4)), jnp.float32)
    valid = jnp.asarray(rng.random(130) > 0.15)
    wd, wi = knn_graph_blocked(x, 3, valid=valid, block=32)
    gd, gi = knn_graph_blocked(x, 3, valid=valid, block=32, impl="fused")
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_fit_with_fused_impl_matches_default_labels(rng):
    """An end-to-end fit pinned to the fused family reproduces the default
    fit's labels — ops without a fused path degrade it to auto."""
    x = jnp.asarray(rng.normal(size=(96, 3)), jnp.float32)
    want = repro.fit(x, 2, 1, "kmeans", k=3).labels
    with runtime.configure(impl="fused"):
        got = repro.fit(x, 2, 1, "kmeans", k=3).labels
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- registry plumbing


def test_impl_registry_accepts_fused_family():
    for impl in ("fused", "fused_bf16", "fused_int8"):
        runtime.RuntimeConfig(impl=impl)  # __post_init__ validates
    with pytest.raises(ValueError):
        runtime.RuntimeConfig(impl="fused_fp4")


def test_unknown_impl_still_rejected_loudly(rng):
    q = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    with pytest.raises(ValueError, match="registered impls"):
        ops.nearest_topk(q, keys, 1, impl="palas")
    # quantized names degrade to fused at the stateless ops layer
    gd, gi = ops.nearest_topk(q, keys, 1, impl="fused_int8")
    wd, wi = ops.nearest_topk(q, keys, 1, impl="ref")
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_non_fused_ops_degrade_fused_impl(rng):
    """pairwise/segment_sum under a process-wide impl="fused" degrade to
    the auto resolution instead of raising."""
    x = jnp.asarray(rng.normal(size=(12, 3)), jnp.float32)
    with runtime.configure(impl="fused"):
        d = ops.pairwise_sq_l2(x, x)
        s, m, _ = ops.segment_sum(x, jnp.zeros((12,), jnp.int32), 2)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(ops.pairwise_sq_l2(x, x)),
                               rtol=0, atol=0)
