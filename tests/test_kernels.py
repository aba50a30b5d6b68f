"""Per-kernel allclose validation: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,m,d", [(7, 9, 3), (64, 64, 8), (130, 257, 33), (1, 5, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2(rng, n, m, d, dtype):
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    y = jnp.asarray(rng.normal(size=(m, d)), dtype)
    got = ops.pairwise_sq_l2(x, y, impl="pallas")
    want = ref.pairwise_sq_l2(x, y)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_pairwise_l2_valid_mask(rng):
    x = jnp.asarray(rng.normal(size=(20, 4)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(31, 4)), jnp.float32)
    v = jnp.asarray(rng.random(31) > 0.4)
    got = ops.pairwise_sq_l2(x, y, y_valid=v, impl="pallas")
    want = ref.pairwise_sq_l2(x, y, y_valid=v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,d,k", [(16, 2, 1), (50, 3, 4), (129, 5, 8)])
def test_knn_topk(rng, n, d, k):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    gd, gi = ops.knn(x, k, impl="pallas")
    wd, wi = ref.knn(x, k)
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(gi, wi)


def test_knn_topk_masked(rng):
    x = jnp.asarray(rng.normal(size=(40, 3)), jnp.float32)
    valid = jnp.asarray(rng.random(40) > 0.5)
    gd, gi = ops.knn(x, 3, valid=valid, impl="pallas")
    wd, wi = ref.knn(x, 3, valid=valid)
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(gi, wi)


def test_knn_insufficient_candidates(rng):
    """Fewer valid points than k: unfilled slots must be (inf, -1)."""
    x = jnp.asarray(rng.normal(size=(5, 2)), jnp.float32)
    gd, gi = ops.knn(x, 8, impl="pallas")
    assert np.all(np.asarray(gi[:, 4:]) == -1)
    assert np.all(np.isinf(np.asarray(gd[:, 4:])))


@pytest.mark.parametrize("n,bq,bk", [
    (300, 256, 512),   # regression: pad=max(212, 0) left 512 % 300 != 0
    (300, 512, 256),   # bq clamps to 300; bk must shrink to a divisor
    (260, 256, 96),    # bk does not divide the bq-padded count
    (7, 256, 512),     # sub-minimum n pads to the floor of 8 rows
])
def test_knn_topk_ragged_blocks(rng, n, bq, bk):
    """Awkward (n, block) combinations must still tile the BlockSpec grid
    exactly (both grid axes cover the padded rows with zero remainder)."""
    from repro.kernels.knn_topk import knn_topk

    x = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
    k = min(4, n - 1)
    gd, gi = knn_topk(x, k, block_q=bq, block_k=bk, interpret=True)
    wd, wi = ref.knn(x, k)
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(gi, wi)


def test_unknown_impl_rejected_loudly(rng):
    """Regression: an unknown impl string used to fall through silently to
    the XLA reference path — a typo'd impl= would quietly benchmark (or
    ship) the wrong kernel. Every ops entry point must reject it with the
    registered list."""
    x = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    ids = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match=r"registered impls.*pallas"):
        ops.knn(x, 2, impl="palas")  # the typo that motivated this
    with pytest.raises(ValueError, match="unknown impl 'xla'"):
        ops.pairwise_sq_l2(x, x, impl="xla")
    with pytest.raises(ValueError, match="unknown impl"):
        ops.segment_sum(x, ids, 2, impl="cuda")
    with pytest.raises(ValueError, match="unknown impl"):
        ops.blocked_segment_sum(x, ids, 2, n_blocks=2, impl="bogus")
    # the valid spellings still resolve (auto included)
    for impl in ("auto", "ref"):
        ops.knn(x, 2, impl=impl)


@pytest.mark.parametrize("n,d,s", [(10, 3, 4), (100, 7, 13), (257, 2, 64)])
def test_segment_sum(rng, n, d, s):
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, s + 1, size=n), jnp.int32)  # incl. OOB
    w = jnp.asarray(rng.random(n), jnp.float32)
    gs, gm, _ = ops.segment_sum(x, ids, s, weights=w, impl="pallas")
    ws, wm = ref.segment_sum(x, ids, s, weights=w)
    np.testing.assert_allclose(gs, ws, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gm, wm, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,d,s,n_blocks", [(64, 3, 8, 8), (100, 2, 5, 8),
                                            (30, 4, 6, 4), (16, 2, 3, 1)])
def test_blocked_segment_sum_matches_plain(rng, n, d, s, n_blocks):
    """The fixed-fold variant is the same function as plain segment_sum up
    to float summation order (exact on integer-valued masses)."""
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, s + 1, size=n), jnp.int32)  # incl. OOB
    w = jnp.asarray(rng.random(n), jnp.float32)
    gs, gm, _ = ops.blocked_segment_sum(x, ids, s, weights=w,
                                        n_blocks=n_blocks, impl="ref")
    ws, wm = ref.segment_sum(x, ids, s, weights=w)
    np.testing.assert_allclose(gs, ws, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gm, wm, rtol=1e-5, atol=1e-5)


# the Pallas case spans three 512-segment tiles: the banded schedule
@pytest.mark.parametrize("impl,s", [("ref", 7), ("pallas", 1500)])
def test_blocked_segment_sum_shard_fold_identity(rng, impl, s):
    """Bitwise contract used by the distributed pipeline (DESIGN.md §4.3):
    per-block partials folded left in block order == blocked_segment_sum."""
    n, d, B = 64, 3, 8
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, s, size=n), jnp.int32)
    w = jnp.asarray(rng.random(n), jnp.float32)
    gs, gm, gt = ops.blocked_segment_sum(x, ids, s, weights=w, n_blocks=B,
                                         impl=impl)
    nb = n // B
    acc_s = acc_m = acc_t = None
    for b in range(B):  # what each shard computes, folded in shard order
        sl = slice(b * nb, (b + 1) * nb)
        ps, pm, pt = ops.segment_sum(x[sl], ids[sl], s, weights=w[sl],
                                     impl=impl)
        acc_s = ps if acc_s is None else acc_s + ps
        acc_m = pm if acc_m is None else acc_m + pm
        acc_t = pt if acc_t is None else acc_t + pt
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(acc_s))
    np.testing.assert_array_equal(np.asarray(gm), np.asarray(acc_m))
    assert int(gt) == int(acc_t)


@pytest.mark.parametrize("lq,lk", [(8, 8), (1, 33), (17, 64), (64, 17)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(rng, lq, lk, causal):
    if causal and lq > lk:
        pytest.skip("causal requires lq <= lk")
    b, h, dh = 2, 4, 16
    q = jnp.asarray(rng.normal(size=(b, h, lq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, lk, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, lk, dh)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, impl="pallas")
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_gqa_bias_softcap(rng):
    b, hq, hkv, l, dh = 2, 8, 2, 24, 16
    q = jnp.asarray(rng.normal(size=(b, hq, l, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, l, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, l, dh)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(b, hkv, l)), jnp.float32)
    got = ops.flash_attention(q, k, v, kv_bias=bias, logit_softcap=30.0,
                              impl="pallas")
    want = ops.flash_attention(q, k, v, kv_bias=bias, logit_softcap=30.0,
                               impl="ref")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_xla_chunked_attention_matches_ref(rng):
    """The production XLA flash path (grouped GQA, chunked) vs oracle."""
    from repro.models.attention import chunked_attention

    b, hq, hkv, lq, lk, dh = 2, 6, 2, 33, 70, 8
    q = jnp.asarray(rng.normal(size=(b, hq, lq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, lk, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, lk, dh)), jnp.float32)
    got = chunked_attention(q, k, v, causal=True, chunk=16)
    kr = jnp.repeat(k, 3, axis=1)
    vr = jnp.repeat(v, 3, axis=1)
    want = ref.flash_attention(q, kr, vr, causal=True)
    # production path keeps the PV matmul in bf16 (see attention.py) ⇒ ~1e-2
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_xla_chunked_attention_window(rng):
    from repro.models.attention import chunked_attention

    b, h, l, dh = 1, 2, 40, 8
    q = jnp.asarray(rng.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, l, dh)), jnp.float32)
    w = 8
    got = chunked_attention(q, k, v, causal=True, window=w, chunk=16)
    # brute force windowed-causal reference
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / (dh**0.5)
    iq = jnp.arange(l)
    mask = (iq[None, :] <= iq[:, None]) & (iq[None, :] > iq[:, None] - w)
    logits = jnp.where(mask, logits, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), vf)
    # bf16 PV matmul in the production path ⇒ ~1e-2 agreement
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
