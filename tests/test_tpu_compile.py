"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

Interpret-mode parity tests cannot see what Mosaic refuses: block layouts,
tiling, fast-memory limits. These tests lower each kernel at real widths
and compile it against a *described* v5e (``topologies``), then check the
compiled program really calls the kernel (``tpu_custom_call``).

The topology is described inside module-scoped fixtures, never at import:
only one process at a time may load the TPU library, and every test worker
imports this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_assign import fused_topk
from repro.kernels.knn_topk import knn_topk
from repro.kernels.pairwise_l2 import pairwise_sq_l2
from repro.kernels.segment_sum import segment_sum


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# name -> (function, [(shape, dtype), ...]) at the widths the fit and the
# assign path run (n = 65,536 kNN blocks, 4k-prototype assign, d = 128)
CASES = {
    "knn_topk_65536x128_k2": (lambda x: knn_topk(x, 2),
                              [((65536, 128), jnp.float32)]),
    "knn_topk_576x2_k2_masked": (lambda x, v: knn_topk(x, 2, v),
                                 [((576, 2), jnp.float32),
                                  ((576,), jnp.bool_)]),
    "fused_topk_2048x4096x128_k1": (lambda q, y: fused_topk(q, y, 1),
                                    [((2048, 128), jnp.float32),
                                     ((4096, 128), jnp.float32)]),
    "fused_topk_self_excluded_k2": (
        lambda q, y, g: fused_topk(q, y, 2, q_gidx=g),
        [((8192, 128), jnp.float32), ((65536, 128), jnp.float32),
         ((8192,), jnp.int32)]),
    "fused_topk_int8_k8": (
        lambda q, y, s, z: fused_topk(q, y, 8, keys_scale=s, keys_zero=z),
        [((2048, 128), jnp.float32), ((4096, 128), jnp.int8),
         ((128,), jnp.float32), ((128,), jnp.float32)]),
    "fused_topk_bf16_k8": (lambda q, y: fused_topk(q, y, 8),
                           [((2048, 128), jnp.bfloat16),
                            ((4096, 128), jnp.bfloat16)]),
    "pairwise_sq_l2_4096x4096x128": (lambda x, y: pairwise_sq_l2(x, y),
                                     [((4096, 128), jnp.float32),
                                      ((4096, 128), jnp.float32)]),
    "segment_sum_65536x128_S32768": (
        lambda x, i, w: segment_sum(x, i, 32768, w),
        [((65536, 128), jnp.float32), ((65536,), jnp.int32),
         ((65536,), jnp.float32)]),
    # the banded reduce at the ITIS level-0 blocks of the benchmark's cells:
    # 10^6 x 128 (sift1m) and 581,012 x 6 (covertype) in 8 blocks
    "segment_sum_banded_125000x128_S500000": (
        lambda x, i, w: segment_sum(x, i, 500000, w),
        [((125000, 128), jnp.float32), ((125000,), jnp.int32),
         ((125000,), jnp.float32)]),
    "segment_sum_banded_72627x6_S290506": (
        lambda x, i, w: segment_sum(x, i, 290506, w),
        [((72627, 6), jnp.float32), ((72627,), jnp.int32),
         ((72627,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
