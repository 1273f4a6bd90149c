"""Pallas kernels vs pure-jnp oracles (the Pallas interpreter on CPU), with
shape/dtype sweeps per the kernel contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # deterministic sweep, see _hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, st

from repro.kernels import (aggregate_diff, aggregate_diff_batched,
                           count_dma_elisions, encode_planes,
                           fps, fps_update, quantize_tensor, reram_linear,
                           reram_matmul_int)
from repro.kernels.ref import (combine_planes, ref_aggregate_diff,
                               ref_fps_update, ref_reram_matmul_int)

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 256, 128), (384, 384, 256)])
def test_reram_matmul_exact_over_shapes(m, k, n):
    x = RNG.integers(-127, 128, (m, k)).astype(np.int8)
    w = RNG.integers(-127, 128, (k, n)).astype(np.int32)
    planes = encode_planes(jnp.asarray(w))
    out = reram_matmul_int(jnp.asarray(x), planes)
    ref = ref_reram_matmul_int(jnp.asarray(x), planes)
    assert out.dtype == jnp.int32
    assert bool(jnp.all(out == ref))


@pytest.mark.parametrize("block", [(128, 128, 128), (256, 128, 128)])
def test_reram_matmul_block_shapes(block):
    x = RNG.integers(-127, 128, (256, 256)).astype(np.int8)
    w = RNG.integers(-127, 128, (256, 256)).astype(np.int32)
    planes = encode_planes(jnp.asarray(w))
    out = reram_matmul_int(jnp.asarray(x), planes, block=block)
    assert bool(jnp.all(out == ref_reram_matmul_int(jnp.asarray(x), planes)))


def test_combine_planes_inverts_encode():
    w = jnp.asarray(RNG.integers(-127, 128, (50, 30)), jnp.int32)
    assert bool(jnp.all(combine_planes(encode_planes(w)) == w))


@given(st.integers(0, 1000), st.sampled_from([1, 3, 17, 100]),
       st.sampled_from([1, 2, 72]))
@settings(max_examples=10, deadline=None)
def test_reram_linear_close_to_float(seed, k, n):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(9, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    out = reram_linear(jnp.asarray(x), jnp.asarray(w))
    ref = x @ w
    tol = 2.5 * (np.abs(x).max() / 127 * np.abs(w).max() / 127) * k ** 0.5 \
        + 0.05 * np.abs(ref).max() + 1e-5
    assert np.max(np.abs(np.asarray(out) - ref)) <= tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,c", [(6, 3, 128), (17, 5, 256), (1, 1, 128)])
def test_aggregate_diff_matches_ref(dtype, m, k, c):
    f = jnp.asarray(RNG.normal(size=(40, c)), dtype)
    nbr = jnp.asarray(RNG.integers(0, 40, (m, k)), jnp.int32)
    ctr = jnp.asarray(RNG.integers(0, 40, (m,)), jnp.int32)
    out = aggregate_diff(f, nbr, ctr)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_aggregate_diff(f, nbr, ctr),
                                          np.float32), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,m,k,c", [(1, 6, 3, 128), (3, 17, 5, 256),
                                       (4, 1, 1, 128)])
def test_aggregate_diff_batched_matches_per_cloud(b, m, k, c):
    """The batch-gridded gather is bitwise the stack of per-cloud gathers:
    the batch axis is outermost in the grid and never interleaves two
    clouds' index streams."""
    f = jnp.asarray(RNG.normal(size=(b, 40, c)), jnp.float32)
    nbr = jnp.asarray(RNG.integers(0, 40, (b, m, k)), jnp.int32)
    ctr = jnp.asarray(RNG.integers(0, 40, (b, m)), jnp.int32)
    out = aggregate_diff_batched(f, nbr, ctr)
    assert out.shape == (b, m, k, c)
    per = jnp.stack([aggregate_diff(f[i], nbr[i], ctr[i]) for i in range(b)])
    assert bool(jnp.all(out == per))


def test_aggregate_diff_batched_rejects_batch_mismatch():
    f = jnp.zeros((2, 8, 128), jnp.float32)
    nbr = jnp.zeros((3, 4, 2), jnp.int32)
    ctr = jnp.zeros((3, 4), jnp.int32)
    with pytest.raises(ValueError, match="batch"):
        aggregate_diff_batched(f, nbr, ctr)


@pytest.mark.parametrize("n,block", [(512, 512), (1024, 256), (128, 128)])
def test_fps_update_matches_ref(n, block):
    pts = jnp.asarray(RNG.normal(size=(3, n)), jnp.float32)
    c = pts[:, 7:8]
    d = jnp.asarray(RNG.uniform(0, 4, (1, n)), jnp.float32)
    out = fps_update(pts, c, d, block_n=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_fps_update(pts, c, d)),
                               rtol=1e-6, atol=1e-6)


def test_kernel_fps_equals_model_fps():
    from repro.models.pointnet2 import farthest_point_sample
    pts = jnp.asarray(RNG.normal(size=(200, 3)), jnp.float32)
    a = fps(pts, 50)
    b = farthest_point_sample(pts, 50)
    assert bool(jnp.all(a == b))


def test_quantize_tensor_symmetric():
    x = jnp.asarray(RNG.normal(size=(32, 32)) * 3)
    q, s = quantize_tensor(x)
    assert int(jnp.max(jnp.abs(q))) <= 127
    assert float(jnp.max(jnp.abs(q * s - x))) <= float(s) / 2 + 1e-6


def test_dma_elision_improves_with_reordering():
    """The TPU twin of the paper's claim: ordering neighbor lists so that
    consecutive grid steps hit the same feature row removes DMAs."""
    nbr = RNG.integers(0, 16, (64, 8))
    base = count_dma_elisions(nbr)
    srt = count_dma_elisions(np.sort(nbr.reshape(-1)).reshape(64, 8))
    assert srt["elided"] > base["elided"]
    assert srt["dma"] + srt["elided"] == srt["steps"]
