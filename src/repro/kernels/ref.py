"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract),
plus :func:`quant_chain_np`, the NumPy oracle of a whole quantized MLP.

Each function computes the same result as its kernel with plain jnp ops;
tests sweep shapes/dtypes and ``assert_allclose`` kernel vs oracle.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["ref_reram_matmul_int", "ref_aggregate_diff", "ref_fps_update",
           "combine_planes", "quantize_np", "quant_chain_np"]


def combine_planes(planes: jnp.ndarray, cell_bits: int = 2,
                   weight_bits: int = 8) -> jnp.ndarray:
    """Recombine offset-binary cell planes into signed integer weights."""
    p = planes.astype(jnp.int32)
    shifts = jnp.array([1 << (cell_bits * i) for i in range(p.shape[0])],
                       dtype=jnp.int32)
    u = jnp.tensordot(shifts, p, axes=(0, 0))
    return u - (1 << (weight_bits - 1))


def ref_reram_matmul_int(x_int: jnp.ndarray, planes: jnp.ndarray,
                         cell_bits: int = 2,
                         weight_bits: int = 8) -> jnp.ndarray:
    w = combine_planes(planes, cell_bits, weight_bits)
    return x_int.astype(jnp.int32) @ w


def ref_aggregate_diff(features: jnp.ndarray, nbr_idx: jnp.ndarray,
                       ctr_idx: jnp.ndarray) -> jnp.ndarray:
    return features[nbr_idx] - features[ctr_idx][:, None, :]


def ref_fps_update(points_t: jnp.ndarray, centroid: jnp.ndarray,
                   dist: jnp.ndarray) -> jnp.ndarray:
    d = jnp.sum((points_t - centroid) ** 2, axis=0, keepdims=True)
    return jnp.minimum(dist, d)


def quantize_np(v):
    """Correctly rounded NumPy twin of ``quantize_tensor`` (INT8): the
    integers (as float64) and the float32 scale."""
    v = np.asarray(v, np.float32)
    qmax = np.float32(127)
    s = np.maximum(np.max(np.abs(v)) / qmax, np.float32(1e-12))
    return np.clip(np.round(v / s), -qmax, qmax).astype(np.float64), s


def quant_chain_np(layers, x, final_relu: bool = True, quantize=None):
    """Correctly-rounded NumPy oracle of the quantized MLP chain that
    ``reram_linear`` and the fused kernel implement: float32 scale, quant
    and dequant ops (one rounding each; NumPy never FMA-contracts) around
    exact integer matmuls (run in float64: every partial sum is an integer
    far below 2^53, so BLAS computes it exactly). ``layers`` is a sequence
    of ``{"w": (k, n), "b": (n,)}`` dicts; ``x`` is ``(m, k)``. Returns
    ``(y, accs)``: the float32 output and each layer's int64 accumulator
    ``x_int @ w_int``.

    ``quantize(v, i, operand)`` -> ``(ints, scale)`` replaces
    :func:`quantize_np` for layer ``i``'s input (``operand`` "x") and
    weights ("w"): a device whose f32 division is not correctly rounded
    can supply its own integers and scales, leaving the rest exact."""
    if quantize is None:
        quantize = lambda v, i, operand: quantize_np(v)
    y = np.asarray(x, np.float32)
    accs = []
    for i, lyr in enumerate(layers):
        xi, sx = quantize(y, i, "x")
        wi, sw = quantize(lyr["w"], i, "w")
        accs.append((np.asarray(xi, np.float64)
                     @ np.asarray(wi, np.float64)).astype(np.int64))
        y = (accs[-1].astype(np.float32) * (np.float32(sx) * np.float32(sw))
             + np.asarray(lyr["b"], np.float32))
        if final_relu or i < len(layers) - 1:
            y = np.maximum(y, np.float32(0))
    return y, accs
