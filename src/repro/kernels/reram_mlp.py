"""Pallas TPU kernel: bit-sliced weight-stationary INT8 matmul.

TPU adaptation of the paper's ReRAM crossbar MLP engine (DESIGN.md §3):

  * one 128x128 ReRAM array  <->  one 128x128 MXU tile / VMEM weight block;
  * 2-bit cells              <->  four 2-bit weight planes (offset-binary),
                                  recombined by shift-and-add — exactly the
                                  crossbar's digital S&A pipeline;
  * weights stay in the crossbar <-> the weight planes for a given (n, k)
                                  tile are VMEM-resident while a whole
                                  ``block_m`` stripe of activations streams
                                  through them (weight-stationary dataflow).

The kernel is integer-exact: the output equals ``x_int @ w_int`` where
``w_int`` is the INT8 weight tensor, matching ``repro.kernels.ref`` and the
NumPy functional model in ``repro.core.reram``.

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost; an int32 VMEM accumulator
carries partial sums across K steps, and the offset-binary correction
(``- 2^(b-1) * sum_k x``) is applied on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import interpret_mode

__all__ = ["reram_matmul_int"]

DEFAULT_BLOCK = (128, 128, 128)   # (bm, bn, bk) = the crossbar geometry


def _kernel(x_ref, planes_ref, o_ref, acc_ref, xsum_ref, *,
            n_planes: int, cell_bits: int, weight_bits: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xsum_ref[...] = jnp.zeros_like(xsum_ref)

    x = x_ref[...]                                        # (bm, bk) int8
    xsum_ref[...] += jnp.sum(x.astype(jnp.int32), axis=1, keepdims=True)
    acc = acc_ref[...]
    for p in range(n_planes):                             # 4 cell planes
        # int8 x int8 -> int32 is the MXU's integer mode (Mosaic has no
        # int32 x int32 matmul); the cells are 0..3, so they fit int8
        part = jax.lax.dot_general(
            x, planes_ref[p], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc + (part << (cell_bits * p))
    acc_ref[...] = acc

    @pl.when(k == k_steps - 1)
    def _finish():
        # offset-binary correction: w = u - 2^(b-1)
        o_ref[...] = acc_ref[...] - (xsum_ref[...] << (weight_bits - 1))


@functools.partial(jax.jit, static_argnames=(
    "cell_bits", "weight_bits", "block"))
def reram_matmul_int(x_int: jnp.ndarray, planes: jnp.ndarray, *,
                     cell_bits: int = 2, weight_bits: int = 8,
                     block: tuple[int, int, int] = DEFAULT_BLOCK
                     ) -> jnp.ndarray:
    """``x_int`` (M, K) int8 activations; ``planes`` (P, K, N) int8
    offset-binary 2-bit planes (LSB first). Returns (M, N) int32 equal to
    ``x_int @ (combine(planes) - 2**(weight_bits-1))``."""
    m, kdim = x_int.shape
    n_planes, k2, n = planes.shape
    assert k2 == kdim, (k2, kdim)
    bm, bn, bk = block
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (
        f"shape ({m},{kdim})x({kdim},{n}) not divisible by block {block}")
    k_steps = kdim // bk
    grid = (m // bm, n // bn, k_steps)
    kernel = functools.partial(
        _kernel, n_planes=n_planes, cell_bits=cell_bits,
        weight_bits=weight_bits, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        name="reram_matmul_int",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((n_planes, bk, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, 1), jnp.int32),
        ],
        interpret=interpret_mode(),
    )(x_int.astype(jnp.int8), planes)
