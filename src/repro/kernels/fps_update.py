"""Pallas TPU kernel: FPS distance-relaxation step.

One farthest-point-sampling iteration relaxes the running minimum distance
against the newly selected centroid: ``d = min(d, ||p - c||^2)``. This is
the front-end hot loop (N points per step, n_samples steps). Layout is
TPU-friendly: coordinates as (3, N) so the point dimension is the 128-wide
lane dimension; distances as (1, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import interpret_mode

__all__ = ["fps_update"]


def _kernel(pts_ref, c_ref, d_ref, o_ref):
    diff = pts_ref[...] - c_ref[...]                 # (3, bn)
    d_new = jnp.sum(diff * diff, axis=0, keepdims=True)
    o_ref[...] = jnp.minimum(d_ref[...], d_new)


@functools.partial(jax.jit, static_argnames=("block_n",))
def fps_update(points_t: jnp.ndarray, centroid: jnp.ndarray,
               dist: jnp.ndarray, *, block_n: int = 512) -> jnp.ndarray:
    """points_t (3, N); centroid (3, 1); dist (1, N) -> relaxed dist (1, N)."""
    _, n = points_t.shape
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        _kernel,
        name="fps_update",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((3, bn), lambda i: (0, i)),
            pl.BlockSpec((3, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), dist.dtype),
        interpret=interpret_mode(),
    )(points_t, centroid, dist)
