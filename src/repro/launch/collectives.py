"""Collective helpers: compressed cross-pod all-reduce, overlap-friendly
TP matmul.

``compressed_psum``: int8-quantized all-reduce for the cross-pod (DCN)
gradient reduction. All participants agree on a scale via one scalar pmax,
quantize to int8, reduce, dequantize. In a ring implementation the wire
format is int8 with int32 accumulation (4x fewer DCN bytes than fp32);
jax's ``psum`` here carries int32, so this module demonstrates the exact
semantics (and its convergence behaviour under error feedback is
unit-tested) while the byte saving is a deployment property recorded in
EXPERIMENTS.md.

``overlapped_tp_matmul``: all-gather-free tensor-parallel matmul that
rotates activation shards around the 'model' axis ring with
``lax.ppermute`` while multiplying — each permute step overlaps with the
local matmul of the previously received shard (collective matmul; used in
§Perf iterations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["compressed_psum", "overlapped_tp_matmul"]


def compressed_psum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """int8-quantized psum over ``axis_name`` (call inside shard_map)."""
    scale = lax.pmax(jnp.max(jnp.abs(x.astype(jnp.float32))),
                     axis_name) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    return (total.astype(jnp.float32) * scale).astype(x.dtype)


def overlapped_tp_matmul(x_shard: jnp.ndarray, w_shard: jnp.ndarray,
                         axis_name: str) -> jnp.ndarray:
    """Compute ``allgather(x, axis) @ w_shard`` without materializing the
    all-gather: ring-rotate x shards, accumulating partial products.

    Inside shard_map with axis size N:
      x_shard (m, k/N)  — activation sharded on the contraction dim,
      w_shard (k/N, n)  — weight row-shard held by this device...

    NOTE: this variant implements the *reduce-scatter-free* pattern for
    column-sharded weights: x_shard (m/N, k), w_shard (k, n/N) would use
    psum; here we do the all-gather form used before a row-parallel matmul.
    """
    n_dev = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(i, state):
        acc, blk, src = state
        # which shard of the contraction dim we currently hold
        shard_id = (idx - i) % n_dev
        k_shard = blk.shape[-1]
        acc = acc + blk @ lax.dynamic_slice_in_dim(
            w_shard, shard_id * k_shard, k_shard, axis=0)
        blk = lax.ppermute(blk, axis_name, perm)
        return acc, blk, src

    acc0 = jnp.zeros((x_shard.shape[0], w_shard.shape[-1]),
                     jnp.promote_types(x_shard.dtype, w_shard.dtype))
    # the accumulator becomes device-varying once shards rotate in
    acc0 = lax.pcast(acc0, (axis_name,), to="varying")
    acc, _, _ = lax.fori_loop(0, n_dev, body, (acc0, x_shard, idx))
    return acc
