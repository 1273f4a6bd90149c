"""Pipeline parallelism across the 'pod' axis (GPipe schedule).

Layers are split into ``n_stages`` contiguous stages (one per pod); a
microbatched forward rotates activations stage-to-stage with
``lax.ppermute`` inside ``shard_map``. The bubble fraction is
(S-1)/(M+S-1) for S stages and M microbatches; the default multi-pod
config prefers cross-pod DP for batch-256 training (lower bubble), but PP
is the right choice when the model does not fit one pod's HBM even fully
sharded — both are first-class here.

``pipeline_forward`` is deliberately model-agnostic: it pipelines any
per-stage function ``stage_fn(stage_params, x) -> x`` over stacked stage
params, so tests validate it against the sequential composition exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_forward"]


def pipeline_forward(stage_fn, stage_params, x_mb, *, axis_name: str):
    """Run inside shard_map, one stage per device along ``axis_name``.

    stage_params : this device's stage parameters
    x_mb         : (M, mb, ...) microbatched input, replicated content-wise
                   (only stage 0 consumes it)
    returns      : (M, mb, ...) outputs valid on the LAST stage.
    """
    s = lax.axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    m = x_mb.shape[0]
    t_total = m + s - 1
    perm = [(i, (i + 1) % s) for i in range(s - 1)]   # no wraparound send

    def step(t, state):
        buf, out = state
        # stage 0 injects microbatch t (if any); others use what arrived
        inject = jnp.where(t < m, t, m - 1)
        h_in = jnp.where(sid == 0, x_mb[inject], buf)
        h_out = stage_fn(stage_params, h_in)
        # last stage retires microbatch t - (s - 1); select instead of
        # cond (shard_map vma: both branches must have identical types)
        mb_done = t - (s - 1)
        write = jnp.logical_and(sid == s - 1, mb_done >= 0)
        upd = lax.dynamic_update_index_in_dim(
            out, h_out, jnp.maximum(mb_done, 0), 0)
        out = jnp.where(write, upd, out)
        buf = lax.ppermute(h_out, axis_name, perm)
        return buf, out

    # loop carries become device-varying after the first ppermute/select
    buf0 = lax.pcast(jnp.zeros_like(x_mb[0]), (axis_name,), to="varying")
    out0 = lax.pcast(jnp.zeros_like(x_mb), (axis_name,), to="varying")
    _, out = lax.fori_loop(0, t_total, step, (buf0, out0))
    # broadcast the last stage's result so the output is replicated
    return lax.psum(jnp.where(sid == s - 1, out, 0), axis_name)
