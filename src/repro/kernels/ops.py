"""Public jit'd wrappers around the Pallas kernels.

``reram_linear`` is the drop-in MLP backend ("--mlp-backend reram"): float
in / float out, INT8 symmetric quantization on both operands, bit-sliced
crossbar matmul in the integer domain (exact), dequantized output. Note it
re-quantizes and re-encodes the weight planes on every traced call — the
weight-stationary path (``mlp_backend='reram-fused'``) builds a
``CrossbarProgram`` once instead and runs the whole MLP through
``reram_mlp_fused``; ``reram_linear`` is kept as the per-layer reference
the fused kernel is tested bit-exact against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .aggregate import aggregate_diff, aggregate_diff_batched
from .fps_update import fps_update
from .program import encode_planes, quantize_tensor
from .reram_mlp import reram_matmul_int
from .ref import combine_planes

__all__ = [
    "encode_planes", "quantize_tensor", "reram_linear",
    "aggregate_diff", "aggregate_diff_batched", "fps_update",
    "fps", "count_dma_elisions",
]


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("fault_model",))
def reram_linear(x: jnp.ndarray, w: jnp.ndarray,
                 b: jnp.ndarray | None = None, *, fault_model=None,
                 fault_key: jnp.ndarray | None = None) -> jnp.ndarray:
    """Float (…, K) @ (K, N) through the bit-sliced crossbar kernel.

    ``fault_model`` (a hashable :class:`repro.reliability.FaultModel`,
    duck-typed so kernels stay below reliability in the layering) injects
    ReRAM non-idealities into the freshly encoded cell planes before the
    MVM — the per-layer twin of faulting a ``CrossbarProgram``. It rides
    through jit as a static argument; ``fault_key`` seeds the injection
    site (defaults to the model's base key)."""
    lead = x.shape[:-1]
    k, n = w.shape
    x2 = x.reshape(-1, k)
    x_int, sx = quantize_tensor(x2)
    w_int, sw = quantize_tensor(w)
    planes = encode_planes(w_int)
    if fault_model is not None and not fault_model.is_ideal_for(2):
        key = fault_model.base_key() if fault_key is None else fault_key
        planes = fault_model.transform_planes(planes, key, cell_bits=2)
    # pad to the 128x128 crossbar geometry
    m0 = x2.shape[0]
    x_p = _pad_to(_pad_to(x_int.astype(jnp.int8), 0, 128), 1, 128)
    planes_p = _pad_to(_pad_to(planes, 1, 128), 2, 128)
    out = reram_matmul_int(x_p, planes_p)
    out = out[:m0, :n].astype(jnp.float32) * (sx * sw)
    if b is not None:
        out = out + b
    return out.reshape(*lead, n)


def fps(points: jnp.ndarray, n_samples: int, *,
        start: int = 0) -> jnp.ndarray:
    """Full farthest-point sampling driven by the ``fps_update`` kernel."""
    n = points.shape[0]
    pts_t = _pad_to(points.T, 1, 128)               # (3, N_pad)
    n_pad = pts_t.shape[1]
    valid = (jnp.arange(n_pad) < n)[None, :]

    def body(i, state):
        idx, dist, cur = state
        idx = idx.at[i].set(cur)
        c = jax.lax.dynamic_slice(pts_t, (0, cur), (3, 1))
        dist = fps_update(pts_t, c, dist)
        dist = jnp.where(valid, dist, -jnp.inf)
        return idx, dist, jnp.argmax(dist[0]).astype(jnp.int32)

    idx0 = jnp.zeros(n_samples, dtype=jnp.int32)
    dist0 = jnp.where(valid, jnp.inf, -jnp.inf).astype(points.dtype)
    idx, _, _ = jax.lax.fori_loop(0, n_samples, body,
                                  (idx0, dist0, jnp.int32(start)))
    return idx


def count_dma_elisions(nbr_idx: np.ndarray, window: int = 1) -> dict:
    """TPU-native twin of the paper's buffer hit rate. ``window=1`` models
    strict Pallas revisit elision (consecutive grid steps mapping to the
    same block skip the copy); ``window=W`` models a W-row VMEM working
    set (multi-buffered blocks / a VMEM-resident row cache — e.g. W=72
    rows ~ the paper's 9 KB buffer at 128 B/row). Reordering rows of
    ``nbr_idx`` (the paper's intra-layer reordering) changes this number
    and nothing else."""
    flat = np.asarray(nbr_idx).reshape(-1)
    if window <= 1:
        elided = int(np.sum(flat[1:] == flat[:-1]))
    else:
        from collections import OrderedDict
        lru: OrderedDict = OrderedDict()
        elided = 0
        for v in flat.tolist():
            if v in lru:
                elided += 1
                lru.move_to_end(v)
            else:
                if len(lru) >= window:
                    lru.popitem(last=False)
                lru[v] = True
    return {"steps": int(flat.size), "elided": elided,
            "dma": int(flat.size) - elided,
            "elision_rate": elided / max(1, flat.size)}
