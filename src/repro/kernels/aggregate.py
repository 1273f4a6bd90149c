"""Pallas TPU kernel: index-driven neighbor gather + difference.

The aggregation step of PointNet++ — for output point i with neighbors
j in nbr(i): ``D(F_i, F_j) = F[nbr[i, j]] - F[ctr[i]]`` — is the irregular
DRAM-access pattern the paper's contributions ② ③ optimize.

TPU mapping (DESIGN.md §3): neighbor indices are **scalar-prefetched** into
SMEM and drive the input ``BlockSpec.index_map``, so each grid step DMAs
exactly one feature row HBM→VMEM. Pallas elides the copy when consecutive
grid steps map to the same block — therefore an execution order that puts
points with overlapping receptive fields next to each other (the paper's
intra-layer reordering) directly removes DMAs here. The
``count_dma_elisions`` helper in ``repro.kernels.ops`` quantifies that —
the TPU-native twin of the paper's buffer hit rate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import interpret_mode

__all__ = ["aggregate_diff", "aggregate_diff_batched"]


def _kernel(nbr_ref, ctr_ref, f_nbr_ref, f_ctr_ref, o_ref):
    del nbr_ref, ctr_ref  # only used by the index_maps
    o_ref[...] = f_nbr_ref[...] - f_ctr_ref[...]


def _gather_diff(features, nbr_idx, ctr_idx, *, name: str):
    """features (B, N, C); nbr_idx (B, M, K); ctr_idx (B, M) -> (B, M, K, C).

    Layout: Mosaic tiles the last two dims of a block (8 sublanes x 128
    lanes) and accepts a block dim that is not a tile multiple only when it
    spans the whole array dim. One feature row per grid step therefore
    rides a unit axis: features are viewed as (B, N, 1, C) and every block
    is (1, C) in its last two dims. The index streams are flattened to 1-D
    before they are prefetched into SMEM, where a 2-D or 3-D array would
    pad its minor dims."""
    b, n, c = features.shape
    if nbr_idx.shape[0] != b or ctr_idx.shape[0] != b:
        raise ValueError(f"batch mismatch: features {features.shape}, "
                         f"nbr {nbr_idx.shape}, ctr {ctr_idx.shape}")
    _, m, k = nbr_idx.shape
    rows = features.reshape(b, n, 1, c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m, k),
        in_specs=[
            pl.BlockSpec((None, None, 1, c),
                         lambda bi, i, j, nbr, ctr:
                         (bi, nbr[(bi * m + i) * k + j], 0, 0)),
            pl.BlockSpec((None, None, 1, c),
                         lambda bi, i, j, nbr, ctr:
                         (bi, ctr[bi * m + i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, None, 1, c),
                               lambda bi, i, j, nbr, ctr: (bi, i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, m, k, 1, c), features.dtype),
        interpret=interpret_mode(),
    )(nbr_idx.reshape(-1), ctr_idx.reshape(-1), rows, rows)
    return out.reshape(b, m, k, c)


@jax.jit
def aggregate_diff(features: jnp.ndarray, nbr_idx: jnp.ndarray,
                   ctr_idx: jnp.ndarray) -> jnp.ndarray:
    """features (N, C); nbr_idx (M, K) int32; ctr_idx (M,) int32
    -> (M, K, C) with out[i, j] = features[nbr_idx[i, j]] - features[ctr_idx[i]].
    The single-cloud launch of the :func:`aggregate_diff_batched` kernel."""
    return _gather_diff(features[None], nbr_idx[None], ctr_idx[None],
                        name="aggregate_diff")[0]


@jax.jit
def aggregate_diff_batched(features: jnp.ndarray, nbr_idx: jnp.ndarray,
                           ctr_idx: jnp.ndarray) -> jnp.ndarray:
    """Batch-gridded :func:`aggregate_diff`: the whole batch of same-shape
    plan-ordered gathers in ONE ``pallas_call`` with a leading batch grid
    axis — the launch shape batched plan-driven execution
    (``CompiledModel.batched_forward`` under a schedule/policy) issues
    exactly once per SA layer instead of a per-cloud Python loop.

    features (B, N, C); nbr_idx (B, M, K) int32; ctr_idx (B, M) int32
    -> (B, M, K, C) with
    out[b, i, j] = features[b, nbr_idx[b, i, j]] - features[b, ctr_idx[b, i]].

    Per batch element the grid walks the same (m, k) step sequence as the
    unbatched kernel, so a plan-ordered index stream elides the same
    HBM→VMEM copies; the batch axis is outermost and never interleaves
    two clouds' streams."""
    return _gather_diff(features, nbr_idx, ctr_idx,
                        name="aggregate_diff_batched")
