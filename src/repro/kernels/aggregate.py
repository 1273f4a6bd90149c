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

__all__ = ["aggregate_diff", "aggregate_diff_batched", "index_chunks",
           "SMEM_INDEX_WORDS"]


#: int32 words of index stream one launch scalar-prefetches into SMEM (the
#: v5e has 1 MiB): 768 KiB. The published single-scale layers at batch 16
#: (139,264 words) stay one launch; larger streams are fetched in chunks.
SMEM_INDEX_WORDS = 3 * 2 ** 16


def _kernel(nbr_ref, ctr_ref, f_nbr_ref, f_ctr_ref, *refs):
    del nbr_ref, ctr_ref  # only used by the index_maps
    o_ref = refs[-1]      # refs[0], where there are two: the aliased output
    o_ref[...] = f_nbr_ref[...] - f_ctr_ref[...]


def index_chunks(b: int, m: int, k: int,
                 budget: int = SMEM_INDEX_WORDS) -> list[tuple]:
    """The launches of one gather: ``(b0, nb, m0, nm)`` blocks of the
    ``(B, M)`` centre rows, in row order, each of whose index words
    (``K + 1`` per row: its neighbours and its centre) fit ``budget``.
    Whole batch elements per launch where a cloud's rows fit, else
    slices of one cloud's rows."""
    rows = max(1, budget // (k + 1))
    if m <= rows:
        per = rows // m
        return [(b0, min(per, b - b0), 0, m) for b0 in range(0, b, per)]
    return [(b0, 1, m0, min(rows, m - m0))
            for b0 in range(b) for m0 in range(0, m, rows)]


def _gather_diff(features, nbr_idx, ctr_idx, centers=None, *, name: str,
                 budget: int = SMEM_INDEX_WORDS):
    """features (B, N, C); nbr_idx (B, M, K); ctr_idx (B, M); centers
    (B, N, C) or None (then ``features``) -> (B, M, K, C) with
    out[b, i, j] = features[b, nbr[b, i, j]] - centers[b, ctr[b, i]].

    Layout: Mosaic tiles the last two dims of a block (8 sublanes x 128
    lanes) and accepts a block dim that is not a tile multiple only when it
    spans the whole array dim. One feature row per grid step therefore
    rides a unit axis: features are viewed as (B, N, 1, C) and every block
    is (1, C) in its last two dims. The index streams are flattened to 1-D
    before they are prefetched into SMEM, where a 2-D or 3-D array would
    pad its minor dims.

    SMEM holds ``budget`` index words per launch, so a longer stream runs
    as several launches over blocks of centre rows (:func:`index_chunks`),
    each writing its block of one output buffer in place (the previous
    launch's output is aliased to the next one's). Every launch walks its
    rows' grid steps in the one-launch order, so the chunked gather is
    bitwise the unchunked one and elides the same copies inside each
    block."""
    b, n, c = features.shape
    if nbr_idx.shape[0] != b or ctr_idx.shape[0] != b:
        raise ValueError(f"batch mismatch: features {features.shape}, "
                         f"nbr {nbr_idx.shape}, ctr {ctr_idx.shape}")
    if centers is not None and centers.shape != features.shape:
        raise ValueError(f"centers {centers.shape} must match features "
                         f"{features.shape}")
    _, m, k = nbr_idx.shape
    rows = features.reshape(b, n, 1, c)
    ctr_rows = rows if centers is None else centers.reshape(b, n, 1, c)
    out_shape = jax.ShapeDtypeStruct((b, m, k, 1, c), features.dtype)
    out = None
    for b0, nb, m0, nm in index_chunks(b, m, k, budget):
        ob = (lambda v: v + b0) if b0 else (lambda v: v)
        om = (lambda v: v + m0) if m0 else (lambda v: v)
        nbr, ctr = nbr_idx, ctr_idx
        if (nb, nm) != (b, m):
            nbr = nbr[b0:b0 + nb, m0:m0 + nm]
            ctr = ctr[b0:b0 + nb, m0:m0 + nm]
        in_specs = [
            pl.BlockSpec((None, None, 1, c),
                         lambda bi, i, j, nbr, ctr, nm=nm, ob=ob:
                         (ob(bi), nbr[(bi * nm + i) * k + j], 0, 0)),
            pl.BlockSpec((None, None, 1, c),
                         lambda bi, i, j, nbr, ctr, nm=nm, ob=ob:
                         (ob(bi), ctr[bi * nm + i], 0, 0)),
        ]
        operands = [nbr.reshape(-1), ctr.reshape(-1), rows, ctr_rows]
        aliases = {}
        if out is not None:          # write into the previous launch's
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            aliases = {len(operands): 0}
            operands.append(out)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, nm, k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, None, 1, c),
                                   lambda bi, i, j, nbr, ctr, ob=ob, om=om:
                                   (ob(bi), om(i), j, 0, 0)),
        )
        out = pl.pallas_call(
            _kernel,
            name=name,
            grid_spec=grid_spec,
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret_mode(),
        )(*operands)
    return out.reshape(b, m, k, c)


@jax.jit
def aggregate_diff(features: jnp.ndarray, nbr_idx: jnp.ndarray,
                   ctr_idx: jnp.ndarray, centers=None) -> jnp.ndarray:
    """features (N, C); nbr_idx (M, K) int32; ctr_idx (M,) int32; centers
    (N, C) or None -> (M, K, C) with
    out[i, j] = features[nbr_idx[i, j]] - centers[ctr_idx[i]] (``centers``
    defaults to ``features``). The single-cloud launch of the
    :func:`aggregate_diff_batched` kernel."""
    return _gather_diff(features[None], nbr_idx[None], ctr_idx[None],
                        None if centers is None else centers[None],
                        name="aggregate_diff")[0]


@jax.jit
def aggregate_diff_batched(features: jnp.ndarray, nbr_idx: jnp.ndarray,
                           ctr_idx: jnp.ndarray,
                           centers=None) -> jnp.ndarray:
    """Batch-gridded :func:`aggregate_diff`: the whole batch of same-shape
    plan-ordered gathers with a leading batch grid axis — the launch shape
    batched plan-driven execution (``CompiledModel.batched_forward`` under
    a schedule/policy) issues once per SA layer (per branch of a
    multi-scale layer) instead of a per-cloud Python loop. Where the
    index stream outgrows SMEM it runs as several launches over blocks of
    rows, all under this one kernel name.

    features (B, N, C); nbr_idx (B, M, K) int32; ctr_idx (B, M) int32;
    centers (B, N, C) or None -> (B, M, K, C) with
    out[b, i, j] = features[b, nbr_idx[b, i, j]] - centers[b, ctr_idx[b, i]]
    (``centers`` defaults to ``features``: ``F_j - F_i``; a multi-scale
    layer passes ``[p ; 0]`` against ``[p ; f]`` for ``[p_j - p_i ; f_j]``).

    Per batch element the grid walks the same (m, k) step sequence as the
    unbatched kernel, so a plan-ordered index stream elides the same
    HBM→VMEM copies; the batch axis is outermost and never interleaves
    two clouds' streams."""
    return _gather_diff(features, nbr_idx, ctr_idx, centers,
                        name="aggregate_diff_batched")
