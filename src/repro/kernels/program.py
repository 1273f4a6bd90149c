"""Weight-stationary crossbar programs (DESIGN.md §3.2).

In the Pointer accelerator, MLP weights are *programmed into the ReRAM
crossbars once* and stay resident while activations stream through. The
TPU twin of that lifecycle is a :class:`CrossbarProgram`: all weights of
one MLP are quantized and bit-plane-encoded exactly once at "program
time", padded to the crossbar/MXU geometry, and stacked into a uniform
pytree of VMEM-ready tensors. The per-forward hot path only streams
activations — ``encode_planes``/``quantize_tensor`` never run on weights
inside a jitted forward again (tests count the calls via monkeypatch).

``quantize_tensor`` and ``encode_planes`` live here (program time is
their natural home); ``repro.kernels.ops`` re-exports them so existing
imports keep working.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp

from .ref import combine_planes

__all__ = [
    "CrossbarProgram", "FUSED_MODES", "FusedPlan", "build_program",
    "encode_planes", "fused_vmem_bytes", "plan_fused_mlp", "quantize_tensor",
]

#: Crossbar / MXU tile edge — every program dimension is padded to this.
CROSSBAR = 128

#: Per-core VMEM the fused kernel is budgeted against (TPU: ~16 MB/core).
VMEM_BUDGET_BYTES = 16 * 2 ** 20

#: The four fused-kernel dataflows (DESIGN.md §3.3):
#:   whole  — single N-tile (bn = d_pad), activation panel in VMEM; fully
#:            weight-stationary, the PR-1 dataflow.
#:   tiled  — N/K-tiled plane staging, activation panel in VMEM; plane
#:            tiles re-stream from HBM once per M-stripe (j innermost).
#:   mtiled — M-tiled activation panel: the panel lives in HBM (the output
#:            buffer doubles as it) and only one (block_m, d_pad) stripe is
#:            VMEM-resident per step, staged by explicit DMA. The only mode
#:            whose residency does not grow with M — panel-bound shapes
#:            (model2 SA-1 at 8192 rows) run fused through it.
#:   wstat  — j-outer weight re-streaming: N-tiles iterate outermost over a
#:            full int8 input-snapshot panel, so plane tiles cross HBM once
#:            per layer instead of once per M-stripe (restores weight
#:            stationarity for act-panel-fitting shapes, +M_pad·d bytes).
FUSED_MODES = ("whole", "tiled", "mtiled", "wstat")


def quantize_tensor(x: jnp.ndarray, bits: int = 8):
    """Symmetric per-tensor quantization -> (int32 values, float scale).

    NaN/Inf inputs are rejected eagerly: a single NaN poisons the
    ``max(|x|)`` scale and silently zeroes the whole tensor. The check
    only runs on concrete arrays — under a jit trace values are abstract
    and the caller keeps responsibility (program weights, the case that
    matters, are always concrete at build time)."""
    x = jnp.asarray(x)
    if not isinstance(x, jax.core.Tracer) and not bool(
            jnp.all(jnp.isfinite(x))):
        raise ValueError("quantize_tensor: input contains NaN/Inf — a "
                         "non-finite value poisons the quantization scale")
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / qmax, 1e-12)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32), scale


def encode_planes(w_int: jnp.ndarray, weight_bits: int = 8,
                  cell_bits: int = 2) -> jnp.ndarray:
    """Signed int weights -> (P, K, N) offset-binary cell planes."""
    offset = 1 << (weight_bits - 1)
    u = (w_int + offset).astype(jnp.uint32)
    n_planes = -(-weight_bits // cell_bits)
    mask = (1 << cell_bits) - 1
    return jnp.stack([((u >> (cell_bits * p)) & mask).astype(jnp.int8)
                      for p in range(n_planes)])


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


def _pad2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                   + [(0, rows - x.shape[-2]), (0, cols - x.shape[-1])])


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class CrossbarProgram:
    """One MLP, programmed. All layers padded to a uniform ``d_pad`` edge so
    the fused kernel (``fused_mlp.py``) can index them with one BlockSpec.

    planes  : (L, P, d_pad, d_pad) int8 offset-binary 2-bit cell planes
    bias    : (L, d_pad) float32, zero beyond each layer's real width
    w_scale : (L, 1) float32 per-layer weight quantization scale
    col_mask: (L, d_pad) float32, 1.0 on each layer's real output columns
    widths  : static (d0, ..., dL) — the original float MLP widths
    ecc     : optional static :class:`repro.reliability.ecc.EccSpec` when
              the planes carry Hamming parity in their spare columns
              (``build_program(..., ecc=...)``); None for bare programs
    """

    planes: jnp.ndarray
    bias: jnp.ndarray
    w_scale: jnp.ndarray
    col_mask: jnp.ndarray
    widths: tuple[int, ...]
    weight_bits: int = 8
    cell_bits: int = 2
    ecc: object | None = None

    # -- pytree protocol (widths & bit layout are static aux data) ----------
    def tree_flatten(self):
        return ((self.planes, self.bias, self.w_scale, self.col_mask),
                (self.widths, self.weight_bits, self.cell_bits, self.ecc))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_layers(self) -> int:
        return len(self.widths) - 1

    @property
    def n_planes(self) -> int:
        return -(-self.weight_bits // self.cell_bits)

    @property
    def d_pad(self) -> int:
        return self.planes.shape[-1]

    # -- decode: the crossbar read-out path, for round-trip tests ----------
    def int_weights(self) -> list[jnp.ndarray]:
        """Per-layer signed int32 weights recombined from the cell planes
        (exact inverse of the encode step, real shapes restored)."""
        return [combine_planes(self.planes[l], self.cell_bits,
                               self.weight_bits)[:k, :n]
                for l, (k, n) in enumerate(zip(self.widths[:-1],
                                               self.widths[1:]))]

    def weights(self) -> list[jnp.ndarray]:
        """Per-layer dequantized float32 weights (within quant tolerance of
        the floats the program was built from)."""
        return [w.astype(jnp.float32) * self.w_scale[l, 0]
                for l, w in enumerate(self.int_weights())]

    def biases(self) -> list[jnp.ndarray]:
        return [self.bias[l, :n] for l, n in enumerate(self.widths[1:])]


def build_program(layers: Sequence, *, weight_bits: int = 8,
                  cell_bits: int = 2, ecc=None) -> CrossbarProgram:
    """Program an MLP into crossbars: quantize + plane-encode every layer
    exactly once, pad to the 128x128 geometry, stack into one pytree.

    ``layers``: sequence of ``{"w": (k, n), "b": (n,)}`` dicts (the
    ``pointnet2`` parameter layout) or ``(w, b)`` tuples.

    ``ecc``: optional :class:`repro.reliability.ecc.EccConfig` (or True
    for the default) — Hamming-encode the planes' spare columns at
    program time (DESIGN.md §13); MVM results are unchanged.
    """
    wbs = []
    for lyr in layers:
        if isinstance(lyr, dict):
            wbs.append((jnp.asarray(lyr["w"]), jnp.asarray(lyr["b"])))
        else:
            w, b = lyr
            wbs.append((jnp.asarray(w), jnp.asarray(b)))
    widths = [wbs[0][0].shape[0]]
    for w, b in wbs:
        if w.shape[0] != widths[-1]:
            raise ValueError(f"MLP widths do not chain: {w.shape} after "
                             f"{widths}")
        if b.shape != (w.shape[1],):
            raise ValueError(f"bias {b.shape} does not match weight {w.shape}")
        widths.append(w.shape[1])
    d = _ceil_to(max(widths), CROSSBAR)

    planes, bias, scale, mask = [], [], [], []
    for w, b in wbs:
        w_int, sw = quantize_tensor(w, bits=weight_bits)
        p = encode_planes(w_int, weight_bits=weight_bits, cell_bits=cell_bits)
        planes.append(_pad2(p, d, d))
        bias.append(jnp.pad(b.astype(jnp.float32), (0, d - b.shape[0])))
        scale.append(sw)
        mask.append((jnp.arange(d) < w.shape[1]).astype(jnp.float32))
    program = CrossbarProgram(
        planes=jnp.stack(planes),
        bias=jnp.stack(bias),
        w_scale=jnp.stack(scale).reshape(-1, 1).astype(jnp.float32),
        col_mask=jnp.stack(mask),
        widths=tuple(widths),
        weight_bits=weight_bits,
        cell_bits=cell_bits,
    )
    if ecc is not None and ecc is not False:
        # Deferred import: reliability sits above kernels in the layering.
        from repro.reliability.ecc import protect_program
        program = protect_program(program, ecc)
    return program


# ---------------------------------------------------------------------------
# VMEM-cost accounting for the fused kernel (DESIGN.md §3.3)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FusedPlan:
    """Static launch geometry for ``reram_mlp_fused`` plus its per-grid-step
    VMEM residency under the double-buffered pipelining model. ``mode`` is
    one of :data:`FUSED_MODES`; ``whole_bytes`` records what the whole-layer
    variant would have cost, so the selection is auditable. ``fits_budget``
    is False only when even the M-tiled dataflow at the smallest tile edge
    cannot fit."""

    d_pad: int
    m_pad: int
    block_m: int
    block_n: int
    block_k: int
    vmem_bytes: int
    whole_bytes: int
    budget: int = VMEM_BUDGET_BYTES
    mode: str = "whole"
    n_planes: int = 4

    @property
    def tiled(self) -> bool:
        """True when the N dimension is split (``block_n < d_pad``)."""
        return self.block_n < self.d_pad

    @property
    def fits_budget(self) -> bool:
        return self.vmem_bytes <= self.budget

    @property
    def n_steps(self) -> int:
        return self.d_pad // self.block_n

    @property
    def m_steps(self) -> int:
        return self.m_pad // self.block_m

    @property
    def plane_tile_fetches_per_layer(self) -> int:
        """How many ``(P, d_pad, block_n)`` plane tiles cross HBM→VMEM per
        layer per batch element. The weight-stationarity metric: with the
        N-tile innermost ('tiled'/'mtiled', ``n_steps > 1``) the plane-tile
        block index changes every grid step, so tiles re-stream once per
        M-stripe; 'wstat' iterates N-tiles outermost and 'whole' has a
        single resident tile, so each plane byte crosses exactly once."""
        if self.mode == "wstat":
            return self.n_steps
        if self.mode == "whole" or self.n_steps == 1:
            return 1
        return self.m_steps * self.n_steps

    @property
    def plane_hbm_bytes_per_layer(self) -> int:
        """Plane bytes crossing HBM→VMEM per layer per batch element
        (``fetches × tile bytes``; equals one full layer for the
        weight-stationary modes)."""
        return (self.plane_tile_fetches_per_layer
                * self.n_planes * self.d_pad * self.block_n)

    @property
    def act_hbm_bytes_per_layer(self) -> int:
        """Activation-panel bytes crossing HBM per layer per batch element:
        zero for the VMEM-panel modes; 'mtiled' reads and writes each f32
        stripe once per layer (layer 0 skips the read — it consumes the
        pre-quantized input block instead — so this slightly overcounts
        the first layer)."""
        return 8 * self.m_pad * self.d_pad if self.mode == "mtiled" else 0


def fused_vmem_bytes(d_pad: int, n_planes: int, m_pad: int,
                     block_m: int, block_n: int,
                     mode: str = "tiled") -> int:
    """Per-grid-step VMEM residency of the fused kernel at tile edge
    ``block_n`` under dataflow ``mode`` (:data:`FUSED_MODES`). Pipelined
    operand/result blocks are double-buffered (×2, the TPU
    prefetch-while-compute discipline); scratch buffers are persistent
    single instances. ``block_k`` does not appear: the K-loop runs over the
    already-resident ``(P, d_pad, block_n)`` plane tile and only bounds the
    MXU op footprint, not residency. 'whole' and 'tiled' share one formula
    (whole is the ``block_n = d_pad`` special case); 'wstat' swaps the
    one-stripe snapshot for a full int8 panel; 'mtiled' is the only mode
    with no ``m_pad`` term — its activation panel lives in HBM and a single
    DMA-staged stripe is resident."""
    if mode not in FUSED_MODES:
        raise ValueError(f"mode={mode!r} must be one of {FUSED_MODES}")
    if mode == "mtiled":
        blocks = (
            n_planes * d_pad * block_n  # int8 plane tile
            + block_m * d_pad           # int8 input stripe (layer 0)
            + 2 * 4 * block_n           # f32 bias + col-mask tiles
        )                               # (output is HBM-resident, no block)
        scratch = (
            4 * block_m * d_pad         # f32 DMA-staged activation stripe
            + block_m * d_pad           # int8 requantized-stripe snapshot
            + 4 * block_m               # int32 stripe row sums
        )
        return 2 * blocks + scratch
    blocks = (
        n_planes * d_pad * block_n      # int8 plane tile
        + block_m * d_pad               # int8 input stripe (layer 0)
        + 4 * block_m * block_n         # f32 output tile
        + 2 * 4 * block_n               # f32 bias + col-mask tiles
    )
    if mode == "wstat":
        scratch = (
            4 * m_pad * d_pad           # f32 inter-layer activation panel
            + m_pad * d_pad             # int8 input-snapshot panel
            + 4 * m_pad                 # int32 panel row sums
        )
    else:                               # whole / tiled
        scratch = (
            4 * m_pad * d_pad           # f32 inter-layer activation panel
            + block_m * d_pad           # int8 requantized-stripe snapshot
            + 4 * block_m               # int32 stripe row sums
        )
    return 2 * blocks + scratch


def _largest_fitting_edge(d, edges, bytes_at, vmem_budget):
    """Largest tile edge among ``edges`` that divides ``d_pad`` and fits."""
    for cand in edges:
        if d % cand == 0 and bytes_at(cand) <= vmem_budget:
            return cand
    return None


def _edge_candidates(mode: str, d: int) -> range:
    """Tile edges a mode may take, largest first. 'whole' is defined as the
    single-N-tile dataflow; 'wstat'/'tiled' only make sense split; 'mtiled'
    may keep the full edge (single N-tile: planes stay resident across
    stripes). Shared by pinned-mode and auto selection so both pick the
    same edge for a given mode."""
    if mode == "whole":
        return range(d, d + 1)
    if mode == "mtiled":
        return range(d, 0, -CROSSBAR)
    return range(d - CROSSBAR, 0, -CROSSBAR)


def plan_fused_mlp(program: "CrossbarProgram", m_rows: int, *,
                   mode: str | None = None,
                   block_m: int = CROSSBAR, block_n: int | None = None,
                   block_k: int | None = None,
                   vmem_budget: int | None = None,
                   policy=None) -> FusedPlan:
    """Pick the fused-kernel launch geometry for ``m_rows`` activation rows.

    With everything unpinned the selector walks :data:`FUSED_MODES` in
    preference order and takes the first dataflow with a fitting tile edge:

    1. ``whole``  — fully weight-stationary, zero inter-layer HBM traffic;
    2. ``wstat``  — weight-stationary (planes cross HBM once per layer),
       activations still on-chip, costs an int8 snapshot panel;
    3. ``tiled``  — activations on-chip but plane tiles re-stream once per
       M-stripe (only reachable in the narrow band where the snapshot
       panel pushes 'wstat' over budget);
    4. ``mtiled`` — the activation panel spills to HBM and residency stops
       growing with M: the panel-bound last resort (model2 SA-1 at 8192
       rows), and the fallback recorded with ``fits_budget=False`` when
       nothing fits.

    ``policy`` (a :class:`repro.core.policy.PlanPolicy`, duck-typed via
    its ``fused_cost``/``vmem_budget`` members) replaces the VMEM-fit-only
    preference walk with a roofline choice: among every dataflow that fits
    the budget (each at its own best tile edge), take the one with the
    lowest predicted cost — ``max`` of MXU-bound cycles and predicted HBM
    bytes over bandwidth, i.e. the mode is picked on predicted
    bytes-per-cycle, not just fit. When no explicit ``vmem_budget`` is
    given the policy's own budget applies. Cost ties keep the preference
    order above, so a compute-bound shape resolves exactly as before.

    Pass ``mode=`` to pin the dataflow (its largest fitting edge is still
    auto-picked), and ``block_n``/``block_k`` to pin tile edges explicitly
    (still validated against the crossbar geometry). For backward
    compatibility an explicit ``block_n`` without ``mode`` selects the
    act-panel-in-VMEM dataflow ('whole' when ``block_n == d_pad``, else
    'tiled'). Pure static arithmetic — safe to call at trace time."""
    d = program.d_pad
    p = program.n_planes
    if vmem_budget is None:
        vmem_budget = (getattr(policy, "vmem_budget", None)
                       if policy is not None else None) or VMEM_BUDGET_BYTES
    if block_m % 8 != 0 or block_m <= 0:
        raise ValueError(f"block_m={block_m} must be a positive multiple "
                         f"of 8 (f32 sublane tiling)")
    if mode is not None and mode not in FUSED_MODES:
        raise ValueError(f"mode={mode!r} must be one of {FUSED_MODES}")
    m_pad = -(-max(m_rows, 1) // block_m) * block_m

    def bytes_at(md, bn):
        return fused_vmem_bytes(d, p, m_pad, block_m, bn, mode=md)

    whole = bytes_at("whole", d)
    if block_k is None:
        bk = min(d, 4 * CROSSBAR)
    else:
        bk = block_k
        if bk <= 0 or bk % CROSSBAR != 0 or d % bk != 0:
            raise ValueError(f"block_k={bk} must be a multiple of "
                             f"{CROSSBAR} dividing d_pad={d}")

    def plan_at(md, bn):
        return FusedPlan(
            d_pad=d, m_pad=m_pad, block_m=block_m, block_n=bn, block_k=bk,
            vmem_bytes=bytes_at(md, bn), whole_bytes=whole,
            budget=vmem_budget, mode=md, n_planes=p)

    if block_n is not None:
        bn = block_n
        if bn <= 0 or bn % CROSSBAR != 0 or d % bn != 0:
            raise ValueError(f"block_n={bn} must be a multiple of "
                             f"{CROSSBAR} dividing d_pad={d}")
        if mode is None:
            mode = "whole" if bn == d else "tiled"
        elif mode == "whole" and bn != d:
            raise ValueError(f"mode='whole' is the single-N-tile dataflow; "
                             f"block_n={bn} != d_pad={d}")
    elif mode is not None:
        if mode == "whole":
            bn = d
        else:
            bn = _largest_fitting_edge(d, _edge_candidates(mode, d),
                                       lambda c: bytes_at(mode, c),
                                       vmem_budget) or CROSSBAR
    else:
        # auto: each mode's largest fitting tile edge is a candidate; a
        # policy ranks the candidates on predicted roofline cycles, the
        # default takes the first in preference order. The smallest
        # M-tiled footprint is the nothing-fits fallback
        # (fits_budget=False).
        fitting: list[tuple[str, int]] = []
        for cand_mode in ("whole", "wstat", "tiled", "mtiled"):
            found = _largest_fitting_edge(
                d, _edge_candidates(cand_mode, d),
                lambda c: bytes_at(cand_mode, c), vmem_budget)
            if found is not None:
                fitting.append((cand_mode, found))
        if not fitting:
            mode, bn = "mtiled", CROSSBAR
        elif policy is None:
            mode, bn = fitting[0]
        else:
            mode, bn = min(
                enumerate(fitting),
                key=lambda t: (policy.fused_cost(plan_at(*t[1])), t[0]))[1]
    return plan_at(mode, bn)
