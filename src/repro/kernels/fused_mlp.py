"""Pallas TPU kernel: fused multi-layer weight-stationary MLP (DESIGN.md §3.3).

The paper's inter-layer coordination keeps intermediate results on-chip
instead of round-tripping to DRAM. Applied *inside* feature computation,
the TPU twin is: run an entire SA-layer MLP (matmul -> bias+ReLU ->
matmul -> bias+ReLU -> matmul) in ONE ``pallas_call``, with inter-layer
activations living on-chip — 1 kernel launch instead of 3.

Four dataflows share one integer pipeline (``FUSED_MODES`` in
program.py; ``plan_fused_mlp`` auto-selects under the 16 MB VMEM budget):

- ``whole``/``tiled`` — grid ``(B, L, M/bm, N/bn)``, batch outermost,
  N-tile innermost. The inter-layer activation panel ``(M_pad, d)`` is a
  VMEM scratch; only a ``(P, d, bn)`` plane tile is staged per grid step
  and an in-kernel K-loop bounds each MXU op to ``(bm, bk) @ (bk, bn)``.
  ``whole`` is the single-N-tile special case (``bn = d``): the plane
  block index is constant within a layer, so the planes stay VMEM-
  resident across stripes — fully weight-stationary. With ``bn < d``
  ('tiled') the plane block index changes every step and tiles re-stream
  from HBM once per M-stripe.
- ``mtiled`` — same grid order, but the activation panel lives in HBM:
  the kernel's own *output buffer* doubles as the panel (ANY memory
  space) and one ``(bm, d)`` f32 stripe is staged in VMEM by explicit
  ``make_async_copy`` DMA — fetched at each stripe's first N-tile,
  flushed at its last. Per-step residency stops growing with M, so
  panel-bound programs (model2 SA-1 at its real 8192 rows) run fused;
  the price is one f32 stripe read + write through HBM per layer.
- ``wstat`` — grid ``(B, L, N/bn, M/bm)``: N-tile *outermost*, so each
  plane tile crosses HBM once per layer (true weight re-streaming
  stationarity) no matter how many stripes pass through it. Layer
  inputs come from a full ``(M_pad, d)`` *int8* snapshot panel written
  at each stripe's first visit (quantized values fit int8), which is
  what makes the j-outer order exact: N-tile ``j`` must not re-read
  activation columns tile ``j-1`` already overwrote.

Three orderings make every tiling exact:

- *Input snapshot*: layer ``l`` both reads stripe ``i`` of the
  activation panel (as its input) and writes it (as its output). At each
  stripe's first N-tile the requantized input is snapshotted (an int8
  stripe for the i-outer modes, the int8 panel for 'wstat') so later
  N-tiles never see half-overwritten rows.
- *Scale finalization*: the running max over layer ``l``'s masked
  outputs (SMEM scratch) accumulates over every tile and finalizes into
  the *global per-tensor* activation scale at layer ``l+1``'s first tile
  — max is order-free, so the scale equals the whole-layer and
  sequential ``reram_linear`` values bitwise in every mode.
- *f32 round-trip* ('mtiled'): activations cross HBM as f32 stripes —
  stored and re-read exactly — so spilling the panel does not perturb a
  single bit vs the VMEM-panel modes.

The batch dimension lives in the grid, not in an outer vmap:
``reram_mlp_fused_batched`` quantizes each batch element separately
(per-element input scale, per-element SMEM running max — reset at each
element's first tile) so one ``pallas_call`` reproduces the vmapped
semantics exactly. ``reram_mlp_fused`` is the B=1 special case that
flattens all leading axes into rows under one shared scale.

Numerics contract (asserted in ``tests/test_fused_mlp.py``): the integer
crossbar pipeline — quantize, plane shift-and-add, offset-binary
correction, requantize — is *exact* and invariant to the M/N/K tiling
and to the loop order (int32 accumulation is associative, max is
order-free). With zero biases every mode matches the correctly-rounded
NumPy oracle of the quantized chain BITWISE on arbitrary float inputs at
any tile edge; with biases the dequant multiply-add may be
FMA-contracted by XLA, so fused vs the separately-compiled per-layer
path agree to ~1 ulp — at most 1 quant LSB after requantization, and
zero integer drift. All four modes are bitwise-identical to each other.

All layers are padded to the program's uniform ``d_pad`` edge. Padded
*columns* of the planes encode cell value 0 (which decodes to weight
-2^(b-1)), so their outputs are garbage — ``col_mask`` is sliced at tile
granularity ``(l, j)`` and zeroes them per N-tile (ragged real widths
land mid-tile) before the max and before feeding the next layer,
mirroring the per-layer path's slice to real shape. Padded *rows* (M)
are likewise zero-masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import interpret_mode
from .program import (CROSSBAR, VMEM_BUDGET_BYTES, CrossbarProgram,
                      _ceil_to, plan_fused_mlp, quantize_tensor)
from .ref import combine_planes

__all__ = ["reram_mlp_fused", "reram_mlp_fused_batched", "requantize",
           "chain_quantizer"]

DEFAULT_BLOCK_M = 128   # activation stripe height (crossbar geometry)

# Mosaic's scoped-VMEM limit for the fused launches. ``plan_fused_mlp``
# budgets the modelled buffers at VMEM_BUDGET_BYTES; Mosaic's own matmul
# temporaries and the lane padding of the ``(rows, 1)`` row-sum scratch
# come on top (model2 SA-1 'wstat' needs 16.3 MiB in all on a v5e, whose
# VMEM is 128 MiB), so the limit leaves the same amount again as headroom.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=2 * VMEM_BUDGET_BYTES)


def _plane_matmul(x_int, planes_ref, row_sums, *, n_planes: int,
                  cell_bits: int, weight_bits: int, block_k: int):
    """Bit-sliced crossbar matmul on one ``(bm, d) @ (d, bn)`` tile:
    shift-and-add over the 2-bit cell planes with a K-loop bounding each
    MXU op to ``(bm, bk) @ (bk, bn)``, then the offset-binary correction
    from the pre-reduced input row sums. Both operands are int8 (the
    requantized activations fit it, the cells are 0..3): int8 x int8 ->
    int32 is the MXU's integer mode, and Mosaic has no int32 matmul."""
    bm, d = x_int.shape
    bn = planes_ref.shape[-1]
    acc = jnp.zeros((bm, bn), jnp.int32)
    for p in range(n_planes):
        part = jnp.zeros((bm, bn), jnp.int32)
        for k0 in range(0, d, block_k):
            part = part + jax.lax.dot_general(
                x_int[:, k0:k0 + block_k],
                planes_ref[0, p, k0:k0 + block_k, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        acc = acc + (part << (cell_bits * p))
    return acc - (row_sums << (weight_bits - 1))


def _dequant_tile(y_int, s, sw_ref, bias_ref, mask_ref, l, i, *,
                  n_layers: int, block_m: int, m_real: int,
                  final_relu: bool):
    """Dequantize + bias + ReLU (the inter-layer stage that used to
    round-trip through HBM), then zero the padded rows/columns exactly as
    the sequential path's slice-to-real-shape does — col_mask at tile
    granularity handles real widths that end mid-tile."""
    y = y_int.astype(jnp.float32) * (s * sw_ref[l]) + bias_ref[...]
    do_relu = jnp.logical_or(l < n_layers - 1, final_relu)
    y = jnp.where(do_relu, jnp.maximum(y, 0.0), y)
    y = y * mask_ref[...]
    row_ids = i * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, 1), 0)
    return jnp.where(row_ids < m_real, y, 0.0)


def _layer_scale(mx, qmax: float):
    """A layer's input scale from max|prev layer output| (the
    ``quantize_tensor`` formula, run inside the kernel)."""
    return jnp.maximum(mx / qmax, 1e-12)


def _requant(act, s, qmax: float):
    return jnp.clip(jnp.round(act / s), -qmax, qmax).astype(jnp.int32)


def _finalize_layer_scale(s_ref, mx_ref, sx0_ref, b, l, qmax: float):
    """At each (batch element, layer)'s first tile: finalize this layer's
    global input scale — the element's external quant scale for layer 0,
    else max|prev layer output| / qmax (``quantize_tensor`` semantics) —
    and zero the running max that accumulates the NEXT layer's scale."""
    s_ref[0] = jnp.where(l == 0, sx0_ref[b], _layer_scale(mx_ref[0], qmax))
    mx_ref[0] = jnp.float32(0)


def _requant_stripe(act_stripe, x0_ref, s, l, qmax: float):
    """Requantize one f32 activation stripe ONCE per (layer, stripe):
    later N-tiles must not re-read rows whose low columns the first
    N-tile already overwrote with this layer's outputs. Layer 0 takes
    the pre-quantized ints instead."""
    return jnp.where(l == 0, x0_ref[0].astype(jnp.int32),
                     _requant(act_stripe, s, qmax))


# ---------------------------------------------------------------------------
# whole / tiled: VMEM activation panel, grid (B, L, M/bm, N/bn)
# ---------------------------------------------------------------------------

def _kernel(x0_ref, planes_ref, bias_ref, sw_ref, sx0_ref, mask_ref,
            o_ref, act_ref, xq_ref, xs_ref, s_ref, mx_ref, *,
            n_layers: int, n_planes: int, cell_bits: int, weight_bits: int,
            block_m: int, block_k: int, m_real: int, final_relu: bool):
    b = pl.program_id(0)
    l = pl.program_id(1)            # layer (sequential, after batch)
    i = pl.program_id(2)            # activation stripe
    j = pl.program_id(3)            # output N-tile (innermost)
    qmax = float(2 ** (weight_bits - 1) - 1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _start_layer():
        _finalize_layer_scale(s_ref, mx_ref, sx0_ref, b, l, qmax)

    s = s_ref[0]
    rows = pl.ds(pl.multiple_of(i * block_m, block_m), block_m)

    @pl.when(j == 0)
    def _snapshot_input():
        # the offset-correction row sums only depend on (l, i) too, so they
        # are reduced here once instead of per N-tile
        x_new = _requant_stripe(act_ref[rows, :], x0_ref, s, l, qmax)
        xq_ref[...] = x_new.astype(jnp.int8)
        xs_ref[...] = jnp.sum(x_new, axis=1, keepdims=True)

    x_int = xq_ref[...]
    bn = planes_ref.shape[-1]
    y_int = _plane_matmul(x_int, planes_ref, xs_ref[...],
                          n_planes=n_planes, cell_bits=cell_bits,
                          weight_bits=weight_bits, block_k=block_k)
    y = _dequant_tile(y_int, s, sw_ref, bias_ref, mask_ref, l, i,
                      n_layers=n_layers, block_m=block_m, m_real=m_real,
                      final_relu=final_relu)

    mx_ref[0] = jnp.maximum(mx_ref[0], jnp.max(jnp.abs(y)))
    act_ref[rows, pl.ds(j * bn, bn)] = y        # stays in VMEM for layer l+1

    @pl.when(l == n_layers - 1)                 # only the last layer's
    def _store():                               # tiles reach the output
        o_ref[0] = y


# ---------------------------------------------------------------------------
# mtiled: HBM activation panel (the output buffer), stripe staged by DMA,
# grid (B, L, M/bm, N/bn)
# ---------------------------------------------------------------------------

def _kernel_mtiled(x0_ref, planes_ref, bias_ref, sw_ref, sx0_ref, mask_ref,
                   o_ref, stripe_ref, xq_ref, xs_ref, s_ref, mx_ref, sem_ref,
                   *, n_layers: int, n_planes: int, cell_bits: int,
                   weight_bits: int, block_m: int, block_k: int, m_real: int,
                   final_relu: bool):
    b = pl.program_id(0)
    l = pl.program_id(1)
    i = pl.program_id(2)            # activation stripe
    j = pl.program_id(3)            # output N-tile (innermost)
    n_steps = pl.num_programs(3)
    qmax = float(2 ** (weight_bits - 1) - 1)
    rows = pl.ds(pl.multiple_of(i * block_m, block_m), block_m)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _start_layer():
        _finalize_layer_scale(s_ref, mx_ref, sx0_ref, b, l, qmax)

    s = s_ref[0]

    @pl.when(j == 0)
    def _stage_stripe():
        # DMA this stripe of the HBM activation panel into VMEM (the output
        # buffer IS the panel) and requantize it once per (l, i). Layer 0
        # reads the pre-quantized x0 block instead, so its panel fetch is
        # skipped — no wasted HBM traffic before the panel holds anything.
        @pl.when(l > 0)
        def _fetch():
            cin = pltpu.make_async_copy(o_ref.at[b, rows, :], stripe_ref,
                                        sem_ref)
            cin.start()
            cin.wait()
        x_new = _requant_stripe(stripe_ref[...], x0_ref, s, l, qmax)
        xq_ref[...] = x_new.astype(jnp.int8)
        xs_ref[...] = jnp.sum(x_new, axis=1, keepdims=True)

    x_int = xq_ref[...]
    bn = planes_ref.shape[-1]
    y_int = _plane_matmul(x_int, planes_ref, xs_ref[...],
                          n_planes=n_planes, cell_bits=cell_bits,
                          weight_bits=weight_bits, block_k=block_k)
    y = _dequant_tile(y_int, s, sw_ref, bias_ref, mask_ref, l, i,
                      n_layers=n_layers, block_m=block_m, m_real=m_real,
                      final_relu=final_relu)

    mx_ref[0] = jnp.maximum(mx_ref[0], jnp.max(jnp.abs(y)))
    # the int8 snapshot already decoupled reads from writes, so the f32
    # stripe buffer is dead after _stage_stripe and collects the outputs
    stripe_ref[:, pl.ds(j * bn, bn)] = y

    @pl.when(j == n_steps - 1)
    def _flush_stripe():                        # stripe complete: DMA back
        cout = pltpu.make_async_copy(stripe_ref, o_ref.at[b, rows, :],
                                     sem_ref)
        cout.start()
        cout.wait()


# ---------------------------------------------------------------------------
# wstat: j-outer weight re-streaming over an int8 snapshot panel,
# grid (B, L, N/bn, M/bm)
# ---------------------------------------------------------------------------

def _kernel_wstat(x0_ref, planes_ref, bias_ref, sw_ref, sx0_ref, mask_ref,
                  o_ref, act_ref, xq_ref, xs_ref, s_ref, mx_ref, *,
                  n_layers: int, n_planes: int, cell_bits: int,
                  weight_bits: int, block_m: int, block_k: int, m_real: int,
                  final_relu: bool):
    b = pl.program_id(0)
    l = pl.program_id(1)
    j = pl.program_id(2)            # output N-tile (OUTERMOST of the sweep)
    i = pl.program_id(3)            # activation stripe (innermost)
    qmax = float(2 ** (weight_bits - 1) - 1)
    rows = pl.ds(pl.multiple_of(i * block_m, block_m), block_m)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _start_layer():
        _finalize_layer_scale(s_ref, mx_ref, sx0_ref, b, l, qmax)

    s = s_ref[0]

    @pl.when(j == 0)
    def _snapshot_stripe():
        # first N-tile of the layer snapshots every stripe it visits into
        # the int8 panel; later N-tiles (different plane tile, same rows)
        # read the panel, never the half-overwritten activations
        x_new = _requant_stripe(act_ref[rows, :], x0_ref, s, l, qmax)
        xq_ref[rows, :] = x_new.astype(jnp.int8)
        xs_ref[rows, :] = jnp.sum(x_new, axis=1, keepdims=True)

    x_int = xq_ref[rows, :]
    bn = planes_ref.shape[-1]
    y_int = _plane_matmul(x_int, planes_ref, xs_ref[rows, :],
                          n_planes=n_planes, cell_bits=cell_bits,
                          weight_bits=weight_bits, block_k=block_k)
    y = _dequant_tile(y_int, s, sw_ref, bias_ref, mask_ref, l, i,
                      n_layers=n_layers, block_m=block_m, m_real=m_real,
                      final_relu=final_relu)

    mx_ref[0] = jnp.maximum(mx_ref[0], jnp.max(jnp.abs(y)))
    act_ref[rows, pl.ds(j * bn, bn)] = y

    @pl.when(l == n_layers - 1)
    def _store():
        o_ref[0] = y


# ---------------------------------------------------------------------------
# launch
# ---------------------------------------------------------------------------

def _launch(x_p, sx, program: CrossbarProgram, *, mode: str, m_real: int,
            final_relu: bool, block_m: int, block_n: int, block_k: int):
    """One ``pallas_call`` over pre-quantized ``(B, m_pad, d)`` int8 rows
    with per-batch-element scales ``sx`` of shape ``(B, 1)``, under the
    ``mode`` dataflow (see module docstring). The per-layer bias and
    col-mask rows ride a unit axis, ``(L, 1, d)`` with ``(1, block_n)``
    blocks: Mosaic accepts a block dim that is not a multiple of the
    8-sublane tile only when it spans the whole array dim."""
    b, m_pad, d = x_p.shape
    m_steps = m_pad // block_m
    n_steps = d // block_n
    n_layers, n_planes = program.n_layers, program.n_planes

    common = dict(n_layers=n_layers, n_planes=n_planes,
                  cell_bits=program.cell_bits,
                  weight_bits=program.weight_bits,
                  block_m=block_m, block_k=block_k, m_real=m_real,
                  final_relu=final_relu)
    operands = (x_p, program.planes, program.bias[:, None, :],
                program.w_scale.reshape(-1), sx.reshape(-1),
                program.col_mask[:, None, :])
    out_shape = jax.ShapeDtypeStruct((b, m_pad, d), jnp.float32)

    if mode == "wstat":
        return pl.pallas_call(
            functools.partial(_kernel_wstat, **common),
            name="reram_mlp_fused_wstat",
            grid=(b, n_layers, n_steps, m_steps),
            in_specs=[
                pl.BlockSpec((1, block_m, d),
                             lambda bb, l, j, i: (bb, i, 0)),
                pl.BlockSpec((1, n_planes, d, block_n),
                             lambda bb, l, j, i: (l, 0, 0, j)),
                pl.BlockSpec((None, 1, block_n),
                             lambda bb, l, j, i: (l, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, block_n),
                             lambda bb, l, j, i: (l, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda bb, l, j, i: (bb, i, j)),
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((m_pad, d), jnp.float32),  # activation panel
                pltpu.VMEM((m_pad, d), jnp.int8),     # input-snapshot panel
                pltpu.VMEM((m_pad, 1), jnp.int32),    # panel row sums
                pltpu.SMEM((1,), jnp.float32),        # current layer scale
                pltpu.SMEM((1,), jnp.float32),        # running max|output|
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret_mode(),
        )(*operands)

    if mode == "mtiled":
        return pl.pallas_call(
            functools.partial(_kernel_mtiled, **common),
            name="reram_mlp_fused_mtiled",
            grid=(b, n_layers, m_steps, n_steps),
            in_specs=[
                pl.BlockSpec((1, block_m, d),
                             lambda bb, l, i, j: (bb, i, 0)),
                pl.BlockSpec((1, n_planes, d, block_n),
                             lambda bb, l, i, j: (l, 0, 0, j)),
                pl.BlockSpec((None, 1, block_n),
                             lambda bb, l, i, j: (l, 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, block_n),
                             lambda bb, l, i, j: (l, 0, j)),
            ],
            # the output stays in HBM and doubles as the activation panel;
            # the kernel DMAs stripes in/out itself
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_m, d), jnp.float32),  # DMA-staged stripe
                pltpu.VMEM((block_m, d), jnp.int8),     # stripe snapshot
                pltpu.VMEM((block_m, 1), jnp.int32),    # stripe row sums
                pltpu.SMEM((1,), jnp.float32),          # current layer scale
                pltpu.SMEM((1,), jnp.float32),          # running max|output|
                pltpu.SemaphoreType.DMA,                # stripe DMA sem
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret_mode(),
        )(*operands)

    return pl.pallas_call(
        functools.partial(_kernel, **common),
        name="reram_mlp_fused_" + mode,
        grid=(b, n_layers, m_steps, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_m, d), lambda bb, l, i, j: (bb, i, 0)),
            pl.BlockSpec((1, n_planes, d, block_n),
                         lambda bb, l, i, j: (l, 0, 0, j)),
            pl.BlockSpec((None, 1, block_n),
                         lambda bb, l, i, j: (l, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_n),
                         lambda bb, l, i, j: (l, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda bb, l, i, j: (bb, i, j)),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((m_pad, d), jnp.float32),   # inter-layer activations
            pltpu.VMEM((block_m, d), jnp.int8),    # input-stripe snapshot
            pltpu.VMEM((block_m, 1), jnp.int32),   # stripe row sums (offset)
            pltpu.SMEM((1,), jnp.float32),         # current layer act scale
            pltpu.SMEM((1,), jnp.float32),         # running max|output|
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
    )(*operands)


def _check_bits(program: CrossbarProgram):
    if program.weight_bits > 8:
        raise ValueError(
            f"reram_mlp_fused streams int8 activations (the 128x128 INT8 "
            f"crossbar geometry); weight_bits={program.weight_bits} > 8 "
            f"would overflow them")


@functools.partial(jax.jit, static_argnames=("final_relu", "mode", "block_m",
                                             "block_n", "block_k"))
def reram_mlp_fused(x: jnp.ndarray, program: CrossbarProgram, *,
                    final_relu: bool = True,
                    mode: str | None = None,
                    block_m: int = DEFAULT_BLOCK_M,
                    block_n: int | None = None,
                    block_k: int | None = None) -> jnp.ndarray:
    """Float ``(…, d0)`` through the whole programmed MLP -> ``(…, dL)``,
    in a single ``pallas_call``. Same quantization scales and exact same
    integer arithmetic as chaining ``reram_linear`` + bias + ReLU per layer
    (float dequant agrees to FMA-contraction ulps — see module docstring),
    with zero weight encoding in the hot path. ``mode`` picks the dataflow
    ('whole' / 'tiled' / 'mtiled' / 'wstat'); it and ``block_n``/``block_k``
    default to ``plan_fused_mlp``'s VMEM-budget auto-selection."""
    _check_bits(program)
    widths = program.widths
    d = program.d_pad
    lead = x.shape[:-1]
    x2 = x.reshape(-1, widths[0])
    m0 = x2.shape[0]
    x_int, sx = quantize_tensor(x2, bits=program.weight_bits)

    plan = plan_fused_mlp(program, m0, mode=mode, block_m=block_m,
                          block_n=block_n, block_k=block_k)
    x_p = jnp.zeros((1, plan.m_pad, d), jnp.int8).at[0, :m0, :widths[0]].set(
        x_int.astype(jnp.int8))
    out = _launch(x_p, sx.reshape(1, 1).astype(jnp.float32), program,
                  mode=plan.mode, m_real=m0, final_relu=final_relu,
                  block_m=plan.block_m, block_n=plan.block_n,
                  block_k=plan.block_k)
    return out[0, :m0, :widths[-1]].reshape(*lead, widths[-1])


@functools.partial(jax.jit, static_argnames=("final_relu", "mode", "block_m",
                                             "block_n", "block_k"))
def reram_mlp_fused_batched(x: jnp.ndarray, program: CrossbarProgram, *,
                            final_relu: bool = True,
                            mode: str | None = None,
                            block_m: int = DEFAULT_BLOCK_M,
                            block_n: int | None = None,
                            block_k: int | None = None) -> jnp.ndarray:
    """Float ``(B, …, d0)`` -> ``(B, …, dL)`` with the batch folded into
    the kernel grid: ONE ``pallas_call`` for the whole batch, no outer
    vmap. Each batch element keeps its own input quantization scale and
    its own inter-layer running-max scales (reset at its first grid
    step), so the result matches ``vmap(reram_mlp_fused)`` — bitwise on
    the integer pipeline, ~1 ulp on the float dequant. Accepts the same
    ``mode``/tile overrides as :func:`reram_mlp_fused`."""
    _check_bits(program)
    widths = program.widths
    d = program.d_pad
    batch = x.shape[0]
    lead = x.shape[1:-1]
    x2 = x.reshape(batch, -1, widths[0])
    m0 = x2.shape[1]
    x_int, sx = jax.vmap(
        lambda xb: quantize_tensor(xb, bits=program.weight_bits))(x2)

    plan = plan_fused_mlp(program, m0, mode=mode, block_m=block_m,
                          block_n=block_n, block_k=block_k)
    x_p = jnp.zeros((batch, plan.m_pad, d), jnp.int8
                    ).at[:, :m0, :widths[0]].set(x_int.astype(jnp.int8))
    out = _launch(x_p, sx.reshape(batch, 1).astype(jnp.float32), program,
                  mode=plan.mode, m_real=m0, final_relu=final_relu,
                  block_m=plan.block_m, block_n=plan.block_n,
                  block_k=plan.block_k)
    return out[:, :m0, :widths[-1]].reshape(batch, *lead, widths[-1])


# ---------------------------------------------------------------------------
# the inter-layer requantizer alone
# ---------------------------------------------------------------------------

def _requant_kernel(mx_ref, y_ref, q_ref, s_ref, *, qmax: float):
    s_ref[0] = _layer_scale(mx_ref[0], qmax)
    q_ref[...] = _requant(y_ref[...], s_ref[0], qmax)


@jax.jit
def requantize(y: jnp.ndarray):
    """The fused kernel's inter-layer requantizer as a ``pallas_call`` of
    its own: float ``(m, n)`` -> ``(int32 values, scale)`` through the same
    scale and rounding ops the kernel applies between two layers (INT8).
    Where f32 division is correctly rounded this is ``quantize_tensor``
    bitwise; where it is not (a TPU), this gives what the fused kernel
    computes there, so an oracle can take the device's own quotients."""
    m, n = y.shape
    block_m = min(DEFAULT_BLOCK_M, _ceil_to(m, 8))
    m_pad = _ceil_to(m, block_m)
    n_pad = _ceil_to(n, CROSSBAR)
    y_p = jnp.zeros((m_pad, n_pad), jnp.float32).at[:m, :n].set(y)
    mx = jnp.max(jnp.abs(y)).reshape(1).astype(jnp.float32)
    q, s = pl.pallas_call(
        functools.partial(_requant_kernel, qmax=127.0),
        name="requantize",
        grid=(m_pad // block_m,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_m, n_pad), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_m, n_pad), lambda i: (i, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((m_pad, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.float32)],
        interpret=interpret_mode(),
    )(mx, y_p)
    return q[:m, :n], s[0]


def chain_quantizer(program: CrossbarProgram):
    """A ``quantize`` hook for :func:`repro.kernels.ref.quant_chain_np`
    that takes the program's arithmetic from the current device: the
    programmed integer weights and scales, ``quantize_tensor`` on the
    first layer's input (as the fused wrappers run it) and
    :func:`requantize` between layers. On a device whose f32 division is
    not correctly rounded, the oracle then differs from the fused kernel
    only where the rest of the kernel's arithmetic does."""
    widths = program.widths
    weights = [
        (np.asarray(combine_planes(program.planes[l], program.cell_bits,
                                   program.weight_bits))[:k, :n],
         np.float32(program.w_scale[l, 0]))
        for l, (k, n) in enumerate(zip(widths[:-1], widths[1:]))]
    first = jax.jit(functools.partial(quantize_tensor,
                                      bits=program.weight_bits))

    def quantize(v, i, operand):
        if operand == "w":
            return weights[i]
        q, s = (first if i == 0 else requantize)(jnp.asarray(v))
        return np.asarray(q), np.float32(s)

    return quantize
