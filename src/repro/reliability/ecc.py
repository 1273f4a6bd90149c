"""ECC-protected crossbar planes: Hamming parity in spare columns.

The plane tensors of a :class:`~repro.kernels.CrossbarProgram` are padded
to a uniform ``d_pad`` edge, so most layers already own *spare columns*
— columns beyond the layer's real width whose MVM outputs ``col_mask``
zeroes anyway. ECC puts them to work: at program time
(:func:`protect_program`, reachable as ``build_program(..., ecc=...)``)
each row of each cell plane is split into codewords of ``group`` data
cells and a Hamming parity symbol is stored in the spare columns; at
read-out (:func:`correct_program`, the digital scrub in front of the
shift-add recombination) syndromes are decoded and single-cell errors
flipped back. Layers whose spare region is too small get the whole
program re-padded one crossbar edge wider — the area price the overhead
report (:func:`ecc_overhead`) charges for.

Code construction — SEC Hamming, per *bit lane*:

  A cell stores ``cell_bits`` bits, and a stuck-at fault corrupts all of
  them at once, so a plain binary Hamming code over the cell bits would
  face a 2-bit error. Instead each codeword is protected lane-wise: lane
  ``b`` collects bit ``b`` of every data cell in the group, and the
  parity *cells* pack one parity bit per lane (parity cell ``j`` holds
  ``sum_b parity[b][j] << b``). Any single faulty cell — data or parity,
  stuck-at or a noise level-flip — corrupts at most one bit per lane,
  and every lane corrects its own single-bit error independently:
  single-cell-per-codeword correction is exact (tested exhaustively in
  ``tests/test_reliability.py``).

Layout per layer (``n_data`` = the layer's real output width)::

    columns [0, n_data)                      data (col_mask = 1)
    columns [n_data, n_data + n_groups * r)  parity cells (col_mask = 0)
    columns beyond                           dead padding, unprotected
                                             (their MVM outputs are
                                             masked; faults there are
                                             harmless and ignored)

Codewords run along rows: codeword = (layer, plane, row, column-group),
so every protected cell belongs to exactly one codeword. Rows are
protected uniformly, padded rows included (a fault in a padded row costs
nothing at MVM time but would otherwise burn a codeword's budget —
keeping the layout uniform keeps the transform one reshape).

Energy/area surcharge (:func:`ecc_overhead`) is fed from
:class:`~repro.core.energy.HWParams` (``e_ecc_per_cell``,
``ecc_cells_per_cycle``) and surfaces in ``CompiledModel.stats()`` under
``reliability.ecc`` so policies can trade protection against cost.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.energy import DEFAULT_HW, HWParams
from repro.core.workload import map_mlps
from repro.kernels.program import CROSSBAR, CrossbarProgram, _pad2

__all__ = [
    "EccConfig", "EccLayerLayout", "EccSpec", "correct_model_program",
    "correct_program", "ecc_overhead", "hamming_r", "protect_program",
]


def hamming_r(k: int) -> int:
    """Parity bits of a SEC Hamming code over ``k`` data bits: the
    smallest ``r`` with ``2**r - r - 1 >= k``."""
    if k < 1:
        raise ValueError(f"codeword needs >= 1 data bit, got {k}")
    r = 2
    while (1 << r) - r - 1 < k:
        r += 1
    return r


def _data_positions(k: int, r: int) -> np.ndarray:
    """Hamming positions (1-based) of the ``k`` data bits: the first
    ``k`` non-power-of-two indices in ``1..k+r``."""
    pos = [i for i in range(1, k + r + 1) if i & (i - 1)]
    return np.asarray(pos[:k], dtype=np.int32)


def _parity_matrix(k: int, r: int) -> np.ndarray:
    """(k, r) 0/1 matrix: ``H[i, j]`` = bit ``j`` of data position ``i``.
    ``parity = data_bits @ H (mod 2)``; the same matrix folds data bits
    into the syndrome at decode time."""
    pos = _data_positions(k, r)
    return ((pos[:, None] >> np.arange(r)[None, :]) & 1).astype(np.int32)


@dataclass(frozen=True)
class EccConfig:
    """User-facing knob: ``group`` data cells per codeword. Smaller groups
    correct denser faults (one cell per ``group`` cells) at a higher
    parity overhead (``hamming_r(group) / group`` extra columns)."""

    group: int = 16

    def __post_init__(self):
        if self.group < 1:
            raise ValueError(f"group must be >= 1, got {self.group}")


@dataclass(frozen=True)
class EccLayerLayout:
    """Static per-layer codeword geometry (hashable pytree aux data)."""

    n_data: int        # real output columns (protected data)
    k: int             # data cells per codeword (min(group, n_data))
    r: int             # parity cells per codeword
    n_groups: int      # codewords per (plane, row)
    parity_start: int  # first parity column (== n_data)

    @property
    def parity_cols(self) -> int:
        return self.n_groups * self.r

    @property
    def cols_needed(self) -> int:
        return self.n_data + self.parity_cols


@dataclass(frozen=True)
class EccSpec:
    """The full static ECC description attached to a protected
    :class:`~repro.kernels.CrossbarProgram` (``program.ecc``)."""

    group: int
    layouts: tuple[EccLayerLayout, ...]

    @property
    def parity_cols(self) -> int:
        return sum(l.parity_cols for l in self.layouts)


def _layer_layout(n_data: int, group: int) -> EccLayerLayout:
    k = min(group, n_data)
    r = hamming_r(k)
    n_groups = -(-n_data // k)
    return EccLayerLayout(n_data=n_data, k=k, r=r, n_groups=n_groups,
                          parity_start=n_data)


def _lane_bits(cells: jnp.ndarray, lane: int) -> jnp.ndarray:
    return (cells.astype(jnp.int32) >> lane) & 1


def _grouped_data(planes_l: jnp.ndarray, lay: EccLayerLayout) -> jnp.ndarray:
    """(P, d, n_data) data region -> (P, d, n_groups, k), last group
    zero-padded with virtual (unstored, always-clean) cells."""
    data = planes_l[:, :, :lay.n_data]
    pad = lay.n_groups * lay.k - lay.n_data
    if pad:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, pad)))
    return data.reshape(*data.shape[:-1], lay.n_groups, lay.k)


def _parity_cells(data_g: jnp.ndarray, lay: EccLayerLayout,
                  cell_bits: int) -> jnp.ndarray:
    """Encode: (P, d, n_groups, k) data cells -> (P, d, n_groups * r)
    parity cells (one parity bit per lane packed per cell)."""
    h = jnp.asarray(_parity_matrix(lay.k, lay.r))
    out = jnp.zeros(data_g.shape[:-1] + (lay.r,), jnp.int32)
    for lane in range(cell_bits):
        par = (_lane_bits(data_g, lane) @ h) % 2
        out = out + (par << lane)
    return out.reshape(*out.shape[:-2], lay.n_groups * lay.r)


def protect_program(program: CrossbarProgram,
                    ecc: EccConfig | bool = True) -> CrossbarProgram:
    """ECC-encode a built program: compute Hamming parity for every
    codeword and store it in the spare columns, re-padding the whole
    program one or more crossbar edges wider when a layer's spare region
    is too small (all layers share ``d_pad``). MVM results are untouched
    — parity columns sit under ``col_mask = 0`` — so a protected program
    is bitwise-equivalent to its unprotected twin on every backend."""
    if program.ecc is not None:
        raise ValueError("program is already ECC-protected")
    if ecc is True:
        ecc = EccConfig()
    layouts = tuple(_layer_layout(n, ecc.group)
                    for n in program.widths[1:])
    need = max(max(l.cols_needed for l in layouts), program.d_pad)
    d_new = -(-need // CROSSBAR) * CROSSBAR
    planes = program.planes
    bias, col_mask = program.bias, program.col_mask
    if d_new > program.d_pad:
        planes = _pad2(planes, d_new, d_new)
        bias = jnp.pad(bias, ((0, 0), (0, d_new - program.d_pad)))
        col_mask = jnp.pad(col_mask, ((0, 0), (0, d_new - program.d_pad)))
    for l, lay in enumerate(layouts):
        par = _parity_cells(_grouped_data(planes[l], lay), lay,
                            program.cell_bits).astype(planes.dtype)
        planes = planes.at[l, :, :,
                           lay.parity_start:
                           lay.parity_start + lay.parity_cols].set(par)
    return dataclasses.replace(program, planes=planes, bias=bias,
                               col_mask=col_mask,
                               ecc=EccSpec(group=ecc.group, layouts=layouts))


def correct_program(program: CrossbarProgram) -> CrossbarProgram:
    """The digital scrub in front of shift-add recombination: decode every
    codeword's syndrome, flip single-cell errors (data or parity
    position), and restore consistent parity. Pure jnp and
    jit-compatible; a clean protected program round-trips bitwise.
    Columns beyond the parity region are dead padding — unprotected and
    left untouched (their MVM outputs are masked)."""
    if program.ecc is None:
        raise ValueError("program has no ECC spec; build it with "
                         "build_program(..., ecc=...) or protect_program")
    planes = program.planes
    cell_bits = program.cell_bits
    for l, lay in enumerate(program.ecc.layouts):
        h = jnp.asarray(_parity_matrix(lay.k, lay.r))
        pos = jnp.asarray(_data_positions(lay.k, lay.r))
        data_g = _grouped_data(planes[l], lay)            # (P, d, G, k)
        par = planes[l][:, :, lay.parity_start:
                        lay.parity_start + lay.parity_cols]
        par_g = par.reshape(*par.shape[:-1], lay.n_groups, lay.r)
        fixed = jnp.zeros_like(data_g)
        for lane in range(cell_bits):
            bits = _lane_bits(data_g, lane)               # (P, d, G, k)
            pbits = _lane_bits(par_g, lane)               # (P, d, G, r)
            synd = ((bits @ h) + pbits) % 2               # (P, d, G, r)
            s = jnp.sum(synd << jnp.arange(lay.r), axis=-1,
                        keepdims=True)                    # (P, d, G, 1)
            fixed = fixed + ((bits ^ (s == pos[None, None, None, :]))
                             << lane)
        data_fixed = fixed.reshape(*fixed.shape[:-2],
                                   lay.n_groups * lay.k)[..., :lay.n_data]
        planes = planes.at[l, :, :, :lay.n_data].set(
            data_fixed.astype(planes.dtype))
        par_fixed = _parity_cells(fixed, lay, cell_bits)
        planes = planes.at[l, :, :,
                           lay.parity_start:
                           lay.parity_start + lay.parity_cols].set(
            par_fixed.astype(planes.dtype))
    return dataclasses.replace(program, planes=planes)


def correct_model_program(programs: dict) -> dict:
    """Scrub a whole-model program dict; programs without an ECC spec
    pass through unchanged (nothing to correct)."""
    return map_mlps(
        lambda _, p: correct_program(p) if p.ecc is not None else p,
        programs)


def ecc_overhead(program: CrossbarProgram,
                 hw: HWParams = DEFAULT_HW) -> dict:
    """The protection bill, fed from :class:`HWParams`: extra cells /
    columns / crossbar arrays the parity occupies (area) and the digital
    syndrome-decode energy and cycles of one full scrub. Cell counts use
    real (unpadded) row heights — padded rows exist only in the TPU-twin
    layout, not on the die."""
    if program.ecc is None:
        raise ValueError("program has no ECC spec")
    p = program.n_planes
    data_cells = data_cols = parity_cells = parity_cols = extra_arrays = 0
    for l, lay in enumerate(program.ecc.layouts):
        rows = program.widths[l]
        data_cols += lay.n_data
        parity_cols += lay.parity_cols
        data_cells += p * rows * lay.n_data
        parity_cells += p * rows * lay.parity_cols
        extra_arrays += (-(-rows // hw.array_rows)
                         * -(-lay.parity_cols * hw.cells_per_weight
                             // hw.array_cols))
    cells = data_cells + parity_cells
    return {
        "group": program.ecc.group,
        "data_cols": data_cols,
        "parity_cols": parity_cols,
        "data_cells": data_cells,
        "parity_cells": parity_cells,
        "area_overhead": parity_cols / max(1, data_cols),
        "extra_arrays": extra_arrays,
        "scrub_energy_j": cells * hw.e_ecc_per_cell,
        "scrub_cycles": cells / hw.ecc_cells_per_cycle,
    }
