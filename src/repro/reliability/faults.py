"""ReRAM non-ideality injection (DESIGN.md §13).

The ideal integer crossbar the rest of the repo models is exactly what
real ReRAM is *not*: programmed conductances drift (device-to-device and
cycle-to-cycle variation), cells get stuck at their lowest/highest level
(forming faults), and the ADC digitizing each bit-line quantizes/clips
the read-out. :class:`FaultModel` is the repo's single description of
those effects, applied as a **pure transform on cell-plane tensors** —
the ``(..., K, N)`` int8 offset-binary planes a
:class:`~repro.kernels.CrossbarProgram` stores and every kernel consumes.
Because the faults land on the planes themselves (program time), every
backend and every fused dataflow inherits the injection unchanged: the
kernels never know whether the planes they stream were clean.

Pipeline per cell (level domain, ``levels = 2**cell_bits``):

  1. **conductance noise** — ``g = c + sigma * N(0, 1)``; the programmed
     level is perturbed by Gaussian write/read noise measured in level
     units (``sigma = 0.3`` means a ~5% chance an adjacent level is read);
  2. **ADC read-out** — ``round`` then clip to ``[0, min(levels,
     2**adc_bits) - 1]``: the sensed level is re-digitized, and an ADC
     narrower than the cell (``adc_bits < cell_bits``) saturates the top
     levels;
  3. **stuck-at masks** — independent per-cell Bernoulli masks force
     cells to level 0 (stuck-at-0 / high-resistance) or ``levels - 1``
     (stuck-at-1 / low-resistance). Physical defects override whatever
     was programmed, so they apply last.

Everything is seeded (``jax.random``, key derived from ``seed`` and
folded per MLP / per layer) and jit-compatible: the config fields are
static Python numbers, the data path is pure jnp. A zero-fault model
(:attr:`FaultModel.is_ideal`) is the *identity* — bitwise, by
construction — so ``fault_model=FaultModel()`` reproduces the ideal path
exactly on every backend (tested in ``tests/test_reliability.py``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.workload import map_mlps

__all__ = ["FaultModel"]


@dataclass(frozen=True)
class FaultModel:
    """Seeded, jit-compatible description of ReRAM cell non-idealities.

    sigma    : Gaussian conductance noise std, in cell-*level* units.
    p_stuck0 : per-cell probability of stuck-at-0 (lowest level).
    p_stuck1 : per-cell probability of stuck-at-1 (highest level).
    adc_bits : ADC resolution in bits; levels above ``2**adc_bits - 1``
               clip (None = ADC at least as wide as the cell, no clipping).
    seed     : base PRNG seed; :meth:`key_for` derives per-site subkeys.

    Frozen + hashable so it can ride through ``jax.jit`` as a static
    argument (``repro.kernels.ops.reram_linear`` does exactly that).
    """

    sigma: float = 0.0
    p_stuck0: float = 0.0
    p_stuck1: float = 0.0
    adc_bits: int | None = None
    seed: int = 0

    def __post_init__(self):
        if self.sigma < 0:
            raise ValueError(f"sigma must be >= 0, got {self.sigma}")
        for name in ("p_stuck0", "p_stuck1"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {p}")
        if self.adc_bits is not None and self.adc_bits < 1:
            raise ValueError(f"adc_bits must be >= 1, got {self.adc_bits}")

    # -- identity ----------------------------------------------------------

    def is_ideal_for(self, cell_bits: int) -> bool:
        """True when the transform is the identity on ``cell_bits`` cells
        (an ADC wider than the cell clips nothing)."""
        return (self.sigma == 0.0 and self.p_stuck0 == 0.0
                and self.p_stuck1 == 0.0
                and (self.adc_bits is None or self.adc_bits >= cell_bits))

    @property
    def is_ideal(self) -> bool:
        """True when no non-ideality is configured at all (identity on any
        cell width)."""
        return (self.sigma == 0.0 and self.p_stuck0 == 0.0
                and self.p_stuck1 == 0.0 and self.adc_bits is None)

    # -- keys --------------------------------------------------------------

    def base_key(self) -> jax.Array:
        return jax.random.PRNGKey(self.seed)

    def key_for(self, *indices: int) -> jax.Array:
        """Deterministic subkey for an injection site (e.g. MLP index,
        layer index): ``fold_in`` over ``indices`` from the base key."""
        key = self.base_key()
        for ix in indices:
            key = jax.random.fold_in(key, ix)
        return key

    # -- the transform -----------------------------------------------------

    def transform_planes(self, planes: jnp.ndarray, key: jax.Array, *,
                         cell_bits: int = 2) -> jnp.ndarray:
        """Inject faults into an offset-binary cell-plane tensor of any
        shape (each element is one cell, values in ``[0, 2**cell_bits)``).
        Pure and jit-compatible; identical ``(self, key, shape)`` →
        identical faults. Identity (bitwise, fast path) when
        :meth:`is_ideal_for` holds."""
        if self.is_ideal_for(cell_bits):
            return planes
        levels = 1 << cell_bits
        k_noise, k_s0, k_s1 = jax.random.split(key, 3)
        g = planes.astype(jnp.float32)
        if self.sigma > 0.0:
            g = g + self.sigma * jax.random.normal(k_noise, planes.shape)
        hi = levels - 1
        if self.adc_bits is not None:
            hi = min(hi, (1 << self.adc_bits) - 1)
        out = jnp.clip(jnp.round(g), 0, hi).astype(planes.dtype)
        if self.p_stuck0 > 0.0:
            out = jnp.where(
                jax.random.uniform(k_s0, planes.shape) < self.p_stuck0,
                jnp.zeros_like(out), out)
        if self.p_stuck1 > 0.0:
            out = jnp.where(
                jax.random.uniform(k_s1, planes.shape) < self.p_stuck1,
                jnp.full_like(out, levels - 1), out)
        return out

    def apply(self, program, key: jax.Array | None = None):
        """Faulty twin of a :class:`~repro.kernels.CrossbarProgram`: same
        static layout (widths, bit geometry, ECC spec), planes passed
        through :meth:`transform_planes`. The ideal model returns the
        program object unchanged."""
        if self.is_ideal_for(program.cell_bits):
            return program
        if key is None:
            key = self.base_key()
        return dataclasses.replace(
            program, planes=self.transform_planes(
                program.planes, key, cell_bits=program.cell_bits))

    def apply_model_program(self, programs: dict,
                            key: jax.Array | None = None) -> dict:
        """Inject into a whole-model program dict (the
        ``{"sa": [...], "sa_all", "head": ...}`` layout of
        :func:`repro.models.pointnet2.build_model_program`), folding a
        distinct subkey per MLP — its seed index
        (:func:`repro.core.workload.map_mlps`) — so faults are independent
        across MLPs."""
        if key is None:
            key = self.base_key()
        return map_mlps(
            lambda ix, p: self.apply(p, jax.random.fold_in(key, ix)),
            programs)
