"""Accuracy / energy / area Pareto sweeps over ReRAM fault grids.

The paper's "no accuracy loss" claim is an ideal-crossbar statement; this
harness measures what protection it actually costs to keep under
non-ideal cells. :func:`sweep` compiles one model per (fault rate,
protection level) grid point — the faults land on the compiled
:class:`~repro.kernels.CrossbarProgram` planes via
``compile_model(fault_model=...)``, so every dataflow inherits them
unchanged — and scores each point on three axes:

  accuracy    : prediction-agreement rate against the ideal compiled
                model on a deck of :func:`~repro.data.synthetic_cloud`
                clouds (the degradation metric; label-free, so the
                ideal-vs-faulty gap is isolated from model quality);
  energy_j    : per-inference energy of the paper's simulator
                (:func:`~repro.core.simulator.run_design`) plus the ECC
                scrub surcharge from :func:`~repro.reliability.ecc.
                ecc_overhead` (fed by ``HWParams.e_ecc_per_cell``);
  area_arrays : 128x128 crossbar arrays of the mapped model
                (:func:`~repro.core.reram.map_mlp_to_arrays`) plus the
                parity arrays ECC occupies.

:func:`pareto_front` extracts the non-dominated points,
:func:`classify_archetypes` names them (Fortress / Efficiency / Frugal /
SpeedDemon, the design-point taxonomy of the ECC-sim related work), and
``PlanPolicy(reliability_target=...).select_protection(points)`` turns
the swept cloud into a decision: cheapest point meeting the accuracy
bound. Everything is seeded — same arguments, same frontier.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from repro.core.energy import DEFAULT_HW, HWParams
from repro.core.reram import map_mlp_to_arrays
from repro.core.workload import PointNetConfig, PointNetWorkload
from repro.data.pointcloud import synthetic_cloud
from repro.reliability.ecc import EccConfig, ecc_overhead
from repro.reliability.faults import FaultModel

__all__ = [
    "ArchetypeBands", "DesignPoint", "classify_archetypes", "pareto_front",
    "sweep",
]


@dataclass(frozen=True)
class DesignPoint:
    """One (fault rate, protection) grid point with its three scores.
    ``accuracy``/``energy_j`` are the fields
    :meth:`~repro.core.policy.PlanPolicy.select_protection` reads."""

    fault_rate: float
    protection: str            # 'none' | 'ecc'
    accuracy: float
    energy_j: float
    area_arrays: int
    ecc_group: int | None = None
    archetype: str | None = None


def _fault_model(rate: float, seed: int) -> FaultModel:
    """Grid knob -> fault model: ``rate`` is the total stuck-cell
    probability, split evenly between stuck-at-0 and stuck-at-1 (the
    symmetric form both CIM fault studies in PAPERS.md use)."""
    return FaultModel(p_stuck0=rate / 2, p_stuck1=rate / 2, seed=seed)


def sweep(params, config: PointNetConfig, *,
          fault_rates=(0.0, 0.01, 0.05),
          protections=("none", "ecc"),
          n_clouds: int = 8,
          seed: int = 0,
          backend: str = "reram-fused",
          design: str = "pointer",
          hw: HWParams = DEFAULT_HW,
          ecc_group: int = 16,
          n_classes: int = 40) -> list[DesignPoint]:
    """Run the fault-rate x protection grid and score every point.

    One ideal reference model is compiled once; each grid point compiles
    the same ``params`` with ``fault_model=`` (and ``ecc=`` for the
    protected arm) and measures agreement on the same ``n_clouds``
    synthetic clouds. ``backend`` must be a fused (program-carrying)
    entry — ECC lives on ``CrossbarProgram`` planes. Deterministic in
    ``seed``; rising ``fault_rates`` trace the accuracy cliff the ECC arm
    flattens (the §13 acceptance curve).
    """
    from repro.models.backend import compile_model  # deferred: layering

    import jax.numpy as jnp  # deferred with the model imports

    clouds = [jnp.asarray(synthetic_cloud(i % n_classes,
                                          n_points=config.n_points,
                                          seed=seed + i))
              for i in range(n_clouds)]
    ideal = compile_model(params, config, backend=backend)
    ref = [int(np.argmax(np.asarray(ideal.forward(c)))) for c in clouds]

    from repro.core.simulator import run_design  # deferred: layering

    workload = PointNetWorkload.random(config, seed=seed)
    base_energy = run_design(workload, design, hw=hw).energy_j
    base_area = map_mlp_to_arrays(config, hw).total_arrays

    points: list[DesignPoint] = []
    for prot in protections:
        if prot not in ("none", "ecc"):
            raise ValueError(f"unknown protection {prot!r}; expected "
                             f"'none' or 'ecc'")
        ecc = EccConfig(group=ecc_group) if prot == "ecc" else None
        surcharge, extra_arrays = 0.0, 0
        if ecc is not None:
            # overheads depend only on the program layout, not the faults
            probe = compile_model(params, config, backend=backend, ecc=ecc)
            rel = probe.stats()["reliability"]["ecc"]
            surcharge, extra_arrays = (rel["scrub_energy_j"],
                                       rel["extra_arrays"])
        for rate in fault_rates:
            fm = _fault_model(rate, seed)
            model = compile_model(params, config, backend=backend,
                                  ecc=ecc, fault_model=fm)
            agree = sum(
                int(np.argmax(np.asarray(model.forward(c)))) == r
                for c, r in zip(clouds, ref))
            points.append(DesignPoint(
                fault_rate=float(rate), protection=prot,
                accuracy=agree / n_clouds,
                energy_j=base_energy + surcharge,
                area_arrays=base_area + extra_arrays,
                ecc_group=ecc_group if ecc is not None else None))
    return points


def pareto_front(points) -> list[DesignPoint]:
    """Non-dominated subset: maximize accuracy, minimize energy and area.
    A point survives unless some other point is at least as good on all
    three axes and strictly better on one."""
    pts = list(points)

    def dominated(p):
        return any(
            q.accuracy >= p.accuracy and q.energy_j <= p.energy_j
            and q.area_arrays <= p.area_arrays
            and (q.accuracy > p.accuracy or q.energy_j < p.energy_j
                 or q.area_arrays < p.area_arrays)
            for q in pts)

    return [p for p in pts if not dominated(p)]


@dataclass(frozen=True)
class ArchetypeBands:
    """Thresholds for :func:`classify_archetypes`. ``fortress_acc`` is an
    absolute accuracy floor; the cost bands are relative positions within
    the swept set (0 = cheapest seen, 1 = priciest), so the taxonomy
    adapts to the sweep's scale instead of hard-coding Joules."""

    fortress_acc: float = 0.99   # near-ideal accuracy, whatever the cost
    efficient_acc: float = 0.90  # still-accurate floor for the cheap bands
    energy_band: float = 0.35    # relative energy below which a point is
                                 # 'cheap' (SpeedDemon/Efficiency side)
    area_band: float = 0.35      # relative area below which it is 'lean'


def _relative(values) -> list[float]:
    lo, hi = min(values), max(values)
    span = hi - lo
    return [0.0 if span == 0 else (v - lo) / span for v in values]


def classify_archetypes(points, bands: ArchetypeBands = ArchetypeBands()):
    """Name every swept design point (the ECC-sim taxonomy):

      Fortress   — accuracy >= ``fortress_acc``: buy the protection, hold
                   the paper's no-accuracy-loss property;
      Efficiency — accurate enough (``efficient_acc``) AND cheap on
                   energy (below ``energy_band`` of the swept range);
      Frugal     — accurate enough AND lean on area;
      SpeedDemon — cheapest-energy band regardless of accuracy (the
                   throughput-at-any-cost corner);
      Unknown    — none of the above (dominated middle ground).

    Precedence top-down, so a point that is both near-ideal and cheap
    reads 'Fortress'. Returns ``{"points": [DesignPoint(archetype=...)],
    "counts": {name: n}}``.
    """
    pts = list(points)
    if not pts:
        return {"points": [], "counts": {}}
    e_rel = _relative([p.energy_j for p in pts])
    a_rel = _relative([p.area_arrays for p in pts])
    labelled, counts = [], {}
    for p, er, ar in zip(pts, e_rel, a_rel):
        if p.accuracy >= bands.fortress_acc:
            name = "Fortress"
        elif p.accuracy >= bands.efficient_acc and er <= bands.energy_band:
            name = "Efficiency"
        elif p.accuracy >= bands.efficient_acc and ar <= bands.area_band:
            name = "Frugal"
        elif er <= bands.energy_band:
            name = "SpeedDemon"
        else:
            name = "Unknown"
        labelled.append(replace(p, archetype=name))
        counts[name] = counts.get(name, 0) + 1
    return {"points": labelled, "counts": counts}
