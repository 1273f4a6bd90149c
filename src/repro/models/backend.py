"""Unified backend/compile API: ``compile_model`` + the backend registry.

This module is the single entry point for running PointNet++ on the ReRAM
twin. It replaces the implicit-kwarg backend selection that used to thread
``matmul=`` / ``program=`` through ``forward``/``batched_forward``/
``loss_fn`` (shims removed one release after PR 3; DESIGN.md §9 keeps the
migration table as the historical record).

Lifecycle — the same three phases as the accelerator:

  program : ``compile_model(params, config, backend=...)`` resolves the
            backend by name from the registry and lets it do its one-time
            work (the 'reram-fused' backend quantizes + plane-encodes every
            MLP into a :class:`~repro.kernels.CrossbarProgram` here, exactly
            once — crossbar programming).
  plan    : ``policy=`` hands both scheduling decisions to a
            :class:`~repro.core.policy.PlanPolicy` cost model (fused
            dataflow by predicted HBM bytes-per-cycle, intra order by
            predicted DMA elisions); ``schedule=`` is the thin adapter
            that pins the order instead (paper Algorithm 1): ``"baseline"``
            is plain layer-by-layer index order; any other preset /
            ``{"intra": ..., "coordinated": ...}`` spec routes execution
            through a per-cloud plan, and a prebuilt
            :class:`~repro.core.schedule.ExecutionPlan` is lowered HERE,
            once, into a jit-safe device-tensor
            :class:`~repro.core.schedule.DevicePlan` (which is also
            accepted directly, possibly batched).
  execute : ``CompiledModel.forward``/``batched_forward``/``loss_fn``/
            ``eval_step``. Under a plan, each SA layer runs its centers in
            ``plan.order_of(k)`` and the gather stage goes through the
            scalar-prefetch ``aggregate_diff`` kernel with plan-ordered
            indices — consecutive grid steps hitting the same feature row
            elide the HBM→VMEM copy, so the paper's reordering directly
            removes DMAs. ``batched_forward`` stacks the per-cloud plans
            into ONE batched DevicePlan and issues a single batch-gridded
            ``aggregate_diff_batched`` launch per SA layer (no per-cloud
            Python loop). Results are scattered back to index order after
            the per-center max reduction (rows are independent and the
            reduction is a max), so logits are bitwise invariant to the
            order; only the DMA traffic changes.

Backends register with the :func:`register_backend` decorator; the five
built-ins are ordinary registry entries:

  'float'              plain ``a @ w`` float matmuls
  'reram'              per-layer bit-sliced INT8 crossbar matmuls
  'reram-fused'        fused weight-stationary MLPs, dataflow auto-picked
                       by ``plan_fused_mlp`` under the 16 MB VMEM budget
  'reram-fused-mtiled' fused with the M-tiled dataflow pinned: the
                       activation panel lives in HBM, per-step residency
                       is one ``(bm, d)`` stripe — panel-bound shapes
                       (model2 SA-1 at 8192 rows) run fused
  'reram-fused-wstat'  fused with the j-outer weight re-streaming
                       dataflow pinned: plane tiles cross HBM once per
                       layer (full stationarity) at +M_pad·d bytes for
                       the int8 input-snapshot panel

New variants plug in the same way (a ``@register_backend`` subclass)
instead of growing new kwargs.

The bucketing contract (serving tier, ``repro.launch.serve``)
--------------------------------------------------------------

``forward``/``batched_forward`` accept ``n_valid`` — the real row count of
a cloud padded up to a shape bucket. The contract: for any cloud ``c`` of
``n`` points padded to a larger bucket with FINITE pad rows appended after
the real rows (the serving tier pads with zeros),

    ``model.forward(pad(c), n_valid=n)`` is **bitwise-equal** to
    ``model.forward(c)``

for every backend and schedule, provided ``n >= K`` (the first layer's
neighbor count) and the FPS start point (row 0) is real. Why it holds:
only the FIRST SA layer ever sees layer-0 rows — masked FPS starts pads at
``-inf`` min-distance (never the argmax; real rows keep exactly the
unpadded distances) and masked kNN forces pad distances to ``+inf``
(strictly worse than any finite real distance, so ``top_k`` values and
index tie-breaks are unchanged) — so the selected indices, hence every
gathered tensor downstream, are identical; later layers operate purely on
FPS-selected real points. ``n_valid`` may be traced, so ONE jit trace per
bucket shape serves every occupancy — this is what keeps the serving
tier's ``jit_batched_forward`` caches warm. ``batched_forward`` also
accepts a prebuilt (possibly cached) batched :class:`DevicePlan` per call
via ``dplan=`` — the serving plan cache's handle for skipping
``device_build_plan`` inside the trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import itertools
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import PlanPolicy
from repro.core.schedule import (DevicePlan, ExecutionPlan,
                                 GREEDY_DENSE_LIMIT, MODE_PRESETS,
                                 build_plan, complete_order,
                                 device_build_plan, inverse_permutation)
from repro.core.workload import (PointNetConfig, PointNetWorkload, mlp_label,
                                 select_mlp)
from repro.kernels import (aggregate_diff, aggregate_diff_batched,
                           count_dma_elisions, plan_fused_mlp, reram_linear,
                           reram_mlp_fused, reram_mlp_fused_batched)
from repro.models import pointnet2 as _pn

__all__ = [
    "Backend",
    "CompiledModel",
    "available_backends",
    "compile_model",
    "register_backend",
]

Params = Any

# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type["Backend"]] = {}


def register_backend(name: str) -> Callable[[type], type]:
    """Class decorator: make ``compile_model(..., backend=name)`` resolve to
    the decorated :class:`Backend` subclass. Registering an existing name
    replaces it (latest wins), so experiments can shadow a built-in; a
    class registered under several names keeps its first name as the class
    default (``compile_model`` stamps the instance with the name it
    resolved, so ``backend_name`` always reports the registry entry
    used)."""
    def deco(cls: type) -> type:
        if getattr(cls, "name", "?") == "?":
            cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def available_backends() -> list[str]:
    """Registered backend names, deterministically sorted (lexicographic —
    NOT registration order, so the listing is stable no matter which
    modules registered entries or in what order). Shadowing rule: the
    registry is name-keyed and latest-wins — ``register_backend`` on an
    existing name replaces that entry in place (the name keeps its sorted
    position; the previous class is simply no longer reachable by it)."""
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# the model's MLPs, by key (``PointNetConfig.mlp_keys``)
# ---------------------------------------------------------------------------

def _mlp_rows(config: PointNetConfig, key) -> int:
    """Rows one cloud sends through MLP ``key``."""
    if key == "head":
        return 1
    if key == "sa_all":
        return config.layers[-1].n_centers
    spec = config.layers[key[1]]
    if len(key) == 2:
        return spec.n_centers * spec.n_neighbors
    return spec.n_centers * spec.branches[key[2]].n_neighbors


# ---------------------------------------------------------------------------
# backends: how one MLP is applied
# ---------------------------------------------------------------------------

class Backend:
    """One way of running the model's MLPs. ``key`` addresses an MLP:
    ``("sa", i)`` for SA layer i's 3-stage MLP, ``"head"`` for the
    classification head (``("sa", i, b)`` for branch b of a multi-scale
    layer, ``"sa_all"`` for the group-all layer;
    ``PointNetConfig.mlp_keys``).
    ``apply_mlp`` must accept any leading dims on
    ``x``; ``apply_mlp_batched`` additionally treats axis 0 as a batch of
    independent clouds (backends with ``batched_in_grid = True`` fold it
    into one kernel launch and are never vmapped over)."""

    name = "?"
    #: True when ``apply_mlp_batched`` folds the batch into the kernel grid
    #: (the compiled model then vmaps only the geometry, never the kernel).
    batched_in_grid = False
    #: :class:`~repro.core.policy.PlanPolicy` stamped by ``compile_model``
    #: (None when compiled without one). Backends with tunable dataflows
    #: consult it for their launch-geometry decisions.
    policy: PlanPolicy | None = None

    def __init__(self, params: Params, config: PointNetConfig):
        self.params = params
        self.config = config

    def _mlp_params(self, key):
        return select_mlp(self.params, key)

    def apply_mlp(self, key, x, *, final_relu: bool = True):
        raise NotImplementedError

    def apply_mlp_batched(self, key, x, *, final_relu: bool = True):
        return self.apply_mlp(key, x, final_relu=final_relu)

    def stats(self) -> dict:
        return {"program_bytes": 0}


@register_backend("float")
class FloatBackend(Backend):
    """Plain ``a @ w`` (or a caller-supplied ``matmul`` — the hook the old
    ``matmul=`` kwarg maps onto)."""

    def __init__(self, params, config, *, matmul=None):
        super().__init__(params, config)
        self.matmul = matmul

    def apply_mlp(self, key, x, *, final_relu=True):
        return _pn._apply_mlp(self._mlp_params(key), x,
                              final_relu=final_relu, matmul=self.matmul)


@register_backend("reram")
class ReramPerLayerBackend(FloatBackend):
    """Per-layer bit-sliced INT8 crossbar matmul (``reram_linear``): same
    arithmetic as the fused path but weights are re-quantized and
    re-plane-encoded inside every traced call, one kernel launch per
    matmul. Kept as the reference the fused kernel is tested against.

    ``fault_model`` (a :class:`repro.reliability.FaultModel`) injects
    ReRAM non-idealities into each matmul's freshly encoded planes, keyed
    per (MLP, layer) site so faults are independent across layers and
    deterministic across calls. The zero-fault model takes the ideal path
    bit-for-bit."""

    def __init__(self, params, config, *, fault_model=None):
        super().__init__(params, config, matmul=reram_linear)
        self.fault_model = fault_model

    def apply_mlp(self, key, x, *, final_relu=True):
        fm = self.fault_model
        if fm is None or fm.is_ideal:
            return super().apply_mlp(key, x, final_relu=final_relu)
        # Site-keyed injection: the counter restarts at 0 for every
        # apply_mlp call (traced or eager), so layer i of MLP `key`
        # always draws from fold_in(seed, mlp_ix, i) — retrace-stable.
        mlp_ix = (0 if key == "head"
                  else self.config.mlp_keys().index(key) + 1)
        layer_ix = itertools.count()
        mm = lambda a, w: reram_linear(
            a, w, fault_model=fm,
            fault_key=fm.key_for(mlp_ix, next(layer_ix)))
        return _pn._apply_mlp(self._mlp_params(key), x,
                              final_relu=final_relu, matmul=mm)


@register_backend("reram-fused")
class ReramFusedBackend(Backend):
    """Weight-stationary path: every MLP programmed into crossbar planes
    exactly once at compile time (or pass a prebuilt ``program=`` from
    :func:`repro.models.pointnet2.build_model_program`), then each MLP runs
    as ONE fused ``pallas_call`` with inter-layer activations on-chip.
    ``mode`` pins the fused dataflow ('whole' / 'tiled' / 'mtiled' /
    'wstat', DESIGN.md §3.3); the default defers to ``plan_fused_mlp``'s
    VMEM-budget auto-selection. The M-tiled and j-outer variants are also
    first-class registry entries ('reram-fused-mtiled' /
    'reram-fused-wstat') — subclasses that pin ``mode``."""

    batched_in_grid = True
    #: fused dataflow this registry entry pins (None = auto-select)
    mode: str | None = None

    def __init__(self, params, config, *, program=None,
                 mode: str | None = None,
                 block_n: int | None = None, block_k: int | None = None,
                 ecc=None, fault_model=None):
        super().__init__(params, config)
        if program is None:
            program = _pn.build_model_program(params, ecc=ecc)
        elif ecc is not None:
            raise ValueError(
                "pass ecc= to build_model_program when prebuilding the "
                "program, not alongside program=")
        if fault_model is not None and not fault_model.is_ideal:
            # protect (at build) -> inject -> correct: the program the
            # kernels see is the post-scrub state of the faulty planes.
            # Without ECC the correction pass is a no-op pass-through and
            # the faults land raw — the unprotected arm of the sweep.
            from repro.reliability.ecc import correct_model_program
            program = correct_model_program(
                fault_model.apply_model_program(program))
        self.program = program
        self.ecc = ecc
        self.fault_model = fault_model
        self.mode = mode if mode is not None else type(self).mode
        self.block_n = block_n
        self.block_k = block_k
        self._plan_cache: dict = {}

    def _prog(self, key):
        return select_mlp(self.program, key)

    def _fused_plan(self, key, m_rows: int):
        """The launch geometry for MLP ``key`` at ``m_rows`` activation
        rows — through the compiled policy's roofline selection when one
        is stamped, else ``plan_fused_mlp``'s VMEM-fit preference walk.
        Cached: one decision per (MLP, shape), made on host at compile/
        first-trace time and pinned into the kernel as static args."""
        ck = (key, int(m_rows))
        if ck not in self._plan_cache:
            self._plan_cache[ck] = plan_fused_mlp(
                self._prog(key), int(m_rows), mode=self.mode,
                block_n=self.block_n, block_k=self.block_k,
                policy=self.policy)
        return self._plan_cache[ck]

    def apply_mlp(self, key, x, *, final_relu=True):
        fp = self._fused_plan(key, int(np.prod(x.shape[:-1], dtype=np.int64)))
        return reram_mlp_fused(x, self._prog(key), final_relu=final_relu,
                               mode=fp.mode, block_n=fp.block_n,
                               block_k=fp.block_k)

    def apply_mlp_batched(self, key, x, *, final_relu=True):
        fp = self._fused_plan(key,
                              int(np.prod(x.shape[1:-1], dtype=np.int64)))
        return reram_mlp_fused_batched(
            x, self._prog(key), final_relu=final_relu, mode=fp.mode,
            block_n=fp.block_n, block_k=fp.block_k)

    def stats(self) -> dict:
        keys = self.config.mlp_keys()
        progs = {mlp_label(k): self._prog(k) for k in keys}
        nbytes = {k: sum(l.nbytes for l in jax.tree_util.tree_leaves(p))
                  for k, p in progs.items()}
        plans = {mlp_label(k): self._plan_row(k, _mlp_rows(self.config, k))
                 for k in keys}
        out = {"program_bytes": sum(nbytes.values()),
               "program_bytes_per_mlp": nbytes,
               "fused_plan": plans}
        rel = {}
        if self.fault_model is not None:
            rel["fault_model"] = dataclasses.asdict(self.fault_model)
        protected = {k: p for k, p in progs.items() if p.ecc is not None}
        if protected:
            from repro.reliability.ecc import ecc_overhead
            per = {k: ecc_overhead(p) for k, p in protected.items()}
            rel["ecc"] = {
                "per_mlp": per,
                "parity_cells": sum(o["parity_cells"] for o in per.values()),
                "extra_arrays": sum(o["extra_arrays"] for o in per.values()),
                "scrub_energy_j": sum(o["scrub_energy_j"]
                                      for o in per.values()),
                "scrub_cycles": sum(o["scrub_cycles"] for o in per.values()),
            }
        if rel:
            out["reliability"] = rel
        return out

    def _plan_row(self, key, rows):
        fp = self._fused_plan(key, rows)
        return {"mode": fp.mode,
                "block_n": fp.block_n, "vmem_bytes": fp.vmem_bytes,
                "fits_budget": fp.fits_budget,
                "plane_tile_fetches_per_layer":
                    fp.plane_tile_fetches_per_layer,
                "plane_hbm_bytes_per_layer": fp.plane_hbm_bytes_per_layer,
                "act_hbm_bytes_per_layer": fp.act_hbm_bytes_per_layer}


@register_backend("reram-fused-mtiled")
class ReramFusedMTiledBackend(ReramFusedBackend):
    """'reram-fused' with the M-tiled dataflow pinned: the inter-layer
    activation panel lives in HBM (the kernel's output buffer) and only one
    ``(block_m, d_pad)`` stripe is VMEM-resident per grid step, staged by
    explicit DMA. Residency stops growing with the row count, so
    panel-bound programs (model2 SA-1 at its real 8192-row count) run
    fused within the 16 MB budget — at one f32 stripe read + write through
    HBM per layer."""

    name = "reram-fused-mtiled"
    mode = "mtiled"


@register_backend("reram-fused-wstat")
class ReramFusedWStatBackend(ReramFusedBackend):
    """'reram-fused' with the j-outer weight re-streaming dataflow pinned:
    N-tiles iterate outermost over a full int8 input-snapshot panel, so
    each plane tile crosses HBM once per layer instead of once per M-stripe
    — restores true weight stationarity for N-tiled shapes whose
    activation panel still fits VMEM (model2 SA-2), at +``M_pad·d_pad``
    bytes for the snapshot panel."""

    name = "reram-fused-wstat"
    mode = "wstat"


# ---------------------------------------------------------------------------
# schedule canonicalization
# ---------------------------------------------------------------------------

def _canonical_schedule(schedule, config: PointNetConfig):
    """-> (spec_dict, host_plan_or_None, device_plan_or_None, planned).
    ``spec_dict`` always has 'intra' and 'coordinated'; ``planned`` is
    False only for the plain layer-by-layer index-order fast path (== the
    'baseline' preset). A prebuilt ``ExecutionPlan`` is lowered to a
    :class:`DevicePlan` HERE — once, at compile time — so planned
    execution runs it as device arrays under jit; a prebuilt
    ``DevicePlan`` (possibly batched) passes straight through."""
    sizes = tuple(s.n_centers for s in config.layers)
    if schedule is None:
        schedule = "baseline"
    if isinstance(schedule, DevicePlan):
        if schedule.layer_sizes != sizes:
            raise ValueError(
                f"DevicePlan layer sizes {schedule.layer_sizes} do not "
                f"match config layers {sizes}")
        return ({"intra": schedule.intra,
                 "coordinated": schedule.coordinated}, None, schedule, True)
    if isinstance(schedule, ExecutionPlan):
        dplan = DevicePlan.lower(schedule, sizes)
        return ({"intra": schedule.intra,
                 "coordinated": schedule.coordinated}, schedule, dplan, True)
    if isinstance(schedule, Mapping):
        spec = dict(schedule)
        unknown = set(spec) - {"intra", "coordinated"}
        if unknown:
            raise ValueError(f"unknown schedule keys {sorted(unknown)}; "
                             f"expected 'intra' and 'coordinated'")
        spec.setdefault("intra", "index")
        spec.setdefault("coordinated", False)
        if spec["intra"] not in ("index", "greedy", "morton"):
            raise ValueError(f"unknown intra mode {spec['intra']!r}; "
                             f"expected 'index', 'greedy' or 'morton'")
        return spec, None, None, True
    if isinstance(schedule, str):
        if schedule not in MODE_PRESETS:
            raise ValueError(
                f"unknown schedule {schedule!r}; expected one of "
                f"{sorted(MODE_PRESETS)}, a {{'intra', 'coordinated'}} "
                f"mapping, an ExecutionPlan, or a DevicePlan")
        return dict(MODE_PRESETS[schedule]), None, None, schedule != "baseline"
    raise TypeError(f"schedule must be a preset name, a mapping, an "
                    f"ExecutionPlan, or a DevicePlan; got "
                    f"{type(schedule).__name__}")


def _device_planning_blocker(spec: dict, config: PointNetConfig,
                             policy: PlanPolicy | None) -> str | None:
    """Why plan construction can NOT be lowered into the trace for this
    (spec, config, policy) — or None when on-device planning is available.
    The two host-only cases: a policy whose intra choice is still
    per-workload (score-on-concrete-geometry; ``precommit`` it first), and
    a greedy order whose last layer exceeds the dense-sweep limit (the
    device sweep materializes the O(n^2) pairwise matrix)."""
    intra = spec["intra"]
    if intra == "auto":
        if policy is None or len(policy.intra_candidates) != 1:
            return ("the policy's intra choice is per-workload (scored on "
                    "concrete geometry); precommit it to one candidate "
                    "first — policy.precommit(representative_workload)")
        intra = policy.intra_candidates[0]
    if intra == "greedy" and config.layers[-1].n_centers > GREEDY_DENSE_LIMIT:
        return (f"device greedy ordering materializes an O(n^2) distance "
                f"matrix and is limited to last-layer sizes <= "
                f"GREEDY_DENSE_LIMIT={GREEDY_DENSE_LIMIT}; this config's "
                f"last layer has {config.layers[-1].n_centers} centers")
    if intra not in ("index", "greedy", "morton"):
        return f"unknown intra mode {intra!r}"
    return None


# ---------------------------------------------------------------------------
# the compiled model
# ---------------------------------------------------------------------------

class CompiledModel:
    """The executable returned by :func:`compile_model`. Holds a programmed
    backend plus a compiled schedule (a :class:`DevicePlan` and/or the
    policy that builds one per workload); exposes the whole old surface as
    methods."""

    def __init__(self, backend: Backend, config: PointNetConfig,
                 schedule_spec: dict, plan: ExecutionPlan | None,
                 planned: bool, device_plan: DevicePlan | None = None,
                 policy: PlanPolicy | None = None,
                 device_planning: bool = False):
        self.backend = backend
        self.config = config
        self._spec = schedule_spec
        self._plan = plan          # user-supplied host plan (stats only)
        self._dplan = device_plan  # compile-time lowered plan, if any
        self._policy = policy
        self._planned = planned
        self._device_planning = device_planning
        self._jit_eval = None
        self._jit_fwd = None
        self._jit_bfwd = None
        self._last_dma: dict | None = None

    # -- public metadata ----------------------------------------------------

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @property
    def schedule(self) -> dict:
        """The canonical ``{'intra': ..., 'coordinated': ...}`` spec (round-
        trips ``MODE_PRESETS`` names passed to ``compile_model``). Under a
        policy, ``intra`` is ``'auto'`` — the cost model picks it per
        workload."""
        return dict(self._spec)

    @property
    def policy(self) -> PlanPolicy | None:
        return self._policy

    @property
    def device_plan(self) -> DevicePlan | None:
        """The compile-time-lowered :class:`DevicePlan` (None when the
        schedule is per-cloud: spec/policy-driven plans are built from
        each cloud's own geometry at call time)."""
        return self._dplan

    @property
    def device_planning(self) -> bool:
        """True when per-cloud plan construction is lowered into the trace
        (``device_build_plan`` on the forward's own geometry tensors —
        zero host sync, jits end to end). False for the host fallbacks
        (``device_planning=False``, a non-precommitted policy, greedy past
        ``GREEDY_DENSE_LIMIT``) and for schedules that need no per-cloud
        construction at all (baseline, prebuilt plans)."""
        return self._device_planning

    @property
    def planned(self) -> bool:
        """True when execution routes through a gather order (any schedule
        but 'baseline') — i.e. when there is a :class:`DevicePlan` for the
        serving tier's plan cache to build and reuse."""
        return self._planned

    def build_device_plan(self, cloud: jnp.ndarray,
                          n_valid=None) -> DevicePlan:
        """The single-cloud :class:`DevicePlan` this model's schedule would
        use for ``cloud`` — the serving plan cache's build hook: cache the
        result under the cloud's content key and pass it back through
        ``forward(dplan=...)`` (or :meth:`DevicePlan.stack` a batch of
        them into ``batched_forward(dplan=...)``) to skip plan
        construction on every repeat. Runs Algorithm 1 in-trace
        (jit-safe) under on-device planning, on host otherwise; returns
        the compile-time plan unchanged when one is bound. ``n_valid``
        masks shape-bucket pad rows out of the geometry, so the plan
        equals the unpadded cloud's. Traced, its ops sit under the named
        scope ``plan``."""
        if not self._planned:
            raise ValueError("this model's schedule is unplanned "
                             "('baseline'); there is no plan to build")
        if self._dplan is not None:
            return self._dplan
        with jax.named_scope("plan"):
            pts_list, ctr_list, nbr_list = self._geometry_pass(
                jnp.asarray(cloud), n_valid)
            if self._device_planning:
                return self._traced_plan(pts_list, nbr_list)
        return self._device_plan_for(pts_list, ctr_list, nbr_list)

    # -- execution ----------------------------------------------------------

    def forward(self, cloud: jnp.ndarray, *, n_valid=None,
                dplan: DevicePlan | None = None) -> jnp.ndarray:
        """Single cloud (N, 3) -> logits (n_classes,).

        ``n_valid`` marks the real row count of a shape-bucket-padded cloud
        (bitwise-equal to the unpadded forward — the bucketing contract in
        the module docstring); ``dplan`` supplies a prebuilt single-cloud
        :class:`DevicePlan` for this call (the serving plan cache), taking
        precedence over in-trace construction and host planning."""
        if self._planned:
            return self._forward_planned(cloud, n_valid=n_valid, dplan=dplan)
        if dplan is not None:
            raise ValueError("dplan= was passed but this model's schedule "
                             "is unplanned ('baseline'); there is no "
                             "gather order for it to drive")
        return self._forward_base(cloud, n_valid)

    def batched_forward(self, clouds: jnp.ndarray, *, n_valid=None,
                        dplan: DevicePlan | None = None) -> jnp.ndarray:
        """Batch (B, N, 3) -> logits (B, n_classes). Grid-batched backends
        get ONE kernel launch per MLP for the whole batch (geometry only is
        vmapped); others vmap the single-cloud forward. Under a schedule or
        policy the per-cloud plans are stacked into one batched
        :class:`DevicePlan` and every SA layer issues ONE batch-gridded
        ``aggregate_diff_batched`` gather — not a per-cloud Python loop.

        ``n_valid`` is a (B,) vector of real row counts for shape-bucket-
        padded clouds (per-row bitwise-equal to the unpadded forwards);
        ``dplan`` supplies a prebuilt batched :class:`DevicePlan` for THIS
        call — the serving tier stacks plan-cache hits into one and skips
        ``device_build_plan`` entirely."""
        if self._planned:
            return self._batched_forward_planned(clouds, n_valid=n_valid,
                                                 dplan=dplan)
        if dplan is not None:
            raise ValueError("dplan= was passed but this model's schedule "
                             "is unplanned ('baseline'); there is no "
                             "gather order for it to drive")
        if self.backend.batched_in_grid:
            return self._batched_in_grid(clouds, n_valid)
        if n_valid is None:
            return jax.vmap(self._forward_base)(clouds)
        return jax.vmap(self._forward_base)(clouds, n_valid)

    def loss_fn(self, clouds, labels):
        """Mean NLL + accuracy over a batch (same contract as the old
        ``pointnet2.loss_fn``)."""
        logits = self.batched_forward(clouds)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
        acc = (jnp.argmax(logits, axis=1) == labels).mean()
        return nll, acc

    def eval_step(self, clouds, labels):
        """Jit-compiled ``loss_fn`` (cached per compiled model). Only
        schedules that still build their plan on host per cloud (host
        fallback: ``device_planning=False`` / non-precommitted policy /
        greedy past the dense limit) run eagerly — with a compile-time
        :class:`DevicePlan` or on-device planning the whole pipeline jits
        like baseline."""
        if self._planned and self._dplan is None and not self._device_planning:
            return self.loss_fn(clouds, labels)
        if self._jit_eval is None:
            self._jit_eval = jax.jit(self.loss_fn)
        return self._jit_eval(clouds, labels)

    def _require_traceable(self, what: str) -> None:
        if self._planned and self._dplan is None and not self._device_planning:
            raise TypeError(
                f"{what} needs the whole pipeline to trace under jax.jit, "
                f"but this model plans on host per cloud (device_planning "
                f"is off); compile with device_planning=True, precommit "
                f"the policy, or pass a prebuilt ExecutionPlan/DevicePlan")

    def jit_forward(self, cloud: jnp.ndarray) -> jnp.ndarray:
        """:meth:`forward` as ONE end-to-end jitted function cloud→logits
        (compiled on first call, cached). Under an on-device-planned
        schedule the jitted computation contains geometry, Algorithm-1
        plan construction, gathers, and MLPs — no host callback
        anywhere."""
        if self._jit_fwd is None:
            self._require_traceable("jit_forward")
            self._jit_fwd = jax.jit(self.forward)
        return self._jit_fwd(cloud)

    def jit_batched_forward(self, clouds: jnp.ndarray) -> jnp.ndarray:
        """:meth:`batched_forward` as ONE end-to-end jitted function
        clouds→logits (compiled per batch shape, cached): batched
        geometry, a vmapped ``device_build_plan``, one batch-gridded
        gather + one batched MLP apply per SA layer."""
        if self._jit_bfwd is None:
            self._require_traceable("jit_batched_forward")
            self._jit_bfwd = jax.jit(self.batched_forward)
        return self._jit_bfwd(clouds)

    # -- introspection ------------------------------------------------------

    def stats(self, cloud=None, *, workload: PointNetWorkload | None = None,
              window: int = 72) -> dict:
        """Compile/execution report: backend name, schedule spec, program
        bytes and fused-plan mode (whole/tiled) per MLP for programmed
        backends, and — given a ``cloud`` or prebuilt ``workload`` (else the
        one cached by the last planned ``forward``) — the predicted DMA
        elisions of the aggregate gather under this schedule, per layer,
        via ``count_dma_elisions`` with a ``window``-row VMEM working set."""
        s = {"backend": self.backend_name, "schedule": self.schedule,
             "planned": self._planned}
        if self._policy is not None:
            s["policy"] = self._policy
        s.update(self.backend.stats())
        dma = None
        if cloud is not None or workload is not None:
            if workload is None:
                workload = PointNetWorkload.build(     # the device's dtype
                    np.asarray(cloud, np.float32), self.config)
            if self._plan is not None:
                plan = self._plan
            elif self._dplan is not None:
                plan = self._dplan
            elif self._policy is not None:
                plan = self._policy.build_plan(workload)
            else:
                plan = build_plan(workload, **self._spec)
            dma = self._dma_report(plan,
                                   [np.asarray(nb)
                                    for nb in workload.neighbors[1:]],
                                   window)
        elif self._last_dma is not None:
            dma = self._last_dma if self._last_dma["window"] == window else {
                **self._dma_report(None, None, window,
                                   streams=self._last_dma["_streams"]),
            }
        if dma is not None:
            s["dma"] = {k: v for k, v in dma.items() if k != "_streams"}
        return s

    @staticmethod
    def _dma_report(plan, neighbors, window, streams=None) -> dict:
        """Per-layer + total elision counts for the plan-ordered neighbor
        index streams that drive the ``aggregate_diff`` gathers.
        ``streams[k-1]`` is a list of one array per cloud (a batched plan
        contributes one stream per batch row; counts never chain across
        cloud boundaries) — layer entries aggregate over the batch."""
        if streams is None:
            streams = []
            for k, nb in enumerate(neighbors, start=1):
                order = np.asarray(plan.order_of(k))
                orders = order[None] if order.ndim == 1 else order
                streams.append([nb[complete_order(o, nb.shape[0], k)]
                                for o in orders])
        layers = []
        for per_cloud in streams:
            counts = [count_dma_elisions(st, window=window)
                      for st in per_cloud]
            steps = sum(c["steps"] for c in counts)
            elided = sum(c["elided"] for c in counts)
            layers.append({"steps": steps, "elided": elided,
                           "dma": steps - elided,
                           "elision_rate": elided / max(1, steps)})
        steps = sum(l["steps"] for l in layers)
        elided = sum(l["elided"] for l in layers)
        return {"window": window, "layers": layers, "steps": steps,
                "elided": elided, "dma": steps - elided,
                "elision_rate": elided / max(1, steps),
                "_streams": streams}

    # -- execution internals ------------------------------------------------

    def _forward_base(self, cloud, n_valid=None):
        """Layer-by-layer index-order execution — identical structure (and
        bitwise-identical results per backend) to the pre-registry
        ``pointnet2.forward``. ``n_valid`` masks layer-0 pad rows (the
        bucketing contract); only the first SA layer ever sees them."""
        cfg = self.config
        feats = _pn.input_features(cfg, cloud)
        pts = cloud
        for i, spec in enumerate(cfg.layers):
            pts, diffs = _pn.sa_group(spec, pts, feats,
                                      n_valid if i == 0 else None)
            feats = self._reduce_branches(i, diffs, axis=1)
        return self._classify(pts, feats)

    def _batched_in_grid(self, clouds, n_valid=None):
        """Batch-in-grid execution: vmap only the per-cloud geometry; every
        MLP is ONE batched kernel launch (never vmap over the kernel)."""
        cfg = self.config
        feats = jax.vmap(functools.partial(_pn.input_features, cfg))(clouds)
        pts = clouds
        for i, spec in enumerate(cfg.layers):
            if i == 0 and n_valid is not None:
                pts, diffs = jax.vmap(
                    functools.partial(_pn.sa_group, spec))(pts, feats,
                                                           n_valid)
            else:
                pts, diffs = jax.vmap(
                    functools.partial(_pn.sa_group, spec))(pts, feats)
            feats = self._reduce_branches(i, diffs, axis=2, batched=True)
        return self._classify_batched(pts, feats)

    def _reduce_branches(self, i, diffs, *, axis, batched=False):
        """SA layer ``i``'s feature computation and reduction on its
        grouped rows: per branch the MLP and the max over its K rows, the
        branches concatenated (one branch: its output)."""
        apply = (self._apply_mlp_batched if batched
                 else self.backend.apply_mlp)
        outs = [jnp.max(apply(key, d), axis=axis)       # reduction over K
                for key, d in zip(self.config.layer_mlp_keys(i), diffs)]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, -1)

    def _group_all(self, pts, feats, *, batched=False):
        """The group-all layer: its MLP over every point's ``[p_j ; f_j]``
        and the max over the points, keeping that axis as one point."""
        apply = (self._apply_mlp_batched if batched
                 else self.backend.apply_mlp)
        h = apply("sa_all", jnp.concatenate([pts, feats], axis=-1))
        return jnp.max(h, axis=-2, keepdims=True)

    def _classify(self, pts, feats):
        """One cloud's last points and features -> logits: the group-all
        layer where there is one, the global max pool, the head."""
        if self.config.group_all is not None:
            with jax.named_scope("sa_all"):
                feats = self._group_all(pts, feats)
        g = jnp.max(feats, axis=0)                       # global max pool
        return self.backend.apply_mlp("head", g, final_relu=False)

    def _classify_batched(self, pts, feats):
        """:meth:`_classify` over a batch."""
        if self.config.group_all is not None:
            with jax.named_scope("sa_all"):
                feats = self._group_all(pts, feats, batched=True)
        with jax.named_scope("head"):
            return self._head_batched(feats)

    def _geometry_pass(self, cloud, n_valid=None):
        """Pass 1 of planned execution: the same FPS/kNN geometry as the
        base path, kept as explicit per-layer device tensors so the plan
        (built from exactly this geometry — on device or on host) permutes
        exactly the rows being gathered."""
        return _pn.geometry_pass(self.config, cloud, n_valid=n_valid)

    def _resolved_intra(self) -> str:
        """The concrete intra mode device planning lowers ('auto' resolves
        to the precommitted policy's single candidate)."""
        intra = self._spec["intra"]
        if intra == "auto":
            return self._policy.intra_candidates[0]
        return intra

    def _traced_plan(self, pts_list, nbr_list) -> DevicePlan:
        """On-device plan construction for one cloud: Algorithm 1 on the
        forward's own traced geometry via
        :func:`~repro.core.schedule.device_build_plan` — no host sync, so
        the caller can be (and under ``jit_forward`` is) a jit trace."""
        cfg = self.config
        nbrs = [nbr_list[k].astype(jnp.int32)
                for k in range(1, cfg.n_layers + 1)]
        return device_build_plan(nbrs, pts_list[-1],
                                 intra=self._resolved_intra(),
                                 coordinated=self._spec["coordinated"])

    def _forward_planned(self, cloud, n_valid=None, dplan=None):
        """Plan-driven execution. Pass 2 runs each SA layer's centers in
        plan order, gathering neighbor differences through the
        scalar-prefetch ``aggregate_diff`` kernel — the plan-ordered index
        stream is what elides DMAs — then scatters the per-center max back
        to index order, which makes the logits bitwise independent of the
        order. The schedule itself is a :class:`DevicePlan`: passed in per
        call (serving plan cache), lowered once at compile time when
        prebuilt, built INSIDE the trace from this cloud's own geometry
        under on-device planning (then the whole function jits with zero
        host transfers), or — host fallback — lowered here from the host
        plan the spec/policy builds for this cloud's geometry."""
        cfg = self.config
        feats = _pn.input_features(cfg, cloud)
        pts_list, ctr_list, nbr_list = self._geometry_pass(cloud, n_valid)
        if dplan is not None:
            pass                              # caller-supplied (plan cache)
        elif self._dplan is not None:
            dplan = self._dplan
        elif self._device_planning:
            dplan = self._traced_plan(pts_list, nbr_list)
        else:
            dplan = self._device_plan_for(pts_list, ctr_list, nbr_list)
        if dplan.batched:
            raise ValueError("compile_model was given a batched DevicePlan; "
                             "use batched_forward for it")
        # measured-stream telemetry is a host pull (np.asarray); device
        # planning keeps the hot path free of host transfers by contract,
        # so only the host-planned / prebuilt eager paths collect it
        collect = (not self._device_planning
                   and not isinstance(cloud, jax.core.Tracer))
        streams = []
        for k in range(1, cfg.n_layers + 1):
            spec = cfg.layers[k - 1]
            order = dplan.order_of(k)
            inv = dplan.inverse_of(k)
            nbr_o = jnp.take(nbr_list[k].astype(jnp.int32), order, axis=0)
            ctr_o = jnp.take(ctr_list[k].astype(jnp.int32), order, axis=0)
            if collect:
                streams.append([np.asarray(nbr_o)])
            rows, ctrs = _pn.grouping_operands(spec, pts_list[k - 1], feats)
            diffs = [aggregate_diff(rows, self._branch(nbr_o, lo, hi),
                                    ctr_o, ctrs)    # plan-ordered gather
                     for lo, hi in _pn.branch_bounds(spec)]
            out = self._reduce_branches(k - 1, diffs, axis=1)
            feats = jnp.take(out, inv, axis=0)           # back to index order
        if collect:
            self._last_dma = self._dma_report(None, None, 72, streams=streams)
        return self._classify(pts_list[-1], feats)

    @staticmethod
    def _branch(nbr, lo, hi):
        """Columns ``lo:hi`` (one branch) of plan-ordered receptive fields;
        the whole array when they are all of it."""
        return nbr if (lo, hi) == (0, nbr.shape[-1]) else nbr[..., lo:hi]

    def _batched_forward_planned(self, clouds, n_valid=None, dplan=None):
        """Batched plan-driven execution — the per-cloud Python loop folded
        into single batch-gridded launches. A caller-supplied ``dplan``
        (the serving plan cache), on-device planning, and any prebuilt
        :class:`DevicePlan` route through the fully-traced
        :meth:`_batched_forward_device` path — vmapped geometry, vmapped
        plan construction, zero host sync. Only the host-planning fallback
        still walks the batch in Python: its per-cloud ``np.asarray``
        geometry pull is exactly what the host plans are built from.
        Either way every SA layer issues exactly one
        ``aggregate_diff_batched`` gather and one batched MLP apply for
        the whole batch. Same arithmetic per row as the per-cloud path, so
        logits are bitwise equal to ``stack([forward(c) for c in clouds])``
        (tested per schedule)."""
        if (dplan is not None or self._dplan is not None
                or self._device_planning):
            return self._batched_forward_device(clouds, n_valid, dplan)
        cfg = self.config
        batch = clouds.shape[0]
        if n_valid is None:
            geoms = [self._geometry_pass(clouds[b]) for b in range(batch)]
        else:
            nv = np.asarray(n_valid)
            geoms = [self._geometry_pass(clouds[b], int(nv[b]))
                     for b in range(batch)]
        dplan = self._device_plan_for(*geoms[0], batch_geoms=geoms)
        tracing = isinstance(clouds, jax.core.Tracer)
        feats = jnp.stack([_pn.input_features(cfg, clouds[b])
                           for b in range(batch)])
        pts = clouds
        streams = None if tracing else []
        for k in range(1, cfg.n_layers + 1):
            nbr_k = jnp.stack([g[2][k] for g in geoms])
            ctr_k = jnp.stack([g[1][k] for g in geoms])
            feats = self._sa_layer_batched(
                k, pts, feats, nbr_k, ctr_k, dplan.order_of(k),
                dplan.inverse_of(k), streams=streams)
            pts = jnp.stack([g[0][k] for g in geoms])
        if not tracing:
            self._last_dma = self._dma_report(None, None, 72, streams=streams)
        return self._classify_batched(pts, feats)

    def _batched_forward_device(self, clouds, n_valid=None, dplan=None):
        """The fully-traced batched path: vmapped geometry, a vmapped
        :func:`~repro.core.schedule.device_build_plan` (unless a prebuilt
        or caller-supplied :class:`DevicePlan` short-circuits it — the
        serving plan cache passes one to skip construction entirely), then
        exactly one ``aggregate_diff_batched`` gather and one batched MLP
        apply per SA layer. No per-cloud Python loop and no ``np.asarray``
        on geometry — the whole thing is ONE jittable clouds→logits
        computation (``jit_batched_forward`` wraps it). Same arithmetic
        per row as the host-planned path, so logits stay bitwise equal to
        it. Each SA layer's ops sit under the named scopes ``gather`` and
        ``mlp`` (the MLP, the max over K and the scatter back to index
        order), the classifier's under ``head``."""
        cfg = self.config
        batch = clouds.shape[0]
        feats = jax.vmap(functools.partial(_pn.input_features, cfg))(clouds)
        if n_valid is None:
            pts_s, ctr_s, nbr_s = jax.vmap(
                functools.partial(_pn.geometry_pass, cfg))(clouds)
        else:
            pts_s, ctr_s, nbr_s = jax.vmap(
                lambda c, nv: _pn.geometry_pass(cfg, c, n_valid=nv))(
                clouds, jnp.asarray(n_valid))
        if dplan is not None or self._dplan is not None:
            dplan = dplan if dplan is not None else self._dplan
            if dplan.batched and dplan.batch_size != batch:
                raise ValueError(
                    f"batched DevicePlan is for batch {dplan.batch_size}, "
                    f"got {batch} clouds")
        else:
            intra = self._resolved_intra()
            coordinated = self._spec["coordinated"]
            dplan = jax.vmap(
                lambda lp, nbs: device_build_plan(
                    nbs, lp, intra=intra, coordinated=coordinated))(
                pts_s[-1], [nbr_s[k].astype(jnp.int32)
                            for k in range(1, cfg.n_layers + 1)])
        for k in range(1, cfg.n_layers + 1):
            order = dplan.order_of(k)
            inv = dplan.inverse_of(k)
            if not dplan.batched:                 # one plan shared batch-wide
                order = jnp.broadcast_to(order, (batch,) + order.shape)
                inv = jnp.broadcast_to(inv, (batch,) + inv.shape)
            feats = self._sa_layer_batched(k, pts_s[k - 1], feats, nbr_s[k],
                                           ctr_s[k], order, inv)
        return self._classify_batched(pts_s[-1], feats)

    def _sa_layer_batched(self, k, pts, feats, nbr, ctr, order, inv, *,
                          streams=None):
        """SA layer ``k`` (1-based) over a batch, in plan order: the
        plan-ordered gather (one ``aggregate_diff_batched`` per branch),
        each branch's MLP and max over K, the branches concatenated and
        scattered back to index order. ``pts``/``feats`` are layer k-1's;
        ``streams`` collects the plan-ordered index streams where given.
        Ops sit under the named scopes ``gather`` and ``mlp``, a
        multi-scale layer's branch b under ``gather/branch<b>`` and
        ``mlp/branch<b>``."""
        spec = self.config.layers[k - 1]
        with jax.named_scope("gather"):
            nbr_o = jnp.take_along_axis(nbr.astype(jnp.int32),
                                        order[:, :, None], axis=1)
            ctr_o = jnp.take_along_axis(ctr.astype(jnp.int32), order,
                                        axis=1)
            if streams is not None:
                streams.append(list(np.asarray(nbr_o)))
            rows, ctrs = _pn.grouping_operands(spec, pts, feats)
        outs = []
        for key, (lo, hi) in zip(self.config.layer_mlp_keys(k - 1),
                                 _pn.branch_bounds(spec)):
            branch = (lambda: jax.named_scope(f"branch{key[2]}")) \
                if len(key) > 2 else contextlib.nullcontext
            with jax.named_scope("gather"), branch():
                diff = aggregate_diff_batched(
                    rows, self._branch(nbr_o, lo, hi), ctr_o, ctrs)
            with jax.named_scope("mlp"), branch():
                h = self._apply_mlp_batched(key, diff)
                outs.append(jnp.max(h, axis=2))          # reduction over K
        with jax.named_scope("mlp"):
            out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, -1)
            return jnp.take_along_axis(out, inv[:, :, None], axis=1)

    def _apply_mlp_batched(self, key, x, *, final_relu=True):
        if self.backend.batched_in_grid:
            return self.backend.apply_mlp_batched(key, x,
                                                  final_relu=final_relu)
        return jax.vmap(lambda v: self.backend.apply_mlp(
            key, v, final_relu=final_relu))(x)

    def _head_batched(self, feats):
        g = jnp.max(feats, axis=1)                       # global max pool
        return self._apply_mlp_batched("head", g, final_relu=False)

    def _host_plan_for(self, pts_list, ctr_list, nbr_list) -> ExecutionPlan:
        """Build the host ``ExecutionPlan`` for one cloud's geometry via
        the policy (cost-model intra selection) or the fixed spec."""
        if any(isinstance(p, jax.core.Tracer) for p in pts_list):
            raise TypeError(
                "compile_model(schedule=...)/compile_model(policy=...) "
                "builds its ExecutionPlan on the host and cannot run under "
                "jit/vmap tracing; jit the 'baseline' schedule, or pass a "
                "prebuilt ExecutionPlan/DevicePlan")
        wl = PointNetWorkload(
            config=self.config,
            points=[np.asarray(p, np.float64) for p in pts_list],
            centers=[None] + [np.asarray(c) for c in ctr_list[1:]],
            neighbors=[None] + [np.asarray(nb) for nb in nbr_list[1:]])
        if self._policy is not None and "auto" in self._spec.values():
            return self._policy.build_plan(wl)
        return build_plan(wl, **self._spec)

    def _device_plan_for(self, pts_list, ctr_list, nbr_list, *,
                         batch_geoms=None) -> DevicePlan:
        """The :class:`DevicePlan` that drives execution: the compile-time
        one when the user passed a prebuilt plan, else per-cloud host plans
        lowered (and, for a batch, stacked) here."""
        if self._dplan is not None:
            return self._dplan
        sizes = tuple(s.n_centers for s in self.config.layers)
        if batch_geoms is None:
            return DevicePlan.lower(
                self._host_plan_for(pts_list, ctr_list, nbr_list), sizes)
        return DevicePlan.lower(
            [self._host_plan_for(*g) for g in batch_geoms], sizes)


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------

def compile_model(params: Params, config: PointNetConfig, *,
                  backend: str = "float", schedule=None,
                  policy: PlanPolicy | None = None,
                  device_planning: bool | None = None,
                  fault_model=None,
                  **backend_opts) -> CompiledModel:
    """Compile PointNet++ ``params`` for execution.

    backend  : registry name — 'float', 'reram' (per-layer INT8 crossbar),
               'reram-fused' (weight-stationary fused kernels), or anything
               added with :func:`register_backend`. ``backend_opts`` go to
               the backend constructor (e.g. ``program=``, ``block_n=``,
               ``ecc=`` on the fused backends).
    fault_model : a :class:`repro.reliability.FaultModel` — inject ReRAM
               non-idealities (conductance noise, stuck-at cells, ADC
               clipping) into the compiled crossbar planes (DESIGN.md
               §13). Only meaningful for crossbar backends; compiling a
               backend without fault support (e.g. 'float' — it has no
               cell planes to fault) raises ``ValueError``. The zero-fault
               model is bitwise-identical to compiling without one.
    policy   : a :class:`~repro.core.policy.PlanPolicy` — the cost model
               that makes both scheduling decisions at compile time: the
               fused backends route their dataflow choice through its
               roofline selector (predicted HBM bytes-per-cycle, not just
               VMEM fit), and — unless ``schedule`` pins one — the
               intra-layer order is picked per workload by predicted DMA
               elisions.
    schedule : the thin adapter predating ``policy=``: None/'baseline'
               (plain layer-by-layer index order, jit-friendly), a
               ``MODE_PRESETS`` name ('pointer-1', 'pointer-12',
               'pointer', 'pointer-morton'), an ``{'intra', 'coordinated'}``
               mapping, a prebuilt :class:`ExecutionPlan` (lowered to a
               :class:`DevicePlan` here, once), or a prebuilt — possibly
               batched — :class:`DevicePlan`. Planned schedules execute
               each SA layer in plan order through the ``aggregate_diff``
               gather kernels (fewer DMAs, same logits); device plans are
               jit-safe.
    device_planning : lower plan CONSTRUCTION (not just execution) into
               the trace — Algorithm 1 as jnp ops via
               :func:`~repro.core.schedule.device_build_plan`, so
               ``forward``/``batched_forward`` become one jittable
               cloud→logits function with no per-cloud host work (wrap
               them with ``jit_forward``/``jit_batched_forward``). Default
               ``None`` auto-enables it whenever the schedule allows
               (spec-driven planned schedule, concrete intra mode or a
               single-candidate / :meth:`~repro.core.policy.PlanPolicy.
               precommit`-ted policy, greedy last layer within
               ``GREEDY_DENSE_LIMIT``); ``True`` demands it (``ValueError``
               naming the blocker when it can't hold); ``False`` keeps the
               PR 5 host planning path, which also collects the measured
               DMA stream telemetry the traced path skips.
    """
    if not isinstance(backend, str):
        raise TypeError(f"backend must be a registry name string; got "
                        f"{type(backend).__name__}")
    try:
        cls = _REGISTRY[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; registered backends: "
                         f"{available_backends()}") from None
    if policy is not None and not isinstance(policy, PlanPolicy):
        raise TypeError(f"policy must be a PlanPolicy; got "
                        f"{type(policy).__name__}")
    if fault_model is not None:
        if "fault_model" not in inspect.signature(cls.__init__).parameters:
            raise ValueError(
                f"backend {backend!r} does not support fault injection "
                f"(no fault_model= constructor option — the float path "
                f"has no crossbar cell planes to fault); use a crossbar "
                f"backend such as 'reram' or 'reram-fused'")
        backend_opts["fault_model"] = fault_model
    if schedule is None and policy is not None:
        # the policy owns the ordering decision: per-workload intra choice
        spec = {"intra": "auto", "coordinated": policy.coordinated}
        plan, dplan, planned = None, None, True
    else:
        spec, plan, dplan, planned = _canonical_schedule(schedule, config)
    if planned and dplan is None and spec is not None:
        blocker = _device_planning_blocker(spec, config, policy)
        if device_planning is None:
            device_planning = blocker is None
        elif device_planning and blocker is not None:
            raise ValueError(f"device_planning=True impossible for this "
                             f"schedule: {blocker}")
    else:
        # baseline, or a prebuilt ExecutionPlan/DevicePlan: construction
        # already happened, there is nothing to lower into the trace
        if device_planning:
            raise ValueError(
                "device_planning=True needs a spec-driven planned schedule "
                "(preset name, {'intra', 'coordinated'} mapping, or "
                "policy=); baseline and prebuilt plans have no plan "
                "construction left to lower")
        device_planning = False
    be = cls(params, config, **backend_opts)
    be.name = backend            # the registry entry actually resolved
    be.policy = policy           # dataflow decisions consult the cost model
    return CompiledModel(be, config, spec, plan, planned,
                         device_plan=dplan, policy=policy,
                         device_planning=bool(device_planning))
