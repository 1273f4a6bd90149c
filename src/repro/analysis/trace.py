"""Trace-contract verifier: statically check a :class:`CompiledModel`'s
compiled artifacts against its declared launch/purity contracts.

Every structural claim the repro makes about its compiled pipelines used
to be enforced by monkeypatching kernel entry points and counting calls
(``tests/test_backend.py`` pre-PR 10) — fragile, private, and only
exercised where a test happened to look. The properties are facts about
the *trace*, so this module reads them off the trace:

  * the jaxpr of ``forward``/``batched_forward`` (``jax.make_jaxpr``):
    every ``pallas_call`` equation carries its kernel name (the kernels
    name their launch sites explicitly), so "exactly ``n_layers`` gather
    launches, never the per-cloud kernel in a batched path" is a count
    over equations;
  * the optimized HLO of the jitted function (reusing
    ``launch/hlo_analysis``'s parser): host-callback custom-calls and
    f64 creep survive to — and are checked in — the artifact XLA
    actually runs;
  * the fused launch plans the trace pinned (``FusedPlan``): every
    planned ``pallas_call``'s VMEM residency stays under budget.

:func:`verify_contracts` is the public API (``repro.verify_contracts``);
``tools/check_static.py`` runs it over the bench model configs in CI.
Violations name the offending primitive and SA layer.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable, Iterator

import jax
import numpy as np
from jax.extend import core as jex_core

from repro.kernels.program import VMEM_BUDGET_BYTES
from repro.launch import hlo_analysis as _ha

__all__ = [
    "CONTRACTS",
    "ContractReport",
    "ContractViolation",
    "LaunchRecord",
    "TraceInfo",
    "trace_info",
    "verify_contracts",
]

#: the contract set, in check order
CONTRACTS = ("traceable", "gather-launches", "mlp-launches",
             "host-callbacks", "f64", "vmem-budget")

#: jaxpr primitives that round-trip through the host at run time
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback_call"}

#: kernel-name prefix -> launch kind (kernels name their pallas_call
#: sites explicitly; see kernels/aggregate.py etc.)
_KIND_PREFIXES = (
    ("aggregate_diff_batched", "gather-batched"),
    ("aggregate_diff", "gather"),
    ("reram_mlp_fused", "mlp"),
    ("reram_matmul_int", "linear"),
    ("fps_update", "geometry"),
)


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ContractViolation:
    """One broken contract, naming the offending primitive and (when the
    contract is per-layer) the SA layer index (head = ``n_layers``)."""

    contract: str
    message: str
    primitive: str | None = None
    layer: int | None = None

    def __str__(self) -> str:
        where = "".join([
            f" [primitive={self.primitive}]" if self.primitive else "",
            f" [layer={self.layer}]" if self.layer is not None else "",
        ])
        return f"[{self.contract}]{where} {self.message}"


@dataclasses.dataclass(frozen=True)
class LaunchRecord:
    """One ``pallas_call`` equation in the trace, in execution order."""

    name: str           # kernel name from the launch site
    kind: str           # gather / gather-batched / mlp / linear / ...
    out_shape: tuple    # first output aval shape (batched gathers lead
                        # with the batch dim — the one-launch-per-layer
                        # proof that the whole batch rode one launch)


@dataclasses.dataclass(frozen=True)
class TraceInfo:
    """Counts read off the jaxpr."""

    launches: tuple[LaunchRecord, ...]
    host_callbacks: tuple[str, ...]
    f64_primitives: tuple[str, ...]
    primitive_counts: dict[str, int]

    @property
    def gather_launches(self) -> int:
        return sum(l.kind in ("gather", "gather-batched")
                   for l in self.launches)

    @property
    def mlp_launches(self) -> int:
        return sum(l.kind == "mlp" for l in self.launches)

    def launches_of(self, kind: str) -> list[LaunchRecord]:
        return [l for l in self.launches if l.kind == kind]


@dataclasses.dataclass
class ContractReport:
    """Everything :func:`verify_contracts` measured plus the violations.
    ``ok`` is the gate; ``raise_if_violated`` formats a hard failure."""

    backend: str
    schedule: dict
    expected_gather_launches: int
    info: TraceInfo | None
    hlo: dict | None
    vmem_rows: dict[str, dict]
    violations: list[ContractViolation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def raise_if_violated(self) -> "ContractReport":
        if self.violations:
            lines = "\n  ".join(str(v) for v in self.violations)
            raise AssertionError(
                f"trace contracts violated for backend "
                f"'{self.backend}':\n  {lines}")
        return self

    def summary(self) -> dict:
        return {
            "backend": self.backend,
            "schedule": self.schedule,
            "gather_launches": None if self.info is None
            else self.info.gather_launches,
            "expected_gather_launches": self.expected_gather_launches,
            "mlp_launches": None if self.info is None
            else self.info.mlp_launches,
            "host_callbacks": [] if self.info is None
            else list(self.info.host_callbacks),
            "hlo": self.hlo,
            "vmem_rows": self.vmem_rows,
            "violations": [str(v) for v in self.violations],
            "ok": self.ok,
        }


# ---------------------------------------------------------------------------
# jaxpr layer
# ---------------------------------------------------------------------------

def _subjaxprs(value: Any) -> Iterator[Any]:
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations, recursing through pjit/scan/while/cond bodies but
    NOT into a pallas_call's kernel jaxpr (the kernel body is the launch's
    interior, not part of the host-visible program)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _iter_eqns(sub)


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    if info is not None:
        return str(info).split(" ")[0]
    return str(eqn.params.get("name", "<unnamed>"))


def _kind_of(name: str) -> str:
    for prefix, kind in _KIND_PREFIXES:
        if name.startswith(prefix):
            return kind
    return "other"


def trace_info(fn: Callable, *args) -> TraceInfo:
    """Trace ``fn(*args)`` to a jaxpr and read off launch records,
    host-callback primitives, and f64-producing equations."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    launches: list[LaunchRecord] = []
    callbacks: list[str] = []
    f64: list[str] = []
    counts: Counter = Counter()
    for eqn in _iter_eqns(jaxpr):
        pname = eqn.primitive.name
        counts[pname] += 1
        if pname == "pallas_call":
            kname = _kernel_name(eqn)
            shape = (tuple(eqn.outvars[0].aval.shape)
                     if eqn.outvars else ())
            launches.append(LaunchRecord(kname, _kind_of(kname), shape))
        if pname in _CALLBACK_PRIMS or "callback" in pname:
            callbacks.append(pname)
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and dt == np.dtype("float64"):
                f64.append(f"{pname} -> f64{tuple(v.aval.shape)}")
    return TraceInfo(tuple(launches), tuple(callbacks), tuple(f64),
                     dict(counts))


# ---------------------------------------------------------------------------
# HLO layer (reuses launch/hlo_analysis's parser)
# ---------------------------------------------------------------------------

#: custom-call targets that are host round-trips (XLA:CPU also emits
#: benign numeric custom-calls, e.g. topk — those are device-side)
_HOST_CALL_MARKERS = ("callback", "xla_python", "py_func", "host")


def hlo_contract_scan(hlo_text: str) -> dict:
    """Scan optimized HLO for host-callback custom-calls and f64 buffers,
    via :func:`repro.launch.hlo_analysis._parse_computations`."""
    comps = _ha._parse_computations(hlo_text)
    host_calls: list[str] = []
    f64_instrs: list[str] = []
    n_instr = 0
    for cname, instrs in comps.items():
        for ins in instrs:
            n_instr += 1
            if ins.opcode == "custom-call":
                target = ins.attrs.lower()
                if any(m in target for m in _HOST_CALL_MARKERS):
                    host_calls.append(f"{cname}:{ins.name}")
            for dt, _dims in _ha._TYPE_RE.findall(ins.result_type):
                if dt == "f64":
                    f64_instrs.append(
                        f"{cname}:{ins.name} = {ins.result_type} "
                        f"{ins.opcode}")
    return {"instructions": n_instr, "host_custom_calls": host_calls,
            "f64_instructions": f64_instrs}


# ---------------------------------------------------------------------------
# the verifier
# ---------------------------------------------------------------------------

def _gather_contract(info: TraceInfo, expected: int,
                     n_layers: int) -> list[ContractViolation]:
    out: list[ContractViolation] = []
    gathers = [l for l in info.launches
               if l.kind in ("gather", "gather-batched")]
    if len(gathers) != expected:
        if len(gathers) > expected:
            extra = gathers[expected]
            out.append(ContractViolation(
                "gather-launches",
                f"expected exactly {expected} gather launch(es) — one per "
                f"SA layer — but the trace has {len(gathers)}; launch "
                f"#{expected + 1} ('{extra.name}') has no SA layer",
                primitive=extra.name, layer=min(expected, n_layers)))
        else:
            out.append(ContractViolation(
                "gather-launches",
                f"expected exactly {expected} gather launch(es) — one per "
                f"SA layer — but the trace has only {len(gathers)}; SA "
                f"layer {len(gathers)} issues no gather",
                primitive="aggregate_diff_batched",
                layer=len(gathers)))
    return out


def _batched_purity(info: TraceInfo, batch: int | None,
                    expected: int) -> list[ContractViolation]:
    """In a batched trace the per-cloud gather kernel must never appear,
    and every batched gather must carry the whole batch in its grid."""
    out: list[ContractViolation] = []
    if batch is None:
        return out
    for i, l in enumerate(info.launches_of("gather")):
        out.append(ContractViolation(
            "gather-launches",
            f"per-cloud gather kernel '{l.name}' in a batched trace — the "
            f"batch must ride ONE batch-gridded launch per SA layer",
            primitive=l.name, layer=i))
    for i, l in enumerate(info.launches_of("gather-batched")):
        if l.out_shape and l.out_shape[0] != batch:
            out.append(ContractViolation(
                "gather-launches",
                f"batched gather launch #{i + 1} carries batch "
                f"{l.out_shape[0]}, expected the full batch of {batch}",
                primitive=l.name, layer=i))
    return out


def _vmem_contract(model, budget: int) -> tuple[dict, list[ContractViolation]]:
    rows: dict[str, dict] = {}
    violations: list[ContractViolation] = []
    cache = getattr(model.backend, "_plan_cache", None)
    if not cache:
        return rows, violations
    from repro.core.workload import mlp_label
    n_layers = model.config.n_layers
    for (key, m_rows), fp in sorted(cache.items(), key=lambda kv: str(kv[0])):
        label = mlp_label(key)
        rows[f"{label}@{m_rows}"] = {
            "mode": fp.mode, "vmem_bytes": fp.vmem_bytes,
            "fits_budget": fp.fits_budget}
        if fp.vmem_bytes > budget:
            layer = key[1] if isinstance(key, tuple) else n_layers
            violations.append(ContractViolation(
                "vmem-budget",
                f"fused plan for MLP '{label}' at {m_rows} rows "
                f"(mode={fp.mode}) needs {fp.vmem_bytes} B of VMEM, over "
                f"the {budget} B budget",
                primitive=f"reram_mlp_fused_{fp.mode}", layer=layer))
    return rows, violations


def _gather_launches(config, batch: int | None) -> int:
    """The gather launches a planned forward makes: one per SA layer and
    branch, times the chunks its index stream takes."""
    from repro.kernels.aggregate import index_chunks
    from repro.models.pointnet2 import branch_bounds
    return sum(len(index_chunks(batch or 1, spec.n_centers, hi - lo))
               for spec in config.layers for lo, hi in branch_bounds(spec))


def verify_contracts(model, x, *, rules: tuple = CONTRACTS,
                     expected_gather_launches: int | None = None,
                     vmem_budget: int = VMEM_BUDGET_BYTES,
                     check_hlo: bool = False) -> ContractReport:
    """Statically verify ``model``'s trace contracts on example input
    ``x`` ((N, 3) cloud -> ``forward``; (B, N, 3) -> ``batched_forward``).

    Checks (select with ``rules``):

      * ``traceable``      — the pipeline traces end to end under
        ``jax.make_jaxpr`` (host-planning fallbacks violate this by
        design: their plan is built from concrete geometry);
      * ``gather-launches``— exactly one gather launch per SA layer (per
        branch of a multi-scale layer; more where an index stream is
        fetched in chunks, ``kernels.aggregate.index_chunks``) for a
        planned model (0 for baseline), batch-gridded with the full
        batch and never the per-cloud kernel in a batched trace;
      * ``mlp-launches``   — batch-in-grid backends fuse each MLP into
        ONE launch: one per SA MLP (per branch), the group-all MLP and
        the head (``PointNetConfig.mlp_keys``);
      * ``host-callbacks`` — zero host-callback primitives in the jaxpr
        (and, with ``check_hlo=True``, zero callback custom-calls in the
        optimized HLO);
      * ``f64``            — no float64 creep in the jaxpr (or HLO);
      * ``vmem-budget``    — every fused launch plan the trace pinned
        fits ``vmem_budget``.

    ``check_hlo=True`` additionally compiles the jitted function and
    scans the optimized HLO through ``launch/hlo_analysis``'s parser —
    slower, but it checks the artifact XLA actually runs. Returns a
    :class:`ContractReport`; violations name the offending primitive and
    SA layer.
    """
    x = np.asarray(x) if not hasattr(x, "ndim") else x
    if x.ndim == 3:
        fn, batch = model.batched_forward, int(x.shape[0])
    elif x.ndim == 2:
        fn, batch = model.forward, None
    else:
        raise ValueError(f"x must be a (N, 3) cloud or (B, N, 3) batch; "
                         f"got shape {tuple(x.shape)}")
    n_layers = model.config.n_layers
    if expected_gather_launches is None:
        expected_gather_launches = (_gather_launches(model.config, batch)
                                    if model.planned else 0)
    report = ContractReport(
        backend=model.backend_name, schedule=model.schedule,
        expected_gather_launches=expected_gather_launches,
        info=None, hlo=None, vmem_rows={}, violations=[])

    try:
        info = trace_info(fn, x)
    except (TypeError, jax.errors.TracerArrayConversionError) as e:
        report.violations.append(ContractViolation(
            "traceable",
            f"{fn.__name__} does not trace end to end: {e}"))
        return report
    report.info = info

    if "gather-launches" in rules:
        report.violations += _gather_contract(
            info, expected_gather_launches, n_layers)
        report.violations += _batched_purity(info, batch,
                                             expected_gather_launches)
    if "mlp-launches" in rules and model.backend.batched_in_grid:
        expected_mlp = len(model.config.mlp_keys())
        if info.mlp_launches != expected_mlp:
            report.violations.append(ContractViolation(
                "mlp-launches",
                f"batch-in-grid backend must fuse each MLP into ONE "
                f"launch: expected {expected_mlp} fused-MLP launches "
                f"({expected_mlp - 1} SA + head), got {info.mlp_launches}",
                primitive="reram_mlp_fused",
                layer=min(info.mlp_launches, n_layers)))
    if "host-callbacks" in rules:
        for prim in info.host_callbacks:
            report.violations.append(ContractViolation(
                "host-callbacks",
                f"host-callback primitive '{prim}' in the trace — the "
                f"compiled pipeline must not round-trip through Python",
                primitive=prim))
    if "f64" in rules:
        for entry in info.f64_primitives:
            report.violations.append(ContractViolation(
                "f64", f"float64 creep in the trace: {entry}",
                primitive=entry.split(" ")[0]))
    if "vmem-budget" in rules:
        report.vmem_rows, v = _vmem_contract(model, vmem_budget)
        report.violations += v

    if check_hlo:
        hlo_text = jax.jit(fn).lower(x).compile().as_text()
        scan = hlo_contract_scan(hlo_text)
        report.hlo = {k: (len(v) if isinstance(v, list) else v)
                      for k, v in scan.items()}
        if "host-callbacks" in rules:
            for name in scan["host_custom_calls"]:
                report.violations.append(ContractViolation(
                    "host-callbacks",
                    f"host-callback custom-call '{name}' survives in the "
                    f"optimized HLO", primitive=name))
        if "f64" in rules:
            for entry in scan["f64_instructions"]:
                report.violations.append(ContractViolation(
                    "f64", f"float64 buffer in optimized HLO: {entry}",
                    primitive=entry.split(" ")[0]))
    return report
