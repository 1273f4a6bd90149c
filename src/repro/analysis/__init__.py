"""Static contract analysis: project lint rules + trace-contract
verification over compiled models.

Two layers, one front door (``tools/check_static.py``, the CI gate):

* :mod:`repro.analysis.lint` — AST linter whose rules encode this
  repo's actual bug history (wall-clock in deterministic tiers,
  unseeded randomness, host sync reachable from jitted paths, a Pallas
  ``interpret=`` flag not derived from the platform, bare excepts,
  unfrozen pytree dataclasses).
  Rules live in a decorator registry (:func:`register_rule`) like the
  backend registry, so new bug classes become new rules.
* :mod:`repro.analysis.trace` — lowers a ``CompiledModel`` to jaxpr /
  optimized HLO and checks the declared launch contracts: exactly
  ``n_layers`` gather launches, zero host callbacks, no f64 creep,
  fused-plan VMEM under budget. :func:`verify_contracts` replaces the
  monkeypatch launch-count assertions that used to live in
  ``tests/test_backend.py``.
"""
from repro.analysis.lint import (Finding, LintRule, RULES, lint_paths,
                                 lint_source, register_rule)
from repro.analysis.trace import (CONTRACTS, ContractReport,
                                  ContractViolation, LaunchRecord,
                                  TraceInfo, hlo_contract_scan,
                                  trace_info, verify_contracts)

__all__ = [
    "CONTRACTS",
    "ContractReport",
    "ContractViolation",
    "Finding",
    "LaunchRecord",
    "LintRule",
    "RULES",
    "TraceInfo",
    "hlo_contract_scan",
    "lint_paths",
    "lint_source",
    "register_rule",
    "trace_info",
    "verify_contracts",
]
