"""repro.analysis.trace: the trace-contract verifier.

Two obligations, tested here:
  * on the REAL compiled models (float / reram-fused × device / host
    planning, per-cloud and batched) the declared contracts hold — the
    public replacement for test_backend.py's old monkeypatch counters;
  * a seeded regression of each contract class (extra gather launch,
    host callback, f64 creep, VMEM budget, untraceable host planning)
    is caught, and the violation names the offending primitive and
    layer — a verifier that can't fail is not a verifier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_model
from repro.analysis import (CONTRACTS, ContractViolation, trace_info,
                            verify_contracts)
from repro.core.workload import PointNetConfig, SALayerSpec
from repro.kernels.aggregate import aggregate_diff_batched
from repro.models import pointnet2 as pn


def tiny_config(n=64, c1=24, c2=8, k=4):
    return PointNetConfig(name="tiny", n_points=n, layers=(
        SALayerSpec(n_centers=c1, n_neighbors=k, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=c2, n_neighbors=k, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config()
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    cloud = jnp.asarray(np.random.default_rng(1).normal(size=(64, 3)),
                        jnp.float32)
    return cfg, params, cloud


def device_model(setup, backend):
    cfg, params, _ = setup
    return compile_model(params, cfg, backend=backend, schedule="pointer",
                         device_planning=True)


class _Proxy:
    """A CompiledModel stand-in whose forward/batched_forward are
    replaced — how the regression tests inject contract breakage without
    monkeypatching library internals."""

    def __init__(self, model, forward=None, batched_forward=None):
        self.forward = forward if forward is not None else model.forward
        self.batched_forward = (batched_forward if batched_forward
                                is not None else model.batched_forward)
        self.config = model.config
        self.backend = model.backend
        self.backend_name = model.backend_name
        self.schedule = model.schedule
        self.planned = model.planned


# ---------------------------------------------------------------------------
# the real models honor their contracts
# ---------------------------------------------------------------------------

class TestContractsHold:
    @pytest.mark.parametrize("backend", ["float", "reram-fused"])
    def test_device_planned_forward_and_batched(self, setup, backend):
        _, _, cloud = setup
        m = device_model(setup, backend)
        for x in (cloud, jnp.stack([cloud] * 3)):
            report = verify_contracts(m, x)
            report.raise_if_violated()
            assert report.info.gather_launches == m.config.n_layers
            assert report.info.host_callbacks == ()
            assert report.info.f64_primitives == ()

    def test_batched_gathers_carry_the_full_batch(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(m, jnp.stack([cloud] * 4))
        report.raise_if_violated()
        recs = report.info.launches_of("gather-batched")
        assert len(recs) == m.config.n_layers
        assert all(r.out_shape[0] == 4 for r in recs)
        # and the per-cloud gather kernel never appears in a batched trace
        assert report.info.launches_of("gather") == []

    def test_fused_backend_one_mlp_launch_per_layer_plus_head(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(m, jnp.stack([cloud] * 2))
        report.raise_if_violated()
        assert report.info.mlp_launches == m.config.n_layers + 1

    def test_baseline_schedule_issues_zero_gathers(self, setup):
        cfg, params, cloud = setup
        m = compile_model(params, cfg, backend="float", schedule="baseline")
        report = verify_contracts(m, cloud)
        report.raise_if_violated()
        assert report.expected_gather_launches == 0
        assert report.info.gather_launches == 0

    def test_host_planned_model_violates_traceable_by_design(self, setup):
        cfg, params, cloud = setup
        m = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer", device_planning=False)
        report = verify_contracts(m, cloud)
        assert not report.ok
        assert [v.contract for v in report.violations] == ["traceable"]

    def test_hlo_scan_clean_on_device_planned_model(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "float")
        report = verify_contracts(m, cloud, check_hlo=True)
        report.raise_if_violated()
        assert report.hlo["instructions"] > 0
        assert report.hlo["host_custom_calls"] == 0
        assert report.hlo["f64_instructions"] == 0

    def test_vmem_rows_populated_for_fused_backend(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(m, cloud)
        assert set(report.vmem_rows)  # head + both SA MLPs traced
        assert all(r["fits_budget"] for r in report.vmem_rows.values())

    def test_summary_is_json_ready(self, setup):
        import json
        _, _, cloud = setup
        report = verify_contracts(device_model(setup, "float"), cloud)
        assert json.loads(json.dumps(report.summary()))["ok"] is True


# ---------------------------------------------------------------------------
# seeded regressions: each contract class must be CATCHABLE
# ---------------------------------------------------------------------------

def violations_of(report, contract):
    return [v for v in report.violations if v.contract == contract]


class TestSeededRegressions:
    def test_extra_gather_launch_is_flagged_with_layer(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "float")
        nbr = jnp.zeros((1, 4, 4), jnp.int32)
        ctr = jnp.zeros((1, 4), jnp.int32)

        def leaky_batched(x):
            out = m.batched_forward(x)
            feats = jnp.zeros((1, 64, out.shape[-1]), out.dtype)
            extra = aggregate_diff_batched(feats, nbr, ctr)
            return out + jnp.sum(extra) * 0.0

        report = verify_contracts(_Proxy(m, batched_forward=leaky_batched),
                                  jnp.stack([cloud] * 2))
        vs = violations_of(report, "gather-launches")
        assert vs, report.violations
        # the violation names the offending kernel and the phantom layer
        assert vs[0].primitive.startswith("aggregate_diff")
        assert vs[0].layer == m.config.n_layers

    def test_missing_gather_launch_is_flagged(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "float")
        report = verify_contracts(m, cloud,
                                  expected_gather_launches=3)
        vs = violations_of(report, "gather-launches")
        assert vs and vs[0].layer == 2  # SA layer 2 issues no gather

    def test_host_callback_is_flagged_by_primitive_name(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "float")

        def chatty_forward(x):
            y = m.forward(x)
            probe = jax.pure_callback(
                lambda a: np.asarray(a, np.float32),
                jax.ShapeDtypeStruct(y.shape, y.dtype), y)
            return y + probe * 0.0

        report = verify_contracts(_Proxy(m, forward=chatty_forward), cloud)
        vs = violations_of(report, "host-callbacks")
        assert vs and "pure_callback" in vs[0].primitive

    def test_f64_creep_is_flagged(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "float")

        def promoted_forward(x):
            return m.forward(x).astype(jnp.float64)

        with jax.enable_x64(True):
            report = verify_contracts(_Proxy(m, forward=promoted_forward),
                                      jnp.asarray(np.asarray(cloud),
                                                  jnp.float32))
        vs = violations_of(report, "f64")
        assert vs and "f64" in vs[0].message

    def test_vmem_budget_breach_names_the_mlp_layer(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(m, cloud, vmem_budget=1)
        vs = violations_of(report, "vmem-budget")
        assert len(vs) == len(report.vmem_rows)
        assert {v.layer for v in vs} <= set(range(m.config.n_layers + 1))
        assert all(v.primitive.startswith("reram_mlp_fused") for v in vs)

    def test_rule_selection_masks_contracts(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(
            m, cloud, vmem_budget=1,
            rules=tuple(c for c in CONTRACTS if c != "vmem-budget"))
        assert report.ok

    def test_raise_if_violated_formats_all_violations(self, setup):
        _, _, cloud = setup
        m = device_model(setup, "reram-fused")
        report = verify_contracts(m, cloud, vmem_budget=1)
        with pytest.raises(AssertionError, match="vmem-budget"):
            report.raise_if_violated()

    def test_bad_input_rank_rejected(self, setup):
        m = device_model(setup, "float")
        with pytest.raises(ValueError, match="cloud"):
            verify_contracts(m, jnp.zeros((4,)))


# ---------------------------------------------------------------------------
# the low-level trace reader
# ---------------------------------------------------------------------------

class TestTraceInfo:
    def test_counts_launches_through_pjit_nesting(self):
        feats = jnp.zeros((1, 8, 4), jnp.float32)
        nbr = jnp.zeros((1, 4, 2), jnp.int32)
        ctr = jnp.zeros((1, 4), jnp.int32)

        def two(f):
            inner = jax.jit(lambda a: aggregate_diff_batched(a, nbr, ctr))
            return inner(f), aggregate_diff_batched(f, nbr, ctr)

        info = trace_info(two, feats)
        assert info.gather_launches == 2
        assert all(r.name == "aggregate_diff_batched"
                   for r in info.launches)

    def test_no_pallas_means_no_launches(self):
        info = trace_info(lambda x: x * 2 + 1, jnp.zeros((3,)))
        assert info.launches == ()
        assert info.host_callbacks == ()

    def test_violation_str_carries_primitive_and_layer(self):
        v = ContractViolation("gather-launches", "boom",
                              primitive="aggregate_diff", layer=1)
        assert "aggregate_diff" in str(v) and "layer=1" in str(v)


# ---------------------------------------------------------------------------
# the CLI front door + baseline workflow
# ---------------------------------------------------------------------------

class TestCheckStaticCLI:
    @pytest.fixture()
    def check_static(self):
        import importlib.util
        import pathlib
        tools = pathlib.Path(__file__).resolve().parents[1] / "tools"
        spec = importlib.util.spec_from_file_location(
            "check_static", tools / "check_static.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_baseline_grandfathers_until_findings_grow(self, check_static,
                                                       tmp_path, capsys):
        bad = tmp_path / "svc.py"
        bad.write_text("import time\nt = time.time()\n")
        base = tmp_path / "baseline.json"

        # 1. a fresh finding is NEW -> strict fails
        argv = [str(bad), "--baseline", str(base), "--no-trace", "--strict"]
        assert check_static.main(argv) == 1
        # 2. grandfather it -> strict passes
        assert check_static.main(argv + ["--update-baseline"]) == 1
        assert check_static.main(argv) == 0
        # 3. the same class GROWING fails again
        bad.write_text("import time\nt = time.time()\nu = time.time()\n")
        assert check_static.main(argv) == 1
        out = capsys.readouterr().out
        assert "NEW" in out and "wall-clock" in out

    def test_nonstrict_reports_but_exits_zero(self, check_static, tmp_path):
        bad = tmp_path / "svc.py"
        bad.write_text("import time\nt = time.time()\n")
        argv = [str(bad), "--baseline", str(tmp_path / "b.json"),
                "--no-trace"]
        assert check_static.main(argv) == 0

    def test_json_report_shape(self, check_static, tmp_path):
        import json
        ok = tmp_path / "clean.py"
        ok.write_text("x = 1\n")
        out = tmp_path / "report.json"
        argv = [str(ok), "--baseline", str(tmp_path / "b.json"),
                "--no-trace", "--strict", "--json-out", str(out)]
        assert check_static.main(argv) == 0
        rep = json.loads(out.read_text())
        assert rep["ok"] is True and rep["lint"]["findings"] == []
