"""Work functions, the peaks table, and the shape of BENCHMARK.json."""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import harness, peaks, readers, work  # noqa: E402


def _cfg(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name, gflop", [("model0-reram", 0.474),
                                         ("model2-reram", 7.58)])
def test_model_flops_match_hand_figures(name, gflop):
    assert work.model_flops_per_cloud(_cfg(name)) / 1e9 == \
        pytest.approx(gflop, abs=5e-3)


def test_model0_flops_by_hand():
    sa0 = 512 * 16 * (4 * 64 + 64 * 64 + 64 * 128)
    sa1 = 128 * 16 * (128 * 128 + 128 * 128 + 128 * 256)
    head = 256 * 256 + 256 * 40
    assert work.model_flops_per_cloud(_cfg("model0-reram")) == \
        2 * (sa0 + sa1 + head)


def test_stage_bytes():
    cfg = _cfg("model2-reram")
    st = {(s["kind"], s["key"]): s for s in work.stages(cfg)}
    g0 = st[("gather", "sa0")]
    # reads at most every input point once, writes M*K rows of 16 floats
    assert work.stage_work(g0) == (0.0, (1024 + 512 * 16) * 16 * 4)
    g1 = st[("gather", "sa1")]
    assert work.stage_work(g1, 2) == (0.0, 2 * (512 + 128 * 16) * 512 * 4)
    ops, nbytes = work.stage_work(st[("mlp", "sa1")], 3)
    macs = 512 * 512 + 512 * 512 + 512 * 1024
    assert ops == 2 * 3 * 128 * 16 * macs
    # int8 rows in, int8 weights once per launch, f32 per-centre rows out
    assert nbytes == 3 * (128 * 16 * 512 + 128 * 1024 * 4) + macs
    head_ops, _ = work.stage_work(st[("mlp", "head")])
    assert head_ops == 2 * (1024 * 256 + 256 * 40)


def test_roofline_bound_is_the_larger_bound():
    assert work.bound_seconds(2e12, 1e9, 1e12, 1e12) == 2.0
    assert work.bound_seconds(1e12, 3e12, 1e12, 1e12) == 3.0
    cfg = _cfg("model0-reram")
    one = work.kind_bound_seconds(cfg, "mlp", [1], 393e12, 819e9)
    assert work.kind_bound_seconds(cfg, "mlp", [1, 1, 0], 393e12, 819e9) \
        == pytest.approx(2 * one)
    # weights are read once per launch, so a batch is bound below n x one
    assert work.kind_bound_seconds(cfg, "mlp", [32], 393e12, 819e9) <= \
        32 * one


def test_peaks_table():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["int8_ops"] == 393e12 and row["bf16_flops"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert peaks.compute_peak_from(row, "int8") == 393e12
    with pytest.raises(peaks.UnknownDevice, match="cpu"):
        peaks.peaks_for("cpu")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_is_well_formed(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1].startswith("benchmarks/chip/")
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert 0 < len(c["why"]) <= 200
        assert c["file"].startswith("benchmarks/chip/configs/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    cells = {w["name"]: w for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] == 1
        assert 0 < len(w["why"]) <= 200
        assert os.path.exists(os.path.join(HERE, "traffic",
                                           f"{w['traffic']}.json"))
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py"))
        assert set(m.get("workloads", [])) <= set(cells)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        if "_roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric, and every per-layer metric's cells report what
    # it moves
    for name in cells:
        e = {m["name"] for m in harness.cell_metrics(bench, name, False)}
        p = harness.cell_metrics(bench, name, True)
        assert "setup_s" in e and len(e) >= 2 and p
        assert all(m["moves"] in e for m in p)


def test_every_metric_file_reads(bench):
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    names |= {f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
              if f.endswith(".py")}
    for name in names:
        assert callable(harness.load_module("metrics", name).read)
    assert readers.STEP_MODULE.search("jit__step")
    assert not readers.STEP_MODULE.search("jit__lambda")
    assert readers.BUILD_MODULE.search("jit__lambda")
    assert not readers.BUILD_MODULE.search("jit__step")
