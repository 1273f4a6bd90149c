"""The MSG cell's pieces on the CPU: its work counts, its per-layer
metric files on a recorded run, its reference against its control, and
its driver's weights and configuration."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import harness, loop, traffic, work, work_msg  # noqa: E402
from chipbench import trace as tr  # noqa: E402

CELL = "pointnet2-msg-reram.saturate"
MSG_METRICS = ["msg.gather_roofline.tput", "msg.mlp_roofline.tput",
               "msg.step_mfu.tput"]
#: the accepted per-layer metrics the cell reports too: their readers take
#: no work counts, so they read an MSG run as they read the others
SHARED_METRICS = ["plan.device_ms_per_cloud.tput", "device.idle_share.tput",
                  "serve.plan_host_ms_per_step.tput", "serve.launch_ms.tput",
                  "device.idle_in_serve_share.tput"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "pointnet2-msg-reram.json")) as fh:
        return json.load(fh)


def test_msg_flops_and_rows_by_hand(cfg):
    """7.864 GFLOP and 118,784 gathered rows per cloud, branch by branch
    from the published widths."""
    macs = lambda w: sum(k * n for k, n in zip(w[:-1], w[1:]))
    sa1 = [(16, (6, 32, 32, 64)), (32, (6, 64, 64, 128)),
           (128, (6, 64, 96, 128))]
    sa2 = [(32, (323, 64, 64, 128)), (64, (323, 128, 128, 256)),
           (128, (323, 128, 128, 256))]
    total = (sum(512 * k * macs(w) for k, w in sa1)
             + sum(128 * k * macs(w) for k, w in sa2)
             + 128 * macs((643, 256, 512, 1024))
             + macs((1024, 512, 256, 40)))
    assert work_msg.model_flops_per_cloud(cfg) == 2 * total
    assert work_msg.model_flops_per_cloud(cfg) / 1e9 == pytest.approx(
        7.864, abs=5e-4)
    gathers = [s for s in work_msg.stages(cfg) if s["kind"] == "gather"]
    assert [s["rows_written"] for s in gathers] == [
        512 * 16, 512 * 32, 512 * 128, 128 * 32, 128 * 64, 128 * 128]
    assert [s["width"] for s in gathers] == [6] * 3 + [323] * 3
    assert sum(s["rows_written"] for s in gathers) == 118784
    assert [s["rows_read"] for s in gathers][2] == 1024   # at most N
    mlps = [s for s in work_msg.stages(cfg) if s["kind"] == "mlp"]
    assert [s["key"] for s in mlps][-2:] == ["sa_all", "head"]
    assert mlps[-2]["rows"] == mlps[-2]["reduce"] == 128
    # the bound of a batch: each stage's own work, summed
    one = work_msg.kind_bound_seconds(cfg, "gather", [1], 393e12, 819e9)
    assert one == pytest.approx(sum(
        work.stage_work(s)[1] for s in gathers) / 819e9)


def _recorded_run(cfg):
    """A saturate window of three batch-16 steps and a trace holding the
    step's kernels and the builder's program."""
    ev = lambda n, s, d: tr.Event(n, float(s), float(d))
    ops = [(ev("while.3", 0, 2e6), "jit__lambda", "/device:TPU:0"),
           (ev("aggregate_diff_batched.2", 3e6, 40e6), "jit__step",
            "/device:TPU:0"),
           (ev("aggregate_diff_batched.5", 43e6, 10e6), "jit__step",
            "/device:TPU:0"),
           (ev("reram_mlp_fused_mtiled.1", 53e6, 30e6), "jit__step",
            "/device:TPU:0"),
           (ev("reram_mlp_fused_whole.4", 83e6, 8e6), "jit__step",
            "/device:TPU:0")]
    summary = tr.TraceSummary((0.0, 100e6), ops, 91e6, [], [], 1)
    steps = [loop.Step(0.1 * i, 0.1 * i + 0.09, 16) for i in range(3)]
    reqs = [loop.Req(i, 0.0, ready=0.3) for i in range(48)]
    win = loop.Window("closed", end=0.3, reqs=reqs, steps=steps)
    return harness.Run(cfg, win, 30.0, {}, {}, summary, 393e12, 819e9)


def test_msg_metric_files_read_a_recorded_run(cfg):
    run = _recorded_run(cfg)
    got = {n: harness.load_module("metrics", n).read(run)
           for n in MSG_METRICS + SHARED_METRICS[:2]}
    flops = work_msg.model_flops_per_cloud(cfg)
    assert got["msg.step_mfu.tput"] == pytest.approx(
        100 * flops * 48 / (3 * 0.09) / 393e12)
    gather_s = work_msg.kind_bound_seconds(cfg, "gather", [16] * 3,
                                           393e12, 819e9)
    assert got["msg.gather_roofline.tput"] == pytest.approx(
        100 * gather_s / 50e-3)
    mlp_s = work_msg.kind_bound_seconds(cfg, "mlp", [16] * 3, 393e12, 819e9)
    assert got["msg.mlp_roofline.tput"] == pytest.approx(100 * mlp_s / 38e-3)
    assert got["plan.device_ms_per_cloud.tput"] == pytest.approx(
        2e-3 * 1e3 / 48)
    assert got["device.idle_share.tput"] == pytest.approx(9.0)
    assert all(0 < v <= 100 for k, v in got.items() if "plan" not in k)
    # untraced: the trace metrics are left out, never made up
    run = run.__class__(**{**run.__dict__, "trace": None})
    assert harness.load_module("metrics", MSG_METRICS[0]).read(run) is None


def test_msg_cell_reports_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    e2e = [m["name"] for m in harness.cell_metrics(bench, CELL, False)]
    assert e2e == ["clouds_per_s", "setup_s"]
    per = [m["name"] for m in harness.cell_metrics(bench, CELL, True)]
    assert per == SHARED_METRICS + MSG_METRICS
    # the single-scale cells do not read MSG work, nor it theirs
    for cell in ("model2-reram.saturate", "model0-reram.poisson"):
        assert not {m["name"] for m in harness.cell_metrics(
            bench, cell, True)} & set(MSG_METRICS)


def _reference_setup(cfg):
    driver = harness.load_module("drivers", "pointcloud_msg")
    weights = driver.host_weights(driver.make_weights(cfg, 2 ** 31 + 3))
    clouds = traffic.Clouds(cfg["n_points"], 2 ** 31 + 3)
    ref = harness.load_module("references", cfg["reference"])
    return driver, weights, [clouds[0], clouds[1]], ref


def test_control_one_precision_below_is_rejected(cfg):
    """The reference in int4 in the program's place, at the published
    widths, on two clouds: the comparison rejects it by its own limit;
    the reference against itself reads 0."""
    driver, weights, sample, ref = _reference_setup(cfg)
    control = [ref.logits(cfg, weights, c, bits=4) for c in sample]
    gap = driver.check(cfg, weights, sample, control)["logit_gap"]
    assert gap > cfg["check_limits"]["logit_gap"]
    same = [ref.logits(cfg, weights, c, bits=8) for c in sample]
    assert driver.check(cfg, weights, sample, same)["logit_gap"] == 0.0


def test_reference_balls_match_the_program_oracle(cfg):
    """The benchmark's own ball query, written apart, agrees with the
    program's NumPy oracle on a served cloud at every published radius."""
    from repro.core.workload import ball_query_np
    _, _, sample, ref = _reference_setup(cfg)
    pts = sample[0]
    for layer in cfg["layers"][:1]:
        for br in layer["branches"]:
            np.testing.assert_array_equal(
                ref.ball(pts[:64], pts, br["radius"], br["n_neighbors"]),
                ball_query_np(pts[:64], pts, br["radius"],
                              br["n_neighbors"]))


def test_driver_config_is_the_published_model(cfg):
    from repro.core.workload import POINTNET2_MSG
    driver = harness.load_module("drivers", "pointcloud_msg")
    pc = driver.pointnet_config(cfg)
    assert pc.layers == POINTNET2_MSG.layers
    assert pc.group_all == POINTNET2_MSG.group_all
    assert pc.head == POINTNET2_MSG.head
    w = driver.make_weights(cfg, 7)
    assert [len(b) for b in w["sa"]] == [3, 3]
    assert w["sa"][1][2][0]["w"].shape == (323, 128)
    assert w["sa_all"][0]["w"].shape == (643, 256)
    assert w["head"][-1]["w"].shape == (256, 40)
    n = sum(l["w"].size for m in [w["sa_all"], w["head"]] for l in m) + sum(
        l["w"].size for layer in w["sa"] for b in layer for l in b)
    assert n == 1734272           # 1.73 M weights: nothing cut


TINY_MSG = {
    "name": "tiny-msg", "driver": "pointcloud_msg",
    "reference": "pointnet2_msg_int8", "n_points": 64, "in_features": 3,
    "layers": [
        {"n_centers": 16, "branches": [
            {"radius": 0.3, "n_neighbors": 4, "mlp": [6, 8, 16]},
            {"radius": 0.6, "n_neighbors": 8, "mlp": [6, 8, 8]}]},
        {"n_centers": 8, "branches": [
            {"radius": 0.6, "n_neighbors": 4, "mlp": [27, 16, 16]},
            {"radius": 1.2, "n_neighbors": 8, "mlp": [27, 16]}]}],
    "group_all": {"mlp": [35, 32]}, "head": [32, 16, 10],
    "backend": "reram-fused", "schedule": "pointer", "arithmetic": "int8",
    "batch_buckets": [1, 2, 4], "check_sample": 4,
    "check_limits": {"logit_gap": 0.03}}


def test_closed_loop_run_of_tiny_msg_is_correct():
    """A whole run through the harness, the MSG driver and its reference,
    in interpret mode (the look for a chip skipped)."""
    metrics = [{"name": n, "unit": "x"} for n in
               ["clouds_per_s", "setup_s", "msg.step_mfu.tput",
                "msg.gather_roofline.tput"]]
    cell = harness.Cell("tiny-msg", 1, TINY_MSG,
                        {"loop": "closed", "outstanding_batches": 2},
                        metrics)
    line = harness.run_cell(cell, 2 ** 31 + 9, 1.0, False, 0.0,
                            device_kind="cpu",
                            peak_table={"int8_ops": 1e12,
                                        "hbm_bytes_per_s": 1e11})
    assert line["correct"] is True and line["failed"] == 0
    assert {"clouds_per_s", "setup_s", "msg.step_mfu.tput"} <= set(
        line["metrics"])
    assert "msg.gather_roofline.tput" not in line["metrics"]
    assert line["checks"]["logit_gap"]["value"] <= 1e-5
