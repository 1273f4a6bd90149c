"""A rehearsal of the benchmark on the CPU: the seeded generators, the
open- and closed-loop drivers and their arithmetic, a whole run of a tiny
configuration in interpret mode (the look for a chip skipped), the same
run with the served answers altered, and the control: the plain
reference one precision below the stated one, which the comparison must
reject."""
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import harness, loop, traffic  # noqa: E402

TINY = {"name": "tiny", "driver": "pointcloud", "reference": "pointnet2_int8",
        "n_points": 64,
        "layers": [{"n_centers": 16, "n_neighbors": 4, "mlp": [4, 8, 8, 16]},
                   {"n_centers": 8, "n_neighbors": 4,
                    "mlp": [16, 16, 16, 32]}],
        "head": [32, 16, 10], "backend": "reram-fused", "schedule": "pointer",
        "arithmetic": "int8", "batch_buckets": [1, 2, 4], "check_sample": 4,
        "check_limits": {"logit_gap": 0.03}}
FAKE_PEAKS = {"int8_ops": 1e12, "hbm_bytes_per_s": 1e11}


# -- generators ---------------------------------------------------------------

def test_clouds_are_seeded_fresh_and_repeatable():
    a = traffic.Clouds(64, 2 ** 31 + 11)
    b = traffic.Clouds(64, 2 ** 31 + 11)
    c = traffic.Clouds(64, 2 ** 31 + 12)
    for i in (0, 3, 300):
        assert a[i].shape == (64, 3) and a[i].dtype == np.float32
        assert np.array_equal(a[i], b[i])
        assert not np.array_equal(a[i], c[i])
    # fresh: no two requests share a cloud, though they share base objects
    keys = {a[i].tobytes() for i in range(2 * traffic.POOL)}
    assert len(keys) == 2 * traffic.POOL
    warm = traffic.Clouds(64, 2 ** 31 + 11,
                          stream=traffic.WARMUP)
    assert not any(np.array_equal(warm[i], a[i]) for i in range(8))


def test_poisson_arrivals_fixed_count_and_rate():
    spec = {"kind": "poisson", "rate_per_s": 150}
    due = traffic.arrivals(spec, 20.0, 5)
    assert len(due) == 3000
    assert np.array_equal(due, traffic.arrivals(spec, 20.0, 5))
    other = traffic.arrivals(spec, 20.0, 6)
    assert not np.array_equal(due, other)
    assert due[0] == 0.0 and due[-1] < 20.0 and np.all(np.diff(due) > 0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / 150, rel=0.01)
    # exponential gaps: standard deviation equal to the mean
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # every seed: the same exponential quantiles, in another order
    k = np.arange(3000)
    base = -np.log1p(-(k + 0.5) / 3000)
    base *= 20.0 / base.sum()
    for g in (gaps, np.diff(other)):
        assert np.abs(base[None, :] - g[:, None]).min(axis=1).max() < 1e-9


def test_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError, match="unknown arrival kind"):
        traffic.arrivals({"kind": "bursts"}, 1.0, 0)


# -- the loops, on a fake system and a fake clock -------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeSystem:
    """Serves up to ``max_batch`` queued requests per step; each step
    takes ``base + per * n`` seconds on the fake clock."""

    def __init__(self, clock, max_batch=4, base=0.01, per=0.001):
        self.clock, self.max_batch = clock, max_batch
        self.base, self.per = base, per
        self.queue = []

    def submit(self, payload):
        h = {"payload": payload}
        self.queue.append(h)
        return h

    def step(self):
        batch, self.queue = (self.queue[:self.max_batch],
                             self.queue[self.max_batch:])
        self.clock.t += self.base + self.per * len(batch)
        return batch

    def block(self, handles):
        pass


def test_open_loop_latency_runs_from_due():
    clock = FakeClock()
    sys_ = FakeSystem(clock)
    due = np.array([0.0, 0.001, 0.002, 0.5, 0.5, 1.0])
    win = loop.open_loop(sys_, list(range(6)), due, clock=clock,
                         sleep=clock.sleep, spans=False)
    assert [r.i for r in win.served] == list(range(6))
    # request 0 alone: 11 ms; 1 and 2 waited for it, then one step of two
    assert win.reqs[0].latency == pytest.approx(0.011)
    assert win.reqs[1].submit == pytest.approx(0.011)
    assert win.reqs[1].latency == pytest.approx(0.011 + 0.012 - 0.001)
    assert win.reqs[2].latency == pytest.approx(0.023 - 0.002)
    assert win.reqs[3].start == pytest.approx(0.5)
    assert win.reqs[4].ready == pytest.approx(0.512)
    assert [s.n for s in win.steps] == [1, 2, 2, 1]
    assert win.end == pytest.approx(1.011)
    lat = win.latencies()
    assert len(lat) == 6 and np.all(lat > 0)


def test_open_loop_overload_counts_every_due_request():
    clock = FakeClock()
    sys_ = FakeSystem(clock, base=0.05)
    due = traffic.arrivals({"kind": "poisson", "rate_per_s": 200}, 1.0, 3)
    win = loop.open_loop(sys_, [None] * len(due), due, clock=clock,
                         sleep=clock.sleep, spans=False)
    assert len(win.served) == len(due) == 200
    assert sum(s.n for s in win.steps) == 200
    assert all(s.n <= 4 for s in win.steps)
    # the generator is late by at most one step; the queue grows
    late = [r.submit - r.due for r in win.reqs]
    assert min(late) >= 0 and max(late) <= 0.05 + 4 * 0.001 + 1e-9
    assert win.latencies().max() > 1.0


def test_closed_loop_keeps_batches_full():
    clock = FakeClock()
    sys_ = FakeSystem(clock, max_batch=4, base=0.01, per=0.0)
    win = loop.closed_loop(sys_, [None] * 10_000, 8, 1.0, clock=clock,
                           sleep=clock.sleep, spans=False)
    assert all(s.n == 4 for s in win.steps)
    # no step starts at or after 1 s; the window closes at the last ready
    assert win.steps[-1].start < 1.0 <= win.end
    assert len(win.steps) == 100 and win.end == pytest.approx(1.0)
    assert len(win.served) == 400
    assert len(win.reqs) == 408 and not sys_.queue  # drained after
    assert all(math.isfinite(r.ready) for r in win.reqs)


# -- whole runs of a tiny configuration ---------------------------------------

OPEN_METRICS = ["latency_p50_ms", "setup_s", "serve.batch_mean.lat",
                "serve.queue_ms.lat", "serve.latency_p95_ms.lat",
                "plan.device_ms_per_cloud.lat", "step_mfu.lat",
                "mlp_roofline.lat", "gather_roofline.lat",
                "device.idle_share.lat"]
CLOSED_METRICS = ["clouds_per_s", "setup_s", "plan.device_ms_per_cloud.tput",
                  "step_mfu.tput", "mlp_roofline.tput",
                  "gather_roofline.tput", "device.idle_share.tput"]


def _cell(loop_kind):
    mix = ({"loop": "open", "arrivals": {"kind": "poisson", "rate_per_s": 8}}
           if loop_kind == "open" else
           {"loop": "closed", "outstanding_batches": 2})
    names = OPEN_METRICS if loop_kind == "open" else CLOSED_METRICS
    metrics = [{"name": n, "unit": "x"} for n in names]
    return harness.Cell("tiny", 1, TINY, mix, metrics)


def _run(loop_kind, seconds=1.0):
    return harness.run_cell(_cell(loop_kind), 2 ** 31 + 5,
                            seconds, False, time.perf_counter(),
                            device_kind="cpu", peak_table=FAKE_PEAKS)


def test_open_loop_run_is_correct_and_counts_match():
    line = _run("open")
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 8
    m = line["metrics"]
    assert {"latency_p50_ms", "setup_s", "serve.batch_mean.lat",
            "serve.queue_ms.lat", "serve.latency_p95_ms.lat",
            "step_mfu.lat"} <= set(m)
    # trace metrics are left out of an untraced run, never made up as 0
    assert "mlp_roofline.lat" not in m and "clouds_per_s" not in m
    assert (m["serve.latency_p95_ms.lat"]["value"]
            >= m["latency_p50_ms"]["value"] > 0)
    assert line["checks"]["logit_gap"]["value"] <= 1e-5


def test_closed_loop_run_with_altered_answers_is_not_correct(monkeypatch):
    """The served path broken where the answer is produced: every cloud's
    first logit moved by a tenth of its largest. The run completes, and
    ``correct`` comes out false."""
    from repro.models.backend import CompiledModel
    head = CompiledModel._head_batched

    def altered(self, feats):
        out = head(self, feats)
        return out.at[:, 0].add(0.1 * jax_max_abs(out))

    monkeypatch.setattr(CompiledModel, "_head_batched", altered)
    line = _run("closed")
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "clouds_per_s" in line["metrics"]
    assert line["checks"]["logit_gap"]["value"] > TINY["check_limits"][
        "logit_gap"]
    assert line["correct"] is False


def jax_max_abs(x):
    import jax.numpy as jnp
    return jnp.max(jnp.abs(x), axis=1)


# -- the control ----------------------------------------------------------------

@pytest.mark.parametrize("name", ["model0-reram", "model2-reram"])
def test_control_one_precision_below_is_rejected(name):
    """The reference in int4 put in the program's place, at the
    configuration's own widths, on two clouds: the comparison rejects it
    by its own limit."""
    with open(os.path.join(HERE, "configs", f"{name}.json")) as fh:
        cfg = json.load(fh)
    driver = harness.load_module("drivers", "pointcloud")
    weights = driver.host_weights(driver.make_weights(cfg, 3))
    clouds = traffic.Clouds(cfg["n_points"], 3)
    sample = [clouds[0], clouds[1]]
    ref = driver.load_reference(cfg["reference"])
    control = [ref.logits(cfg, weights, c, bits=4) for c in sample]
    gap = driver.check(cfg, weights, sample, control)["logit_gap"]
    assert gap > cfg["check_limits"]["logit_gap"]
    same = [ref.logits(cfg, weights, c, bits=8) for c in sample]
    assert driver.check(cfg, weights, sample, same)["logit_gap"] == 0.0


def test_bench_without_a_chip_exits_nonzero_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        cell = json.load(fh)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""
