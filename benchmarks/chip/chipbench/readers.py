"""Readers shared by the metric files under ``metrics/``.

Each reader takes the finished run (:class:`chipbench.harness.Run`) and
returns one number, or None where the run holds nothing to read: a trace
metric in an untraced run, a kernel that never ran. A share of a peak or
a roofline is never made up as 0.
"""
from __future__ import annotations

import re

import numpy as np

from . import work

#: the serving step's program: ``jax.jit(PointCloudServable._step)``
STEP_MODULE = re.compile(r"^jit__step$")
#: the plan builder's program: ``PointCloudServable._jit_build``, a
#: ``jax.jit`` of a lambda over ``CompiledModel.build_device_plan``
BUILD_MODULE = re.compile(r"^jit__lambda$")
#: the kernels' ``pallas_call`` names (not their jitted wrappers')
MLP_KERNEL = r"\breram_mlp_fused_(whole|tiled|mtiled|wstat)\b"
GATHER_KERNEL = r"\baggregate_diff_batched\b"


def latency_ms(run, q: float):
    lat = run.window.latencies()
    return float(np.percentile(lat, q)) * 1e3 if len(lat) else None


def clouds_per_s(run):
    win = run.window
    return len(win.served) / win.end if win.served and win.end > 0 else None


def batch_mean(run):
    d_req = run.counters1["requests"] - run.counters0["requests"]
    d_bat = run.counters1["batches"] - run.counters0["batches"]
    return d_req / d_bat if d_bat else None


def queue_ms(run):
    waits = [r.start - r.due for r in run.window.served]
    return float(np.median(waits)) * 1e3 if waits else None


def _window_batches(run) -> list[int]:
    return [s.n for s in run.window.steps if s.ready <= run.window.end]


def step_mfu(run):
    """Model FLOPs of the clouds served over the summed wall time of the
    steps that served them, as a share of the int8 (or bf16) peak."""
    steps = [s for s in run.window.steps if s.ready <= run.window.end]
    wall = sum(s.ready - s.start for s in steps)
    clouds = sum(s.n for s in steps)
    if not wall or not clouds:
        return None
    flops = work.model_flops_per_cloud(run.cfg) * clouds
    return 100.0 * flops / wall / run.peak_ops


def plan_device_ms_per_cloud(run):
    """Device time of the plan builder's program, per cloud served. None
    unless the trace holds both the builder's and the serving step's
    programs by name: a renamed or split program leaves the metric out
    rather than moving one's time into the other."""
    if run.trace is None or not run.window.served:
        return None
    if run.trace.module_seconds(STEP_MODULE.search) is None:
        return None
    secs = run.trace.module_seconds(BUILD_MODULE.search)
    if secs is None:
        return None
    return secs * 1e3 / len(run.window.served)


def roofline(run, kind: str, pattern: str):
    if run.trace is None:
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    bound = work.kind_bound_seconds(run.cfg, kind, _window_batches(run),
                                    run.peak_ops, run.peak_bytes_per_s)
    return 100.0 * bound / secs


def mlp_roofline(run):
    return roofline(run, "mlp", MLP_KERNEL)


def gather_roofline(run):
    return roofline(run, "gather", GATHER_KERNEL)


def idle_share(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
