"""Readers of the MSG cell's per-layer metrics: ``readers.py``'s, with
the work of ``work_msg.py`` (several branches per layer, group-all) in
place of ``work.py``'s one receptive field per layer. Each returns None
where the run holds nothing to read."""
from __future__ import annotations

from . import readers, work_msg


def step_mfu(run):
    """Model FLOPs of the clouds served over the summed wall time of the
    steps that served them, as a share of the configuration's peak."""
    steps = [s for s in run.window.steps if s.ready <= run.window.end]
    wall = sum(s.ready - s.start for s in steps)
    clouds = sum(s.n for s in steps)
    if not wall or not clouds:
        return None
    flops = work_msg.model_flops_per_cloud(run.cfg) * clouds
    return 100.0 * flops / wall / run.peak_ops


def roofline(run, kind: str, pattern: str):
    if run.trace is None:
        return None
    secs = run.trace.op_seconds(pattern)
    if not secs:
        return None
    bound = work_msg.kind_bound_seconds(
        run.cfg, kind, readers._window_batches(run), run.peak_ops,
        run.peak_bytes_per_s)
    return 100.0 * bound / secs


def mlp_roofline(run):
    return roofline(run, "mlp", readers.MLP_KERNEL)


def gather_roofline(run):
    return roofline(run, "gather", readers.GATHER_KERNEL)

