"""Readers of the spans the program itself writes into a traced window.

The serving tier (``repro.launch.serve``) opens ``serve.*`` spans on the
thread that calls ``step``, which is the harness's loop thread, so they
sit in ``TraceSummary.host`` beside the ``bench.*`` spans:
``serve.step`` (arg ``requests``) holds ``serve.select`` and
``serve.run_batch`` (args ``batch_real``, ``batch_bucket``,
``points_bucket``), which holds ``serve.pad``, one ``serve.plan`` per
cloud (arg ``hit``; inside it ``serve.plan_key`` and, on a miss,
``serve.plan_build``), ``serve.plan_stack``, ``serve.dispatch`` and
``serve.unpad``.

Each reader returns None, never 0, where the trace holds nothing it
reads.
"""
from __future__ import annotations

import numpy as np

from . import trace as tr
from .readers import STEP_MODULE

STEP_SPAN = "serve.step"
BATCH_SPAN = "serve.run_batch"
PLAN_SPAN = "serve.plan"
DISPATCH_SPAN = "serve.dispatch"


def spans(trace, name: str) -> list:
    """The host spans called ``name`` that start inside the window, in
    start order."""
    lo, hi = trace.window_ns
    return sorted((ev for ev in trace.host
                   if ev.name == name and lo <= ev.start_ns < hi),
                  key=lambda ev: ev.start_ns)


def plan_host_ms_per_step(run):
    """Median over the window's ``serve.run_batch`` spans of the summed
    wall time of the ``serve.plan`` spans inside each: the host time a
    step spends finding or building its clouds' plans."""
    if run.trace is None:
        return None
    plans = spans(run.trace, PLAN_SPAN)
    batches = spans(run.trace, BATCH_SPAN)
    if not plans or not batches:
        return None
    per_step = [sum(p.dur_ns for p in plans if b.start_ns <= p.start_ns
                    and p.end_ns <= b.end_ns) for b in batches]
    return float(np.median(per_step)) * 1e-6


def executions(trace) -> dict:
    """{device: [start of each execution]} of the serving step's program.
    A device runs one program at a time, so an execution is a maximal run
    of the program's ops among all the device's ops in start order. Two
    executions with no other program's op between them read as one (the
    serving tier runs the plan stacking's programs between two steps)."""
    by_dev: dict = {}
    for ev, mod, dev in trace.ops:
        by_dev.setdefault(dev, []).append((ev.start_ns, mod))
    out = {}
    for dev, ops in by_dev.items():
        starts, inside = [], False
        for start, mod in sorted(ops, key=lambda o: o[0]):
            keep = bool(STEP_MODULE.search(mod))
            if keep and not inside:
                starts.append(start)
            inside = keep
        if starts:
            out[dev] = starts
    return out


def launch_ms(run):
    """Median delay from a ``serve.dispatch`` span's start to the first
    device op of the serving step's execution it launched: the k-th
    dispatch in the window with the k-th execution on each device. None
    where the counts disagree or a delay is negative (the host's and the
    device's clocks would then disagree)."""
    if run.trace is None:
        return None
    dispatches = [ev.start_ns for ev in spans(run.trace, DISPATCH_SPAN)]
    runs = executions(run.trace)
    if not dispatches or not runs:
        return None
    delays = []
    for starts in runs.values():
        if len(starts) != len(dispatches):
            return None
        delays += [s - d for s, d in zip(starts, dispatches)]
    if min(delays) < 0:
        return None
    return float(np.median(delays)) * 1e-6


def idle_in_serve_share(run):
    """Share of the window in which the device is idle while the loop
    thread is inside a ``serve.step`` span, by overlap: a gap counts for
    the part of it that a step covers, wherever the gap opened."""
    if run.trace is None:
        return None
    steps = [(ev.start_ns, ev.end_ns) for ev in spans(run.trace, STEP_SPAN)]
    if not steps:
        return None
    lo, hi = run.trace.window_ns
    idle = sum(tr.union(steps, s, e)[0] for s, e in run.trace.gaps)
    return 100.0 * idle / run.trace.n_devices / (hi - lo)


def pad_share(run):
    """Share of the serving step's rows that were padding over the
    window's batches: 1 - sum(batch_real) / sum(batch_bucket)."""
    if run.trace is None:
        return None
    batches = [ev.stats for ev in spans(run.trace, BATCH_SPAN)]
    batches = [b for b in batches if "batch_real" in b and "batch_bucket"
               in b]
    if not batches:
        return None
    real = sum(int(b["batch_real"]) for b in batches)
    rows = sum(int(b["batch_bucket"]) for b in batches)
    return 100.0 * (1.0 - real / rows)
