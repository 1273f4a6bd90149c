"""Run one cell of ``BENCHMARK.json`` and print its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``configs/<config>.json``: the sizes as run, their source, ``reduced``
  and ``assumed``, the ``driver`` and ``reference`` modules by name, the
  arithmetic whose peak bounds it, and the limit of each number the
  correctness check compares;
- ``drivers/<driver>.py``: builds and warms the system under test
  (``build``) and compares its answers with the reference (``check``);
- ``references/<reference>.py``: the plain reference;
- ``traffic/<mix>.json``: the mix's parameters (``chipbench.traffic``);
- ``metrics/<metric>.py``: ``read(run) -> float | None``.

A run: check the device, set up (weights, model, warm-up, the window's
inputs), measure for ``--seconds``, read the peak memory, free the
program, compare a seeded sample of what the window served with the
reference, and print the end-to-end metrics (``--trace 0``) or the
per-layer metrics, with ``busy_s``, ``window_s`` and ``breakdown`` from
the profiler's trace (``--trace 1``).
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from . import loop, peaks, traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
#: JAX's persistent compilation cache: a fixed directory of the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: a traced run measures this much of its window at most: the serial
#: geometry loops emit millions of device ops per second, and a longer
#: trace overflows the profiler's buffers, which then drop events
TRACE_WINDOW_S = 0.5
#: JAX's environment, set before JAX is imported: closed-over arrays enter
#: a compiled program as its arguments, not as constants inside it. The
#: serving step closes over the crossbar program, whose values follow
#: ``--seed``; as constants they would make every seed compile a step of
#: its own, and a seed's first run would set up slower than its second.
JAX_ENV = {"JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS": "1"}
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    metrics: list          # BENCHMARK.json entries this run reports


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or its per-layer ones when traced: a
    metric with ``workloads`` is reported in those cells; one without in
    every cell (per-layer: every cell that reports what it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def load_cell(name: str, trace: bool, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 f"{w['traffic']}.json"))
    return Cell(name, int(w["chips"]), cfg, mix,
                cell_metrics(bench, name, trace))


@dataclass
class Run:
    """What the metric readers read."""

    cfg: dict
    window: loop.Window
    setup_s: float
    counters0: dict
    counters1: dict
    trace: object              # chipbench.trace.TraceSummary or None
    peak_ops: float
    peak_bytes_per_s: float


class CompileCounter:
    """Counts JAX's tracing and compiling events, to prove the window
    compiles nothing."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1


class GcClock:
    """The garbage collector's collections while it is open, and the
    seconds they took."""

    def __init__(self):
        self.counts, self.seconds, self._t = [0, 0, 0], 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.counts[info["generation"]] += 1
            self.seconds += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._on)


def device_line(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chip(chips: int) -> str:
    """The device kind, or exit non-zero with no result: JAX must see at
    least ``chips`` TPUs of a kind the peaks table knows."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX's first device is "
                         f"{devs[0].platform} ({kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    peaks.peaks_for(kind)            # an unknown kind is an error
    return kind


def start_jax(chips: int) -> str:
    """Set JAX's environment, look for the chips, and keep compiled
    programs in the persistent cache; returns the device kind. Call it
    before anything imports JAX."""
    if "jax" in sys.modules:
        raise RuntimeError("JAX was imported before its environment was set")
    os.environ.update(JAX_ENV)
    kind = require_chip(chips)
    import jax
    if not jax.config.jax_use_simplified_jaxpr_constants:
        raise RuntimeError("JAX did not take JAX_USE_SIMPLIFIED_JAXPR_"
                           "CONSTANTS from the environment")
    log(f"cache {enable_compile_cache()}; {kind}")
    return kind


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start:
             float, *, device_kind: str, peak_table: dict | None = None,
             keep: dict | None = None) -> dict:
    """Set up, measure and check one cell; returns the result line.
    ``keep``, where given, receives the reference's weights and the
    sampled clouds (``control.py`` compares the control on them)."""
    cfg, mix = cell.cfg, cell.mix
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    table = peak_table if peak_table is not None else peaks.peaks_for(
        device_kind)
    driver = load_module("drivers", cfg["driver"])
    counter = CompileCounter()

    warm = traffic.Clouds(cfg["n_points"], seed, stream=traffic.WARMUP)
    system = driver.build(cfg, mix["loop"], seed, warm, log)
    clouds = traffic.Clouds(cfg["n_points"], seed)
    if mix["loop"] == "open":
        due = traffic.arrivals(mix["arrivals"], seconds, seed)
        clouds.prefetch(len(due))
    # what set-up left is kept out of the window's collections
    gc.collect()
    gc.freeze()
    counters0 = system.counters()
    setup_s = time.perf_counter() - t_start
    compiles0 = counter.n
    gcs = GcClock()

    tdir = None
    if trace:
        import jax
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        if mix["loop"] == "open":
            win = loop.open_loop(system, clouds, due)
        else:
            win = loop.closed_loop(
                system, clouds, mix["outstanding_batches"] * system.max_batch,
                seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        gcs.close()
        gc.unfreeze()
    compiles = counter.n - compiles0
    counters1 = system.counters()
    device = device_line(cell.chips)

    summary = None
    if trace:
        from . import trace as tr
        summary = tr.summarize(tr.load_trace_dir(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    # the comparison: a seeded sample of what the window served
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed, traffic.SAMPLE]))
    served = win.served
    pick = rng.choice(len(served), size=min(cfg["check_sample"],
                                            len(served)), replace=False)
    sample = [served[i] for i in sorted(pick)]
    answers = [system.result(r.handle) for r in sample]
    sample_clouds = [clouds[r.i] for r in sample]
    weights = system.release()
    if keep is not None:
        keep.update(weights=weights, clouds=sample_clouds)
    for r in win.reqs:
        r.handle = None
    t_check = time.perf_counter()
    numbers = driver.check(cfg, weights, sample_clouds, answers) \
        if sample else {}
    check_s = time.perf_counter() - t_check

    failed = sum(1 for r in win.reqs if not math.isfinite(r.ready))
    limits = cfg["check_limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = (bool(sample) and failed == 0 and set(numbers) == set(limits)
               and all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))

    run = Run(cfg, win, setup_s, counters0, counters1, summary,
              peaks.compute_peak_from(table, cfg["arithmetic"]),
              float(table["hbm_bytes_per_s"]))
    metrics = {}
    for m in cell.metrics:
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    late = [r.submit - r.due for r in win.reqs if math.isfinite(r.submit)]
    slow = max(win.steps, key=lambda st: st.ready - st.start)
    log(f"window: {win.loop} loop, {len(win.served)} served of "
        f"{len(win.reqs)} in {win.end:.6f} s ({len(win.steps)} steps); "
        f"generator late: median {np.median(late) * 1e3:.6f} ms, max "
        f"{max(late) * 1e3:.6f} ms")
    log(f"slowest step: {(slow.ready - slow.start) * 1e3:.6f} ms for "
        f"{slow.n} clouds at {slow.start:.6f} s, dispatched after "
        f"{(slow.dispatched - slow.start) * 1e3:.6f} ms, loop thread CPU "
        f"{slow.cpu_s * 1e3:.6f} ms; garbage collections in "
        f"the window {gcs.counts} (by generation), {gcs.seconds * 1e3:.6f} "
        f"ms")
    log(f"compiles inside the window: {compiles} (jit traces "
        f"{counters0['jit_traces']} -> {counters1['jit_traces']})")
    log(f"plan cache in the window: "
        f"{counters1['plan_hits'] - counters0['plan_hits']} hits, "
        f"{counters1['plan_misses'] - counters0['plan_misses']} misses")
    log(f"memory peak: {device['memory_peak_bytes']} bytes; setup "
        f"{setup_s:.6f} s; reference check {check_s:.6f} s over "
        f"{len(sample)} clouds")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")

    line = {"correct": correct, "attempted": len(win.reqs),
            "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        line["breakdown"] = summary.breakdown()
    line["checks"] = checks
    return line


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative integer")
    cell = load_cell(args.workload, bool(args.trace))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    kind = start_jax(cell.chips)
    log(f"cell {cell.name}; seed {args.seed}; {args.seconds} s; trace "
        f"{args.trace}")
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    t_start, device_kind=kind)
    print(json.dumps(line))
    return 0
