"""The measured window: open- and closed-loop drivers over a system that
offers ``submit`` / ``step`` / ``block``.

The harness owns the loop and the clock. Each request records when it was
due, when it was submitted, when the step that served it started and when
its result was ready, all in seconds from the window's start:

- open loop: a request is due at its scheduled time whatever the server
  does; it is submitted at the first moment the loop is free at or after
  that time, and its latency runs from the due time to its result being
  ready, so a stalled server is charged for the requests it kept waiting.
  Every request due in the window is served; the window closes when the
  last one is ready.
- closed loop: ``outstanding`` requests stay queued; each served request
  is replaced as soon as its step is dispatched. No step starts after
  ``seconds``; the window closes when the last started step is ready, so
  throughput counts all the work and all the time of the window. What is
  still queued then is drained after the window, untimed.

Each phase runs under a host span of its own (``bench.window``,
``bench.submit``, ``bench.wait_arrival``, ``bench.step``, ``bench.block``)
that a traced run records beside the device's activity.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Req:
    i: int
    due: float
    submit: float = float("nan")
    start: float = float("nan")
    ready: float = float("nan")
    handle: object = None

    @property
    def latency(self) -> float:
        return self.ready - self.due


@dataclass
class Step:
    start: float
    ready: float
    n: int
    dispatched: float = float("nan")   # when ``step`` returned
    cpu_s: float = float("nan")        # the loop thread's CPU time in it


@dataclass
class Window:
    """What a measured window leaves for the metrics."""

    loop: str
    end: float = 0.0               # when the window closed
    reqs: list = field(default_factory=list)
    steps: list = field(default_factory=list)

    @property
    def served(self) -> list:
        return [r for r in self.reqs if np.isfinite(r.ready)
                and r.ready <= self.end]

    def latencies(self) -> np.ndarray:
        return np.array([r.latency for r in self.served])


def _spans(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Loop:
    """Shared plumbing: the clock, spans and per-request bookkeeping."""

    def __init__(self, system, clouds, *, clock=time.perf_counter,
                 sleep=time.sleep, spans: bool = True):
        self.system, self.clouds = system, clouds
        self.clock, self.sleep = clock, sleep
        self.span = _spans(spans)
        self.pending: dict[int, Req] = {}
        self.t0 = 0.0

    def now(self) -> float:
        return self.clock() - self.t0

    def submit(self, req: Req) -> None:
        req.handle = self.system.submit(self.clouds[req.i])
        req.submit = self.now()
        self.pending[id(req.handle)] = req

    def serve_one(self, steps: list, after_dispatch=None) -> list:
        """One step: dispatch, optionally do host work while the device
        runs (``after_dispatch(n_served)``), then wait for the results."""
        with self.span("bench.step"):
            start, cpu0 = self.now(), time.thread_time()
            handles = self.system.step()
            dispatched = self.now()
        if not handles:
            raise RuntimeError("the system served nothing while "
                               f"{len(self.pending)} requests were queued")
        if after_dispatch is not None:
            after_dispatch(len(handles))
        with self.span("bench.block"):
            self.system.block(handles)
        ready = self.now()
        served = [self.pending.pop(id(h)) for h in handles]
        for r in served:
            r.start, r.ready = start, ready
        steps.append(Step(start, ready, len(served), dispatched,
                          time.thread_time() - cpu0))
        return served


def open_loop(system, clouds, due: np.ndarray, **kw) -> Window:
    """Serve requests due at ``due`` (seconds from the window's start)."""
    lp = Loop(system, clouds, **kw)
    reqs = [Req(i, float(t)) for i, t in enumerate(due)]
    win = Window("open", reqs=reqs)
    nxt = 0
    with lp.span("bench.window"):
        lp.t0 = lp.clock()
        while True:
            now = lp.now()
            if nxt < len(reqs) and reqs[nxt].due <= now:
                with lp.span("bench.submit"):
                    while nxt < len(reqs) and reqs[nxt].due <= now:
                        lp.submit(reqs[nxt])
                        nxt += 1
            if lp.pending:
                lp.serve_one(win.steps)
                continue
            if nxt == len(reqs):
                break
            with lp.span("bench.wait_arrival"):
                wait = reqs[nxt].due - lp.now()
                if wait > 0:
                    lp.sleep(wait)
        win.end = lp.now()
    return win


def closed_loop(system, clouds, outstanding: int, seconds: float,
                **kw) -> Window:
    """Keep ``outstanding`` requests queued for ``seconds``."""
    lp = Loop(system, clouds, **kw)
    win = Window("closed")

    def top_up(n):
        with lp.span("bench.submit"):
            for _ in range(n):
                req = Req(len(win.reqs), lp.now())
                win.reqs.append(req)
                lp.submit(req)
                req.due = req.submit

    with lp.span("bench.window"):
        lp.t0 = lp.clock()
        top_up(outstanding)
        while lp.now() < seconds:
            lp.serve_one(win.steps, after_dispatch=top_up)
        win.end = lp.now()
    while lp.pending:                      # drain, untimed
        lp.serve_one([])
    return win
