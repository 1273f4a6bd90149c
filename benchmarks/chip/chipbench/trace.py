"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time, idle gaps named by the host span open at the time, and the
``breakdown`` of a traced run.

The trace is first turned into plain :class:`Plane` / :class:`Line` /
:class:`Event` records, so every reduction below runs on data a test can
write by hand. Device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per executed HLO op (a Pallas kernel is one op),
and ``XLA Modules`` one per executed program. Host planes hold the
harness's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``) and
JAX's dispatch spans, on the same clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the harness's span around the whole measured window
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"[.:]\d+$")
#: a device op event may be named by its whole HLO text, "%name = ..."
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
#: what the profiler writes where it threw events away
DROPPED = "Trace Buffers Dropped"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict, compare=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Line:
    name: str
    events: tuple


@dataclass(frozen=True)
class Plane:
    name: str
    lines: tuple


def planes_from_profile(profile) -> list[Plane]:
    """Plain records from a ``jax.profiler.ProfileData``."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = tuple(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns), dict(e.stats))
                           for e in line.events)
            lines.append(Line(line.name, events))
        out.append(Plane(plane.name, tuple(lines)))
    return out


def load_trace_dir(log_dir: str) -> list[Plane]:
    """Every plane of the one ``.xplane.pb`` the profiler wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return planes_from_profile(ProfileData.from_file(paths[0]))


def instruction(ev: Event) -> str:
    """An op's HLO instruction name (``fusion.12``), also where the event
    is named by the instruction's whole text; a Pallas kernel's is the
    ``name=`` of its call with a numeric suffix."""
    m = _HLO_NAME.match(ev.name)
    return m.group(1) if m else ev.name


def op_name(ev: Event) -> str:
    """An op's instruction name without XLA's numeric suffix
    (``fusion.12`` -> ``fusion``)."""
    return _SUFFIX.sub("", instruction(ev))


def union(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Covered length of ``intervals`` (start, end) clipped to [lo, hi],
    and the uncovered gaps as (start, end) pairs."""
    covered, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s or e <= cur:
            continue
        if s > cur:
            gaps.append((cur, s))
            cur = s
        covered += e - cur
        cur = e
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


@dataclass
class TraceSummary:
    """What the per-layer metrics read from one traced window."""

    window_ns: tuple              # (start, end) of bench.window
    ops: list                     # device ops: (Event, module, device)
    busy_ns: float                # union of op intervals, mean over chips
    gaps: list                    # idle (start, end) pairs, every chip
    host: list                    # the harness thread's events in it
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def _covered_s(self, keep) -> float | None:
        """Device time covered by the ops that ``keep(event, module)``
        accepts: the union of their intervals on each device, summed over
        the devices, so an op nested in another (a loop's body in its
        ``while``) is not counted twice. None when no op is accepted."""
        spans = defaultdict(list)
        for ev, mod, dev in self.ops:
            if keep(ev, mod):
                spans[dev].append((ev.start_ns, ev.end_ns))
        if not spans:
            return None
        total = 0.0
        for iv in spans.values():
            total += union(iv, min(s for s, _ in iv),
                           max(e for _, e in iv))[0]
        return total * 1e-9

    def op_seconds(self, pattern: str) -> float | None:
        """Device time of the ops whose instruction name matches
        ``pattern`` (``re.search``); None when no op matches."""
        rx = re.compile(pattern)
        return self._covered_s(lambda ev, mod: rx.search(instruction(ev)))

    def module_seconds(self, keep) -> float | None:
        """Device time of the ops whose program name satisfies
        ``keep(module)``; None when none does."""
        return self._covered_s(lambda ev, mod: keep(mod))

    def host_spans_at(self, times) -> list[str]:
        """The innermost host span open at each of ``times`` (ascending),
        on the harness's own thread: its spans nest, so a sweep with a
        stack of open spans finds each in one pass. 'no host span' where
        none is open."""
        events = sorted(self.host, key=lambda e: (e.start_ns, -e.dur_ns))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(events) and events[i].start_ns <= t:
                stack.append(events[i])
                i += 1
            open_ = [ev for ev in stack if ev.end_ns > t]
            stack = open_
            inner = [ev for ev in open_ if ev.name != WINDOW_SPAN]
            out.append(min(inner, key=lambda e: e.dur_ns).name
                       if inner else "no host span")
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took the most time (by program and op
        name), and the idle time by the host span open at each gap's
        start, each list longest first, in seconds."""
        ops = defaultdict(float)
        for ev, mod, _ in self.ops:
            ops[f"{mod}:{op_name(ev)}"] += ev.dur_ns * 1e-9
        idle = defaultdict(float)
        gaps = sorted(self.gaps)
        for (s, e), name in zip(gaps, self.host_spans_at(
                [s for s, _ in gaps])):
            idle[name] += (e - s) * 1e-9
        rank = lambda d: [[k, v] for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops), "idle_gaps": rank(idle)}


def module_name(name: str) -> str:
    """A program's name without the id XLA appends (``jit__step(12)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def _module_of(ev: Event, modules: list, starts: list) -> str:
    """The program an op ran in: its ``hlo_module`` stat, else the module
    event that covers its start."""
    mod = ev.stats.get("hlo_module")
    if mod:
        return module_name(str(mod))
    i = bisect.bisect_right(starts, ev.start_ns) - 1
    if i >= 0 and ev.start_ns < modules[i].end_ns:
        return module_name(modules[i].name)
    return "?"


def summarize(planes: list[Plane]) -> TraceSummary:
    """Reduce the planes of one traced window. The window is the host's
    ``bench.window`` span; a trace without one, or with no device op
    inside it, is an error."""
    host_lines = [ln for p in planes if not DEVICE_PLANE.match(p.name)
                  for ln in p.lines
                  if any(ev.name == WINDOW_SPAN for ev in ln.events)]
    windows = [ev for ln in host_lines for ev in ln.events
               if ev.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"trace holds {len(windows)} '{WINDOW_SPAN}' "
                           f"spans; expected one")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    host = list(host_lines[0].events)
    ops, busy, gaps, n_dev = [], 0.0, [], 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln.events for ln in plane.lines}
        if any(ev.name == DROPPED for evs in lines.values() for ev in evs):
            raise RuntimeError(f"the profiler dropped events on {plane.name}"
                               f"; trace a shorter window")
        modules = sorted(lines.get(MODULES_LINE, ()),
                         key=lambda e: e.start_ns)
        dev_ops = [ev for ev in lines.get(OPS_LINE, ())
                   if ev.end_ns > lo and ev.start_ns < hi]
        if not dev_ops:
            continue
        n_dev += 1
        starts = [m.start_ns for m in modules]
        ops.extend((ev, _module_of(ev, modules, starts), plane.name)
                   for ev in dev_ops)
        covered, dev_gaps = union(
            [(ev.start_ns, ev.end_ns) for ev in dev_ops], lo, hi)
        busy += covered
        gaps.extend(dev_gaps)
    if not n_dev:
        raise RuntimeError("no device op ran inside the traced window")
    host_in = [ev for ev in host if ev.end_ns > lo and ev.start_ns < hi]
    return TraceSummary(window_ns=(lo, hi), ops=ops, busy_ns=busy / n_dev,
                        gaps=gaps, host=host_in, n_devices=n_dev)
