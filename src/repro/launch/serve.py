"""Model-agnostic serving tier: one engine over any ``CompiledModel``.

The paper's setting is latency-bound streaming inference (LiDAR sweeps
arriving continuously); this module is the software tier that turns the
repo's compiled artifacts into a request path:

  ``ServingEngine``       — request queue + continuous batching behind a
                            pluggable :class:`Scheduler`: each step asks
                            the scheduler for one same-bucket batch and
                            runs it. :class:`FIFOScheduler` (default) is
                            the PR-7 discipline — oldest request fixes the
                            bucket, same-bucket requests skim in FIFO
                            order; :class:`EDFScheduler` adds per-request
                            ``deadline_us``/``priority``
                            (earliest-deadline-first within a priority
                            tier, deadline-aware batch admission, and an
                            aging bound so nothing starves) — the
                            streaming-LiDAR discipline (DESIGN.md §14).
  ``PointCloudServable``  — the point-cloud adapter over ``CompiledModel``:
                            pads requests into point-count shape buckets so
                            the jitted batched forward retraces only once
                            per bucket (the bucketing contract in
                            ``repro.models.backend`` makes padded logits
                            bitwise-equal to the unpadded ``forward``),
                            reuses plans through a content-keyed
                            :class:`~repro.core.schedule.PlanCache`, and
                            optionally fans batches across a replica mesh.
  ``LMServable``          — the LM adapter: the pre-existing ``generate``
                            path (prefill + sampled decode) as a servable,
                            with the jitted prefill/decode-step callables
                            hoisted into module caches so repeated calls
                            never retrace (they used to re-jit through a
                            fresh ``lambda`` per call).

``generate`` keeps its exact signature and stats keys but now runs as a
thin client of the same engine. ``make_serve_step`` is unchanged — it is
what the dry-run lowers for the decode_32k / long_500k shapes.

Spans: the request path opens ``jax.profiler.TraceAnnotation`` host spans
on the calling thread, which record nothing unless a profiler session is
open. Each nests in the one that called it:

  ``serve.step``            ``ServingEngine.step``; arg ``requests``, the
                            served requests' ids, space-separated
    ``serve.select``        the scheduler's ``select``
    ``serve.run_batch``     ``PointCloudServable.run_batch``; args
                            ``batch_real``, ``batch_bucket``,
                            ``points_bucket``
      ``serve.pad``         padding the batch on the host
      ``serve.plan``        one cloud's plan lookup; arg ``hit`` (0/1)
        ``serve.plan_key``  ``cloud_content_key``
        ``serve.plan_build``  the plan builder on a miss
      ``serve.plan_stack``  ``DevicePlan.stack``
      ``serve.dispatch``    the batch's copy to the device and the serving
                            step's call
      ``serve.unpad``       slicing the real rows out of the logits

The two device programs carry named scopes: the plan builder's ops sit
under ``plan`` (``CompiledModel.build_device_plan``), the serving step's
under ``step``, and below them ``geometry/fps``, ``geometry/knn``,
``order``, ``coordinate``, ``gather``, ``mlp`` and ``head``.
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.schedule import (DevicePlan, FrameTracker, PlanCache,
                                 cloud_content_key)
from repro.models import lm

#: a host span in the profiler's trace (nothing is recorded unless a
#: profiler session is open); the module docstring lists them
_span = jax.profiler.TraceAnnotation

__all__ = [
    "ShapeBuckets",
    "Request",
    "Servable",
    "PointCloudServable",
    "LMServable",
    "Scheduler",
    "FIFOScheduler",
    "EDFScheduler",
    "SCHEDULERS",
    "VirtualClock",
    "ServingEngine",
    "make_serve_step",
    "generate",
]


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------

class VirtualClock:
    """Deterministic injectable clock for the serving tier.

    ``ServingEngine`` measures batch service time as the delta between
    two ``clock.monotonic()`` calls; on the default wall clock
    (``time``), a GC pause or a noisy CI host lands inside that window
    and inflates p99 nondeterministically. A ``VirtualClock`` advances
    by exactly ``tick_s`` on every ``monotonic()`` call instead, so each
    served batch costs one deterministic virtual tick and every latency
    percentile — and every deadline-miss decision — is a pure function
    of the arrival stream and the scheduler. The scheduler regression
    tests run on it."""

    def __init__(self, tick_s: float = 0.0, *, start: float = 0.0):
        if tick_s < 0.0:
            raise ValueError(f"tick_s must be >= 0; got {tick_s}")
        self.tick_s = float(tick_s)
        self.t = float(start)

    def monotonic(self) -> float:
        self.t += self.tick_s
        return self.t

    def advance(self, dt: float) -> None:
        """Manually advance the clock by ``dt`` seconds."""
        if dt < 0.0:
            raise ValueError(f"dt must be >= 0; got {dt}")
        self.t += float(dt)


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeBuckets:
    """The discrete shapes the serving tier is allowed to run.

    ``points`` are the point-count buckets (ascending): a request of n
    points is padded up to the smallest bucket >= n, so the jitted batched
    forward sees at most ``len(points) * len(batch)`` distinct shapes —
    ever — and every later request hits a warm jit cache. ``batch`` are
    the batch-size buckets the same way (short batches pad by replicating
    row 0; the pads are discarded before results leave the servable).
    """

    points: tuple[int, ...] = (1024,)
    batch: tuple[int, ...] = (1, 2, 4, 8)

    def __post_init__(self):
        if (not self.points or not self.batch
                or tuple(sorted(self.points)) != tuple(self.points)
                or tuple(sorted(self.batch)) != tuple(self.batch)):
            raise ValueError("ShapeBuckets needs non-empty ascending "
                             "'points' and 'batch' tuples")

    @property
    def max_batch(self) -> int:
        return self.batch[-1]

    def point_bucket(self, n: int) -> int:
        """Smallest point bucket >= n (ValueError past the largest — the
        engine must never silently truncate a cloud)."""
        for b in self.points:
            if n <= b:
                return b
        raise ValueError(f"cloud with {n} points exceeds the largest "
                         f"point bucket {self.points[-1]}")

    def batch_bucket(self, b: int) -> int:
        for bb in self.batch:
            if b <= bb:
                return bb
        raise ValueError(f"batch of {b} exceeds the largest batch bucket "
                         f"{self.batch[-1]}")


# ---------------------------------------------------------------------------
# requests + the servable protocol
# ---------------------------------------------------------------------------

@dataclass
class Request:
    """One queued unit of work. ``payload`` is whatever the servable
    understands (a cloud for ``PointCloudServable``, a 1-D prompt for
    ``LMServable``); ``result`` and ``t_done`` are filled by the engine.

    ``deadline_us`` is the request's latency budget in microseconds
    *relative to its arrival* (None = no deadline); ``priority`` is an
    integer tier, higher = more urgent. Both are FIFO-inert under the
    default :class:`FIFOScheduler` and drive :class:`EDFScheduler`."""

    id: int
    payload: Any
    t_arrival: float = 0.0
    deadline_us: float | None = None
    priority: int = 0
    result: Any = None
    t_done: float | None = None

    @property
    def latency(self) -> float | None:
        return None if self.t_done is None else self.t_done - self.t_arrival

    @property
    def deadline(self) -> float | None:
        """Absolute deadline on the arrival clock (seconds), or None."""
        return (None if self.deadline_us is None
                else self.t_arrival + self.deadline_us * 1e-6)

    @property
    def missed(self) -> bool:
        """True iff the request had a deadline and completed past it
        (False while still queued)."""
        return (self.t_done is not None and self.deadline is not None
                and self.t_done > self.deadline)


class Servable:
    """What the engine needs from a model adapter. ``bucket_of`` maps a
    payload to a hashable bucket key (requests batch together iff their
    keys are equal); ``run_batch`` executes one same-bucket batch and
    returns one result per payload, in order; ``max_batch`` bounds batch
    assembly; ``stats`` reports adapter-side counters."""

    max_batch: int = 8

    def bucket_of(self, payload) -> Any:
        raise NotImplementedError

    def run_batch(self, payloads: list) -> list:
        raise NotImplementedError

    def stats(self) -> dict:
        return {}


# ---------------------------------------------------------------------------
# schedulers: the pluggable queue discipline
# ---------------------------------------------------------------------------

class Scheduler:
    """The engine's pluggable queue discipline.

    Owns the pending requests: :meth:`push` enqueues, :meth:`select`
    removes and returns ONE same-bucket batch (the engine runs it as one
    ``run_batch``), :meth:`pending` snapshots what is still queued in
    arrival order. ``select`` receives ``bucket_of`` (payload → bucket
    key), ``max_batch``, the current time ``now`` and an optional
    ``est_service(bucket, batch_size) -> seconds`` estimator (the
    engine's measured EMA) for deadline feasibility decisions.

    Contract every scheduler must keep: each pushed request is selected
    exactly once (no loss, no duplication), and a selected batch is
    same-bucket (the servable pads/stacks it as one shape)."""

    name = "scheduler"

    def __init__(self):
        self._pending: deque[Request] = deque()

    def push(self, req: Request) -> None:
        self._pending.append(req)

    def __len__(self) -> int:
        return len(self._pending)

    def pending(self) -> tuple[Request, ...]:
        """Still-queued requests, in arrival order."""
        return tuple(self._pending)

    def select(self, *, bucket_of: Callable[[Any], Any], max_batch: int,
               now: float = 0.0,
               est_service: Callable[[Any, int], float] | None = None,
               ) -> list[Request]:
        raise NotImplementedError


class FIFOScheduler(Scheduler):
    """PR-7 discipline, unchanged: the oldest request fixes the shape
    bucket; every queued same-bucket request joins in FIFO order up to
    ``max_batch``; other buckets keep their queue position. Deadlines
    and priorities are carried but ignored — FIFO is the policy
    baseline the EDF rows are compared against."""

    name = "fifo"

    def select(self, *, bucket_of, max_batch, now=0.0, est_service=None):
        if not self._pending:
            return []
        bucket = bucket_of(self._pending[0].payload)
        batch: list[Request] = []
        rest: deque[Request] = deque()
        while self._pending:
            req = self._pending.popleft()
            if (len(batch) < max_batch
                    and bucket_of(req.payload) == bucket):
                batch.append(req)
            else:
                rest.append(req)
        self._pending = rest
        return batch


class EDFScheduler(Scheduler):
    """Deadline/priority discipline for streaming LiDAR (DESIGN.md §14).

    Selection order (most-urgent first):

    1. **aged** requests — anything waiting ``aging_s`` or longer
       escalates past every priority and deadline, FIFO among
       themselves. This is the starvation bound: the oldest aged
       request is ALWAYS in the next batch (property-tested), so no
       admitted request waits more than the aging window plus its
       bucket's service seniority.
    2. higher ``priority`` tier first;
    3. within a tier, **feasible** deadlines (meetable given the
       service estimate: ``now + est <= deadline``; no deadline counts
       as feasible) before infeasible ones — a lost cause must never
       delay a request that can still make it;
    4. earliest absolute deadline first (no deadline sorts last);
    5. FIFO (arrival id) on ties — equal-priority equal-deadline
       requests keep their arrival order.

    Batch admission: the head request fixes the bucket; candidates join
    in the order above only while the batch stays *deadline-safe* —
    growing the batch to size ``b+1`` (estimated completion
    ``now + est_service(bucket, b+1)``) must not blow the candidate's
    own still-meetable deadline, nor the deadline of any request
    already admitted. A candidate whose deadline this batch would blow
    keeps its queue slot (it rides a later, smaller batch or ages);
    aged requests bypass admission entirely — the starvation bound
    dominates the deadline economics."""

    name = "edf"

    def __init__(self, *, aging_s: float | None = 1.0):
        super().__init__()
        if aging_s is not None and aging_s <= 0.0:
            raise ValueError(f"aging_s must be > 0 or None; got {aging_s}")
        self.aging_s = aging_s

    def _aged(self, req: Request, now: float) -> bool:
        return (self.aging_s is not None
                and now - req.t_arrival >= self.aging_s)

    def _key(self, req: Request, now: float, est0: float):
        if self._aged(req, now):
            return (0, 0, 0, 0.0, req.id)          # FIFO among the aged
        dl = req.deadline
        infeasible = dl is not None and now + est0 > dl
        return (1, -req.priority, 1 if infeasible else 0,
                math.inf if dl is None else dl, req.id)

    def select(self, *, bucket_of, max_batch, now=0.0, est_service=None):
        if not self._pending:
            return []
        est = est_service if est_service is not None else lambda b, n: 0.0
        order = sorted(
            self._pending,
            key=lambda r: self._key(r, now, est(bucket_of(r.payload), 1)))
        head = order[0]
        bucket = bucket_of(head.payload)
        batch = [head]
        for cand in order[1:]:
            if len(batch) >= max_batch:
                break
            if bucket_of(cand.payload) != bucket:
                continue
            t_done = now + est(bucket, len(batch) + 1)
            if not self._aged(cand, now):
                dl = cand.deadline
                if (dl is not None and t_done > dl
                        and now + est(bucket, 1) <= dl):
                    # this batch would blow a still-meetable deadline:
                    # keep the candidate queued for a batch it can make
                    continue
                if any(r.deadline is not None and t_done > r.deadline
                       and not self._aged(r, now) for r in batch):
                    # growing the batch blows an admitted deadline; any
                    # further growth completes no earlier — stop here
                    break
            batch.append(cand)
        selected = {id(r) for r in batch}
        self._pending = deque(r for r in self._pending
                              if id(r) not in selected)
        return batch


#: registry for ``ServingEngine(scheduler="fifo" | "edf")``
SCHEDULERS: dict[str, type[Scheduler]] = {
    "fifo": FIFOScheduler,
    "edf": EDFScheduler,
}


# ---------------------------------------------------------------------------
# point clouds: the CompiledModel adapter
# ---------------------------------------------------------------------------

class PointCloudServable(Servable):
    """Serve any :class:`~repro.models.backend.CompiledModel` (any backend,
    any schedule).

    Request lifecycle: bucket (pad the cloud with zero rows up to its
    point bucket) → batch (stack same-bucket requests; pad the batch dim
    to a batch bucket by replicating row 0) → ONE jitted
    ``batched_forward(clouds, n_valid=..., dplan=...)`` → unpad (drop the
    replicated rows). The bucketing contract guarantees each returned row
    is bitwise-equal to ``model.forward(cloud)`` on the bare request.

    The plan cache (on by default for planned schedules) keys each
    request's REAL rows by content hash: a repeated cloud skips FPS/kNN +
    Algorithm 1 entirely — its :class:`DevicePlan` is stacked straight
    into the batch. Cache misses build through
    ``model.build_device_plan``; hits/misses surface in :meth:`stats`.
    For host-planning models the cache is also what makes the whole step
    jittable (the plan becomes a device operand instead of a host loop).

    ``mesh`` (a 1-D replica mesh from
    :func:`repro.launch.mesh.make_replica_mesh`) runs the step once per
    replica under ``shard_map``: the batch is padded up to a multiple of
    the replica count, its operands are sharded over the replica axis,
    and every replica runs the whole ``batched_forward`` — Pallas
    kernels included — on its own slice. (A ``pallas_call`` is a custom
    call that GSPMD cannot partition, so the step is never left to it.)
    Batch buckets of at least 2x the replica count keep float results
    bitwise-equal: a lone cloud per replica is the singleton-batch case
    again (XLA collapses the local unit batch dim and re-fuses the float
    matmuls).
    """

    def __init__(self, model, *, buckets: ShapeBuckets | None = None,
                 plan_cache: PlanCache | bool | None = True,
                 mesh=None,
                 frame_reuse: FrameTracker | bool = False):
        self.model = model
        self.buckets = buckets if buckets is not None else ShapeBuckets()
        self.max_batch = self.buckets.max_batch
        self.mesh = mesh
        # compile-time plans need no per-request planning; 'baseline' has
        # no plan at all — the cache only earns its keep for per-cloud
        # planned schedules
        cacheable = model.planned and model.device_plan is None
        if plan_cache is True:
            self.plan_cache = PlanCache() if cacheable else None
        elif plan_cache in (False, None):
            self.plan_cache = None
        else:
            if not cacheable:
                raise ValueError(
                    "plan_cache= was given but this model has no "
                    "per-cloud plan to cache (baseline schedule or "
                    "compile-time DevicePlan)")
            self.plan_cache = plan_cache
        # frame-coherent plan reuse (streaming LiDAR): a near-duplicate
        # of the last-planned frame skips keying + planning entirely and
        # serves the anchor's DevicePlan (bitwise-safe: logits are
        # order-invariant in the plan — see FrameTracker)
        if isinstance(frame_reuse, FrameTracker):
            self.frame_tracker = frame_reuse
        else:
            self.frame_tracker = FrameTracker() if frame_reuse else None
        if self.frame_tracker is not None and self.plan_cache is None:
            raise ValueError(
                "frame_reuse= needs the per-cloud plan path (a planned "
                "schedule with plan_cache enabled); this servable has "
                "no plan to reuse across frames")
        self.requests = 0
        self.batches = 0
        self.jit_traces = 0
        # the host-planning fallback (planned model, cache off, no traced
        # plan construction) cannot live under jit — everything else runs
        # through the ONE cached jitted step per bucket shape
        self._jittable = (self.plan_cache is not None or not model.planned
                          or model.device_planning
                          or model.device_plan is not None)
        step = self._step
        if mesh is not None:
            if not self._jittable:
                raise ValueError(
                    "mesh= needs a jittable serving step; this model plans "
                    "on host per cloud with the plan cache off")
            (axis,) = mesh.axis_names
            step = jax.shard_map(step, mesh=mesh,
                                 in_specs=jax.sharding.PartitionSpec(axis),
                                 out_specs=jax.sharding.PartitionSpec(axis),
                                 check_vma=False)
        self._jit_step = jax.jit(step)
        # cache misses build the plan OUTSIDE the serving step; for
        # device-planning models the whole build (masked FPS/kNN +
        # Algorithm 1) is traceable, so compile it once per point bucket —
        # eager lax over the plan construction is orders of magnitude
        # slower. Host-planning models build on host (NumPy) instead.
        self._jit_build = (jax.jit(
            lambda c, nv: model.build_device_plan(c, n_valid=nv))
            if self.plan_cache is not None and model.device_planning
            else None)

    # the body below runs ONCE per (shape, dplan-structure) — at trace
    # time — so the counters measure exactly what bucketing is meant to
    # bound: how often XLA recompiles the serving step
    def _step(self, clouds, n_valid, dplan):
        self.jit_traces += 1
        with jax.named_scope("step"):
            return self.model.batched_forward(clouds, n_valid=n_valid,
                                              dplan=dplan)

    def bucket_of(self, payload) -> int:
        return self.buckets.point_bucket(np.asarray(payload).shape[0])

    def _plan_for(self, padded, n: int):
        with _span("serve.plan") as span:
            if self.frame_tracker is not None:
                plan = self.frame_tracker.lookup(padded, n_valid=n)
                if plan is not None:
                    span.set_metadata(hit=1)
                    return plan
            with _span("serve.plan_key"):
                key = cloud_content_key(padded, n_valid=n)
            misses = self.plan_cache.misses

            def build():
                with _span("serve.plan_build"):
                    if self._jit_build is not None:
                        return self._jit_build(jnp.asarray(padded),
                                               jnp.int32(n))
                    return self.model.build_device_plan(padded, n_valid=n)

            plan = self.plan_cache.get_or_build(key, build)
            span.set_metadata(hit=int(self.plan_cache.misses == misses))
            if self.frame_tracker is not None:
                self.frame_tracker.update(padded, plan, n_valid=n)
            return plan

    def run_batch(self, payloads: list) -> list:
        with _span("serve.run_batch") as span:
            with _span("serve.pad"):
                clouds = [np.asarray(p, np.float32) for p in payloads]
                n_bucket = self.buckets.point_bucket(clouds[0].shape[0])
                b_real = len(clouds)
                b_bucket = self.buckets.batch_bucket(b_real)
                if b_bucket == 1:
                    # never run a TRUE singleton batch: XLA collapses the
                    # unit batch dim and re-fuses the float matmuls, which
                    # breaks the bitwise tie between the batched step and
                    # the per-request eager forward; one replicated row
                    # keeps the vmapped program intact at negligible cost
                    # in the latency-bound regime
                    b_bucket = 2
                if self.mesh is not None:        # whole slices per replica
                    n_rep = self.mesh.devices.size
                    b_bucket = -(-b_bucket // n_rep) * n_rep
                padded = np.zeros((b_bucket, n_bucket, 3), np.float32)
                n_valid = np.empty((b_bucket,), np.int32)
                for i, c in enumerate(clouds):
                    padded[i, :c.shape[0]] = c
                    n_valid[i] = c.shape[0]
                padded[b_real:] = padded[0]      # batch pads: replicate row 0
                n_valid[b_real:] = n_valid[0]
            span.set_metadata(batch_real=b_real, batch_bucket=b_bucket,
                              points_bucket=n_bucket)

            dplan = None
            if self.plan_cache is not None:
                plans = [self._plan_for(padded[i], int(n_valid[i]))
                         for i in range(b_real)]
                # the pads reuse row 0's plan
                plans += [plans[0]] * (b_bucket - b_real)
                with _span("serve.plan_stack"):
                    dplan = DevicePlan.stack(plans)

            with _span("serve.dispatch"):
                # the batch goes to the device after the plan builds are
                # dispatched, so its copy overlaps their device time
                clouds_d = jnp.asarray(padded)
                nv_d = jnp.asarray(n_valid)
                if self._jittable:
                    logits = self._jit_step(clouds_d, nv_d, dplan)
                else:
                    logits = self.model.batched_forward(clouds_d, n_valid=nv_d)
            self.requests += b_real
            self.batches += 1
            with _span("serve.unpad"):
                return list(logits[:b_real])

    def stats(self) -> dict:
        s = {"requests": self.requests, "batches": self.batches,
             "jit_traces": self.jit_traces}
        if self.plan_cache is not None:
            s["plan_cache"] = self.plan_cache.stats()
        if self.frame_tracker is not None:
            s["frame_tracker"] = self.frame_tracker.stats()
        return s


# ---------------------------------------------------------------------------
# LMs: prefill + sampled decode as a servable
# ---------------------------------------------------------------------------

# jitted callables hoisted out of `generate`, keyed on the (hashable,
# frozen) ArchConfig — the old per-call ``jax.jit(lambda ...)`` created a
# fresh jit object every call, so its trace cache NEVER hit and every
# request re-traced prefill. One entry per (cfg, max_seq) now; the
# regression test asserts one trace across two calls.
_PREFILL_CACHE: dict = {}
_STEP_CACHE: dict = {}


def _jit_prefill(cfg: ArchConfig, max_seq: int):
    key = (cfg, int(max_seq))
    if key not in _PREFILL_CACHE:
        _PREFILL_CACHE[key] = jax.jit(
            partial(lm.prefill, cfg=cfg, max_seq=max_seq))
    return _PREFILL_CACHE[key]


def _jit_step(cfg: ArchConfig):
    if cfg not in _STEP_CACHE:
        _STEP_CACHE[cfg] = jax.jit(make_serve_step(cfg))
    return _STEP_CACHE[cfg]


def make_serve_step(cfg: ArchConfig):
    def serve_step(params, cache, ids1, pos, *, image_embeds=None,
                   embeds1=None):
        return lm.decode_step(params, cfg, cache, ids1=ids1, pos=pos,
                              embeds1=embeds1, image_embeds=image_embeds)
    return serve_step


class LMServable(Servable):
    """The LM ``generate`` path as a servable: payloads are 1-D int32
    prompts, bucketed on exact length (same-length prompts batch; decode
    state is per-batch so there is no cross-length padding story here —
    point clouds are where the padding contract lives). ``run_batch``
    stacks the batch, runs one cached-jit prefill and ``max_new_tokens``
    cached-jit decode steps, and returns the full (prompt + generated)
    row per request. Timing accumulates on the instance; ``generate``
    turns it into the historical stats dict."""

    def __init__(self, params, cfg: ArchConfig, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, key=None, image_embeds=None,
                 max_batch: int = 8, clock=None):
        self.params = params
        self.cfg = cfg
        # same injectable-clock contract as ServingEngine (the PR 9
        # serve_stream bug class): anything with .monotonic(), e.g.
        # VirtualClock, makes the timing stats deterministic under test
        self.clock = clock if clock is not None else time
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.image_embeds = image_embeds
        self.max_batch = int(max_batch)
        self.requests = 0
        self.batches = 0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.tokens = 0

    def bucket_of(self, payload) -> tuple:
        return ("lm", int(np.asarray(payload).shape[-1]))

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.temperature).astype(jnp.int32)

    def run_batch(self, payloads: list) -> list:
        prompts = jnp.stack([jnp.asarray(p, jnp.int32) for p in payloads])
        b, s = prompts.shape
        t0 = self.clock.monotonic()
        logits, cache = _jit_prefill(self.cfg, s + self.max_new_tokens)(
            self.params, ids=prompts, image_embeds=self.image_embeds)
        jax.block_until_ready(logits)
        t1 = self.clock.monotonic()
        step = _jit_step(self.cfg)
        self.key, key = jax.random.split(self.key)
        toks = [self._sample(logits, key)]
        for i in range(self.max_new_tokens - 1):
            self.key, key = jax.random.split(self.key)
            lg, cache = step(self.params, cache, toks[-1][:, None],
                             jnp.int32(s + i),
                             image_embeds=self.image_embeds)
            toks.append(self._sample(lg, key))
        jax.block_until_ready(toks[-1])
        t2 = self.clock.monotonic()
        self.prefill_s += t1 - t0
        self.decode_s += t2 - t1
        self.tokens += b * self.max_new_tokens
        self.requests += b
        self.batches += 1
        out = jnp.concatenate([prompts, jnp.stack(toks, axis=1)], axis=1)
        return list(out)

    def stats(self) -> dict:
        return {"requests": self.requests, "batches": self.batches,
                "prefill_s": self.prefill_s, "decode_s": self.decode_s,
                "decode_tok_per_s":
                    self.tokens / max(self.decode_s, 1e-9)}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Scheduled queue + continuous batching over one :class:`Servable`.

    :meth:`step` forms one batch per call by asking the
    :class:`Scheduler` (default :class:`FIFOScheduler`; pass
    ``scheduler="edf"`` or any :class:`Scheduler` instance) for one
    same-bucket batch and running it as one ``run_batch``. Scheduling is
    a pure *policy*: served results are bitwise-identical under every
    scheduler (only order and latency change — tested). :meth:`drain`
    steps until empty; :meth:`serve_stream` replays a timed arrival
    stream against a virtual clock — service time is measured on the
    injectable ``clock`` (wall by default; a :class:`VirtualClock` makes
    every percentile and deadline decision deterministic) — and reports
    p50/p99 request latency, throughput and deadline-miss rate.

    The engine also keeps a per-(bucket, batch-size) EMA of measured
    batch service time (:meth:`service_estimate`), which deadline-aware
    schedulers use for feasibility and batch admission; seed it with
    :meth:`seed_service_estimate` for deterministic tests.
    """

    def __init__(self, servable: Servable, *, max_batch: int | None = None,
                 scheduler: Scheduler | str | None = None, clock=None):
        self.servable = servable
        self.max_batch = (servable.max_batch if max_batch is None
                          else min(int(max_batch), servable.max_batch))
        if scheduler is None:
            scheduler = FIFOScheduler()
        elif isinstance(scheduler, str):
            if scheduler not in SCHEDULERS:
                raise ValueError(
                    f"unknown scheduler {scheduler!r}; available: "
                    f"{sorted(SCHEDULERS)}")
            scheduler = SCHEDULERS[scheduler]()
        self.scheduler = scheduler
        self.clock = clock if clock is not None else time
        self._next_id = 0
        self.completed: list[Request] = []
        #: measured EMA of batch service seconds: bucket -> {batch_size:
        #: seconds}; `service_estimate` answers from it
        self._svc: dict[Any, dict[int, float]] = {}
        self.default_service_s = 0.0

    @property
    def queue(self) -> tuple[Request, ...]:
        """Still-queued requests in arrival order (scheduler-owned)."""
        return self.scheduler.pending()

    # -- service-time model -------------------------------------------------

    def service_estimate(self, bucket, batch_size: int = 1) -> float:
        """Estimated seconds to serve a ``batch_size`` batch of
        ``bucket``: the EMA recorded at the smallest measured batch size
        >= ``batch_size`` (conservative), else the largest measured,
        else ``default_service_s``."""
        sizes = self._svc.get(bucket)
        if not sizes:
            return self.default_service_s
        for s in sorted(sizes):
            if s >= batch_size:
                return sizes[s]
        return sizes[max(sizes)]

    def seed_service_estimate(self, bucket, seconds: float, *,
                              batch_size: int = 1) -> None:
        """Pin the estimate for (bucket, batch_size) — deterministic
        scheduling decisions in tests and benches."""
        self._svc.setdefault(bucket, {})[int(batch_size)] = float(seconds)

    def _record_service(self, bucket, batch_size: int, dt: float) -> None:
        sizes = self._svc.setdefault(bucket, {})
        prev = sizes.get(int(batch_size))
        sizes[int(batch_size)] = (dt if prev is None
                                  else 0.7 * prev + 0.3 * dt)

    # -- the request path ---------------------------------------------------

    def submit(self, payload, *, t: float = 0.0,
               deadline_us: float | None = None,
               priority: int = 0) -> Request:
        """Enqueue one request (``t`` is its arrival time on whatever
        clock the caller keeps; ``deadline_us`` a latency budget relative
        to it, ``priority`` an integer tier — higher is more urgent) and
        return its :class:`Request` handle — ``result`` is filled when a
        :meth:`step` serves it."""
        req = Request(id=self._next_id, payload=payload, t_arrival=t,
                      deadline_us=deadline_us, priority=int(priority))
        self._next_id += 1
        self.scheduler.push(req)
        return req

    def step(self, *, now: float = 0.0) -> list[Request]:
        """Serve ONE scheduler-selected batch and return the completed
        requests; [] when the queue is empty."""
        with _span("serve.step") as span:
            with _span("serve.select"):
                batch = self.scheduler.select(
                    bucket_of=self.servable.bucket_of,
                    max_batch=self.max_batch, now=now,
                    est_service=self.service_estimate)
            if not batch:
                return []
            span.set_metadata(requests=" ".join(str(r.id) for r in batch))
            results = self.servable.run_batch([r.payload for r in batch])
            for req, res in zip(batch, results):
                req.result = res
                req.t_done = now
            self.completed.extend(batch)
            return batch

    def drain(self, *, now: float = 0.0) -> list[Request]:
        """Step until the queue is empty; returns everything completed by
        this call, in completion order."""
        done: list[Request] = []
        while self.queue:
            done.extend(self.step(now=now))
        return done

    def serve_stream(self, stream: Iterable, *,
                     payload_of: Callable = None,
                     deadline_us: float | Callable | None = None,
                     priority_of: Callable = None) -> dict:
        """Replay ``stream`` — an iterable of ``(t_arrival, payload)`` (or
        longer tuples; extra fields are ignored) — under a virtual clock:
        requests are admitted when the clock passes their arrival time,
        each batch advances the clock by its service time as measured on
        the engine's injectable ``clock`` (a :class:`VirtualClock` makes
        the whole replay deterministic), and an empty queue fast-forwards
        to the next arrival. ``deadline_us`` (a scalar for every request,
        or a callable ``item -> budget_us | None``) and ``priority_of``
        (``item -> int``) attach scheduling metadata per arrival. Returns
        latency / throughput / deadline stats (p50/p99 in ms) merged with
        the servable's own counters (plan-cache and frame-tracker hit
        rates, trace counts, ...)."""
        arrivals = deque(stream)
        clock = 0.0
        latencies: list[float] = []
        submitted: list[Request] = []
        n_served = 0
        while arrivals or self.queue:
            if not self.queue and arrivals:
                clock = max(clock, float(arrivals[0][0]))
            while arrivals and float(arrivals[0][0]) <= clock:
                item = arrivals.popleft()
                payload = item[1] if payload_of is None else payload_of(item)
                d_us = (deadline_us(item) if callable(deadline_us)
                        else deadline_us)
                prio = 0 if priority_of is None else int(priority_of(item))
                submitted.append(self.submit(
                    payload, t=float(item[0]), deadline_us=d_us,
                    priority=prio))
            t0 = self.clock.monotonic()
            served = self.step(now=clock)
            if served:
                # jax dispatch is asynchronous — a latency measurement
                # must wait for the logits, not the dispatch
                jax.block_until_ready([r.result for r in served])
            dt = self.clock.monotonic() - t0
            clock += dt
            for req in served:
                req.t_done = clock
                latencies.append(req.latency)
            if served:
                self._record_service(
                    self.servable.bucket_of(served[0].payload),
                    len(served), dt)
            n_served += len(served)
        lat = (np.asarray(latencies, np.float64) if latencies
               else np.zeros(1))
        deadlined = [r for r in submitted if r.deadline_us is not None]
        misses = sum(r.missed for r in deadlined)
        stats = {"n_requests": n_served, "wall_s": clock,
                 "throughput_rps": n_served / max(clock, 1e-9),
                 "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                 "p99_ms": float(np.percentile(lat, 99)) * 1e3,
                 "mean_ms": float(lat.mean()) * 1e3,
                 "scheduler": self.scheduler.name,
                 "n_deadlined": len(deadlined),
                 "n_deadline_misses": int(misses),
                 "deadline_miss_rate":
                     misses / len(deadlined) if deadlined else 0.0}
        stats.update(self.servable.stats())
        return stats

    def stats(self) -> dict:
        """Engine-side queue counters merged with the servable's."""
        s = {"queued": len(self.queue), "completed": len(self.completed),
             "scheduler": self.scheduler.name}
        s.update(self.servable.stats())
        return s


# ---------------------------------------------------------------------------
# the historical LM entry point, now a thin engine client
# ---------------------------------------------------------------------------

def generate(params, cfg: ArchConfig, prompts: jnp.ndarray, *,
             max_new_tokens: int = 32, temperature: float = 0.0,
             key=None, image_embeds=None, verbose: bool = False):
    """prompts (B, S) int32 -> (B, S + max_new_tokens) with timing stats.

    Same signature and stats keys as always, but the work now flows
    through :class:`ServingEngine` + :class:`LMServable` — one cached-jit
    prefill and decode step per (cfg, max_seq), shared with every other
    client of the engine (calling this twice traces once)."""
    b, s = prompts.shape
    servable = LMServable(params, cfg, max_new_tokens=max_new_tokens,
                          temperature=temperature, key=key,
                          image_embeds=image_embeds, max_batch=b)
    engine = ServingEngine(servable)
    reqs = [engine.submit(prompts[i]) for i in range(b)]
    engine.drain()
    out = jnp.stack([r.result for r in reqs])
    st = servable.stats()
    stats = {"prefill_s": st["prefill_s"],
             "decode_tok_per_s": st["decode_tok_per_s"],
             "decode_s": st["decode_s"]}
    if verbose:
        print(f"[serve] prefill {stats['prefill_s']*1e3:.1f} ms, "
              f"{stats['decode_tok_per_s']:.1f} tok/s")
    return out, stats
