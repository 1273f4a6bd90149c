"""Scheduling Order Generation (paper Algorithm 1) + beyond-paper variants.

The scheduler is pure host-side logic — in the Pointer accelerator this is
the small "order generator" unit in the front-end (Fig. 6, orange); here it
produces an ``ExecutionPlan`` consumed by
  * the cycle/energy simulator (``repro.core.simulator``), and
  * the JAX/Pallas execution path (gather orders for the ``aggregate``
    kernel in ``repro.kernels``).

Three scheduling levers (orthogonal, matching the paper's ablation):
  intra-layer order of the LAST layer:
      'index'    — point-index order (paper baseline / Pointer-1 / Pointer-12)
      'greedy'   — topology-aware greedy nearest-neighbor chain
                   (paper Algorithm 1 lines 1-8; the full Pointer)
      'morton'   — beyond-paper: space-filling-curve (Morton/Z-order) order.
                   Same goal as 'greedy' (consecutive points spatially close)
                   but O(n log n) and with no chain-jump pathology.
  inter-layer coordination (paper Algorithm 1 lines 9-13):
      off — layer-by-layer execution (previous SA layer fully completes),
      on  — receptive-field-by-receptive-field execution: a last-layer point
            runs as soon as every member of its pyramid receptive field has
            been produced; members shared between consecutive fields are
            computed once and re-fetched from the on-chip buffer.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Literal, Sequence

import numpy as np

from .workload import PointNetWorkload

__all__ = [
    "ExecutionPlan",
    "DevicePlan",
    "PlanCache",
    "FrameTracker",
    "cloud_content_key",
    "frame_fingerprint",
    "greedy_nn_order",
    "morton_order",
    "coordinate_layers",
    "build_plan",
    "complete_order",
    "inverse_permutation",
    "device_order_greedy",
    "device_order_morton",
    "device_coordinate",
    "device_build_plan",
    "MODE_PRESETS",
]

IntraMode = Literal["index", "greedy", "morton"]


@dataclass(frozen=True)
class ExecutionPlan:
    """orders[k-1]: execution order (point indices) of layer k (k=1..L).
    trace: the interleaved execution sequence [(layer, point_idx), ...] —
    Eq. (1)/(2) of the paper. Each point appears exactly once.

    Immutable: a plan fully describes one execution and is consumed by both
    the simulator and the compiled-model execution path
    (``repro.models.backend``); ``intra`` is set by whoever builds it.
    """

    orders: list[np.ndarray]
    trace: list[tuple[int, int]]
    intra: str
    coordinated: bool

    @property
    def n_layers(self) -> int:
        return len(self.orders)

    def order_of(self, layer: int) -> np.ndarray:
        """Execution order of layer ``layer`` (1-based, like the paper).
        Raises ``ValueError`` for a layer outside ``1..n_layers`` — Python
        indexing would otherwise silently wrap ``layer=0`` to the LAST
        layer and feed a wrong gather order downstream."""
        if not 1 <= layer <= self.n_layers:
            raise ValueError(
                f"layer must be in 1..{self.n_layers} (1-based SA layer "
                f"index); got {layer}")
        return self.orders[layer - 1]


def inverse_permutation(order: np.ndarray) -> np.ndarray:
    """Inverse of a permutation: ``inv[order] = arange(n)`` — the scatter
    that puts plan-ordered results back into index order."""
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0], dtype=order.dtype)
    return inv


def complete_order(order: np.ndarray, n: int, layer: int = 0) -> np.ndarray:
    """Complete a (possibly partial) layer order into a full permutation of
    ``range(n)``.

    A coordinated plan schedules a lower-layer point only when some
    last-layer receptive field needs it; points outside every field are
    dead compute for the network output and absent from the order. The
    dense kernels still run all ``n`` rows (the fused MLP's quant scales
    are global over the launch), so the orphans are appended at the tail —
    after every scheduled point, changing no scheduled DMA.

    Duplicate or out-of-range indices raise ``ValueError`` (even when the
    order is already full length — a duplicated index would otherwise
    silently drop a row from the gather and double another)."""
    order = np.asarray(order)
    if order.ndim != 1:
        raise ValueError(f"layer-{layer} order must be 1-D; got shape "
                         f"{order.shape}")
    if order.shape[0] > n or (order.size
                              and (order.min() < 0 or order.max() >= n)):
        raise ValueError(
            f"ExecutionPlan layer-{layer} order has {order.shape[0]} "
            f"indices; expected at most {n} distinct values in [0, {n})")
    if np.unique(order).shape[0] != order.shape[0]:
        raise ValueError(
            f"ExecutionPlan layer-{layer} order contains duplicate "
            f"indices; each point must be scheduled exactly once")
    if order.shape[0] == n:
        return order
    missing = np.setdiff1d(np.arange(n, dtype=order.dtype), order)
    return np.concatenate([order, missing])


class DevicePlan:
    """A frozen, device-array ``ExecutionPlan``: the schedule as a compiled
    artifact rather than a host loop.

    ``lower`` completes each layer order to a full permutation of the
    layer's size (``complete_order``), builds the inverse scatter
    permutations, converts everything to stacked int32 device tensors, and
    — given several same-config plans — stacks them along a leading batch
    axis. The result is a registered pytree of plain ``jnp`` arrays, so it
    is jit/vmap-safe: ``compile_model(..., schedule=plan)`` lowers the
    plan once at compile time, and planned ``forward``/``batched_forward``
    run under ``jax.jit`` with the orders as ordinary device operands
    (the host never rebuilds the plan per call).

    orders[k-1]   : (n_k,) — or (B, n_k) when batched — int32 permutation
                    executing layer k (padded/completed to the layer size)
    inverses[k-1] : matching inverse permutations (the scatter back to
                    index order that keeps logits order-invariant)
    """

    def __init__(self, orders, inverses, layer_sizes, intra="custom",
                 coordinated=False):
        self.orders = tuple(orders)
        self.inverses = tuple(inverses)
        self.layer_sizes = tuple(int(s) for s in layer_sizes)
        self.intra = intra
        self.coordinated = coordinated

    @classmethod
    def lower(cls, plans, layer_sizes: Sequence[int]) -> "DevicePlan":
        """Lower one ``ExecutionPlan`` (-> unbatched) or a sequence of
        same-shape plans (-> batched, leading batch axis) into device
        tensors. ``layer_sizes[k-1]`` is layer k's point count (the
        ``n_centers`` of the config) — partial coordinated orders are
        completed to it."""
        import jax.numpy as jnp

        single = isinstance(plans, ExecutionPlan)
        plan_list = [plans] if single else list(plans)
        if not plan_list:
            raise ValueError("DevicePlan.lower needs at least one plan")
        layer_sizes = tuple(int(s) for s in layer_sizes)
        if any(p.n_layers != len(layer_sizes) for p in plan_list):
            raise ValueError(
                f"plan layer count does not match layer_sizes "
                f"{layer_sizes}")
        orders, inverses = [], []
        for k, n in enumerate(layer_sizes, start=1):
            per = [complete_order(np.asarray(p.order_of(k)), n, k)
                   for p in plan_list]
            inv = [inverse_permutation(o) for o in per]
            if single:
                orders.append(jnp.asarray(per[0], jnp.int32))
                inverses.append(jnp.asarray(inv[0], jnp.int32))
            else:
                orders.append(jnp.asarray(np.stack(per), jnp.int32))
                inverses.append(jnp.asarray(np.stack(inv), jnp.int32))
        p0 = plan_list[0]
        return cls(orders, inverses, layer_sizes,
                   intra=p0.intra, coordinated=p0.coordinated)

    @classmethod
    def stack(cls, plans: Sequence["DevicePlan"]) -> "DevicePlan":
        """Stack single-cloud :class:`DevicePlan` s along a new leading
        batch axis — the serving tier's batch assembly: per-request plans
        come out of the plan cache one at a time and go into
        ``batched_forward(dplan=...)`` as one batched plan. All plans must
        share ``layer_sizes`` and be unbatched; ``intra``/``coordinated``
        provenance is taken from the first (they describe how the orders
        were built, not what they do — execution only reads the
        tensors)."""
        import jax.numpy as jnp

        plan_list = list(plans)
        if not plan_list:
            raise ValueError("DevicePlan.stack needs at least one plan")
        p0 = plan_list[0]
        for p in plan_list:
            if p.batched:
                raise ValueError("DevicePlan.stack takes single-cloud "
                                 "plans; got a batched one")
            if p.layer_sizes != p0.layer_sizes:
                raise ValueError(
                    f"cannot stack plans with layer sizes {p.layer_sizes} "
                    f"and {p0.layer_sizes}")
        orders = [jnp.stack([p.orders[k] for p in plan_list])
                  for k in range(p0.n_layers)]
        inverses = [jnp.stack([p.inverses[k] for p in plan_list])
                    for k in range(p0.n_layers)]
        return cls(orders, inverses, p0.layer_sizes,
                   intra=p0.intra, coordinated=p0.coordinated)

    @property
    def n_layers(self) -> int:
        return len(self.orders)

    @property
    def batched(self) -> bool:
        return self.orders[0].ndim == 2

    @property
    def batch_size(self) -> int | None:
        return int(self.orders[0].shape[0]) if self.batched else None

    def order_of(self, layer: int):
        if not 1 <= layer <= self.n_layers:
            raise ValueError(
                f"layer must be in 1..{self.n_layers} (1-based SA layer "
                f"index); got {layer}")
        return self.orders[layer - 1]

    def inverse_of(self, layer: int):
        if not 1 <= layer <= self.n_layers:
            raise ValueError(
                f"layer must be in 1..{self.n_layers} (1-based SA layer "
                f"index); got {layer}")
        return self.inverses[layer - 1]

    # -- pytree protocol (sizes & provenance are static aux data) -----------
    def tree_flatten(self):
        return ((self.orders, self.inverses),
                (self.layer_sizes, self.intra, self.coordinated))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def _register_device_plan() -> None:
    import jax
    jax.tree_util.register_pytree_node_class(DevicePlan)


_register_device_plan()


# ---------------------------------------------------------------------------
# the plan cache: content-keyed geometry/plan reuse (serving tier)
# ---------------------------------------------------------------------------

def cloud_content_key(cloud, n_valid: int | None = None) -> str:
    """Content hash of one cloud's REAL rows — the plan-cache key.

    blake2b over the raw bytes of ``cloud[:n_valid]`` (C-contiguous,
    host-pulled) plus the trimmed shape and dtype, so a cloud and its
    shape-bucket-padded copy hash identically (pads carry no plan
    information: masked FPS/kNN never select them — the bucketing contract
    in ``repro.models.backend``), while any byte-level change to a real
    coordinate misses.

    Deliberately row-order-SENSITIVE: FPS is a function of row order (it
    starts at row 0 and ``argmax`` tie-breaks by index), so a permuted
    copy of the same point set has different geometry and needs a
    different plan — two permuted-but-identical clouds must NOT collide
    (tested). Keys are hex strings: stable across processes, printable in
    ``stats()``."""
    arr = np.ascontiguousarray(np.asarray(cloud))
    if n_valid is not None:
        arr = np.ascontiguousarray(arr[:int(n_valid)])
    h = hashlib.blake2b(digest_size=16)
    h.update(str((arr.shape, arr.dtype.str)).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


class PlanCache:
    """Content-keyed LRU cache of single-cloud :class:`DevicePlan` s.

    The serving tier's geometry shortcut: repeated or temporally-coherent
    clouds (the paper's streaming-inference setting — consecutive LiDAR
    sweeps) hash to keys already seen, so planning is skipped entirely —
    ``device_build_plan`` never runs for a hit (device path), and neither
    does the host Algorithm-1 walk (host path). Values are device-resident
    int32 tensors (~``2 * sum(n_k) * 4`` bytes each), so ``capacity`` is
    cheap to keep in the hundreds.

    Eviction is least-recently-USED: ``get`` hits refresh recency, and
    inserting past ``capacity`` drops the coldest entry (counted in
    ``evictions``). ``stats()`` surfaces hits/misses/evictions plus the
    derived ``hit_rate`` — the serving engine merges this into its own
    ``stats()``.

    Invalidation: content addressing makes stale entries unreachable
    rather than wrong — a plan is a pure function of the cloud's real
    rows and the model's schedule spec, so use one cache per compiled
    model (different schedules map the same key to different plans) and
    ``clear()`` on model swap."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1; got {capacity}")
        self.capacity = int(capacity)
        self._entries: OrderedDict[str, DevicePlan] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: str) -> bool:
        return key in self._entries

    def get(self, key: str) -> DevicePlan | None:
        """The cached plan for ``key`` (refreshing its recency), or None —
        counted as a hit/miss."""
        plan = self._entries.get(key)
        if plan is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return plan

    def put(self, key: str, plan: DevicePlan) -> None:
        """Insert (or refresh) ``key``; evicts the least-recently-used
        entry when past capacity."""
        if key in self._entries:
            self._entries.move_to_end(key)
        self._entries[key] = plan
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1

    def get_or_build(self, key: str,
                     build: Callable[[], DevicePlan]) -> DevicePlan:
        """``get(key)``, calling ``build()`` and caching its result on a
        miss — the one-liner the serving engine uses per request."""
        plan = self.get(key)
        if plan is None:
            plan = build()
            self.put(key, plan)
        return plan

    def clear(self) -> None:
        """Drop every entry (counters keep accumulating — they describe
        the cache's lifetime, not its current contents)."""
        self._entries.clear()

    def stats(self) -> dict:
        """``{'size', 'capacity', 'hits', 'misses', 'evictions',
        'hit_rate'}`` — hit_rate over all lookups so far (0.0 before
        any)."""
        total = self.hits + self.misses
        return {"size": len(self._entries), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0}


# ---------------------------------------------------------------------------
# frame-coherent plan reuse: the inter-layer coordination story across time
# ---------------------------------------------------------------------------

def frame_fingerprint(cloud, n_valid: int | None = None, *,
                      cell: float = 1e-3) -> str:
    """Cheap coarse fingerprint of one cloud's REAL rows — the
    frame-tracker's fast path, checked BEFORE the exact
    :func:`cloud_content_key`.

    Each valid coordinate is floored onto an absolute grid of pitch
    ``cell`` (float64, so the bucketing is dtype-stable) and the int64
    bucket array is blake2b-hashed together with the trimmed shape.
    Equal fingerprints on equal shapes therefore certify that every
    point moved LESS than ``cell`` per axis since the reference frame —
    a displacement bound by construction, not a heuristic. The converse
    does not hold (a point sitting on a grid line flips buckets under
    any jitter), which is why :class:`FrameTracker` falls back to the
    exact displacement check on a fingerprint mismatch.

    Pad rows are trimmed before hashing (same contract as
    :func:`cloud_content_key`): a cloud and its shape-bucket-padded copy
    fingerprint identically."""
    if cell <= 0.0:
        raise ValueError(f"cell must be > 0; got {cell}")
    arr = np.asarray(cloud)
    if n_valid is not None:
        arr = arr[:int(n_valid)]
    q = np.floor(np.asarray(arr, np.float64) / cell).astype(np.int64)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(arr.shape).encode())
    h.update(np.ascontiguousarray(q).tobytes())
    return h.hexdigest()


class FrameTracker:
    """Frame-coherent :class:`DevicePlan` reuse for streaming LiDAR.

    Consecutive sweeps of a driving scene are near-duplicates: every
    point moves a little, so the exact :func:`cloud_content_key` misses
    on every frame even though the plan it would build is (bit for bit)
    the one built last frame. The tracker keeps one ANCHOR — the last
    cloud a plan was actually built for — and serves that plan for any
    new frame within ``tol`` of it: first the coarse
    :func:`frame_fingerprint` (equality certifies per-axis displacement
    < ``cell``), then the exact max-displacement check against the
    stored anchor rows. A hit (``frame_hits``) skips keying, cache
    lookup and plan construction entirely; a miss re-anchors on the new
    frame's freshly built plan, so total drift is bounded by ``tol`` no
    matter how long the stream runs.

    Safety argument (DESIGN.md §14): a ``DevicePlan`` is a set of
    per-layer *permutations* — planned execution gathers in plan order
    and scatters straight back to index order, so logits are bitwise
    order-invariant in the plan (tested since PR 3). Reusing a
    neighbor frame's plan can therefore never change served bits, only
    the DMA-elision quality of the order; ``tol`` is a performance
    knob that keeps the reused order near-optimal (and at streaming
    jitter scales, bit-identical to the fresh build — property-tested),
    not a correctness gate."""

    def __init__(self, tol: float = 1e-3, *, cell: float | None = None):
        if tol <= 0.0:
            raise ValueError(f"tol must be > 0; got {tol}")
        self.tol = float(tol)
        self.cell = self.tol if cell is None else float(cell)
        if self.cell <= 0.0:
            raise ValueError(f"cell must be > 0; got {cell}")
        self._anchor: np.ndarray | None = None
        self._anchor_fp: str | None = None
        self._anchor_plan: DevicePlan | None = None
        self.frame_hits = 0
        self.frame_misses = 0
        self.fingerprint_hits = 0
        self.reanchors = 0

    def _trim(self, cloud, n_valid):
        arr = np.asarray(cloud)
        return arr if n_valid is None else arr[:int(n_valid)]

    def lookup(self, cloud, n_valid: int | None = None) -> DevicePlan | None:
        """The anchor's plan if ``cloud``'s real rows are a near-duplicate
        of the anchor frame (fingerprint equality, else max per-coordinate
        displacement <= ``tol``), recording a ``frame_hit``; None — a
        ``frame_miss`` — otherwise. A miss means the caller should build
        (or cache-fetch) a fresh plan and :meth:`update` with it."""
        arr = self._trim(cloud, n_valid)
        if (self._anchor is None or arr.shape != self._anchor.shape
                or arr.dtype != self._anchor.dtype):
            self.frame_misses += 1
            return None
        if frame_fingerprint(arr, cell=self.cell) == self._anchor_fp:
            self.fingerprint_hits += 1
            self.frame_hits += 1
            return self._anchor_plan
        disp = np.max(np.abs(np.asarray(arr, np.float64)
                             - np.asarray(self._anchor, np.float64)))
        if disp <= self.tol:
            self.frame_hits += 1
            return self._anchor_plan
        self.frame_misses += 1
        return None

    def update(self, cloud, plan: DevicePlan,
               n_valid: int | None = None) -> None:
        """Re-anchor on ``cloud`` (real rows) and its freshly built
        ``plan`` — called after every :meth:`lookup` miss."""
        arr = np.array(self._trim(cloud, n_valid), copy=True)
        self._anchor = arr
        self._anchor_fp = frame_fingerprint(arr, cell=self.cell)
        self._anchor_plan = plan
        self.reanchors += 1

    def clear(self) -> None:
        """Drop the anchor (counters keep accumulating)."""
        self._anchor = None
        self._anchor_fp = None
        self._anchor_plan = None

    def stats(self) -> dict:
        """``{'frame_hits', 'frame_misses', 'fingerprint_hits',
        'reanchors', 'hit_rate'}`` — hit_rate over all lookups so far."""
        total = self.frame_hits + self.frame_misses
        return {"frame_hits": self.frame_hits,
                "frame_misses": self.frame_misses,
                "fingerprint_hits": self.fingerprint_hits,
                "reanchors": self.reanchors,
                "hit_rate": self.frame_hits / total if total else 0.0}


#: Above this many points ``greedy_nn_order`` recomputes distances per step
#: instead of materializing the O(n^2) pairwise matrix (n=2048 -> 32 MB).
GREEDY_DENSE_LIMIT = 2048


def greedy_nn_order(points: np.ndarray, start: int = 0) -> np.ndarray:
    """Paper Algorithm 1, lines 1-8: repeatedly append the unscheduled point
    nearest to the last scheduled one. n is the last layer's size (128 in
    the paper), so for n <= GREEDY_DENSE_LIMIT the full pairwise distance
    matrix is precomputed once and each step is a masked argmin over a row
    — the per-step ``np.sum((points - points[cur])**2)`` recompute only
    remains as the large-n fallback. The coordinate-wise accumulation below
    reproduces ``np.sum(..., axis=1)`` rounding exactly, so the order is
    bit-identical to the per-step variant (regression-tested)."""
    n = points.shape[0]
    if n == 0:
        return np.empty(0, dtype=np.int64)
    dense = n <= GREEDY_DENSE_LIMIT
    if dense:
        d2 = (points[:, 0, None] - points[None, :, 0]) ** 2
        for c in range(1, points.shape[1]):
            d2 += (points[:, c, None] - points[None, :, c]) ** 2
    remaining = np.ones(n, dtype=bool)
    order = np.empty(n, dtype=np.int64)
    cur = int(start)
    for i in range(n):
        order[i] = cur
        remaining[cur] = False
        if i == n - 1:
            break
        if dense:
            d = np.where(remaining, d2[cur], np.inf)
        else:
            d = np.sum((points - points[cur]) ** 2, axis=1)
            d[~remaining] = np.inf
        cur = int(np.argmin(d))
    return order


def _interleave_bits(v: np.ndarray, nbits: int) -> np.ndarray:
    out = np.zeros(v.shape[0], dtype=np.uint64)
    for b in range(nbits):
        out |= ((v[:, 0].astype(np.uint64) >> b) & 1) << np.uint64(3 * b + 2)
        out |= ((v[:, 1].astype(np.uint64) >> b) & 1) << np.uint64(3 * b + 1)
        out |= ((v[:, 2].astype(np.uint64) >> b) & 1) << np.uint64(3 * b)
    return out


def morton_order(points: np.ndarray, nbits: int = 10) -> np.ndarray:
    """Beyond-paper: order points along a Morton (Z-order) space-filling
    curve. Unlike the greedy chain it cannot "strand" far-away points for
    the end of the order, and it needs no O(n^2) search.

    Degenerate axes (``hi == lo``: planar or collinear clouds) are clamped
    to bucket 0 by treating their extent as 1, instead of dividing by the
    old fixed ``1e-12`` epsilon — which left bucket 0 only by the accident
    of exact ``points - lo`` cancellation and quantized any sub-epsilon
    spread relative to the epsilon rather than the true extent, collapsing
    distinct coordinates into one bucket. Quantization happens in the
    input dtype, so :func:`device_order_morton` on the same coordinates
    produces the bit-identical permutation (regression-tested)."""
    lo = points.min(axis=0, keepdims=True)
    hi = points.max(axis=0, keepdims=True)
    extent = hi - lo
    safe = np.where(extent > 0, extent, np.ones_like(extent))
    q = ((points - lo) / safe * (2**nbits - 1)).astype(np.uint64)
    return np.argsort(_interleave_bits(q, nbits), kind="stable")


def coordinate_layers(workload: PointNetWorkload, last_order: np.ndarray,
                      *, intra: str = "custom") -> ExecutionPlan:
    """Paper Algorithm 1, lines 9-13 (+ the dedup described in §3.2): walk
    the last layer in ``last_order``; recursively schedule each point's
    receptive-field members in lower layers immediately before it, skipping
    members already executed ("they only need to be calculated once")."""
    L = workload.n_layers
    done = [np.zeros(workload.points[k].shape[0], dtype=bool)
            for k in range(L + 1)]
    orders: list[list[int]] = [[] for _ in range(L + 1)]
    trace: list[tuple[int, int]] = []

    def execute(layer: int, i: int) -> None:
        if done[layer][i]:
            return
        if layer > 1:
            for m in workload.neighbors[layer][i]:
                execute(layer - 1, int(m))
        done[layer][i] = True
        orders[layer].append(i)
        trace.append((layer, i))

    for j in last_order:
        execute(L, int(j))
    return ExecutionPlan(
        orders=[np.asarray(orders[k], dtype=np.int64) for k in range(1, L + 1)],
        trace=trace, intra=intra, coordinated=True)


# ---------------------------------------------------------------------------
# on-device planning: the same three passes as JAX computations
# ---------------------------------------------------------------------------
#
# The NumPy functions above are the host oracles; the ``device_*`` twins
# below re-express them in jnp/lax so plan CONSTRUCTION — not just plan
# execution (PR 5) — happens inside a jit trace. This is the paper's
# Algorithm 1 running where the hardware runs it: Pointer's order generator
# sits in the accelerator front-end, and PointAcc makes the same argument
# with a dedicated mapping unit. Contract: on the same coordinates (same
# dtype), each device function returns the bit-identical permutation to its
# host oracle (tie-breaks included: ``argmin``/``argsort`` pick the first
# minimum on both sides, stable sorts preserve index order on equal keys).
# The device greedy sweep materializes the O(n^2) pairwise matrix, so it is
# limited to n <= GREEDY_DENSE_LIMIT — exactly the regime where the host
# dense path (whose rounding it mirrors) runs.


def device_order_greedy(points, start: int = 0):
    """Device twin of :func:`greedy_nn_order` (paper Algorithm 1 lines
    1-8): a masked-argmin ``lax.fori_loop`` sweep over the precomputed
    pairwise distance matrix. ``points`` is a traced/device ``(n, d)``
    array with n <= ``GREEDY_DENSE_LIMIT`` (static); returns ``(n,)``
    int32. The distance matrix accumulates coordinate-wise in the same
    order as the host dense path, so orders are bit-identical for equal
    input dtype."""
    import jax.numpy as jnp
    from jax import lax

    points = jnp.asarray(points)
    n = points.shape[0]
    if n > GREEDY_DENSE_LIMIT:
        raise ValueError(
            f"device_order_greedy materializes an O(n^2) distance matrix "
            f"and is limited to n <= {GREEDY_DENSE_LIMIT}; got n={n} "
            f"(use the host greedy_nn_order fallback)")
    if n == 0:
        return jnp.empty(0, dtype=jnp.int32)
    d2 = (points[:, 0, None] - points[None, :, 0]) ** 2
    for c in range(1, points.shape[1]):
        d2 = d2 + (points[:, c, None] - points[None, :, c]) ** 2

    def body(i, state):
        order, remaining, cur = state
        order = order.at[i].set(cur)
        remaining = remaining.at[cur].set(False)
        d = jnp.where(remaining, d2[cur], jnp.inf)
        return order, remaining, jnp.argmin(d).astype(jnp.int32)

    order, _, _ = lax.fori_loop(
        0, n, body,
        (jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.bool_),
         jnp.asarray(start, jnp.int32)))
    return order


def device_order_morton(points, nbits: int = 10):
    """Device twin of :func:`morton_order`: quantize each axis to
    ``nbits`` buckets (degenerate axes pinned to bucket 0, same clamp as
    the host), interleave bits into a uint32 Z-order key, stable-argsort.
    Trivially vectorizable — no loops over points at all."""
    import jax.numpy as jnp

    if 3 * nbits > 32:
        raise ValueError(f"3*nbits must fit a uint32 key; got nbits={nbits}")
    points = jnp.asarray(points)
    lo = points.min(axis=0, keepdims=True)
    hi = points.max(axis=0, keepdims=True)
    extent = hi - lo
    safe = jnp.where(extent > 0, extent, jnp.ones_like(extent))
    q = ((points - lo) / safe * (2**nbits - 1)).astype(jnp.uint32)
    key = jnp.zeros(points.shape[0], jnp.uint32)
    for b in range(nbits):
        key = key | (((q[:, 0] >> b) & 1) << (3 * b + 2))
        key = key | (((q[:, 1] >> b) & 1) << (3 * b + 1))
        key = key | (((q[:, 2] >> b) & 1) << (3 * b))
    return jnp.argsort(key, stable=True).astype(jnp.int32)


def device_coordinate(neighbors, last_order):
    """Device twin of :func:`coordinate_layers` (paper Algorithm 1 lines
    9-13): the recursive receptive-field walk in closed form, one
    loop-free pass per layer from the top down.

    neighbors[k-1] : (n_k, K_k) device int array — layer k's receptive
                     fields, indices into layer k-1 (k = 1..L; the layer-1
                     entry is carried for shape/size only, its contents
                     never gate scheduling below layer 1).
    last_order     : (n_L,) device int array, the layer-L execution order.

    Returns one int32 **full permutation per layer** (1..L) in
    :class:`DevicePlan` layout: the walk's partial order with the orphan
    points (outside every last-layer receptive field) appended at the tail
    in ascending index order — exactly ``complete_order`` of the host
    walk's output, bit-identical (tested).

    The host recursion visits layer k in this order: the live (walked)
    layer-(k+1) points in their execution order, each one's receptive
    field row by row, each index kept at its first occurrence only
    ("calculated once"). So each layer is one scatter-min of flat row
    positions (the first occurrence of every index; positions under an
    orphan parent, which the walk never enters, are masked to a sentinel)
    and one argsort whose key puts the visited indices in first-occurrence
    order and the orphans after them in ascending index order."""
    import jax.numpy as jnp

    nbrs = [jnp.asarray(nb, jnp.int32) for nb in neighbors]
    L = len(nbrs)
    order = jnp.asarray(last_order, jnp.int32)
    live = jnp.ones(order.shape[0], jnp.bool_)
    orders = [order]
    for k in range(L - 1, 0, -1):
        n_k = nbrs[k - 1].shape[0]
        rows = nbrs[k][order]                       # (n_{k+1}, K), walk order
        sentinel = rows.size
        pos = jnp.arange(sentinel, dtype=jnp.int32).reshape(rows.shape)
        pos = jnp.where(live[:, None], pos, sentinel)
        first = (jnp.full(n_k, sentinel, jnp.int32)
                 .at[rows.ravel()].min(pos.ravel()))
        visited = first < sentinel
        key = jnp.where(visited, first,
                        sentinel + jnp.arange(n_k, dtype=jnp.int32))
        order = jnp.argsort(key).astype(jnp.int32)
        live = visited[order]
        orders.append(order)
    return orders[::-1]


def _device_inverse(order):
    """Device :func:`inverse_permutation`: ``inv[order] = arange(n)``."""
    import jax.numpy as jnp

    return (jnp.zeros_like(order)
            .at[order].set(jnp.arange(order.shape[0], dtype=order.dtype)))


def device_build_plan(neighbors, last_points, *, intra: IntraMode = "index",
                      coordinated: bool = False, start: int = 0,
                      nbits: int = 10) -> DevicePlan:
    """Build a single-cloud :class:`DevicePlan` entirely from device
    arrays — the whole of :func:`build_plan` + ``DevicePlan.lower`` as one
    traceable computation (vmap it over stacked per-cloud geometry for a
    batched plan). ``neighbors``/``last_points`` are the traced geometry
    outputs of the forward pass itself: neighbors[k-1] is layer k's
    (n_k, K) receptive fields, last_points the layer-L coordinates that
    the intra order sorts. The intra order's ops sit under the named
    scope ``order``, the coordination walk's under ``coordinate``."""
    import jax
    import jax.numpy as jnp

    sizes = tuple(int(nb.shape[0]) for nb in neighbors)
    with jax.named_scope("order"):
        if intra == "index":
            last = jnp.arange(sizes[-1], dtype=jnp.int32)
        elif intra == "greedy":
            last = device_order_greedy(last_points, start=start)
        elif intra == "morton":
            last = device_order_morton(last_points, nbits=nbits)
        else:
            raise ValueError(f"unknown intra mode {intra!r}")
    if coordinated:
        with jax.named_scope("coordinate"):
            orders = device_coordinate(neighbors, last)
    else:
        orders = [jnp.arange(n, dtype=jnp.int32) for n in sizes[:-1]] + [last]
    return DevicePlan(orders, [_device_inverse(o) for o in orders], sizes,
                      intra=intra, coordinated=coordinated)


def _layer_by_layer(workload: PointNetWorkload, last_order: np.ndarray,
                    *, intra: str = "custom") -> ExecutionPlan:
    """No coordination: each SA layer completes before the next begins.
    Lower layers run in index order (paper §3.1); the last layer runs in
    ``last_order`` (index order for the baseline / Pointer-1 / Pointer-12)."""
    L = workload.n_layers
    orders = [np.arange(workload.points[k].shape[0], dtype=np.int64)
              for k in range(1, L + 1)]
    orders[L - 1] = np.asarray(last_order, dtype=np.int64)
    trace = [(k, int(i)) for k in range(1, L + 1) for i in orders[k - 1]]
    return ExecutionPlan(orders=orders, trace=trace, intra=intra,
                         coordinated=False)


def build_plan(workload: PointNetWorkload, *, intra: IntraMode = "index",
               coordinated: bool = False, start: int = 0) -> ExecutionPlan:
    last_pts = workload.points[workload.n_layers]
    if intra == "index":
        last_order = np.arange(last_pts.shape[0], dtype=np.int64)
    elif intra == "greedy":
        last_order = greedy_nn_order(last_pts, start=start)
    elif intra == "morton":
        last_order = morton_order(last_pts)
    else:
        raise ValueError(f"unknown intra mode {intra!r}")
    return (coordinate_layers(workload, last_order, intra=intra) if coordinated
            else _layer_by_layer(workload, last_order, intra=intra))


#: Paper design points: ``(intra, coordinated)``.
MODE_PRESETS: dict[str, dict] = {
    "baseline":   dict(intra="index", coordinated=False),  # MARS-like / Pointer-1 order
    "pointer-1":  dict(intra="index", coordinated=False),
    "pointer-12": dict(intra="index", coordinated=True),
    "pointer":    dict(intra="greedy", coordinated=True),
    # beyond-paper
    "pointer-morton": dict(intra="morton", coordinated=True),
}
