"""PointNet++ in pure JAX — the workload Pointer accelerates.

Implements the paper's two-stage set-abstraction (SA) pipeline exactly as
described in Fig. 1:

  point mapping   : farthest point sampling (FPS) + k-NN neighbor search
  feature proc.   : aggregation  D(F_i, F_j) = F_j - F_i   (per neighbor)
                    feature computation  M(D(...))          (3-stage MLP)
                    reduction            column-wise max over neighbors

plus a classification head for the end-to-end training example, and
PointNet++'s multi-scale grouping (``MSGLayerSpec``): per branch a ball
query (:func:`ball_query`) and its own MLP over the grouped rows
``[p_j - p_i ; f_j]``, the branches concatenated, then a group-all layer
over ``[p_j ; f_j]``. The
geometry functions are the JAX twins of the NumPy ones in
``repro.core.workload`` (cross-checked in tests); this module is what the
dry-run/trainer lower, while ``repro.core`` is what the accelerator
simulator consumes.

Backend selection lives in ``repro.models.backend`` (the registry +
``compile_model`` entry point — see the backend table in README.md and
DESIGN.md §9); this module keeps the geometry primitives (FPS, kNN,
``_sa_geometry``), parameter init, ``build_model_program``, and
``_apply_mlp`` that the registered backends compose, plus
``forward``/``batched_forward``/``loss_fn`` as thin float-backend
delegates for quick scripting. The pre-registry ``matmul=``/``program=``
kwargs — deprecated shims since PR 3 — are gone; DESIGN.md §9 keeps the
migration table as the historical record.

All ReRAM backends are numerically the quantized network (paper's
no-accuracy-variation property); the fused paths share the per-layer
path's integer arithmetic exactly.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.workload import (MSGLayerSpec, PointNetConfig, SALayerSpec,
                                 map_mlps)
from repro.kernels import build_program

Params = Any


# ---------------------------------------------------------------------------
# geometry: the "point mapping" stage
# ---------------------------------------------------------------------------

def farthest_point_sample(points: jnp.ndarray, n_samples: int,
                          start: int = 0, *,
                          n_valid=None) -> jnp.ndarray:
    """FPS over ``points`` (N, 3) -> (n_samples,) int32 indices.
    Deterministic (start point given); identical to
    ``core.workload.farthest_point_sample_np``.

    ``n_valid`` masks trailing pad rows (the serving tier's shape-bucket
    padding): rows ``>= n_valid`` start at ``-inf`` min-distance, so the
    running ``argmax`` can never select them, while every real row keeps
    exactly the distances the unpadded cloud would produce — the selected
    indices are bitwise-identical to FPS on ``points[:n_valid]``
    (``argmax`` picks the first maximum on both sides, and the pads are
    strictly smaller than any real squared distance). ``n_valid`` may be a
    traced scalar, so one jit trace serves every occupancy of a bucket."""
    n = points.shape[0]

    def body(i, state):
        idx, dist, cur = state
        idx = idx.at[i].set(cur)
        d = jnp.sum((points - points[cur]) ** 2, axis=1)
        dist = jnp.minimum(dist, d)
        return idx, dist, jnp.argmax(dist).astype(jnp.int32)

    idx0 = jnp.zeros(n_samples, dtype=jnp.int32)
    dist0 = jnp.full((n,), jnp.inf, dtype=points.dtype)
    if n_valid is not None:
        dist0 = jnp.where(jnp.arange(n) < n_valid, dist0, -jnp.inf)
    idx, _, _ = jax.lax.fori_loop(0, n_samples, body,
                                  (idx0, dist0, jnp.int32(start)))
    return idx


def knn(queries: jnp.ndarray, points: jnp.ndarray, k: int, *,
        n_valid=None) -> jnp.ndarray:
    """(Q, k) indices of k nearest ``points`` per query (self included when
    the query is a member of ``points``).

    ``n_valid`` masks trailing pad rows (serving shape buckets): their
    distance is forced to ``+inf``, so as long as ``k <= n_valid`` the
    ``top_k`` selection — values AND index tie-breaks — is bitwise the
    selection over ``points[:n_valid]`` alone (the pads are strictly worse
    than any finite real distance and all real comparisons are
    unchanged)."""
    d = jnp.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1)
    if n_valid is not None:
        d = jnp.where(jnp.arange(points.shape[0])[None, :] < n_valid,
                      d, jnp.inf)
    _, idx = jax.lax.top_k(-d, k)
    return idx


def ball_query(queries: jnp.ndarray, points: jnp.ndarray, radius: float,
               k: int, *, n_valid=None) -> jnp.ndarray:
    """(Q, k) int32: per query the first ``k`` points in index order whose
    squared distance is below ``radius**2``, the slots past the last hit
    filled with the first hit (the reference code's ``query_ball_point``);
    identical to ``core.workload.ball_query_np`` on the same coordinates.
    Squared distances accumulate x, y, z in that order, and ``radius**2``
    is formed in the points' dtype, as on the host.

    ``n_valid`` masks trailing pad rows out of every ball, so a padded
    cloud selects exactly what the unpadded one does. A query that is a
    member of ``points`` is its own first hit, so every slot is filled."""
    n = points.shape[0]
    r = jnp.asarray(radius, points.dtype)
    d = (queries[:, None, 0] - points[None, :, 0]) ** 2
    for c in range(1, points.shape[1]):
        d = d + (queries[:, None, c] - points[None, :, c]) ** 2
    hit = d < r * r
    if n_valid is not None:
        hit = hit & (jnp.arange(n)[None, :] < n_valid)
    # a hit's key is its index, a miss's is n: the k smallest keys are
    # the first k hits in index order, then misses
    key = jnp.where(hit, jnp.arange(n, dtype=jnp.int32)[None, :], n)
    first = -jax.lax.top_k(-key, k)[0]
    return jnp.where(first == n, first[:, :1], first)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _init_mlp(key, widths: tuple[int, ...], dtype=jnp.float32):
    params = []
    for i, (n, m) in enumerate(zip(widths[:-1], widths[1:])):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (n, m), dtype) * jnp.sqrt(2.0 / n)
        params.append({"w": w, "b": jnp.zeros((m,), dtype)})
    return params


def init_params(key, config: PointNetConfig, n_classes: int = 40,
                dtype=jnp.float32) -> Params:
    """He-normal weights and zero biases: ``sa[i]`` is layer i's MLP (a
    list of ``{"w", "b"}``), or for a multi-scale layer one such MLP per
    branch; ``sa_all`` the group-all MLP where the config has one;
    ``head`` the classifier."""
    keys = jax.random.split(key, config.n_layers + 1)
    sa = []
    for k, spec in zip(keys[:-1], config.layers):
        if isinstance(spec, MSGLayerSpec):
            sa.append([_init_mlp(kb, b.mlp, dtype) for kb, b in zip(
                jax.random.split(k, len(spec.branches)), spec.branches)])
        else:
            sa.append(_init_mlp(k, spec.mlp, dtype))
    head = _init_mlp(keys[-1], (config.out_features, *config.head,
                                n_classes), dtype)
    params = {"sa": sa, "head": head}
    if config.group_all is not None:
        params["sa_all"] = _init_mlp(jax.random.fold_in(keys[-1], 1),
                                     config.group_all.mlp, dtype)
    return params


def build_model_program(params: Params, *, ecc=None) -> dict:
    """Program every MLP of the model into crossbars ('reram-fused'
    backend): one :class:`~repro.kernels.CrossbarProgram` per SA layer —
    per branch of a multi-scale layer — plus one for the group-all layer
    where there is one and one for the classification head. Weights are
    quantized and plane-encoded here, exactly once — pass the result to
    ``forward``/``batched_forward`` and the per-forward hot path never
    touches ``encode_planes``/``quantize_tensor`` on weights again.

    ``ecc`` (an :class:`repro.reliability.EccConfig`, or True for the
    default) Hamming-protects every program's spare columns at build time
    (DESIGN.md §13); MVM results are unchanged."""
    return map_mlps(lambda _, mlp: build_program(mlp, ecc=ecc), params)


# ---------------------------------------------------------------------------
# feature processing
# ---------------------------------------------------------------------------

def _apply_mlp(mlp_params, x, *, final_relu=True, matmul=None):
    mm = matmul if matmul is not None else lambda a, w: a @ w
    for i, lyr in enumerate(mlp_params):
        x = mm(x, lyr["w"]) + lyr["b"]
        if final_relu or i < len(mlp_params) - 1:
            x = jax.nn.relu(x)
    return x


def lift_features(points: jnp.ndarray, n_features: int) -> jnp.ndarray:
    """Deterministic layer-0 features of width ``n_features`` from raw
    coordinates (xyz, bias, and sin/cos liftings — stands in for the
    normals/colors real datasets provide)."""
    n = points.shape[0]
    feats = [points, jnp.ones((n, 1), points.dtype),
             jnp.sin(3.0 * points), jnp.cos(3.0 * points),
             jnp.sin(7.0 * points), jnp.cos(7.0 * points)]
    f = jnp.concatenate(feats, axis=-1)
    return f[:, :n_features]


def point_normals(points: jnp.ndarray) -> jnp.ndarray:
    """The 3 normal channels a multi-scale model's first layer takes,
    stood in by each point's direction from the centre,
    ``p / max(|p|, 1e-6)``: the synthetic objects are centred and carry
    no normals."""
    norm = jnp.sqrt(jnp.sum(points * points, axis=-1, keepdims=True))
    return points / jnp.maximum(norm, 1e-6)


def input_features(config: PointNetConfig, cloud: jnp.ndarray):
    """Layer-0 features of one cloud: the stand-in normals for a
    multi-scale first layer, else :func:`lift_features`."""
    first = config.layers[0]
    if isinstance(first, MSGLayerSpec):
        return point_normals(cloud)
    return lift_features(cloud, first.in_features)


def branch_bounds(spec) -> list[tuple[int, int]]:
    """Column range of each branch in a layer's concatenated receptive
    fields: one range for a single-scale layer."""
    if not isinstance(spec, MSGLayerSpec):
        return [(0, spec.n_neighbors)]
    ends = np.cumsum([0] + [b.n_neighbors for b in spec.branches])
    return [(int(a), int(b)) for a, b in zip(ends[:-1], ends[1:])]


def grouping_operands(spec, points, features):
    """``(rows, centres)``: what a layer's gather reads at a neighbour
    and subtracts at the centre. Single-scale: ``F_j - F_i`` on the
    features alike (``centres`` None: the rows themselves); multi-scale:
    ``[p_j ; f_j] - [p_i ; 0]``, the grouped row ``[p_j - p_i ; f_j]``.
    Leading batch dims pass through."""
    if not isinstance(spec, MSGLayerSpec):
        return features, None
    rows = jnp.concatenate([points, features], axis=-1)
    ctrs = jnp.concatenate([points, jnp.zeros_like(features)], axis=-1)
    return rows, ctrs


def geometry_pass(config: PointNetConfig, cloud: jnp.ndarray, *,
                  n_valid=None):
    """The full FPS/kNN geometry of every SA layer on one cloud, as
    device tensors that never leave the trace: per layer k = 1..L the
    FPS-selected coordinates ``pts[k]`` (n_k, 3), center indices
    ``ctr[k]`` (n_k,) into layer k-1, and receptive fields ``nbr[k]``
    (n_k, K) into layer k-1 (index 0 holds the input cloud / None / None,
    matching :class:`~repro.core.workload.PointNetWorkload` layout). A
    multi-scale layer's ``nbr[k]`` is its branches' ball queries
    concatenated, (n_k, sum K_b) (:func:`branch_bounds` splits it).

    This is the planning pipeline's input: ``compile_model``'s planned
    execution builds its gather orders from exactly these tensors —
    on device via :func:`repro.core.schedule.device_build_plan` (so the
    whole cloud→logits function jits), or on host after an explicit
    ``np.asarray`` pull when device planning is off. vmap it for a batch;
    every output is an ordinary jnp array (int32 indices), so nothing
    here forces a host sync.

    ``n_valid`` marks the real row count of a shape-bucket-padded cloud
    (serving tier): it masks the FIRST layer's FPS/kNN only — every later
    layer operates on FPS-selected real points, so the rest of the pass is
    untouched and the whole geometry is bitwise-equal to the unpadded
    cloud's (the bucketing contract in ``repro.models.backend``).

    Its ops sit under the named scopes ``geometry/fps`` and
    ``geometry/knn`` (``geometry/ball`` for ball queries), so a profile
    tells them apart wherever the pass is traced."""
    pts_list, ctr_list, nbr_list = [cloud], [None], [None]
    pts = cloud
    with jax.named_scope("geometry"):
        for li, spec in enumerate(config.layers):
            nv = n_valid if li == 0 else None
            with jax.named_scope("fps"):
                centers = farthest_point_sample(pts, spec.n_centers,
                                                n_valid=nv)
                c_pts = pts[centers]
            if isinstance(spec, MSGLayerSpec):
                with jax.named_scope("ball"):
                    nbr = jnp.concatenate(
                        [ball_query(c_pts, pts, b.radius, b.n_neighbors,
                                    n_valid=nv) for b in spec.branches],
                        axis=1)
            else:
                with jax.named_scope("knn"):
                    nbr = knn(c_pts, pts, spec.n_neighbors, n_valid=nv)
            pts_list.append(c_pts)
            ctr_list.append(centers)
            nbr_list.append(nbr)
            pts = c_pts
    return pts_list, ctr_list, nbr_list


def _sa_geometry(spec: SALayerSpec, points, features, n_valid=None):
    """The point-mapping + aggregation half of one SA layer on a single
    cloud: FPS centers, k-NN gather, neighbor-minus-center differences.
    points (N, 3), features (N, C_in) -> (M, 3), (M, K, C_in). ``n_valid``
    masks trailing pad rows (layer-0 shape buckets) out of FPS and kNN."""
    centers = farthest_point_sample(points, spec.n_centers, n_valid=n_valid)
    c_pts = points[centers]
    nbr = knn(c_pts, points, spec.n_neighbors, n_valid=n_valid)  # (M, K)
    f_nbr = features[nbr]                               # (M, K, C)
    f_ctr = features[centers][:, None, :]
    return c_pts, f_nbr - f_ctr                         # aggregation D(.)


def sa_group(spec, points, features, n_valid=None):
    """:func:`_sa_geometry` for any layer: the centres' coordinates and a
    list of grouped-row tensors, one (M, K_b, C) per branch — a single
    ``F_j - F_i`` entry for a single-scale layer, ``[p_j - p_i ; f_j]``
    per ball query for a multi-scale one."""
    if not isinstance(spec, MSGLayerSpec):
        c_pts, diff = _sa_geometry(spec, points, features, n_valid)
        return c_pts, [diff]
    centers = farthest_point_sample(points, spec.n_centers, n_valid=n_valid)
    c_pts = points[centers]
    rows, ctrs = grouping_operands(spec, points, features)
    c_rows = ctrs[centers][:, None, :]
    return c_pts, [rows[ball_query(c_pts, points, b.radius, b.n_neighbors,
                                   n_valid=n_valid)] - c_rows
                   for b in spec.branches]


def sa_layer(mlp_params, spec: SALayerSpec, points, features):
    """One set-abstraction layer on a single cloud, float backend.
    points (N, 3), features (N, C_in) -> (M, 3), (M, C_out). For any other
    backend, compose ``_sa_geometry`` with a registered backend's
    ``apply_mlp`` (``repro.models.backend``)."""
    c_pts, diff = _sa_geometry(spec, points, features)
    h = _apply_mlp(mlp_params, diff)                    # feature comp. M(.)
    out = jnp.max(h, axis=1)                            # reduction
    return c_pts, out


def forward(params: Params, config: PointNetConfig, cloud: jnp.ndarray, *,
            schedule=None, policy=None) -> jnp.ndarray:
    """Single-cloud float forward: (N, 3) -> logits (n_classes,). Thin
    delegate to :func:`repro.models.backend.compile_model` — the canonical
    entry point, and the place to pick any other backend. ``schedule=`` /
    ``policy=`` pass straight through (a preset / plan runs the gathers
    plan-ordered; a :class:`~repro.core.policy.PlanPolicy` picks the order
    per workload by predicted DMA elisions)."""
    from repro.models.backend import compile_model
    return compile_model(params, config, schedule=schedule,
                         policy=policy).forward(cloud)


def batched_forward(params, config, clouds, *, schedule=None, policy=None):
    """Batch of clouds (B, N, 3) -> logits (B, n_classes), float backend.
    Thin delegate to the compiled-model API; backend dispatch (vmapped
    forward for float / per-layer reram, ONE batch-in-grid ``pallas_call``
    per MLP for the fused backends, ONE batch-gridded
    ``aggregate_diff_batched`` gather per SA layer under a planned
    schedule/policy) lives in ``repro.models.backend.CompiledModel``."""
    from repro.models.backend import compile_model
    return compile_model(params, config, schedule=schedule,
                         policy=policy).batched_forward(clouds)


def loss_fn(params, config, clouds, labels, *, schedule=None, policy=None):
    from repro.models.backend import compile_model
    return compile_model(params, config, schedule=schedule,
                         policy=policy).loss_fn(clouds, labels)


@functools.partial(jax.jit, static_argnames=("config",))
def eval_step(params, config: PointNetConfig, clouds, labels):
    return loss_fn(params, config, clouds, labels)
