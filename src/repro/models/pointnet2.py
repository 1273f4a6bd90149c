"""PointNet++ in pure JAX — the workload Pointer accelerates.

Implements the paper's two-stage set-abstraction (SA) pipeline exactly as
described in Fig. 1:

  point mapping   : farthest point sampling (FPS) + k-NN neighbor search
  feature proc.   : aggregation  D(F_i, F_j) = F_j - F_i   (per neighbor)
                    feature computation  M(D(...))          (3-stage MLP)
                    reduction            column-wise max over neighbors

plus a classification head for the end-to-end training example. The
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

from repro.core.workload import PointNetConfig, SALayerSpec
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
    keys = jax.random.split(key, config.n_layers + 1)
    sa = [_init_mlp(k, spec.mlp, dtype)
          for k, spec in zip(keys[:-1], config.layers)]
    d_last = config.layers[-1].out_features
    head = _init_mlp(keys[-1], (d_last, 256, n_classes), dtype)
    return {"sa": sa, "head": head}


def build_model_program(params: Params, *, ecc=None) -> dict:
    """Program every MLP of the model into crossbars ('reram-fused'
    backend): one :class:`~repro.kernels.CrossbarProgram` per SA layer plus
    one for the classification head. Weights are quantized and
    plane-encoded here, exactly once — pass the result to
    ``forward``/``batched_forward`` and the per-forward hot path never
    touches ``encode_planes``/``quantize_tensor`` on weights again.

    ``ecc`` (an :class:`repro.reliability.EccConfig`, or True for the
    default) Hamming-protects every program's spare columns at build time
    (DESIGN.md §13); MVM results are unchanged."""
    return {"sa": [build_program(mlp, ecc=ecc) for mlp in params["sa"]],
            "head": build_program(params["head"], ecc=ecc)}


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


def geometry_pass(config: PointNetConfig, cloud: jnp.ndarray, *,
                  n_valid=None):
    """The full FPS/kNN geometry of every SA layer on one cloud, as
    device tensors that never leave the trace: per layer k = 1..L the
    FPS-selected coordinates ``pts[k]`` (n_k, 3), center indices
    ``ctr[k]`` (n_k,) into layer k-1, and receptive fields ``nbr[k]``
    (n_k, K) into layer k-1 (index 0 holds the input cloud / None / None,
    matching :class:`~repro.core.workload.PointNetWorkload` layout).

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
    ``geometry/knn``, so a profile tells the two apart wherever the pass
    is traced."""
    pts_list, ctr_list, nbr_list = [cloud], [None], [None]
    pts = cloud
    with jax.named_scope("geometry"):
        for li, spec in enumerate(config.layers):
            nv = n_valid if li == 0 else None
            with jax.named_scope("fps"):
                centers = farthest_point_sample(pts, spec.n_centers,
                                                n_valid=nv)
                c_pts = pts[centers]
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
