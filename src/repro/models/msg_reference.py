"""Plain float32 ``jax.numpy`` reference of PointNet++ multi-scale
grouping (MSG) classification — the model of Qi et al., "PointNet++",
NeurIPS 2017 (arXiv:1706.02413), ``models/pointnet2_cls_msg.py`` of
github.com/charlesq34/pointnet2 — against which the served program is
tested.

One cloud, written from the published description alone: its own
farthest point sampling and ball query, no kernel, plan, padding or
batching, every matmul at ``jax.default_matmul_precision("highest")``.
Per multi-scale layer: FPS centres; per branch the first ``nsample``
points (index order) within ``radius`` of each centre, the empty slots
filled with the first hit; the grouped rows through the branch MLP (ReLU
after every layer); the max over the rows; the branches concatenated.
Group-all: the MLP over every point's absolute ``[p_j ; f_j]``, max over
the points. Head: fully connected layers, ReLU between them.

Departures from the published description:

- Grouped rows are ``[p_j - p_i ; f_j]``; the reference code concatenates
  ``[f_j ; p_j - p_i]``. That permutes the first weight matrix's input
  rows, the same function under permuted weights.
- Batch norm is folded into the weights (inference) and dropout is off.
- The 3 normal channels are stood in by ``p / max(|p|, 1e-6)``
  (:func:`repro.models.pointnet2.point_normals`): the benchmark's
  synthetic clouds are centred and carry no normals.
- Squared distances accumulate x, y and z in that order and
  ``radius**2`` is formed in float32, as the reference code's CUDA does;
  the program does the same, so both make the same ball decisions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.workload import PointNetConfig

__all__ = ["msg_forward"]


def _fps(points, m: int):
    """Farthest point sampling from point 0, first maximum on ties."""
    def body(i, state):
        idx, dist = state
        cur = idx[i - 1]
        d = jnp.sum((points - points[cur]) ** 2, axis=1)
        dist = jnp.minimum(dist, d)
        return idx.at[i].set(jnp.argmax(dist).astype(jnp.int32)), dist

    idx = jnp.zeros(m, jnp.int32)
    dist = jnp.full(points.shape[0], jnp.inf, points.dtype)
    return jax.lax.fori_loop(1, m, body, (idx, dist))[0]


def _ball(queries, points, radius: float, k: int):
    """First ``k`` points within ``radius``, in index order, padded with
    the first hit."""
    d = ((queries[:, None, 0] - points[None, :, 0]) ** 2
         + (queries[:, None, 1] - points[None, :, 1]) ** 2
         + (queries[:, None, 2] - points[None, :, 2]) ** 2)
    r = jnp.float32(radius)
    hit = d < r * r
    first = jnp.argsort(jnp.where(hit, 0, 1), axis=1, stable=True)[:, :k]
    count = jnp.sum(hit, axis=1, keepdims=True)
    return jnp.where(jnp.arange(k)[None, :] < count, first, first[:, :1])


def _mlp(layers, x, final_relu: bool = True):
    for i, lyr in enumerate(layers):
        x = jnp.dot(x, lyr["w"]) + lyr["b"]
        if final_relu or i < len(layers) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def msg_forward(params, config: PointNetConfig, cloud) -> jnp.ndarray:
    """Logits (n_classes,) of one (N, 3) cloud. ``params`` in the layout
    of :func:`repro.models.pointnet2.init_params`: ``sa[i][b]`` branch b
    of MSG layer i, ``sa_all``, ``head``; every layer of ``config`` must
    be multi-scale and it must end in a group-all layer."""
    with jax.default_matmul_precision("highest"):
        pts = jnp.asarray(cloud, jnp.float32)
        norm = jnp.sqrt(jnp.sum(pts * pts, axis=-1, keepdims=True))
        feats = pts / jnp.maximum(norm, 1e-6)
        for spec, branches in zip(config.layers, params["sa"]):
            c_pts = pts[_fps(pts, spec.n_centers)]
            outs = []
            for br, layers in zip(spec.branches, branches):
                idx = _ball(c_pts, pts, br.radius, br.n_neighbors)
                rows = jnp.concatenate(
                    [pts[idx] - c_pts[:, None, :], feats[idx]], axis=-1)
                outs.append(jnp.max(_mlp(layers, rows), axis=1))
            pts, feats = c_pts, jnp.concatenate(outs, axis=-1)
        g = jnp.max(_mlp(params["sa_all"],
                         jnp.concatenate([pts, feats], axis=-1)), axis=0)
        return _mlp(params["head"], g, final_relu=False)
