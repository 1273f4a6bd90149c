"""Plain NumPy reference of the served PointNet++ multi-scale grouping
(MSG) classifier with INT8 crossbar MLPs, written apart from the program
under test (it imports nothing of ``repro``).

One cloud at a time, in index order, with no plan, kernel, batch or
cache. Layer-0 features are the stand-in normals ``p / max(|p|, 1e-6)``.
Each MSG layer: farthest point sampling from point 0; per branch a ball
query (squared distances accumulated x, y, z in that order against
``radius**2`` formed in float32; the first ``nsample`` hits in index
order, the empty slots filled with the first hit); the grouped rows
``[p_j - p_i ; f_j]`` through the branch MLP; the max over the branch's
rows; the branches concatenated. Group-all: the MLP over every point's
``[p_j ; f_j]``, max over the points. Then the head. Every MLP layer is
the quantized chain of ``pointnet2_int8.mlp`` (symmetric per-tensor
``bits``-bit quantization of the layer's input — one scale per branch
of a cloud — and of its weights, an exact integer matmul, dequantization,
bias, ReLU except after the head's last layer).

``bits=8`` is the configuration as stated; ``bits=4`` is the control, the
nearest precision below it, which the comparison must reject.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the single-scale reference: its FPS, INT8 chain and logit gap
_int8 = _sibling("pointnet2_int8")
farthest_points, mlp, logit_gap = (_int8.farthest_points, _int8.mlp,
                                   _int8.logit_gap)


def normals(points: np.ndarray) -> np.ndarray:
    p = points.astype(np.float32)
    norm = np.sqrt(np.sum(p * p, axis=-1, keepdims=True))
    return p / np.maximum(norm, np.float32(1e-6))


def ball(queries: np.ndarray, points: np.ndarray, radius: float,
         k: int) -> np.ndarray:
    d = ((queries[:, None, 0] - points[None, :, 0]) ** 2
         + (queries[:, None, 1] - points[None, :, 1]) ** 2
         + (queries[:, None, 2] - points[None, :, 2]) ** 2)
    hit = d < np.float32(radius) * np.float32(radius)
    first = np.argsort(~hit, axis=1, kind="stable")[:, :k]
    count = hit.sum(axis=1, keepdims=True)
    return np.where(np.arange(k)[None, :] < count, first, first[:, :1])


def logits(cfg: dict, weights: dict, cloud: np.ndarray,
           bits: int = 8) -> np.ndarray:
    """``weights``: {"sa": [[[(w, b), ...] per branch] per layer],
    "sa_all": [(w, b), ...], "head": [(w, b), ...]} as float32 NumPy
    arrays; ``cloud`` (N, 3) float32."""
    pts = np.asarray(cloud, np.float32)
    feats = normals(pts)
    for spec, branches in zip(cfg["layers"], weights["sa"]):
        m = spec["n_centers"]
        ctr = farthest_points(pts, m)
        c_pts = pts[ctr]
        outs = []
        for br, layers in zip(spec["branches"], branches):
            k = br["n_neighbors"]
            idx = ball(c_pts, pts, br["radius"], k)
            rows = np.concatenate([pts[idx] - c_pts[:, None, :],
                                   feats[idx]], axis=-1)
            h = mlp(layers, rows.reshape(m * k, -1), bits)
            outs.append(h.reshape(m, k, -1).max(axis=1))
        pts, feats = c_pts, np.concatenate(outs, axis=-1)
    g = mlp(weights["sa_all"], np.concatenate([pts, feats], axis=-1),
            bits).max(axis=0)
    return mlp(weights["head"], g[None], bits, final_relu=False)[0]
