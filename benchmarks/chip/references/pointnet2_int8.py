"""Plain NumPy reference of the served PointNet++ classifier with INT8
crossbar MLPs, written apart from the program under test.

One cloud at a time, in index order, with no plan, kernel, batch or
cache: farthest point sampling from point 0, k nearest neighbours by
squared distance (ties to the lower index), neighbour-minus-centre
features through each set-abstraction MLP, max over neighbours, max over
points, then the head. Every MLP layer is the quantized chain the
crossbar arithmetic defines: symmetric per-tensor ``bits``-bit
quantization of the layer's input and of its weights (scale
``max|v| / (2^(bits-1) - 1)``, round half to even), an exact integer
matmul, dequantization by the product of the two float32 scales, bias,
ReLU (not after the head's last layer). Float32 arithmetic throughout,
each operation rounded once; the integer matmuls run as float32 BLAS,
exact while every partial sum stays below 2^24.

``bits=8`` is the configuration as stated; ``bits=4`` is the control, the
nearest precision below it, which the comparison must reject.
"""
from __future__ import annotations

import numpy as np

EXACT_F32 = 2 ** 24


def lift_features(points: np.ndarray, n_features: int) -> np.ndarray:
    """The input's features: xyz, a constant 1, then sin/cos liftings of
    xyz at frequencies 3 and 7, cut to ``n_features`` columns."""
    p = points.astype(np.float32)
    f = np.concatenate([p, np.ones((len(p), 1), np.float32),
                        np.sin(3 * p), np.cos(3 * p),
                        np.sin(7 * p), np.cos(7 * p)], axis=1)
    return f[:, :n_features].astype(np.float32)


def farthest_points(points: np.ndarray, m: int) -> np.ndarray:
    idx = np.zeros(m, np.int64)
    dist = np.full(len(points), np.inf, np.float32)
    cur = 0
    for i in range(m):
        idx[i] = cur
        d = np.sum((points - points[cur]) ** 2, axis=1, dtype=np.float32)
        dist = np.minimum(dist, d)
        cur = int(np.argmax(dist))
    return idx


def nearest(queries: np.ndarray, points: np.ndarray, k: int) -> np.ndarray:
    d = np.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1,
               dtype=np.float32)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def quantize(v: np.ndarray, bits: int):
    qmax = np.float32(2 ** (bits - 1) - 1)
    s = np.maximum(np.max(np.abs(v)) / qmax, np.float32(1e-12))
    return np.clip(np.round(v / s), -qmax, qmax), np.float32(s)


def mlp(layers, x: np.ndarray, bits: int, final_relu: bool = True):
    y = x.astype(np.float32)
    qmax = 2 ** (bits - 1) - 1
    for i, (w, b) in enumerate(layers):
        if w.shape[0] * qmax * qmax >= EXACT_F32:
            raise ValueError("integer matmul would leave float32's exact "
                             "range")
        xi, sx = quantize(y, bits)
        wi, sw = quantize(w, bits)
        acc = xi.astype(np.float32) @ wi.astype(np.float32)
        y = acc * (sx * sw) + b
        if final_relu or i < len(layers) - 1:
            y = np.maximum(y, np.float32(0))
    return y


def logits(cfg: dict, weights: dict, cloud: np.ndarray,
           bits: int = 8) -> np.ndarray:
    """``weights``: {"sa": [[(w, b), ...] per layer], "head": [(w, b), ...]}
    as float32 NumPy arrays; ``cloud`` (N, 3) float32."""
    pts = np.asarray(cloud, np.float32)
    feats = lift_features(pts, cfg["layers"][0]["mlp"][0])
    for spec, layers in zip(cfg["layers"], weights["sa"]):
        m, k = spec["n_centers"], spec["n_neighbors"]
        ctr = farthest_points(pts, m)
        nbr = nearest(pts[ctr], pts, k)
        diff = feats[nbr] - feats[ctr][:, None, :]
        h = mlp(layers, diff.reshape(m * k, -1), bits)
        pts, feats = pts[ctr], h.reshape(m, k, -1).max(axis=1)
    return mlp(weights["head"], feats.max(axis=0)[None], bits,
               final_relu=False)[0]


def logit_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between a served logit and the reference's, relative to
    the largest reference logit of that cloud, over every cloud given."""
    served, ref = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    if served.shape != ref.shape or not np.all(np.isfinite(served)):
        return float("inf")
    gap = np.abs(served - ref).max(axis=1) / np.abs(ref).max(axis=1)
    return float(gap.max())
