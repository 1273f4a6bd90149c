"""PointNet++ workload description for the Pointer accelerator model.

This module is deliberately NumPy-only: it is the host-side view of the
workload that the paper's "order generator" hardware unit would see (point
coordinates, FPS-selected centers, neighbor lists). The JAX model in
``repro.models.pointnet2`` implements the same geometry on-device; tests
cross-check the two implementations.

Terminology follows the paper:
  - layer 0 is the input point cloud (1024 points in the paper's models),
  - layer k (k >= 1) is the output of the k-th set-abstraction (SA) layer,
  - ``centers[k][i]`` is the index *into layer k-1's point set* of the i-th
    output point of layer k (FPS selects a subset),
  - ``neighbors[k][i]`` are the K nearest layer-(k-1) points of that center
    (the receptive field of one SA step) — for a multi-scale (MSG) layer,
    each branch's ball query concatenated, ``sum(K_b)`` indices,
  - features of layer k-1 are fetched per neighbor during aggregation; this
    fetch is the DRAM-traffic bottleneck the paper attacks.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

__all__ = [
    "SALayerSpec",
    "BallBranch",
    "MSGLayerSpec",
    "GroupAllSpec",
    "PointNetConfig",
    "PointNetWorkload",
    "farthest_point_sample_np",
    "map_mlps",
    "mlp_label",
    "select_mlp",
    "knn_np",
    "ball_query_np",
    "PAPER_MODELS",
    "POINTNET2_MSG",
]


@dataclass(frozen=True)
class SALayerSpec:
    """One set-abstraction layer (paper Table 1)."""

    n_centers: int                 # "The Number of Central Point"
    n_neighbors: int               # "The Number of Neighbors" (K)
    in_features: int               # input feature vector length
    mlp: tuple[int, ...]           # widths, e.g. (4, 64, 64, 128) = 3 matmuls
    # ``mlp[0]`` must equal ``in_features``; ``mlp[-1]`` is the output length.

    @property
    def out_features(self) -> int:
        return self.mlp[-1]

    @property
    def mlp_shapes(self) -> tuple[tuple[int, int], ...]:
        return tuple(zip(self.mlp[:-1], self.mlp[1:]))

    @property
    def weights(self) -> int:
        return sum(n * m for n, m in self.mlp_shapes)

    @property
    def macs_per_vector(self) -> int:
        return self.weights


@dataclass(frozen=True)
class BallBranch:
    """One scale of a multi-scale grouping layer: a ball query of
    ``radius`` keeping ``n_neighbors`` indices, then its own MLP over the
    grouped rows ``[p_j - p_i ; f_j]`` (so ``mlp[0]`` is 3 + the layer's
    ``in_features``)."""

    radius: float
    n_neighbors: int
    mlp: tuple[int, ...]


@dataclass(frozen=True)
class MSGLayerSpec:
    """A PointNet++ multi-scale grouping (MSG) set-abstraction layer
    (Qi et al. 2017, §3.3): FPS centres, then per branch a ball query,
    the grouped rows ``[p_j - p_i ; f_j]``, the branch MLP and a max over
    its ``n_neighbors`` rows; the branches' outputs are concatenated.

    ``in_features`` is the width of the previous layer's features
    ``f_j`` (3 normal channels at layer 0). ``n_neighbors`` is the width
    of the concatenated receptive field, ``sum(K_b)``: the plan orders a
    centre's branches as one row of indices."""

    n_centers: int
    in_features: int
    branches: tuple[BallBranch, ...]

    def __post_init__(self):
        for b in self.branches:
            if b.mlp[0] != 3 + self.in_features:
                raise ValueError(
                    f"branch MLP {b.mlp} must take 3 + in_features = "
                    f"{3 + self.in_features} inputs ([p_j - p_i ; f_j])")

    @property
    def n_neighbors(self) -> int:
        return sum(b.n_neighbors for b in self.branches)

    @property
    def out_features(self) -> int:
        return sum(b.mlp[-1] for b in self.branches)


@dataclass(frozen=True)
class GroupAllSpec:
    """PointNet++'s last set abstraction, ``group_all``: one MLP over
    every remaining point's absolute row ``[p_j ; f_j]``, then a max over
    the points. It has a single centre, so it lies outside the plan."""

    in_features: int
    mlp: tuple[int, ...]

    def __post_init__(self):
        if self.mlp[0] != 3 + self.in_features:
            raise ValueError(f"group-all MLP {self.mlp} must take 3 + "
                             f"in_features = {3 + self.in_features} inputs")

    @property
    def out_features(self) -> int:
        return self.mlp[-1]


@dataclass(frozen=True)
class PointNetConfig:
    """``layers`` are the planned set-abstraction layers (each with its
    own centres); ``group_all`` the optional final group-all layer;
    ``head`` the classifier's hidden widths, between the global feature
    and the classes."""

    name: str
    n_points: int
    layers: tuple[SALayerSpec | MSGLayerSpec, ...]
    group_all: GroupAllSpec | None = None
    head: tuple[int, ...] = (256,)

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def out_features(self) -> int:
        """Width of the global feature the head takes."""
        last = self.group_all if self.group_all is not None \
            else self.layers[-1]
        return last.out_features

    def layer_mlp_keys(self, i: int) -> list:
        """The MLP keys of SA layer ``i``: ``("sa", i)``, or ``("sa", i, b)``
        per branch b of a multi-scale layer."""
        spec = self.layers[i]
        if isinstance(spec, MSGLayerSpec):
            return [("sa", i, b) for b in range(len(spec.branches))]
        return [("sa", i)]

    def mlp_keys(self) -> list:
        """Every MLP key of the model in execution order: the SA layers'
        (:meth:`layer_mlp_keys`), ``"sa_all"`` where there is a group-all
        layer, ``"head"``."""
        keys = [k for i in range(self.n_layers) for k in self.layer_mlp_keys(i)]
        if self.group_all is not None:
            keys.append("sa_all")
        return keys + ["head"]


def mlp_label(key) -> str:
    """``sa0``, ``sa1.b2``, ``sa_all``, ``head``: an MLP key as a name."""
    if isinstance(key, str):
        return key
    return f"sa{key[1]}" + (f".b{key[2]}" if len(key) > 2 else "")


# A model's MLPs sit in a ``{"sa": [...], "sa_all", "head"}`` tree of
# parameters (an MLP is a list of ``{"w", "b"}`` layers) or of programs
# (an MLP is one ``CrossbarProgram``). An ``sa`` entry holds one MLP, or a
# list of one MLP per branch for a multi-scale layer; the two functions
# below are the only ones that tell those apart.

def _branched(entry) -> bool:
    return isinstance(entry, list) and not isinstance(entry[0], dict)


def select_mlp(tree: dict, key):
    """The MLP of ``tree`` (parameters or programs) under ``key``
    (:meth:`PointNetConfig.mlp_keys`)."""
    if isinstance(key, str):
        return tree[key]
    entry = tree["sa"][key[1]]
    return entry[key[2]] if len(key) > 2 else entry


def map_mlps(fn, tree: dict) -> dict:
    """``tree`` in the same layout with ``fn(ix, mlp)`` applied to every
    MLP. ``ix`` is the MLP's seed index: 0 for the head, then 1, 2, ... in
    :meth:`PointNetConfig.mlp_keys` order, so SA layer i of a single-scale
    model is i + 1."""
    ix = itertools.count(1)
    one = lambda mlp: fn(next(ix), mlp)
    out = {"sa": [[one(m) for m in e] if _branched(e) else one(e)
                  for e in tree["sa"]]}
    if "sa_all" in tree:
        out["sa_all"] = one(tree["sa_all"])
    out["head"] = fn(0, tree["head"])
    return out


def _paper_model(name: str, f0: int, w1: int, w2: int) -> PointNetConfig:
    """Paper Table 1 models. f0 in {4,8,16}; w1/w2 are layer-1/2 base widths.

    Note: Table 1 lists Model 0's layer-2 "Input Feature Vector Length" as
    129, which is inconsistent with its own MLP shape (128*128). We follow
    the MLP shape (the authoritative one for both compute and fetch traffic).
    """
    return PointNetConfig(
        name=name,
        n_points=1024,
        layers=(
            SALayerSpec(
                n_centers=512, n_neighbors=16, in_features=f0,
                mlp=(f0, w1, w1, 2 * w1),
            ),
            SALayerSpec(
                n_centers=128, n_neighbors=16, in_features=2 * w1,
                mlp=(2 * w1, w2, w2, 2 * w2),
            ),
        ),
    )


#: The three PointNet++ configurations evaluated in the paper (Table 1).
PAPER_MODELS: dict[str, PointNetConfig] = {
    "model0": _paper_model("model0", f0=4, w1=64, w2=128),
    "model1": _paper_model("model1", f0=8, w1=128, w2=256),
    "model2": _paper_model("model2", f0=16, w1=256, w2=512),
}


#: PointNet++ MSG classification (Qi et al., NeurIPS 2017, arXiv:1706.02413;
#: ``models/pointnet2_cls_msg.py`` of github.com/charlesq34/pointnet2): 1,024
#: points with 3 normal channels, three scales per SA layer, group-all, and a
#: (1024, 512, 256, 40) head.
POINTNET2_MSG = PointNetConfig(
    name="pointnet2-msg",
    n_points=1024,
    layers=(
        MSGLayerSpec(n_centers=512, in_features=3, branches=(
            BallBranch(0.1, 16, (6, 32, 32, 64)),
            BallBranch(0.2, 32, (6, 64, 64, 128)),
            BallBranch(0.4, 128, (6, 64, 96, 128)),
        )),
        MSGLayerSpec(n_centers=128, in_features=320, branches=(
            BallBranch(0.2, 32, (323, 64, 64, 128)),
            BallBranch(0.4, 64, (323, 128, 128, 256)),
            BallBranch(0.8, 128, (323, 128, 128, 256)),
        )),
    ),
    group_all=GroupAllSpec(in_features=640, mlp=(643, 256, 512, 1024)),
    head=(512, 256),
)


def farthest_point_sample_np(points: np.ndarray, n_samples: int,
                             start: int = 0) -> np.ndarray:
    """Classic FPS. ``points``: (N, 3). Returns indices (n_samples,).

    Deterministic given ``start``. O(N * n_samples).
    """
    n = points.shape[0]
    if n_samples > n:
        raise ValueError(f"n_samples {n_samples} > n points {n}")
    idx = np.empty(n_samples, dtype=np.int64)
    dist = np.full(n, np.inf)
    cur = int(start)
    for i in range(n_samples):
        idx[i] = cur
        d = np.sum((points - points[cur]) ** 2, axis=1)
        dist = np.minimum(dist, d)
        cur = int(np.argmax(dist))
    return idx


def knn_np(queries: np.ndarray, points: np.ndarray, k: int) -> np.ndarray:
    """Indices (Q, k) of the k nearest ``points`` for each query (includes
    the query itself when it is a member of ``points``)."""
    d = np.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1)
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def ball_query_np(queries: np.ndarray, points: np.ndarray, radius: float,
                  k: int) -> np.ndarray:
    """Indices (Q, k): per query the first ``k`` points, in index order,
    with squared distance (x, y and z accumulated in that order) below
    ``radius**2``; slots past the last hit repeat the first hit, as the
    reference code's ``query_ball_point`` fills them. Squared distances
    and ``radius**2`` are formed in the points' dtype."""
    dt = points.dtype
    r2 = dt.type(radius) * dt.type(radius)
    d = (queries[:, None, 0] - points[None, :, 0]) ** 2
    for c in range(1, points.shape[1]):
        d = d + (queries[:, None, c] - points[None, :, c]) ** 2
    out = np.empty((queries.shape[0], k), np.int64)
    for q, row in enumerate(d < r2):
        hits = np.flatnonzero(row)[:k]
        if hits.size == 0:
            raise ValueError(f"query {q} has no point within radius "
                             f"{radius}")
        out[q] = hits[0]
        out[q, :hits.size] = hits
    return out


def _receptive_fields(spec, prev: np.ndarray, c: np.ndarray) -> np.ndarray:
    """A layer's receptive fields on the host: kNN for SSG, each branch's
    ball query concatenated for MSG."""
    if isinstance(spec, MSGLayerSpec):
        return np.concatenate([ball_query_np(prev[c], prev, b.radius,
                                             b.n_neighbors)
                               for b in spec.branches], axis=1)
    return knn_np(prev[c], prev, spec.n_neighbors)


@dataclass
class PointNetWorkload:
    """A concrete (point cloud x config) instance: everything the scheduler
    and the simulator need.

    points[k]   : (n_k, 3) coordinates of layer-k point set (k = 0..L)
    centers[k]  : (n_k,)  index into layer k-1 of each layer-k point (k>=1)
    neighbors[k]: (n_k, K) indices into layer k-1 (the receptive field)
    """

    config: PointNetConfig
    points: list[np.ndarray]
    centers: list[np.ndarray | None]
    neighbors: list[np.ndarray | None]

    @classmethod
    def build(cls, cloud: np.ndarray, config: PointNetConfig) -> "PointNetWorkload":
        """Host geometry of ``cloud`` under ``config``, computed in the
        cloud's own floating dtype (float64 for an integer cloud): a
        float32 cloud makes the device's ball-query decisions."""
        if cloud.shape[0] != config.n_points:
            raise ValueError(
                f"cloud has {cloud.shape[0]} points, config wants {config.n_points}")
        cloud = np.asarray(cloud)
        if not np.issubdtype(cloud.dtype, np.floating):
            cloud = cloud.astype(np.float64)
        points: list[np.ndarray] = [cloud]
        centers: list[np.ndarray | None] = [None]
        neighbors: list[np.ndarray | None] = [None]
        for spec in config.layers:
            prev = points[-1]
            c = farthest_point_sample_np(prev, spec.n_centers)
            nb = _receptive_fields(spec, prev, c)
            points.append(prev[c])
            centers.append(c)
            neighbors.append(nb)
        return cls(config=config, points=points, centers=centers,
                   neighbors=neighbors)

    @classmethod
    def random(cls, config: PointNetConfig, seed: int = 0,
               kind: str = "surface") -> "PointNetWorkload":
        """Random workload. ``kind='surface'`` (default) samples a deformed
        ellipsoid surface — ModelNet40 clouds are sampled from CAD mesh
        *surfaces*, and surface (2-manifold) geometry is what gives
        receptive fields their strong overlap; volume sampling ('ball') is
        kept as a pessimistic stress case."""
        rng = np.random.default_rng(seed)
        cloud = rng.normal(size=(config.n_points, 3))
        cloud /= np.maximum(np.linalg.norm(cloud, axis=1, keepdims=True), 1e-9)
        if kind == "surface":
            cloud *= rng.uniform(np.array([[0.4, 0.3, 0.2]]),
                                 np.array([[1.0, 0.8, 0.6]]))
            cloud += 0.1 * np.sin(5.0 * cloud[:, [1, 2, 0]])
        elif kind == "ball":
            cloud *= rng.uniform(0.2, 1.0, size=(config.n_points, 1))
        else:
            raise ValueError(f"unknown cloud kind {kind!r}")
        return cls.build(cloud, config)

    @property
    def n_layers(self) -> int:
        return self.config.n_layers

    def receptive_field(self, layer: int, i: int) -> np.ndarray:
        """Direct (one-level) receptive field of point i of layer ``layer``:
        the layer-(layer-1) indices it aggregates over."""
        return self.neighbors[layer][i]

    def pyramid_receptive_field(self, layer: int, i: int) -> list[np.ndarray]:
        """Full pyramid receptive field (paper Fig. 4): for each lower layer
        j < layer, the set of layer-j point indices point (layer, i) depends
        on, outermost (layer-1) first."""
        fields: list[np.ndarray] = []
        frontier = np.asarray([i])
        for k in range(layer, 0, -1):
            members = np.unique(np.concatenate(
                [self.neighbors[k][int(p)] for p in frontier]))
            fields.append(members)
            frontier = members
        return fields
