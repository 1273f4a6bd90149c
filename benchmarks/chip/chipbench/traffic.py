"""The one traffic generator: arrivals and point clouds from a mix's
parameters and the run's ``--seed``.

A mix is a JSON file under ``traffic/``:

- ``loop``: ``"open"`` (requests are due on a schedule, whatever the
  server does) or ``"closed"`` (``outstanding_batches`` x the largest
  batch bucket stay queued; each served request is replaced at once);
- ``arrivals`` (open loop): ``{"kind": "poisson", "rate_per_s": r}``.

Each request's cloud is one of a seeded pool of ``POOL`` synthetic
ModelNet40-like objects under a random rotation of its own, so no two
requests share content and every request misses the plan cache.

Every seed gets the same amount of work: an open loop always sends
``round(rate * seconds)`` requests whose gaps are the same exponential
quantiles in a seeded order, spread over exactly the window.
"""
from __future__ import annotations

import numpy as np

N_CLASSES = 40
_PRIMITIVES = 8     # x 5 deformation levels = 40 classes
#: SeedSequence stream tags: disjoint streams for traffic and warm-up
TRAFFIC, WARMUP, SAMPLE = 1, 2, 3
#: base objects per seed
POOL = 64


# ---------------------------------------------------------------------------
# synthetic objects: a copy of the repo's ModelNet40-like generator
# ---------------------------------------------------------------------------

def _unit_sphere(rng, n):
    p = rng.normal(size=(n, 3))
    return p / np.maximum(np.linalg.norm(p, axis=1, keepdims=True), 1e-9)


def _primitive(rng, prim: int, n: int) -> np.ndarray:
    u = rng.uniform(0, 2 * np.pi, n)
    v = rng.uniform(-1, 1, n)
    if prim == 0:      # sphere
        return _unit_sphere(rng, n)
    if prim == 1:      # ellipsoid
        return _unit_sphere(rng, n) * np.array([1.0, 0.6, 0.35])
    if prim == 2:      # cylinder (side + caps)
        side = np.stack([np.cos(u), np.sin(u), v], axis=1)
        ncap = n // 5
        r = np.sqrt(rng.uniform(0, 1, ncap))
        a = rng.uniform(0, 2 * np.pi, ncap)
        caps = np.stack([r * np.cos(a), r * np.sin(a),
                         np.sign(rng.uniform(-1, 1, ncap))], axis=1)
        side[:ncap] = caps
        return side
    if prim == 3:      # cone
        h = rng.uniform(0, 1, n)
        return np.stack([(1 - h) * np.cos(u), (1 - h) * np.sin(u),
                         2 * h - 1], axis=1)
    if prim == 4:      # torus
        w = rng.uniform(0, 2 * np.pi, n)
        return np.stack([(1 + 0.35 * np.cos(w)) * np.cos(u),
                         (1 + 0.35 * np.cos(w)) * np.sin(u),
                         0.35 * np.sin(w)], axis=1) / 1.35
    if prim == 5:      # box surface
        face = rng.integers(0, 6, n)
        a = rng.uniform(-1, 1, n)
        b = rng.uniform(-1, 1, n)
        s = np.where(face % 2 == 0, 1.0, -1.0)
        out = np.empty((n, 3))
        ax = face // 2
        for d in range(3):
            m = ax == d
            cols = [c for c in range(3) if c != d]
            out[m, d] = s[m]
            out[m, cols[0]] = a[m]
            out[m, cols[1]] = b[m]
        return out
    if prim == 6:      # helix tube
        t = rng.uniform(-2, 2, n)
        jitter = 0.15 * _unit_sphere(rng, n)
        return (np.stack([np.cos(3 * t), np.sin(3 * t), t / 2], axis=1)
                + jitter) / 1.4
    # 7: two-sphere dumbbell
    p = _unit_sphere(rng, n) * 0.55
    p[:, 0] += np.sign(rng.uniform(-1, 1, n)) * 0.55
    return p


def synthetic_cloud(label: int, n_points: int, seed: int) -> np.ndarray:
    """One (n_points, 3) float32 cloud of class ``label`` in [0, 40),
    unit-scale and centred."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, label]))
    prim, deform = label % _PRIMITIVES, label // _PRIMITIVES
    p = _primitive(rng, prim, n_points)
    amp = 0.05 + 0.06 * deform
    p = p + amp * np.sin((2 + deform) * p[:, [1, 2, 0]])
    theta = 0.15 * deform * p[:, 2]
    rot = np.stack([np.cos(theta), -np.sin(theta)], axis=1)
    x = p[:, 0] * rot[:, 0] + p[:, 1] * rot[:, 1]
    y = p[:, 0] * -rot[:, 1] + p[:, 1] * rot[:, 0]
    p = np.stack([x, y, p[:, 2]], axis=1)
    p -= p.mean(axis=0, keepdims=True)
    p /= np.max(np.linalg.norm(p, axis=1))
    return p.astype(np.float32)


def _rotations(rng, n: int) -> np.ndarray:
    """``n`` uniformly random 3x3 rotation matrices."""
    q = rng.normal(size=(n, 4))
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    w, x, y, z = q.T
    return np.stack([
        1 - 2 * (y * y + z * z), 2 * (x * y - z * w), 2 * (x * z + y * w),
        2 * (x * y + z * w), 1 - 2 * (x * x + z * z), 2 * (y * z - x * w),
        2 * (x * z - y * w), 2 * (y * z + x * w), 1 - 2 * (x * x + y * y),
    ], axis=1).reshape(n, 3, 3)


class Clouds:
    """Request ``i``'s cloud, for any ``i``, from the seed: made in chunks
    so a closed loop of unknown length pays for its clouds in bulk."""

    CHUNK = 256

    def __init__(self, n_points: int, seed: int, stream: int = TRAFFIC):
        self.n_points = int(n_points)
        self.seed, self.stream = int(seed), int(stream)
        self.base = np.stack([
            synthetic_cloud(i % N_CLASSES, self.n_points,
                            seed=self.seed * 7919 + i) for i in range(POOL)])
        self._chunks: dict[int, np.ndarray] = {}

    def _chunk(self, c: int) -> np.ndarray:
        if c not in self._chunks:
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, self.stream, c]))
            n, first = self.CHUNK, c * self.CHUNK
            src = first + np.arange(n)
            rot = _rotations(rng, n).astype(np.float32)
            self._chunks[c] = np.matmul(self.base[src % POOL],
                                        rot.transpose(0, 2, 1))
        return self._chunks[c]

    def __getitem__(self, i: int) -> np.ndarray:
        return self._chunk(i // self.CHUNK)[i % self.CHUNK]

    def prefetch(self, n: int) -> None:
        """Make the clouds of requests ``0 .. n-1`` now (set-up)."""
        for c in range(-(-n // self.CHUNK)):
            self._chunk(c)


def arrivals(spec: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start, ascending) of an open
    loop's requests."""
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    rate = float(spec["rate_per_s"])
    n = max(1, round(rate * seconds))
    # the same exponential quantiles for every seed, in a seeded order
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng(np.random.SeedSequence([seed, TRAFFIC, 0]))
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())
