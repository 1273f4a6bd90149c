"""Driver for PointNet++ configurations served through the repo's serving
tier: ``compile_model`` -> ``PointCloudServable`` -> ``ServingEngine``.

The benchmark makes the weights itself, on the device, in one jitted call
from the seed (He-normal weights, zero biases, float32), and hands the
same float weights to the program and, as a host copy, to the reference.
Set-up warms exactly the shapes the cell's loop will use: every batch
size up to the largest bucket for an open loop (each size has its own
small slicing programs, each bucket its own step and plan stack), only
the largest for a closed loop, and the plan builder at one cloud.
"""
from __future__ import annotations

import gc
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: extra full batches served after the compiling ones, so the window
#: starts from a steady device and host
SETTLE_BATCHES = 2


def load_reference(name: str):
    path = os.path.join(HERE, "..", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mlp_shapes(cfg: dict) -> dict:
    pairs = lambda w: list(zip(w[:-1], w[1:]))
    return {"sa": [pairs(l["mlp"]) for l in cfg["layers"]],
            "head": pairs(cfg["head"])}


def make_weights(cfg: dict, seed: int) -> dict:
    """{"sa": [[{"w", "b"}, ...] per layer], "head": [...]}, float32, on
    the device, from ``seed`` (any non-negative integer)."""
    shapes = mlp_shapes(cfg)
    flat = [s for layer in shapes["sa"] for s in layer] + shapes["head"]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(flat))
        return [{"w": jax.random.normal(k, (n, m), jnp.float32)
                 * jnp.sqrt(2.0 / n), "b": jnp.zeros((m,), jnp.float32)}
                for k, (n, m) in zip(keys, flat)]

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                             seed // 2 ** 32)
    layers, out = init(key), {"sa": []}
    for shape in shapes["sa"]:
        out["sa"].append(layers[:len(shape)])
        layers = layers[len(shape):]
    out["head"] = layers
    return out


def host_weights(weights: dict) -> dict:
    """The reference's copy: (w, b) float32 NumPy pairs."""
    pair = lambda l: (np.asarray(l["w"], np.float32),
                      np.asarray(l["b"], np.float32))
    return {"sa": [[pair(l) for l in layer] for layer in weights["sa"]],
            "head": [pair(l) for l in weights["head"]]}


def pointnet_config(cfg: dict):
    from repro.core.workload import PointNetConfig, SALayerSpec
    layers = tuple(SALayerSpec(n_centers=l["n_centers"],
                               n_neighbors=l["n_neighbors"],
                               in_features=l["mlp"][0], mlp=tuple(l["mlp"]))
                   for l in cfg["layers"])
    return PointNetConfig(name=cfg["name"], n_points=cfg["n_points"],
                          layers=layers)


class System:
    """One compiled model behind one engine, warmed for one loop kind."""

    def __init__(self, cfg: dict, loop: str, seed: int, warm_clouds, log):
        from repro import compile_model
        from repro.launch.serve import (PointCloudServable, ServingEngine,
                                        ShapeBuckets)
        self.cfg = cfg
        self.weights = make_weights(cfg, seed)
        self.model = compile_model(self.weights, pointnet_config(cfg),
                                   backend=cfg["backend"],
                                   schedule=cfg["schedule"])
        buckets = ShapeBuckets(points=(cfg["n_points"],),
                               batch=tuple(cfg["batch_buckets"]))
        self.servable = PointCloudServable(self.model, buckets=buckets)
        self.engine = ServingEngine(self.servable)
        self.max_batch = buckets.max_batch
        self._warm(loop, warm_clouds)
        plans = self.model.stats().get("fused_plan", {})
        log(f"fused dataflows: "
            + " ".join(f"{k}={v['mode']}" for k, v in plans.items()))

    def _warm(self, loop: str, clouds) -> None:
        top = self.max_batch
        fresh = iter(range(10 ** 9))
        batch = lambda n: [clouds[next(fresh)] for _ in range(n)]
        first = batch(top)
        self._serve(first)                     # builder, largest bucket
        if loop == "open":                     # every batch size, hits
            for n in range(1, top):
                self._serve(first[:n])
        for _ in range(SETTLE_BATCHES):
            self._serve(batch(top))

    def _serve(self, clouds) -> None:
        handles = [self.engine.submit(c) for c in clouds]
        while self.engine.queue:
            self.block(self.engine.step())
        self.block(handles)

    # -- what the loop drives ------------------------------------------------

    def submit(self, cloud):
        return self.engine.submit(cloud)

    def step(self) -> list:
        return self.engine.step()

    def block(self, handles) -> None:
        jax.block_until_ready([h.result for h in handles])

    def result(self, handle) -> np.ndarray:
        return np.asarray(handle.result, np.float32)

    def counters(self) -> dict:
        s = self.servable.stats()
        cache = s.get("plan_cache", {})
        return {"requests": s["requests"], "batches": s["batches"],
                "jit_traces": s["jit_traces"],
                "plan_hits": cache.get("hits", 0),
                "plan_misses": cache.get("misses", 0)}

    def release(self) -> dict:
        """Free the program's device state; returns the reference's host
        copy of the weights."""
        host = host_weights(self.weights)
        del self.engine, self.servable, self.model, self.weights
        gc.collect()
        return host


def build(cfg: dict, loop: str, seed: int, warm_clouds, log) -> System:
    return System(cfg, loop, seed, warm_clouds, log)


def check(cfg: dict, weights: dict, clouds, served, bits: int = 8) -> dict:
    """{name: value} of the comparison with the plain reference over the
    sampled clouds and their served logits."""
    ref_mod = load_reference(cfg["reference"])
    ref = np.stack([ref_mod.logits(cfg, weights, c, bits=bits)
                    for c in clouds])
    return {"logit_gap": ref_mod.logit_gap(np.stack(served), ref)}
