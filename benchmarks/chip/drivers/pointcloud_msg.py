"""Driver for PointNet++ multi-scale grouping (MSG) configurations, served
like the single-scale ones (``drivers/pointcloud.py``, whose serving loop,
warm-up and counters this reuses): ``compile_model`` ->
``PointCloudServable`` -> ``ServingEngine``.

The weights are made on the device, in one jitted call from the seed
(He-normal weights, zero biases, float32): one MLP per branch of each
multi-scale layer, the group-all MLP and the head. The same float weights
go to the program and, as a host copy, to the reference.
"""
from __future__ import annotations

import gc
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str):
    path = os.path.join(HERE, "..", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: the single-scale driver: its System serves any compiled model
_base = _load("drivers", "pointcloud")


def mlp_widths(cfg: dict) -> dict:
    """{"sa": [[branch widths, ...] per layer], "sa_all": widths,
    "head": widths}."""
    return {"sa": [[b["mlp"] for b in l["branches"]] for l in cfg["layers"]],
            "sa_all": cfg["group_all"]["mlp"], "head": cfg["head"]}


def make_weights(cfg: dict, seed: int) -> dict:
    """``mlp_widths``' layout with a list of ``{"w", "b"}`` per MLP,
    float32, on the device, from ``seed`` (any non-negative integer)."""
    widths = mlp_widths(cfg)
    mlps = [w for layer in widths["sa"] for w in layer] + [
        widths["sa_all"], widths["head"]]
    flat = [(n, m) for w in mlps for n, m in zip(w[:-1], w[1:])]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(flat))
        return [{"w": jax.random.normal(k, (n, m), jnp.float32)
                 * jnp.sqrt(2.0 / n), "b": jnp.zeros((m,), jnp.float32)}
                for k, (n, m) in zip(keys, flat)]

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                             seed // 2 ** 32)
    layers = init(key)

    def take(w):
        nonlocal layers
        out, layers = layers[:len(w) - 1], layers[len(w) - 1:]
        return out

    sa = [[take(w) for w in layer] for layer in widths["sa"]]
    return {"sa": sa, "sa_all": take(widths["sa_all"]),
            "head": take(widths["head"])}


def host_weights(weights: dict) -> dict:
    """The reference's copy: (w, b) float32 NumPy pairs."""
    pairs = lambda mlp: [(np.asarray(l["w"], np.float32),
                          np.asarray(l["b"], np.float32)) for l in mlp]
    return {"sa": [[pairs(b) for b in layer] for layer in weights["sa"]],
            "sa_all": pairs(weights["sa_all"]),
            "head": pairs(weights["head"])}


def pointnet_config(cfg: dict):
    from repro.core.workload import (BallBranch, GroupAllSpec, MSGLayerSpec,
                                     PointNetConfig)
    layers, c_in = [], cfg["in_features"]
    for l in cfg["layers"]:
        spec = MSGLayerSpec(
            n_centers=l["n_centers"], in_features=c_in,
            branches=tuple(BallBranch(b["radius"], b["n_neighbors"],
                                      tuple(b["mlp"]))
                           for b in l["branches"]))
        layers.append(spec)
        c_in = spec.out_features
    return PointNetConfig(
        name=cfg["name"], n_points=cfg["n_points"], layers=tuple(layers),
        group_all=GroupAllSpec(in_features=c_in,
                               mlp=tuple(cfg["group_all"]["mlp"])),
        head=tuple(cfg["head"][1:-1]))


class System(_base.System):
    """One compiled MSG model behind one engine, warmed for one loop
    kind."""

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
    ref_mod = _load("references", cfg["reference"])
    ref = np.stack([ref_mod.logits(cfg, weights, c, bits=bits)
                    for c in clouds])
    return {"logit_gap": ref_mod.logit_gap(np.stack(served), ref)}
