"""Logical sharding rules: params (FSDP x TP), activations, caches.

Strategy (DESIGN.md §4):
  * TP over 'model'  — head / d_ff / vocab dimensions;
  * FSDP over 'data' — the d_model (or other large non-TP) dimension of
    every big weight; XLA inserts the per-layer all-gathers (ZeRO-3);
  * DP over 'pod' (+'data' for the batch dimension of activations);
  * small leaves (< _MIN_SHARD_SIZE elements) stay replicated;
  * decode caches: batch over ('pod','data') when divisible, otherwise the
    *sequence* dimension shards there (long-context sequence parallelism —
    the 500k-token cache of long_500k); KV heads over 'model'.

Rules are name-based on the param tree path with a divisibility guard —
a dimension that does not divide its mesh axis stays unsharded rather than
erroring (the apply-time head padding in repro.models.lm makes the main
dims divisible by construction).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["param_pspecs", "input_pspecs", "cache_pspecs",
            "named_shardings", "state_pspecs", "replica_pspecs",
            "shard_batch"]

_MIN_SHARD_SIZE = 1 << 20          # replicate anything smaller (1M elems)

# suffix-regex -> spec for the LAST TWO dims (earlier dims get None)
_COL = ("data", "model")           # (d_in, d_out-ish): FSDP x TP
_ROW = ("model", "data")           # (d_out-ish, d_in): TP x FSDP
_RULES: list[tuple[str, tuple]] = [
    # embedding + head: vocab over 'model' ONLY. Putting 'data' on their
    # d_model dim conflicts with the batch's 'data' sharding and makes the
    # partitioner replicate the (tokens, vocab) logits — 37 GiB/device at
    # train_4k (measured; see EXPERIMENTS.md §Perf iteration 0).
    (r"embed/w$", ("model", None)),            # (vocab, d_model)
    (r"lm_head/w$", (None, "model")),          # (d_model, vocab)
    (r"(q|k|v|r|g|w|gate|up|in_proj|img_proj)/w$", _COL),
    (r"(o|out|down|out_proj)/w$", _ROW),
    (r"router/w$", ("data", None)),
    (r"conv_w$", (None, "model")),
    (r"time/u$", (None, None)),
]


def _pspec_for(path: str, leaf, mesh: Mesh) -> P:
    if np.prod(leaf.shape) < _MIN_SHARD_SIZE:
        return P()
    spec2 = None
    for pat, s in _RULES:
        if re.search(pat, path):
            spec2 = s
            break
    if spec2 is None:
        # fallback heuristic for any future large param
        spec2 = _COL if leaf.ndim >= 2 else ("model",)
    dims = [None] * leaf.ndim
    for rel, ax in zip(range(leaf.ndim - len(spec2), leaf.ndim), spec2):
        if ax is None or rel < 0:
            continue
        if ax in mesh.shape and leaf.shape[rel] % mesh.shape[ax] == 0:
            dims[rel] = ax
    return P(*dims)


def _tree_pspecs(tree, mesh: Mesh, fn):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append(fn(key, leaf))
    return jax.tree.unflatten(jax.tree.structure(tree), out)


def param_pspecs(params, mesh: Mesh):
    """PartitionSpec pytree for a param (or optimizer-state) tree."""
    return _tree_pspecs(params, mesh,
                        lambda key, leaf: _pspec_for(key, leaf, mesh))


def state_pspecs(train_state, mesh: Mesh):
    """Train state = {params, opt:{m,v,step}, ...}: moments inherit the
    param sharding; scalars replicated."""
    return _tree_pspecs(
        train_state, mesh,
        lambda key, leaf: (P() if leaf.ndim == 0
                           else _pspec_for(key, leaf, mesh)))


def _batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def input_pspecs(inputs: dict, mesh: Mesh) -> dict:
    """Shardings for model inputs (ids/labels/embeds/image_embeds/decode
    cache/pos)."""
    ba = _batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in ba]))
    out = {}
    for name, spec in inputs.items():
        if name == "cache":
            out[name] = cache_pspecs(spec, mesh)
        elif name == "pos":
            out[name] = P()
        elif name in ("ids", "labels", "ids1"):
            b = spec.shape[0]
            out[name] = P(ba if b % nb == 0 else None,
                          *([None] * (len(spec.shape) - 1)))
        elif name in ("embeds", "embeds1", "image_embeds"):
            b = spec.shape[0]
            out[name] = P(ba if b % nb == 0 else None,
                          *([None] * (len(spec.shape) - 1)))
        else:
            raise KeyError(name)
    return out


def _cache_pspec(key: str, leaf, mesh: Mesh) -> P:
    ba = _batch_axes(mesh)
    nb = int(np.prod([mesh.shape[a] for a in ba]))
    tp = mesh.shape.get("model", 1)
    shape = leaf.shape
    if leaf.ndim == 0:
        return P()
    if key.split("/")[-1].startswith(("k", "v")):
        # (..., B, S, Hkv, D): batch over pod+data if divisible, else
        # sequence-parallel on the cache (long-context serving)
        b, s, h = shape[-4], shape[-3], shape[-2]
        lead = [None] * (leaf.ndim - 4)
        hax = "model" if h % tp == 0 else None
        if b % nb == 0:
            return P(*lead, ba, None, hax, None)
        if s % nb == 0:
            return P(*lead, None, ba, hax, None)
        return P(*lead, None, None, hax, None)
    # ssm / conv / shift states: (..., B, ...) — find the batch dim by the
    # structure: ssm (L.., B, H, P, N) / conv (L.., B, K, C) / last (L,B,1,D)
    if key.startswith(("ssm", "state")):
        lead = [None] * (leaf.ndim - 4)
        b, h = shape[-4], shape[-3]
        return P(*lead, ba if b % nb == 0 else None,
                 "model" if h % tp == 0 else None, None, None)
    if key.startswith("conv"):
        lead = [None] * (leaf.ndim - 3)
        b, c = shape[-3], shape[-1]
        return P(*lead, ba if b % nb == 0 else None, None,
                 "model" if c % tp == 0 else None)
    if key.startswith(("last", "img")):
        if key.startswith("img"):
            lead = [None] * (leaf.ndim - 4)
            b, h = shape[-4], shape[-2]
            return P(*lead, ba if b % nb == 0 else None, None,
                     "model" if h % tp == 0 else None, None)
        lead = [None] * (leaf.ndim - 3)
        b, d = shape[-3], shape[-1]
        return P(*lead, ba if b % nb == 0 else None, None,
                 "model" if d % tp == 0 else None)
    return P()


def cache_pspecs(cache, mesh: Mesh):
    return _tree_pspecs(cache, mesh,
                        lambda key, leaf: _cache_pspec(key, leaf, mesh))


def named_shardings(pspecs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# generic CompiledModel replication (serving tier)
# ---------------------------------------------------------------------------
#
# The LM rules above are name-based on the param tree — useless for an
# arbitrary CompiledModel batch. Replica fan-out needs exactly one rule:
# shard the leading (batch) dimension of every operand over the replica
# axis when it divides, replicate otherwise. Params stay host-side
# closures of the compiled model (small for PointNet++), so only the
# per-step operands — clouds, n_valid, a batched DevicePlan — move.

def replica_pspecs(tree, mesh: Mesh, *, axis: str = "replica"):
    """PartitionSpec pytree for batch operands on a 1-D replica mesh
    (:func:`repro.launch.mesh.make_replica_mesh`): leading dim over
    ``axis`` when divisible by the replica count, else fully replicated
    (correct for stragglers like scalars and non-divisible batches)."""
    n = mesh.shape[axis]

    def spec(leaf):
        arr = jnp.shape(leaf)
        if len(arr) >= 1 and arr[0] % n == 0:
            return P(axis, *([None] * (len(arr) - 1)))
        return P()
    return jax.tree.map(spec, tree)


def shard_batch(tree, mesh: Mesh, *, axis: str = "replica"):
    """``device_put`` a pytree of batch operands with
    :func:`replica_pspecs` shardings — the serving engine calls this on
    (clouds, n_valid, dplan) before its per-replica ``shard_map`` step, so
    each replica's slice is already on its device."""
    specs = replica_pspecs(tree, mesh, axis=axis)
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        tree, specs)
