#!/usr/bin/env python
"""Serve the paper's PointNet++ models on a TPU chip and check the logits.

For each of model0, model1 and model2 (1,024 points, paper Table-1 widths,
40 classes, parameters from a fixed seed) this builds
``compile_model(..., schedule="pointer")`` for the 'reram-fused' and the
'float' backends and serves a short request stream through
``PointCloudServable`` + ``ServingEngine`` with every Pallas kernel
compiled by Mosaic (never interpreted). It then checks, per model:

- reram-fused, integer accumulators: every layer of every served crossbar
  program, run alone through the fused kernel in the dataflow the served
  MLP uses, at its real row count, on integer inputs (activation scale
  exactly 1): the accumulators must equal the NumPy quantized-chain
  oracle's (``repro.kernels.ref.quant_chain_np``) bit for bit;
- reram-fused, logits: the served logits against the same oracle run on
  the same geometry (FPS/kNN indices and lifted features computed on the
  chip): ``max|diff| <= RERAM_RTOL * max|oracle|`` and the same argmax;
- reram-fused, logits on the chip's own arithmetic: the same oracle, fed
  the programmed integer weights and the chip's own quantizer
  (``repro.kernels.chain_quantizer``: the first layer's ``quantize_tensor``
  and the fused kernel's inter-layer ``requantize``, each run on the
  chip), must give the served logits bit for bit. This is what isolates
  the RERAM_RTOL gap to the chip's f32 division alone;
- float, logits: the served logits against a plain float32 ``jax.numpy``
  forward (``float_reference``, written apart from ``repro.models``) under
  ``jax.default_matmul_precision("highest")``:
  ``max|diff| <= FLOAT_RTOL * max|reference|`` and the same argmax.

It prints compile seconds, served p50, the fused dataflow of each MLP, the
jit trace count and the device for each model (smoke numbers, not a
benchmark), and as its last line ``{"ok": true, "device": {...}}``. Any
failed phase or check raises, so the script exits non-zero and prints no
such line; so does a machine whose first JAX device is not a TPU.

``--chips 4`` runs only the replica phase instead: model0 served by a
four-replica engine (``make_replica_mesh(4)``, batch bucket 8, the step
run per replica under ``shard_map``) against the same requests served on
one device, within the tolerances above.

Usage:  python chip_smoke.py [--chips {1,4}]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: reram-fused logits vs the correctly rounded NumPy oracle, relative to
#: max|oracle logit|: four INT8 quantization steps. The chip's f32
#: division is not correctly rounded, so an activation at a rounding
#: boundary can quantize one step apart, and a step that moves a layer's
#: max moves the next layer's scale and every activation after it. The
#: oracle fed the chip's own quotients must then match bit for bit.
RERAM_RTOL = 4 / 127
#: float logits served at the default matmul precision (TPU f32 matmuls
#: take bf16 passes, ~2^-8 relative per product) vs the "highest"
#: precision reference, relative to max|reference logit|: twice the
#: largest difference read on a v5e (0.77 %).
FLOAT_RTOL = 1.5e-2

SEED = 0
N_REQUESTS = 8
N_REPLICAS = 4
REPLICA_BATCH = 8


def _device_line():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _requests(n, n_points):
    from repro.data.pointcloud import request_stream
    return list(request_stream(n, n_points=(n_points,), pool=n, seed=SEED))


def _engine(model, buckets, mesh=None):
    from repro.launch.serve import PointCloudServable, ServingEngine
    servable = PointCloudServable(model, buckets=buckets, mesh=mesh)
    return ServingEngine(servable), servable


def _served_logits(engine, first_id):
    import numpy as np
    done = sorted((r for r in engine.completed if r.id >= first_id),
                  key=lambda r: r.id)
    return np.stack([np.asarray(r.result) for r in done])


def serve(model, stream, warm, buckets):
    """Warm every batch bucket on ``warm`` clouds (compile), then replay
    ``stream``. Returns (logits in arrival order, compile s, stats)."""
    import jax
    engine, servable = _engine(model, buckets)
    t0 = time.perf_counter()
    for size in buckets.batch:
        reqs = [engine.submit(c) for c in warm[:size]]
        engine.drain()
        jax.block_until_ready([r.result for r in reqs])
    compile_s = time.perf_counter() - t0
    traces = servable.jit_traces
    first = len(engine.completed)
    stats = engine.serve_stream(stream)
    if servable.jit_traces != traces:
        raise AssertionError(f"the served stream retraced the step "
                             f"({traces} -> {servable.jit_traces} traces)")
    return _served_logits(engine, first), compile_s, stats


def oracle_logits(params, cfg, clouds, programs=None):
    """The reram-fused model in NumPy (``quant_chain_np`` per MLP) on the
    chip's own geometry and lifted features; with ``programs`` (the served
    crossbar programs), on the chip's own quantizer and integer weights."""
    import jax
    import numpy as np

    from repro.kernels import chain_quantizer
    from repro.kernels.ref import quant_chain_np
    from repro.models import pointnet2 as pn

    hooks = {"sa": [None] * len(cfg.layers), "head": None}
    if programs is not None:
        hooks = {"sa": [chain_quantizer(p) for p in programs["sa"]],
                 "head": chain_quantizer(programs["head"])}
    geometry = jax.jit(lambda c: pn.geometry_pass(cfg, c))
    lift = jax.jit(lambda c: pn.lift_features(c, cfg.layers[0].in_features))
    out = []
    for cloud in clouds:
        _, ctrs, nbrs = jax.device_get(geometry(cloud))
        feats = np.asarray(lift(cloud))
        for k, spec in enumerate(cfg.layers, start=1):
            diff = feats[nbrs[k]] - feats[ctrs[k]][:, None, :]
            y, _ = quant_chain_np(params["sa"][k - 1],
                                  diff.reshape(-1, diff.shape[-1]),
                                  quantize=hooks["sa"][k - 1])
            feats = y.reshape(spec.n_centers, spec.n_neighbors, -1).max(1)
        logits, _ = quant_chain_np(params["head"], feats.max(0)[None],
                                   final_relu=False, quantize=hooks["head"])
        out.append(logits[0])
    return np.stack(out)


def float_reference(params, cfg, cloud):
    """Plain float32 ``jax.numpy`` PointNet++ forward of one cloud, apart
    from ``repro.models``' backends: FPS from point 0, kNN by squared
    distance, neighbour-minus-centre features through each SA MLP, max
    over neighbours, then max over points into the head. Only the input
    featurization (``lift_features``) is shared: it defines the input."""
    import jax
    import jax.numpy as jnp

    from repro.models.pointnet2 import lift_features

    def mlp(layers, x, final_relu=True):
        for i, lyr in enumerate(layers):
            x = x @ lyr["w"] + lyr["b"]
            if final_relu or i < len(layers) - 1:
                x = jnp.maximum(x, 0.0)
        return x

    def fps(pts, m):
        def body(i, state):
            idx, dist = state
            d = jnp.sum((pts - pts[idx[i]]) ** 2, axis=1)
            dist = jnp.minimum(dist, d)
            return idx.at[i + 1].set(jnp.argmax(dist).astype(jnp.int32)), dist
        idx = jnp.zeros(m + 1, jnp.int32)
        dist = jnp.full((pts.shape[0],), jnp.inf, pts.dtype)
        return jax.lax.fori_loop(0, m, body, (idx, dist))[0][:m]

    pts = cloud
    feats = lift_features(cloud, cfg.layers[0].in_features)
    for spec, layers in zip(cfg.layers, params["sa"]):
        ctr = fps(pts, spec.n_centers)
        d = jnp.sum((pts[ctr][:, None, :] - pts[None, :, :]) ** 2, axis=-1)
        nbr = jax.lax.top_k(-d, spec.n_neighbors)[1]
        h = mlp(layers, feats[nbr] - feats[ctr][:, None, :])
        pts, feats = pts[ctr], h.max(axis=1)
    return mlp(params["head"], feats.max(axis=0), final_relu=False)


def check_int_accumulators(model, cfg):
    """Each layer of each served crossbar program, alone, through the fused
    kernel in its served dataflow and row count, on integer inputs with
    max |x| = 127 (scale 1): accumulators bitwise-equal to the oracle's.
    Returns the number of layers checked."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import CrossbarProgram, reram_mlp_fused_batched
    from repro.kernels.ref import quant_chain_np

    programs = model.backend.program
    plans = model.stats()["fused_plan"]
    rng = np.random.default_rng(SEED)
    mlps = [(f"sa{i}", spec.n_centers * spec.n_neighbors, programs["sa"][i])
            for i, spec in enumerate(cfg.layers)]
    mlps.append(("head", 1, programs["head"]))
    checked = 0
    for key, rows, prog in mlps:
        plan = plans[key]           # the served MLP's dataflow at `rows`
        for l, (k, n) in enumerate(zip(prog.widths[:-1], prog.widths[1:])):
            layer = CrossbarProgram(
                planes=prog.planes[l:l + 1],
                bias=jnp.zeros_like(prog.bias[l:l + 1]),
                w_scale=prog.w_scale[l:l + 1],
                col_mask=prog.col_mask[l:l + 1], widths=(k, n),
                weight_bits=prog.weight_bits, cell_bits=prog.cell_bits)
            x = rng.integers(-127, 128, size=(2, rows, k)).astype(np.float32)
            x[:, 0, 0] = 127
            out = np.asarray(reram_mlp_fused_batched(
                jnp.asarray(x), layer, final_relu=False, mode=plan["mode"],
                block_n=plan["block_n"]), np.float64)
            sw = float(prog.w_scale[l, 0])
            w_deq = np.asarray(layer.weights()[0])
            for b in range(2):
                _, (acc,) = quant_chain_np(
                    [{"w": w_deq, "b": np.zeros(n, np.float32)}], x[b])
                # out = acc * sw (one f32 rounding); rint recovers acc
                # exactly while |acc| < 2^23
                if np.abs(acc).max() >= 2 ** 23:
                    raise AssertionError(f"{key} layer {l}: accumulator "
                                         f"too large to recover exactly")
                chip = np.rint(out[b] / sw).astype(np.int64)
                if not np.array_equal(chip, acc):
                    bad = int(np.sum(chip != acc))
                    raise AssertionError(
                        f"{key} layer {l} ({plan['mode']}): {bad} integer "
                        f"accumulators differ from the oracle")
            checked += 1
    return checked


def _compare(name, got, ref, rtol, *, argmax):
    import numpy as np
    err = float(np.max(np.abs(got - ref)))
    bound = rtol * float(np.max(np.abs(ref)))
    agree = int(np.sum(got.argmax(1) == ref.argmax(1)))
    print(f"  {name}: max|diff| {err:.6g} <= {bound:.6g} "
          f"(rtol {rtol:g}), argmax {agree}/{len(ref)}", flush=True)
    if not np.all(np.isfinite(got)) or got.shape != ref.shape:
        raise AssertionError(f"{name}: logits not finite or wrong shape "
                             f"{got.shape}")
    if err > bound:
        raise AssertionError(f"{name}: max|diff| {err} exceeds {bound}")
    if argmax and agree != len(ref):
        raise AssertionError(f"{name}: argmax differs on "
                             f"{len(ref) - agree} clouds")


def smoke_model(cfg):
    """Serve one model on both backends and run every check."""
    import jax
    import numpy as np

    from repro import compile_model
    from repro.data.pointcloud import synthetic_cloud
    from repro.launch.serve import ShapeBuckets
    from repro.models import pointnet2 as pn

    params = pn.init_params(jax.random.PRNGKey(SEED), cfg, n_classes=40)
    stream = _requests(N_REQUESTS, cfg.n_points)
    clouds = [cloud for _, cloud, _ in stream]
    warm = [synthetic_cloud(i, cfg.n_points, seed=SEED + 1000)
            for i in range(4)]
    buckets = ShapeBuckets(points=(cfg.n_points,), batch=(1, 4))
    device = jax.devices()[0].device_kind
    for backend in ("reram-fused", "float"):
        model = compile_model(params, cfg, backend=backend,
                              schedule="pointer")
        logits, compile_s, st = serve(model, stream, warm, buckets)
        plan = ""
        if backend == "reram-fused":
            plan = " plan " + " ".join(
                f"{k}={v['mode']}"
                for k, v in model.stats()["fused_plan"].items())
        print(f"{cfg.name} {backend}: compile {compile_s:.2f} s, served "
              f"p50 {st['p50_ms']:.3f} ms over {st['n_requests']} "
              f"requests, jit traces {st['jit_traces']},{plan} "
              f"device {device}", flush=True)
        if backend == "reram-fused":
            n = check_int_accumulators(model, cfg)
            print(f"  integer accumulators: bitwise equal in all {n} "
                  f"programmed layers", flush=True)
            _compare("reram-fused logits vs NumPy oracle", logits,
                     oracle_logits(params, cfg, clouds), RERAM_RTOL,
                     argmax=True)
            _compare("reram-fused logits vs NumPy oracle on the chip's "
                     "quantizer", logits,
                     oracle_logits(params, cfg, clouds,
                                   model.backend.program), 0.0, argmax=True)
        else:
            ref_fwd = jax.jit(lambda c: float_reference(params, cfg, c))
            with jax.default_matmul_precision("highest"):
                ref = np.stack([np.asarray(ref_fwd(c)) for c in clouds])
            _compare("float logits vs plain jnp reference", logits, ref,
                     FLOAT_RTOL, argmax=True)


def smoke_replicas(cfg):
    """One batch of REPLICA_BATCH requests through an N_REPLICAS-replica
    engine and a one-device engine, per backend; results within the
    tolerances."""
    import jax
    import numpy as np

    from repro import compile_model
    from repro.launch.mesh import make_replica_mesh
    from repro.launch.serve import ShapeBuckets
    from repro.models import pointnet2 as pn

    params = pn.init_params(jax.random.PRNGKey(SEED), cfg, n_classes=40)
    clouds = [c for _, c, _ in _requests(REPLICA_BATCH, cfg.n_points)]
    buckets = ShapeBuckets(points=(cfg.n_points,), batch=(REPLICA_BATCH,))
    mesh = make_replica_mesh(N_REPLICAS)
    for backend, rtol in (("reram-fused", RERAM_RTOL),
                          ("float", FLOAT_RTOL)):
        model = compile_model(params, cfg, backend=backend,
                              schedule="pointer")
        out = {}
        for label, m in (("one device", None), ("replicas", mesh)):
            engine, servable = _engine(model, buckets, mesh=m)
            t0 = time.perf_counter()
            reqs = [engine.submit(c) for c in clouds]
            engine.drain()
            jax.block_until_ready([r.result for r in reqs])
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            again = [engine.submit(c) for c in clouds]
            engine.drain()
            jax.block_until_ready([r.result for r in again])
            warm_ms = (time.perf_counter() - t0) * 1e3
            out[label] = np.stack([np.asarray(r.result) for r in reqs])
            print(f"{cfg.name} {backend} {label}: first batch "
                  f"{first_s:.2f} s (compile), warm batch {warm_ms:.3f} ms, "
                  f"jit traces {servable.jit_traces}", flush=True)
        _compare(f"{backend} replicas vs one device", out["replicas"],
                 out["one device"], rtol, argmax=backend == "reram-fused")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica serving phase")
    args = ap.parse_args(argv)

    import jax

    from repro.core.workload import PAPER_MODELS
    from repro.launch.compile_cache import enable_compile_cache

    device = _device_line()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{device['platform']} ({device['kind']})", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1
    print(f"cache {enable_compile_cache()}, jax {jax.__version__}, "
          f"{device['count']} x {device['kind']}", flush=True)
    if args.chips == 4:
        smoke_replicas(PAPER_MODELS["model0"])
    else:
        for name in ("model0", "model1", "model2"):
            smoke_model(PAPER_MODELS[name])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
