"""Serve a PointNet++ CompiledModel: shape-bucketed continuous batching
over a Poisson request stream, with the content-keyed plan cache.

The stream mixes point counts (the engine pads them into two shape
buckets) and repeats clouds (the plan cache skips FPS/kNN + Algorithm 1
on repeats). Every served result is bitwise-equal to the unpadded
per-request ``forward`` — asserted below for the whole stream.

Run:  PYTHONPATH=src python examples/serve_pointcloud.py
          [--backend reram-fused --requests 24]
"""
import argparse

import jax
import jax.numpy as jnp

from repro import compile_model
from repro.core.workload import PointNetConfig, SALayerSpec
from repro.data.pointcloud import request_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import PointCloudServable, ServingEngine, ShapeBuckets
from repro.models import pointnet2 as pn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reram-fused")
    ap.add_argument("--schedule", default="pointer")
    ap.add_argument("--requests", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = PointNetConfig(name="serve-demo", n_points=64, layers=(
        SALayerSpec(n_centers=24, n_neighbors=4, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=8, n_neighbors=4, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    model = compile_model(params, cfg, backend=args.backend,
                          schedule=args.schedule)

    servable = PointCloudServable(
        model, buckets=ShapeBuckets(points=(48, 64), batch=(1, 2, 4)))
    engine = ServingEngine(servable)
    stream = list(request_stream(args.requests, rate_hz=500.0,
                                 n_points=(40, 48, 56, 64), pool=6,
                                 repeat_p=0.7, seed=0))
    stats = engine.serve_stream(stream, payload_of=lambda item: item[1])

    print(f"served {stats['n_requests']} requests in "
          f"{stats['wall_s']*1e3:.0f} ms  "
          f"(p50 {stats['p50_ms']:.1f} ms, p99 {stats['p99_ms']:.1f} ms, "
          f"{stats['throughput_rps']:.1f} req/s)")
    print(f"batches: {stats['batches']}  jit traces: {stats['jit_traces']} "
          f"(bucketed: at most |points| x |batch| ever)")
    pc = stats["plan_cache"]
    print(f"plan cache: {pc['hits']} hits / {pc['misses']} misses "
          f"(hit rate {pc['hit_rate']:.0%})")

    # the bucketing contract, end to end: padded+batched serving returns
    # the same bits as the unpadded per-request forward (completion order
    # differs from arrival order, so match on request ids)
    by_id = {r.id: r for r in engine.completed}
    for rid, (_, cloud, _) in enumerate(stream):
        ref = model.forward(jnp.asarray(cloud))
        assert bool(jnp.all(jnp.asarray(by_id[rid].result) == ref)), rid
    print("bitwise check vs per-request forward: OK")


if __name__ == "__main__":
    main()
