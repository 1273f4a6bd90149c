"""Streaming-LiDAR serving demo: deadline scheduling + frame-coherent
plan reuse (the paper's autonomous-driving scenario, end to end).

One periodic sensor emits temporally coherent frames (drifting object
clusters + per-frame jitter — never bitwise-equal, so the exact-key plan
cache misses every frame, but within the FrameTracker tolerance, so the
frame-coherent fast path reuses the anchor DevicePlan). The same stream
replays under FIFO and under EDF on a deterministic virtual clock: every
3rd frame is urgent (tight deadline). Under overload FIFO serves strictly
in arrival order, so urgent frames queue behind relaxed ones and miss;
EDF serves earliest-feasible-deadline first and meets them. Logits are
bitwise-identical either way — scheduling is a policy, not a numerics
change — asserted below for the whole matrix.

Run:  PYTHONPATH=src python examples/serve_lidar.py
          [--backend reram-fused --frames 18]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import FrameTracker, compile_model
from repro.core.workload import PointNetConfig, SALayerSpec
from repro.data.pointcloud import request_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import (PointCloudServable, ServingEngine,
                                ShapeBuckets, VirtualClock)
from repro.models import pointnet2 as pn

SERVICE_S = 2e-3          # virtual seconds per batch (one clock tick)
URGENT_US, RELAXED_US = 4_000, 100_000


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="reram-fused")
    ap.add_argument("--frames", type=int, default=18)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = PointNetConfig(name="lidar-demo", n_points=64, layers=(
        SALayerSpec(n_centers=24, n_neighbors=4, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=8, n_neighbors=4, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    model = compile_model(params, cfg, backend=args.backend,
                          schedule="pointer")
    # 800 frames/s against 2 ms service at batch 1 = overload: the queue
    # grows and the scheduling policy decides who eats the delay
    stream = list(request_stream(args.frames, rate_hz=800.0,
                                 n_points=(64,), pool=4, seed=0,
                                 mode="lidar"))

    def replay(scheduler):
        servable = PointCloudServable(
            model, buckets=ShapeBuckets(points=(64,), batch=(1,)),
            frame_reuse=FrameTracker(tol=1e-3))
        engine = ServingEngine(servable, scheduler=scheduler, max_batch=1,
                               clock=VirtualClock(tick_s=SERVICE_S))
        engine.seed_service_estimate(64, SERVICE_S)
        stats = engine.serve_stream(
            stream, payload_of=lambda it: it[1],
            deadline_us=lambda it: URGENT_US if it[2] % 3 == 0
            else RELAXED_US)
        return engine, stats

    results = {}
    for name in ("fifo", "edf"):
        engine, stats = replay(name)
        results[name] = (engine, stats)
        ft = stats["frame_tracker"]
        print(f"{name:4s}: deadline misses "
              f"{stats['n_deadline_misses']}/{stats['n_deadlined']} "
              f"(rate {stats['deadline_miss_rate']:.0%})  "
              f"p50 {stats['p50_ms']:.1f} ms  p99 {stats['p99_ms']:.1f} ms  "
              f"frame hits {ft['frame_hits']}/{args.frames} "
              f"(rate {ft['hit_rate']:.0%})")

    f_stats, e_stats = results["fifo"][1], results["edf"][1]
    assert e_stats["deadline_miss_rate"] < f_stats["deadline_miss_rate"], \
        "EDF must beat FIFO under binding deadlines"
    assert e_stats["frame_tracker"]["hit_rate"] > 0.5

    # scheduling is a pure policy: both replays, frame reuse and all,
    # return the same bits as the unscheduled per-request forward
    for name, (engine, _) in results.items():
        by_id = {r.id: r for r in engine.completed}
        for rid, (_, cloud, _) in enumerate(stream):
            ref = model.forward(jnp.asarray(cloud))
            got = jnp.asarray(by_id[rid].result)
            assert bool(jnp.all(got == ref)), (name, rid)
    print("bitwise check vs per-request forward (both schedulers): OK")


if __name__ == "__main__":
    main()
