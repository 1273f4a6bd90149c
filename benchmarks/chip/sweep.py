#!/usr/bin/env python3
"""Open-loop rate sweep of one configuration on the chip, to find the
knee: the highest Poisson rate the served path sustains without a
growing backlog. One process, one warm-up; then, per rate, a window of
fresh clouds at that rate, reporting latency and how long the queue took
to drain after the last request was due.

Usage:  python3 benchmarks/chip/sweep.py --workload <open-loop cell> \\
            --rates 50,100,150,200 --seconds 10 --seed 7
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness, loop, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, False)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.start_jax(cell.chips)
    cfg, mix = cell.cfg, cell.mix
    driver = harness.load_module("drivers", cfg["driver"])
    warm = traffic.Clouds(cfg["n_points"], args.seed,
                          stream=traffic.WARMUP)
    system = driver.build(cfg, "open", args.seed, warm, harness.log)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        spec = dict(mix["arrivals"], rate_per_s=rate)
        due = traffic.arrivals(spec, args.seconds, args.seed + k)
        clouds = traffic.Clouds(cfg["n_points"],
                                args.seed + 1000 + k)
        clouds.prefetch(len(due))
        win = loop.open_loop(system, clouds, due)
        lat = win.latencies() * 1e3
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "served_per_s": len(win.served) / win.end,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "max_ms": float(lat.max()),
            "drain_s": win.end - float(due[-1]),
            "batch_mean": len(win.served) / len(win.steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
