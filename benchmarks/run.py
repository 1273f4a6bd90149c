"""Benchmark harness: one function per paper table/figure + kernel micro +
beyond-paper scheduling. Prints ``name,us_per_call,derived`` CSV and writes
the same rows machine-readably to a ``BENCH_*.json`` trajectory file
(rows + run metadata: git sha, jax version, interpret mode) so runs can be
diffed across commits.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only PREFIX]
                                                [--json-out PATH]
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def _parse_row(line: str) -> dict:
    """'name,us,derived' (derived may itself contain ';'-joined pairs)."""
    name, us, derived = line.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def _metadata(args) -> dict:
    import jax

    from repro.kernels import interpret_mode
    return {
        "git_sha": _git_sha(),
        "jax_version": jax.__version__,
        "backend_platform": jax.default_backend(),
        # off-TPU the Pallas kernels run in the interpreter, so absolute
        # µs characterize the host
        "pallas_interpret_mode": interpret_mode(),
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "quick": bool(args.quick),
        "only": args.only,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="single workload seed (faster)")
    ap.add_argument("--only", default=None,
                    help="run only benches whose name starts with this")
    ap.add_argument("--json-out", default=None,
                    help="trajectory file path (default: "
                         "BENCH_<utc-timestamp>.json in the cwd)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from .common import workloads
    from .paper_figs import (fig10_hitrate, fig7_speedup, fig8_energy,
                             fig9a_traffic, fig9b_buffer_speedup)
    from .kernels_bench import kernels
    from .beyond_schedule import beyond
    from .reliability_bench import reliability

    wls = workloads(seeds=(0,) if args.quick else (0, 1, 2))
    benches = [
        ("fig7", lambda: fig7_speedup(wls)),
        ("fig8", lambda: fig8_energy(wls)),
        ("fig9a", lambda: fig9a_traffic(wls)),
        ("fig9b", lambda: fig9b_buffer_speedup(wls)),
        ("fig10", lambda: fig10_hitrate(wls)),
        ("kernel", kernels),
        ("beyond", lambda: beyond(wls)),
        ("reliability", lambda: reliability(4 if args.quick else 8)),
    ]
    meta = _metadata(args)
    records = []
    print("name,us_per_call,derived")
    for name, fn in benches:
        if args.only and not name.startswith(args.only):
            continue
        t0 = time.monotonic()
        for line in fn():
            print(line)
            records.append({"bench": name, **_parse_row(line)})
        print(f"# {name} done in {time.monotonic() - t0:.1f}s",
              file=sys.stderr)
    out = args.json_out or time.strftime("BENCH_%Y%m%dT%H%M%SZ.json",
                                         time.gmtime())
    with open(out, "w") as f:
        json.dump({"metadata": meta, "rows": records}, f, indent=2)
        f.write("\n")
    print(f"# wrote {len(records)} rows to {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
