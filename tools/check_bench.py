"""Benchmark regression gate: diff a fresh ``BENCH_*.json`` against the
committed baseline and fail CI on wall-clock regressions.

Only rows whose names match a STABLE prefix are gated — interpret-mode
host timings jitter, but the gated rows (compiled plan construction) are
warmed before measurement and have stayed reproducible run-to-run. Rows
present in only one file are reported but never fail the gate, EXCEPT
prefixes named via ``--require``: those must appear in the new run (this
is how CI notices a bench silently dropping out of the harness).

Run:  PYTHONPATH=src python tools/check_bench.py NEW.json \\
          [--baseline BENCH_20260808T115407Z.json] [--threshold 0.20] \\
          [--require reliability/pareto] [--gate plan/device_build]
CI runs it after the bench smoke steps on every push.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: committed reference run (regenerate with ``python -m benchmarks.run``
#: and update this name deliberately — the gate is only as honest as its
#: baseline)
DEFAULT_BASELINE = "BENCH_20260808T125424Z.json"

#: rows stable enough to gate: compiled (jitted) plan construction
DEFAULT_GATES = ("plan/device_build",)


def load_rows(path: pathlib.Path) -> dict[str, float]:
    with open(path) as f:
        data = json.load(f)
    return {r["name"]: float(r["us_per_call"]) for r in data["rows"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("new", help="fresh BENCH_*.json to check")
    ap.add_argument("--baseline", default=str(ROOT / DEFAULT_BASELINE))
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="max allowed relative slowdown on gated rows")
    ap.add_argument("--gate", action="append", default=None,
                    help="row-name prefix to gate (repeatable; default: "
                         + ", ".join(DEFAULT_GATES) + ")")
    ap.add_argument("--require", action="append", default=[],
                    help="row-name prefix that MUST appear in the new run")
    args = ap.parse_args(argv)
    gates = tuple(args.gate) if args.gate else DEFAULT_GATES

    base = load_rows(pathlib.Path(args.baseline))
    new = load_rows(pathlib.Path(args.new))
    failures = []

    for prefix in args.require:
        if not any(n.startswith(prefix) for n in new):
            failures.append(f"required rows '{prefix}*' missing from "
                            f"{args.new}")

    gated = sorted(n for n in new if n.startswith(gates))
    for name in gated:
        if name not in base:
            print(f"-- {name}: new row (no baseline), not gated")
            continue
        ratio = new[name] / max(base[name], 1e-9)
        verdict = "FAIL" if ratio > 1.0 + args.threshold else "ok"
        print(f"-- {name}: {base[name]:.1f} -> {new[name]:.1f} us "
              f"({ratio - 1.0:+.0%} vs baseline) {verdict}")
        if verdict == "FAIL":
            failures.append(
                f"{name} regressed {ratio - 1.0:+.0%} "
                f"({base[name]:.1f} -> {new[name]:.1f} us, "
                f"threshold {args.threshold:.0%})")
    for name in sorted(base):
        if name.startswith(gates) and name not in new:
            print(f"-- {name}: in baseline only (bench not run), not gated")

    if failures:
        print("\nBENCH GATE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"OK: {len(gated)} gated row(s) within "
          f"{args.threshold:.0%} of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
