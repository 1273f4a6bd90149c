#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, on the chip, in one
process.

For each seed this runs the cell as a benchmark run does (set-up, a short
window at the cell's own load, the seeded sample of what it served,
the comparison with the plain reference) and prints the program's
readings. On the same weights and clouds it then puts the control in the
program's place, the reference one precision below the configuration's
(int4 for the stated INT8), and prints its readings. The lower reading of
a number is the largest the program gives, the upper the smallest the
control gives; the limit in the configuration's file lies between them.

Usage:  python3 benchmarks/chip/control.py --workload <cell> \\
            --seeds 101,102,103 --seconds 4
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402

CONTROL_BITS = {"int8": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, False)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    kind = harness.start_jax(cell.chips)
    driver = harness.load_module("drivers", cell.cfg["driver"])
    ref = harness.load_module("references", cell.cfg["reference"])
    bits = CONTROL_BITS[cell.cfg["arithmetic"]]
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        keep = {}
        line = harness.run_cell(cell, seed, args.seconds, False,
                                time.perf_counter(), device_kind=kind,
                                keep=keep)
        low = [ref.logits(cell.cfg, keep["weights"], c, bits=bits)
               for c in keep["clouds"]]
        ctl = driver.check(cell.cfg, keep["weights"], keep["clouds"], low)
        for k, c in line["checks"].items():
            program.setdefault(k, []).append(c["value"])
        for k, v in ctl.items():
            control.setdefault(k, []).append(v)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "program": {k: c["value"] for k, c in
                                      line["checks"].items()},
                          "control": ctl, "failed": line["failed"],
                          "served_sampled": len(keep["clouds"])}),
              flush=True)
    print(json.dumps({"workload": cell.name, "control_bits": bits,
                      "lower": {k: max(v) for k, v in program.items()},
                      "upper": {k: min(v) for k, v in control.items()},
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
