#!/usr/bin/env python3
"""Reads the two numbers every limit is set from, on the chip, at the cell's
own size: what sound runs of the program give, and what the control gives.

    chiprun -- python3 benchmarks/tools/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds 8 [--control 1]

One process, one seed after another (set-up is long, so the seeds share it as
far as the compile cache goes). For each seed the cell's driver runs a short
window and prints the program's readings; with ``--control 1`` the control is
then read on the same inputs: the plain reference computed in the next
precision below the configuration's (float8 e4m3 for bfloat16), put in the
program's place. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def lower_precision(sizes):
    from benchmarks import reference

    return reference.bf16 if sizes.get("dtype", "bfloat16") == "float32" else reference.fp8


def control_check(cell, seed, facts):
    """The cell's comparison with the control in the program's place."""
    quant = lower_precision(cell.config)
    if "first_steps" in facts:
        from benchmarks.drivers import train

        first = facts["first_steps"]
        got = train.reference_numbers(cell, seed, first["rows"], first["batch"], quant=quant)
        return train.hold_to_reference(cell, got, first["reference"])
    from benchmarks.drivers import serve

    return serve.served_check(cell, seed, facts["finished"], quant=quant)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()
    from benchmarks import loader, program, run

    manifest = loader.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    device = run.find_device(cell.chips)
    program.cache_dir(ROOT)
    driver = manifest.driver(cell.settings["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(ROOT, time.perf_counter())
        out = driver.run(cell, seed, args.seconds, False, ctx)
        readings = {ln["name"]: ln["value"] for ln in out["check"].lines}
        print(json.dumps({"seed": seed, "who": "program", "correct": out["check"].ok,
                          "readings": readings, "notes": out["check"].notes,
                          "end_to_end": out["end_to_end"], "failed": out["failed"],
                          "attempted": out["attempted"],
                          "compiles_in_window": ctx.compiles_in_window,
                          "peak": out["memory_peak_bytes"]}), flush=True)
        if args.control:
            t0 = time.perf_counter()
            check = control_check(cell, seed, out["facts"])
            print(json.dumps({"seed": seed, "who": "control", "correct": check.ok,
                              "readings": {ln["name"]: ln["value"] for ln in check.lines},
                              "notes": check.notes, "seconds": time.perf_counter() - t0}),
                  flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
