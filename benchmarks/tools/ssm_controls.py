#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own size, the numbers a state-space
cell's limits are set from: what sound runs of the program give, and what
each of TWO controls gives in the program's place.

    chiprun -- python3 benchmarks/tools/ssm_controls.py --workload serve-ssm-chat \\
        --seeds 11,12 --seconds 8

``control.py`` is the tool for the first control: the plain reference with
both operands of every matmul in float8. A state-space model states a second
precision, that of the scan's carried state (float32), and a second control
follows from it: the reference with every matmul as it is and the state
carried in bfloat16 (the family's ``reference.StateOnly``). A comparison
that admits either is too loose. One process, one seed after another; for
each seed the cell's driver runs a short window and prints the program's
readings, then each control's on the same requests. The benchmark's own runs
never run a control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    from benchmarks import loader, program, run
    from benchmarks.drivers import serve
    from benchmarks.tools import control

    manifest = loader.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    device = run.find_device(cell.chips)
    program.cache_dir(ROOT)
    driver = manifest.driver(cell.settings["driver"])
    controls = {"float8": control.lower_precision(cell.config),
                "state_bfloat16": cell.family.reference.StateOnly()}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(ROOT, time.perf_counter())
        out = driver.run(cell, seed, args.seconds, False, ctx)
        print(json.dumps({
            "seed": seed, "who": "program", "correct": out["check"].ok,
            "readings": {ln["name"]: ln["value"] for ln in out["check"].lines},
            "notes": out["check"].notes, "end_to_end": out["end_to_end"],
            "failed": out["failed"], "attempted": out["attempted"],
            "peak": out["memory_peak_bytes"], "device": device}), flush=True)
        for who, quant in controls.items():
            t0 = time.perf_counter()
            check = serve.served_check(cell, seed, out["facts"]["finished"], quant=quant)
            print(json.dumps({
                "seed": seed, "who": who, "correct": check.ok,
                "readings": {ln["name"]: ln["value"] for ln in check.lines},
                "notes": check.notes, "seconds": time.perf_counter() - t0}), flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
