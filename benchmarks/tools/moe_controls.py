#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own size, the numbers a cell that
trains routed experts sets its limits from: what sound runs of the program
give, and what each of TWO controls gives in the program's place; and how
the program's routing compares with the reference's, pair for pair.

    chiprun -- python3 benchmarks/tools/moe_controls.py --workload train-moe-8k \\
        --seeds 11,12 --seconds 8 [--controls 1]

``control.py`` is the tool for the first control: the plain reference with
both operands of every matmul in float8. A model of routed experts trained
WITHOUT a capacity states a second promise, that no pair is dropped, and a
second control follows from it: the reference with every matmul as it is and
the pairs an expert gets beyond 1.25 x the mean load dropped (the family's
``reference.DropBeyond``), as a capacity-bounded dispatch would. A comparison
that admits either is too loose. One process, one seed after another; for
each seed the cell's driver runs a short window and prints the program's
readings, then each control's on the same rows. Then the routing of the
job's first batch under the seeded weights, before any update: the rows each
held expert got from the program's forward against the reference's count,
and the pairs whose chosen expert differs between the two (the program's
choices are read where its router returns them, by wrapping
``route_sigmoid_bias`` for the one call). The benchmark's own runs never run
a control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def routing_gap(cell, seed: int, rows) -> dict:
    """The first batch through the program's forward and the reference's."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models import lfm2

    sizes, family = cell.config, cell.family
    cfg = family.program.model_config(
        sizes, max_seq=rows.shape[1], **dict(cell.settings.get("model", {}), remat=False))
    seen, route = [], lfm2.route_sigmoid_bias

    def spying(*args, **kwargs):
        idx, w = route(*args, **kwargs)
        seen.append(idx)
        return idx, w

    def forward(params, tokens):
        del seen[:]
        _, held = lfm2.forward(params, tokens, cfg, return_hidden=True)
        return held, list(seen)

    lfm2.route_sigmoid_bias = spying
    try:
        held, picked = jax.jit(forward)(family.weights.make_params_on_device(sizes, seed), rows)
    finally:
        lfm2.route_sigmoid_bias = route
    held, picked = np.asarray(held), [np.asarray(p) for p in picked]
    _, want = family.reference.teacher_forced_logits(sizes, seed, rows, choices=True)
    want = [np.asarray(w) for w in want]
    m = family.weights.dims(sizes)
    lo, hi = m["first"], m["first"] + m["held"]
    out = {"held_pairs": held.sum(axis=1).tolist(),
           # nothing is dropped between the router and the grouped products:
           # the rows the held experts got are the program's own choices on them
           "program_choices_held": [int(((p >= lo) & (p < hi)).sum()) for p in picked],
           "max_expert_rows": held.max(axis=1).tolist(), "reference_held_pairs": [
        int(((w >= lo) & (w < hi)).sum()) for w in want], "pairs": int(picked[0].size)}
    # a token's choices as a set: the order among equal scores is no difference
    out["pairs_differing"] = [
        int((np.sort(a, axis=1) != np.sort(b, axis=1)).sum()) for a, b in zip(picked, want)]
    out["tokens_differing"] = [
        int((np.sort(a, axis=1) != np.sort(b, axis=1)).any(axis=1).sum())
        for a, b in zip(picked, want)]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--controls", type=int, default=1)
    args = ap.parse_args()
    from benchmarks import loader, program, run
    from benchmarks.drivers import train
    from benchmarks.tools import control

    manifest = loader.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    device = run.find_device(cell.chips)
    program.cache_dir(ROOT)
    driver = manifest.driver(cell.settings["driver"])
    controls = {"float8": control.lower_precision(cell.config),
                "drop_beyond_1.25": cell.family.reference.DropBeyond(1.25)}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(ROOT, time.perf_counter())
        out = driver.run(cell, seed, args.seconds, False, ctx)
        print(json.dumps({
            "seed": seed, "who": "program", "correct": out["check"].ok,
            "readings": {ln["name"]: ln["value"] for ln in out["check"].lines},
            "details": {ln["name"]: ln["detail"] for ln in out["check"].lines},
            "end_to_end": out["end_to_end"], "failed": out["failed"],
            "attempted": out["attempted"], "peak": out["memory_peak_bytes"],
            "device": device}), flush=True)
        first = out["facts"]["first_steps"]
        for who, quant in (controls.items() if args.controls else ()):
            t0 = time.perf_counter()
            got = train.reference_numbers(cell, seed, first["rows"], first["batch"], quant=quant)
            check = train.hold_to_reference(cell, got, first["reference"])
            print(json.dumps({
                "seed": seed, "who": who, "correct": check.ok,
                "readings": {ln["name"]: ln["value"] for ln in check.lines},
                "details": {ln["name"]: ln["detail"] for ln in check.lines},
                "seconds": time.perf_counter() - t0}), flush=True)
        print(json.dumps(dict(routing_gap(cell, seed, first["rows"][: first["batch"]]),
                              seed=seed, who="routing")), flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
