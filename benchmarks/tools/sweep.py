#!/usr/bin/env python3
"""Finds the knee of an open-loop cell once, on the chip: the same cell at a
list of fixed rates, one after another in one process, without the reference
check (this is no benchmark run).

    chiprun -- python3 benchmarks/tools/sweep.py --workload serve-dense-chat \\
        --rates 1.0,1.25,1.5 --seconds 51 --seeds 5

A rate is sustained if the backlog does not grow over the window: the time
to first token of requests due in the window's second half is not worse than
in its first half, and few requests are still waiting when it closes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="5", help="each rate is run on each seed")
    args = ap.parse_args()
    from benchmarks import loader, program, run, stats
    from benchmarks.correct import Check
    from benchmarks.drivers import serve

    manifest = loader.Manifest(ROOT)
    base = manifest.cell(args.workload)
    run.find_device(base.chips)
    program.cache_dir(ROOT)
    serve.served_check = lambda cell, seed, done, quant=None: Check()
    plan = [(float(r), int(s)) for r in args.rates.split(",") for s in args.seeds.split(",")]
    for rate, seed in plan:
        cell = replace(base, traffic=dict(base.traffic, rate_per_s=rate))
        ctx = run.Context(ROOT, time.perf_counter())
        out = serve.run(cell, seed, args.seconds, False, ctx)
        facts = out["facts"]
        ttft = facts["ttft_s"]
        half = len(ttft) // 2
        print(json.dumps({
            "rate": rate, "seed": seed, "attempted": out["attempted"], "failed": out["failed"],
            "ttft_p50_first_half_ms": stats.percentile(ttft[:half], 50) * 1e3,
            "ttft_p50_second_half_ms": stats.percentile(ttft[half:], 50) * 1e3,
            "ttft_p50_ms": out["end_to_end"]["ttft_p50_ms"],
            "ttft_p90_ms": stats.percentile(ttft, 90) * 1e3,
            "itl_p50_ms": stats.percentile(facts["itl_s"], 50) * 1e3,
            "itl_p99_ms": out["end_to_end"]["itl_p99_ms"],
            "tokens_per_s_in_window": facts["tokens_in_window"] / facts["window_s"],
            "lag_p99_ms": stats.percentile(facts["generator_lag_s"], 99) * 1e3,
            "compiles_in_window": ctx.compiles_in_window,
            "counters": {k: v for k, v in facts["counters"].items() if not k.startswith("pool.") or "highwater" in k or "num_blocks" in k or "deferred" in k},
            "peak": out["memory_peak_bytes"], "setup_s": out["end_to_end"]["setup_s"],
        }), flush=True)
        del out, facts
    return 0


if __name__ == "__main__":
    sys.exit(main())
