#!/usr/bin/env python3
"""What a closed-loop cell's seeds will spread, by arithmetic on the CPU: the
mix's own request lists replayed over given tick costs.

    python3 benchmarks/tools/closed_loop_replay.py --workload <cell> --seeds 48 \\
        --decode-ms 22 --prefill-ms 256:20,512:35,1024:70,2048:150 [--seconds 51]

The engine as the cell sets it, reduced to its clock: a tick decodes every
occupied slot (one token each, ``--decode-ms``) and first admits at most one
queued request into a free slot, whose prefill at its prompt's rung costs
``--prefill-ms`` more; a finished request's client sends its next at once. The
mix (``benchmarks/traffic.py``: the same multiset of lengths every seed, the
seed deals them) is the cell's own file, the window opens after its ``ramp_s``
and counts the tokens sampled inside it. Printed: tokens/s a seed, requests
finished a window, the standard deviation over seeds, and how sets of six
spread (interquartile range over the median, as the driver reads a set). No
device number comes from here: the costs are what a chip run measured, or a
prediction, and the output says which it was given.
"""
from __future__ import annotations

import argparse
import collections
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def replay(mix, engine, seed, seconds, decode_s, prefill_s):
    """(tokens/s inside the window, requests finished inside it)."""
    from benchmarks import traffic

    plan = traffic.closed_loop(mix, seed, vocab=3)
    rungs = sorted(prefill_s)
    queue = collections.deque()
    sent = 0

    def send():
        nonlocal sent
        req = plan[sent % len(plan)]
        sent += 1
        queue.append((min(r for r in rungs if r >= len(req.prompt)), req.new_tokens))

    for _ in range(int(mix["clients"])):
        send()
    left = []  # tokens each occupied slot still owes
    now, t_open = 0.0, float(mix.get("ramp_s", 0.0))
    t_close = t_open + seconds
    tokens = finished = 0
    while now < t_close:
        cost = decode_s
        if queue and len(left) < engine["num_slots"]:
            rung, new = queue.popleft()
            left.append(new)
            cost += prefill_s[rung]
        now += cost
        inside = t_open <= now < t_close
        tokens += len(left) * inside
        done = sum(1 for n in left if n == 1)
        left = [n - 1 for n in left if n > 1]
        finished += done * inside
        for _ in range(done):
            send()
    return tokens / seconds, finished


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=48)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--decode-ms", type=float, required=True)
    ap.add_argument("--prefill-ms", required=True, help="rung:ms,rung:ms,...")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    args = ap.parse_args()
    from benchmarks import loader

    manifest = loader.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    mix, engine = dict(cell.traffic), dict(cell.settings["engine"])
    if args.slots:
        engine["num_slots"] = args.slots
    if args.clients:
        mix["clients"] = args.clients
        mix["stagger_first"] = min(mix.get("stagger_first", 0), args.slots or args.clients)
    seconds = args.seconds or manifest.run_seconds
    prefill_s = {int(r): float(ms) * 1e-3 for r, ms in
                 (pair.split(":") for pair in args.prefill_ms.split(","))}
    runs = [replay(mix, engine, 3_000_000_000 + 7919 * i, seconds, args.decode_ms * 1e-3, prefill_s)
            for i in range(args.seeds)]
    rates = [r for r, _ in runs]
    print(f"{args.workload}: {engine['num_slots']} slots, {mix['clients']} clients, "
          f"{seconds:g} s, decode {args.decode_ms} ms, prefill {args.prefill_ms} (CPU arithmetic)")
    print(f"tokens/s: median {statistics.median(rates):.1f}, standard deviation "
          f"{100 * statistics.pstdev(rates) / statistics.mean(rates):.2f} % over {len(rates)} seeds; "
          f"requests finished a window: {statistics.mean(n for _, n in runs):.0f}")
    sets = [spread(rates[i: i + 6]) for i in range(0, len(rates) - 5, 6)]
    print("sets of six, spread in %:", " ".join(f"{100 * s:.2f}" for s in sets))
    return 0


if __name__ == "__main__":
    sys.exit(main())
