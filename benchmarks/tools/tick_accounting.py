#!/usr/bin/env python3
"""Holds what the engine says of its ticks against the device, from one
profiler trace.

    python3 benchmarks/tools/tick_accounting.py <trace.xplane.pb> [--json]

The engine's phase spans carry whose tick they belong to (``prefills=``) and,
on a call's first dispatch while a tick was in flight, what its starvation
probe saw (``starved=0|1``: the tick in flight was complete, so the device had
nothing queued). Three tables, over the spans lying whole inside the device
window, moved onto the device's clock as ``program_breakdown.py`` moves them:

- ``probe``: every ``rlt.serve.decode_dispatch`` / ``rlt.serve.prefill`` span
  with a ``starved=`` argument against the device's own record: did the
  program that the span enqueued start out of an idle gap of the device (of
  ``MIN_GAP_US`` or more: two queued programs stand a few microseconds apart)?
  The gap is looked for at the program's start on ``XLA Modules`` and not
  inside the span: a dispatch returns before the device starts, 0.1-0.6 ms
  before it in a profiled long cell, and the margin that moves the host's
  spans is good to about as much. Agreement both ways, as two counts:
  ``starved=1`` with such a gap, ``starved=0`` without;
- ``idle``: the device's idle time under ``rlt.serve.decode_prep``,
  ``rlt.serve.decode_dispatch`` and ``rlt.serve.sample_sync``, split by whether
  the span's ``prefills=`` is 0 or more (a span without the argument, as an
  older program's prepare and dispatch, goes under ``?``);
- ``rungs``: the device time of ``jit_serve_prefill``'s runs by the ``rung=``
  of the ``rlt.serve.prefill`` span that enqueued each.

A span's program is found by order: programs run in the order they were
enqueued and no run starts before its span does, so the runs of a program are
laid against its spans at the smallest offset that keeps that (runs enqueued
before the trace began come first and have no span).

A trace of a program without the arguments prints the tables it can and empty
ones for the rest; it never raises for their absence.
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace, stats, trace_reduce  # noqa: E402
from benchmarks.tools import program_breakdown  # noqa: E402

PREFILL = "rlt.serve.prefill"
DISPATCH = "rlt.serve.decode_dispatch"
PHASES = ("rlt.serve.decode_prep", DISPATCH, program_trace.SAMPLE_SYNC)
MODULES = {PREFILL: "jit_serve_prefill", DISPATCH: "jit_serve_decode"}
MIN_GAP_US = 50.0

Gap = Tuple[float, float]
Run = Tuple[float, float]


def device_gaps(per_device: Dict[int, List[trace_reduce.Interval]]) -> List[Gap]:
    """The idle intervals between the device's operations, by start. One
    device, as every serving cell has: with more, the first one's."""
    events = per_device[min(per_device)]
    merged = trace_reduce.union((s, e) for s, e, _ in events)
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:]) if s1 > e0]


def _overlap(gaps: Sequence[Gap], start: float, end: float) -> float:
    return sum(min(e, end) - max(s, start) for s, e in gaps if s < end and e > start)


def _prefills_class(span: program_trace.Span) -> str:
    if "prefills" not in span.args:
        return "?"
    return "prefills=0" if int(span.args["prefills"]) == 0 else "prefills>0"


def lay(spans: Sequence[program_trace.Span], runs: Sequence[Run]
        ) -> List[Tuple[program_trace.Span, Run]]:
    """Each span with the run it enqueued: the i-th span and the (i +
    offset)-th run, at the smallest offset at which no run starts before its
    span."""
    for offset in range(len(runs) + 1):
        pairs = list(zip(spans, runs[offset:]))
        if all(sp.start_ns <= run[0] for sp, run in pairs):
            return pairs
    return []


def idle_before(gaps: Sequence[Gap], at_ns: float) -> float:
    """Length of the device's idle gap that ends at ``at_ns`` (to a
    microsecond: a module's event and its first operation start nanoseconds
    apart), 0 where the device was busy until then."""
    return max((e - s for s, e in gaps if abs(e - at_ns) <= 1e3), default=0.0)


def probe_table(spans: Sequence[program_trace.Span], gaps: Sequence[Gap],
                runs: Dict[str, Sequence[Run]]) -> Dict[str, Any]:
    """The ``starved=`` argument of each probed dispatch against whether its
    program started out of an idle gap of the device."""
    rows = []
    for name, module in MODULES.items():
        for sp, (start, _) in lay(program_trace.named(spans, name), runs.get(module, ())):
            if "starved" in sp.args:
                rows.append((int(sp.args["starved"]), idle_before(gaps, start) * 1e-3))
    said = {flag: [gap for f, gap in rows if f == flag] for flag in (1, 0)}
    return {
        "probed": len(rows),
        "starved_1": len(said[1]),
        "starved_1_and_a_gap_before_its_program": sum(g >= MIN_GAP_US for g in said[1]),
        "starved_0": len(said[0]),
        "starved_0_and_no_gap_before_its_program": sum(g < MIN_GAP_US for g in said[0]),
        "gap_us_median_where_starved_1": stats.median(said[1]) if said[1] else None,
        "gap_us_median_where_starved_0": stats.median(said[0]) if said[0] else None,
    }


def idle_table(spans: Sequence[program_trace.Span], gaps: Sequence[Gap]) -> Dict[str, Any]:
    """Idle time of the device under the three phases that can keep it
    waiting, by whether the span's tick holds prefills."""
    out: Dict[str, Dict[str, float]] = {}
    for sp in spans:
        if sp.name not in PHASES:
            continue
        row = out.setdefault(f"{sp.name} {_prefills_class(sp)}",
                             {"spans": 0, "span_ms": 0.0, "idle_ms": 0.0})
        row["spans"] += 1
        row["span_ms"] += sp.ms
        row["idle_ms"] += _overlap(gaps, sp.start_ns, sp.end_ns) * 1e-6
    for row in out.values():
        row["idle_ms_a_span"] = row["idle_ms"] / row["spans"]
    return dict(sorted(out.items()))


def rung_table(spans: Sequence[program_trace.Span], runs: Sequence[Run]) -> Dict[str, Any]:
    """``jit_serve_prefill``'s runs by the rung of the span that enqueued
    each."""
    prefills = program_trace.named(spans, PREFILL)
    pairs = lay(prefills, runs)
    by_rung: Dict[str, List[float]] = defaultdict(list)
    for sp, (s, e) in pairs:
        by_rung[str(sp.args.get("rung", "?"))].append((e - s) * 1e-6)
    return {
        "runs": len(runs), "spans": len(prefills), "runs_without_a_span": len(runs) - len(pairs),
        "by_rung": {rung: {"runs": len(ms), "median_ms": stats.median(ms), "total_ms": sum(ms)}
                    for rung, ms in sorted(by_rung.items(), key=lambda kv: (len(kv[0]), kv[0]))},
    }


def accounting(path: str) -> Dict[str, Any]:
    per_device = trace_reduce.device_events(path)
    every = [ev for evs in per_device.values() for ev in evs]
    if not every:
        raise RuntimeError(f"{path}: no operation ran on the device")
    w0, w1 = min(s for s, _, _ in every), max(e for _, e, _ in every)
    shift = program_breakdown.clock_shift_ns(path)
    spans = [sp._replace(start_ns=sp.start_ns - shift, end_ns=sp.end_ns - shift)
             for sp in program_trace.spans(path)]
    spans = [sp for sp in spans if sp.start_ns >= w0 and sp.end_ns <= w1]
    gaps = device_gaps(per_device)
    runs: Dict[str, List[Run]] = defaultdict(list)
    for s, e, name in program_breakdown.module_runs(path).get(min(per_device), ()):
        if s >= w0 and e <= w1:
            runs[name].append((s, e))
    return {
        "window_s": (w1 - w0) * 1e-9, "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "clock_shift_us": shift * 1e-3, "min_gap_us": MIN_GAP_US,
        "probe": probe_table(spans, gaps, runs),
        "idle": idle_table(spans, gaps),
        "rungs": rung_table(spans, runs[MODULES[PREFILL]]),
    }


def render(a: Dict[str, Any]) -> str:
    p, r = a["probe"], a["rungs"]
    out = [f"window {a['window_s']:.4f} s, idle {a['idle_s'] * 1e3:.2f} ms; host spans moved "
           f"by {a['clock_shift_us']:.1f} us",
           f"probe: {p['probed']} dispatches carry starved= (the device's idle before the "
           f"program each enqueued; a gap counts from {a['min_gap_us']:.0f} us):",
           f"  starved=1 {p['starved_1']:>6}, its program started out of a gap "
           f"{p['starved_1_and_a_gap_before_its_program']:>6}",
           f"  starved=0 {p['starved_0']:>6}, its program started out of none  "
           f"{p['starved_0_and_no_gap_before_its_program']:>6}"]
    for flag in (1, 0):
        gap = p[f"gap_us_median_where_starved_{flag}"]
        if gap is not None:
            out.append(f"  median idle before the program where starved={flag}: {gap:.1f} us")
    out.append("idle: the device's idle time under a phase, by the prefills of its tick:")
    out += [f"  {k:<40}{v['spans']:>6} spans {v['span_ms']:>10.2f} ms, idle {v['idle_ms']:>9.3f} ms"
            f" ({v['idle_ms_a_span']:.4f} ms a span)" for k, v in a["idle"].items()]
    out.append(f"rungs: {r['runs']} runs of {MODULES[PREFILL]}, {r['spans']} {PREFILL} spans, "
               f"{r['runs_without_a_span']} runs without a span:")
    out += [f"  rung {k:<8}{v['runs']:>5} runs, median {v['median_ms']:.3f} ms, "
            f"total {v['total_ms']:.2f} ms" for k, v in r["by_rung"].items()]
    return "\n".join(out)


def main(argv: List[str]) -> int:
    paths = [a for a in argv if not a.startswith("--")]
    if len(paths) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    a = accounting(paths[0])
    print(json.dumps(a) if "--json" in argv else render(a))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
