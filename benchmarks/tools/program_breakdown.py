#!/usr/bin/env python3
"""Lays the program's own spans against the device, from one profiler trace.

    python3 benchmarks/tools/program_breakdown.py <trace.xplane.pb> [--json]

What the manifest's readers cannot report yet (``PERF.md`` section 7 says
why), for ``PERF.md`` and for whoever works on a layer next:

- ``kernels``: own time of every Mosaic custom call by the kernel's name (the
  ``name=`` of its ``pl.pallas_call``; all of them, not the ten largest
  operations). A custom call the program did not name shows under the scope
  it was traced in (``closed_call``, ``rematted_computation``, ``wrapped``).
  ``mosaic_other`` holds what else ``trace_reduce`` counts into ``mosaic_s``
  and is no kernel: an operation whose HLO text merely mentions a custom call
  (a fusion that reads a kernel's result) and the compiler's own custom calls
  (``AllocateBuffer``, ``ConcatBitcast``); the two together are ``mosaic_s``;
- ``modules``: each jitted program's runs on the device's ``XLA Modules``
  line (``jit_serve_prefill``, ``jit_serve_decode``, ``jit_train_step``):
  count and median length;
- ``gaps``: the device's idle time by ``rlt.*`` span: every gap is cut at the
  spans' edges and each piece goes to the innermost span over it (a gap of
  some milliseconds between two ticks' programs runs through several phases).
  The spans are moved onto the device's clock by the margin
  ``trace_reduce.host_spans`` applies to the benchmark's own spans: the
  difference between a raw ``bench.sync_probe`` event and the same span as
  ``host_spans`` returns it (nothing moves where the trace holds no probe);
- ``tick_cover``: the share of the ``rlt.serve.tick`` spans' time that their
  children cover, and each child's share of it.

Only events lying whole inside the device window (first operation's start to
last operation's end) count, spans and module runs alike.
"""
from __future__ import annotations

import json
import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_trace, stats, trace_reduce  # noqa: E402

NO_SPAN = "no_rlt_span"
_RUN_ID = re.compile(r"\(\d+\)$")


def clock_shift_ns(path: str) -> float:
    """How far ``trace_reduce.host_spans`` moves a host span: a raw sync-probe
    event's start less the same span's start as it returns it."""
    moved = [s for s in trace_reduce.host_spans(path) if s[2] == trace_reduce.SYNC_SPAN]
    if not moved:
        return 0.0
    raw: List[float] = []
    for plane in trace_reduce._load(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            raw += [float(e.start_ns) for e in line.events if e.name == trace_reduce.SYNC_SPAN]
    return min(raw) - min(s[0] for s in moved) if raw else 0.0


def module_runs(path: str) -> Dict[int, List[Tuple[float, float, str]]]:
    out: Dict[int, List[Tuple[float, float, str]]] = {}
    for plane in trace_reduce._load(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                out[int(m.group(1))] = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns),
                     _RUN_ID.sub("", e.name)) for e in line.events)
    return out


def breakdown(path: str) -> Dict[str, Any]:
    """The numbers of the docstring; seconds unless a key says otherwise."""
    per_device = trace_reduce.device_events(path)
    every = [ev for evs in per_device.values() for ev in evs]
    if not every:
        raise RuntimeError(f"{path}: no operation ran on the device")
    w0, w1 = min(s for s, _, _ in every), max(e for _, e, _ in every)
    n, ns = len(per_device), 1e-9
    inside = lambda s, e: s >= w0 and e <= w1

    shift = clock_shift_ns(path)
    spans = [sp._replace(start_ns=sp.start_ns - shift, end_ns=sp.end_ns - shift)
             for sp in program_trace.spans(path)]
    spans = [sp for sp in spans if inside(sp.start_ns, sp.end_ns)]

    kernels: Dict[str, float] = defaultdict(float)
    other: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    busy = 0.0
    cuts = sorted({t for sp in spans for t in (sp.start_ns, sp.end_ns)})
    for events in per_device.values():
        for name, own in trace_reduce.self_times(events):
            if trace_reduce._is(name, trace_reduce.MOSAIC):
                kernel = trace_reduce.kernel_name(name)
                (other if kernel is None else kernels)[
                    kernel or trace_reduce.short_name(name)] += own / n
        merged = trace_reduce.union((s, e) for s, e, _ in events)
        busy += trace_reduce._length(merged) / n
        edges = [(w0, w0)] + merged + [(w1, w1)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            pieces = [e0] + [t for t in cuts if e0 < t < s1] + [s1]
            for a, b in zip(pieces, pieces[1:]):
                over = program_trace.innermost((a + b) / 2.0, spans)
                gaps[over.name if over else NO_SPAN] += (b - a) / n

    runs: Dict[str, List[float]] = defaultdict(list)
    for events in module_runs(path).values():
        for s, e, name in events:
            if inside(s, e):
                runs[name].append((e - s) * ns)

    ticks = program_trace.named(spans, program_trace.TICK)
    tick_ns = sum(t.end_ns - t.start_ns for t in ticks)
    child_ns: Dict[str, float] = defaultdict(float)
    for t in ticks:
        for c in program_trace.children(t, spans):
            child_ns[c.name] += c.end_ns - c.start_ns
    by_time = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {
        "window_s": (w1 - w0) * ns, "busy_s": busy * ns, "clock_shift_us": shift * 1e-3,
        "kernels": by_time({k: v * ns for k, v in kernels.items()}),
        "mosaic_other": by_time({k: v * ns for k, v in other.items()}),
        "mosaic_s": (sum(kernels.values()) + sum(other.values())) * ns,
        "modules": {k: {"count": len(v), "median_ms": stats.median(v) * 1e3}
                    for k, v in sorted(runs.items())},
        "gaps": by_time({k: v * ns for k, v in gaps.items()}),
        "spans": {name: {"count": len(picked),
                         "median_ms": program_trace.median_ms(picked),
                         "total_ms": sum(s.ms for s in picked)}
                  for name in sorted({s.name for s in spans})
                  for picked in [program_trace.named(spans, name)]},
        "tick_cover": None if not tick_ns else {
            "ticks": len(ticks), "share": sum(child_ns.values()) / tick_ns,
            "children": by_time({k: v / tick_ns for k, v in child_ns.items()})},
    }


def render(b: Dict[str, Any]) -> str:
    busy, idle = b["busy_s"], b["window_s"] - b["busy_s"]
    out = [f"window {b['window_s']:.4f} s, busy {busy:.4f} s, idle {idle * 1e3:.2f} ms "
           f"({100 * idle / b['window_s']:.2f} %); host spans moved by {b['clock_shift_us']:.1f} us",
           f"trace_reduce's Mosaic bucket: {b['mosaic_s'] * 1e3:.2f} ms, "
           f"{100 * b['mosaic_s'] / busy:.2f} % of busy; the kernels in it:"]
    out += [f"  {k:<34}{v * 1e3:>11.3f} ms {100 * v / busy:>7.2f} % of busy"
            for k, v in b["kernels"].items()]
    rest = sum(b["mosaic_other"].values())
    out.append(f"  and {len(b['mosaic_other'])} operations that are no Pallas kernel: "
               f"{rest * 1e3:.3f} ms {100 * rest / busy:.2f} % of busy")
    out.append("programs on XLA Modules (whole inside the window):")
    out += [f"  {k:<34}{v['count']:>5} runs, median {v['median_ms']:.3f} ms"
            for k, v in b["modules"].items()]
    out.append("idle gaps, cut at the rlt.* spans' edges, by the innermost span:")
    out += [f"  {k:<34}{v * 1e3:>11.3f} ms {100 * v / max(idle, 1e-12):>7.2f} % of idle"
            for k, v in b["gaps"].items()]
    out.append("rlt.* spans:")
    out += [f"  {k:<34}{v['count']:>5} x median {v['median_ms']:.3f} ms, total {v['total_ms']:.2f} ms"
            for k, v in b["spans"].items()]
    cover = b["tick_cover"]
    if cover:
        out.append(f"children cover {100 * cover['share']:.2f} % of {cover['ticks']} rlt.serve.tick spans:")
        out += [f"  {k:<34}{100 * v:>7.2f} %" for k, v in cover["children"].items()]
    return "\n".join(out)


def main(argv: List[str]) -> int:
    paths = [a for a in argv if not a.startswith("--")]
    if len(paths) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    b = breakdown(paths[0])
    print(json.dumps(b) if "--json" in argv else render(b))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
