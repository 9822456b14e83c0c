"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction is kept with the benchmark so that every PR computes the same
number in the same way. It reads the trace with nothing but JAX
(``jax.profiler.ProfileData``):

- device planes are the planes named ``/device:TPU:<n>``; their line
  ``XLA Ops`` holds one event per operation the chip ran, nested where an
  operation (a ``while``, a fusion's parent) contains others;
- busy time is the union of those events' intervals, per device, averaged
  over the devices; the window runs from the first operation's start to the
  last one's end over all devices;
- an operation's own time is its duration less that of the events nested
  directly inside it, so a loop does not count its body twice;
- a Mosaic (Pallas) kernel shows as a custom call whose HLO text holds
  ``MOSAIC_TARGET``, named after the ``name=`` of its ``pl.pallas_call``:
  ``kernels`` holds every such kernel's own time by that name, over all
  events (``device_ops`` is the ten largest operations only); ``mosaic_s``
  is the older single bucket of everything that mentions a custom call;
- host and device events sit about a millisecond apart on the trace's clock.
  ``Tracer.start`` therefore runs a tiny named program a few times inside
  ``bench.sync_probe`` spans: each run's device event has to end before its
  span does, and the smallest of those margins is taken off the host spans;
- an idle gap is a maximal interval inside the window in which no operation
  ran on the device; it is named after the innermost of the benchmark's own
  host spans (``bench.*`` ``TraceAnnotation``s, same clock) that covers its
  middle.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MOSAIC = ("custom-call", "custom_call", "mosaic", "pallas")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'  # in a Pallas kernel's HLO text
SPAN_PREFIX = "bench."
SYNC_SPAN = "bench.sync_probe"
SYNC_MODULE = "jit_bench_sync_probe"
MODULES_LINE = "XLA Modules"
WAIT_SPANS = ("bench.wait_request", "bench.drain", SYNC_SPAN)  # named only where nothing else covers
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*?[\s)](?P<op>[a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")

_active = {"on": False}


def span(name: str):
    """A host span on the profiler's clock while a trace is being taken,
    nothing otherwise. Names start with ``bench.``."""
    if not _active["on"]:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """``with Tracer(dir) as t: ...`` then ``t.path`` is the ``.xplane.pb``.
    ``start``/``stop`` do the same from callbacks."""

    def __init__(self, out_dir: str):
        import jax
        import jax.numpy as jnp

        self.out_dir = out_dir
        self.path: Optional[str] = None

        def bench_sync_probe(x):
            return x + 1

        # compiled here, in set-up, so that the window compiles nothing
        self._probe = jax.jit(bench_sync_probe)
        self._x = jnp.zeros((8, 128), jnp.float32)
        jax.block_until_ready(self._probe(self._x))

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        _active["on"] = True
        for _ in range(5):
            with span(SYNC_SPAN):
                jax.block_until_ready(self._probe(self._x))

    def stop(self) -> None:
        import jax

        _active["on"] = False
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler left no .xplane.pb under {self.out_dir}")
        self.path = found[-1]

    def __enter__(self) -> "Tracer":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def describe(path: str, events_per_line: int = 6) -> None:
    """Print what a trace holds, for reading one by hand."""
    for plane in _load(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:events_per_line]:
                print(f"    {ev.name!r} start={ev.start_ns:.0f} dur={ev.duration_ns:.0f}")


Interval = Tuple[float, float, str]  # start_ns, end_ns, name


def device_events(path: str) -> Dict[int, List[Interval]]:
    out: Dict[int, List[Interval]] = {}
    for plane in _load(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[int(m.group(1))] = sorted(
                    (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                    for e in line.events)
    return out


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...), kind=kOutput, ...`` ->
    ``fusion %fusion.3 kOutput``: the trace names an operation by its whole
    HLO text."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    kind = _KIND.search(hlo)
    return f"{m.group('op')} %{m.group('name')}" + (f" {kind.group(1)}" if kind else "")


_INSTRUCTION = re.compile(r"^custom-call %(?P<name>.+?)(\.\d+)?$")
_TRANSFORMS = re.compile(r"^((jvp|transpose|vmap|remat|checkpoint)_)+")


def kernel_of(short: str) -> Optional[str]:
    """The kernel behind ``short_name``'s ``custom-call
    %flash_fwd.3``: ``flash_fwd``; None for any other operation. The compiler
    names a Mosaic custom call after the innermost scope of its ``op_name``,
    which is the ``name=`` of the ``pl.pallas_call`` wrapped in the
    transformations it was traced under: ``jax.grad`` with no
    ``jax.checkpoint`` round it gives ``%jvp_flash_fwd_.1`` and
    ``%transpose_jvp_flash_bwd_dq__.1``. Those wrappers are taken off."""
    m = _INSTRUCTION.match(short)
    if not m:
        return None
    name = m.group("name")
    bare = _TRANSFORMS.sub("", name)
    return bare.rstrip("_") if bare != name else name


def kernel_name(hlo: str) -> Optional[str]:
    """The program's name for the Pallas kernel behind an event's HLO text
    (``flash_fwd``, ``paged_decode_attention``); None for any other
    operation, the compiler's own custom calls among them."""
    return kernel_of(short_name(hlo)) if MOSAIC_TARGET in hlo else None


def host_spans(path: str) -> List[Interval]:
    """The benchmark's host spans, moved onto the device events' clock."""
    out: List[Interval] = []
    probes: List[float] = []  # device ends of the sync probe's runs
    for plane in _load(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    probes += [float(e.start_ns + e.duration_ns) for e in line.events
                               if e.name.startswith(SYNC_MODULE)]
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name))
    out.sort()
    syncs = [sp for sp in out if sp[2] == SYNC_SPAN]
    probes.sort()
    if syncs and len(syncs) == len(probes):
        # a probe's device event ends before its span does: the smallest
        # margin is how far the host clock runs ahead (or behind)
        shift = min(sp[1] - dev_end for sp, dev_end in zip(syncs, probes))
        out = [(s - shift, e - shift, n) for s, e, n in out]
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events: Sequence[Interval]) -> List[Tuple[str, float]]:
    """(name, own nanoseconds) per event: duration less the events nested
    directly inside it. ``events`` sorted by start."""
    out: List[List[Any]] = []
    stack: List[int] = []  # indices into out, open events
    ends: List[float] = []
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and s >= ends[-1]:
            stack.pop()
            ends.pop()
        if stack:
            out[stack[-1]][1] -= (e - s)
        out.append([name, e - s])
        stack.append(len(out) - 1)
        ends.append(e)
    return [(n, max(t, 0.0)) for n, t in out]


def _is(name: str, needles: Sequence[str]) -> bool:
    low = name.lower()
    return any(n in low for n in needles)


def _label(mid: float, spans: Sequence[Interval]) -> str:
    best: Optional[Interval] = None
    for s, e, n in spans:
        if not s <= mid < e:
            continue
        rank = (n in WAIT_SPANS, e - s)  # a waiting span only where nothing else
        if best is None or rank < (best[2] in WAIT_SPANS, best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "no_bench_span"


def reduce(path: str, top: int = 10) -> Dict[str, Any]:
    """The trace's numbers; seconds unless the key says otherwise."""
    per_device = device_events(path)
    if not per_device:
        raise RuntimeError(f"{path}: no '{OPS_LINE}' line on any /device:TPU plane")
    every = [ev for evs in per_device.values() for ev in evs]
    if not every:
        raise RuntimeError(f"{path}: no operation ran on the device")
    w0 = min(s for s, _, _ in every)
    w1 = max(e for _, e, _ in every)
    spans = host_spans(path)
    busy, mosaic = [], []
    ops: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    longest_gap = 0.0
    for dev, events in sorted(per_device.items()):
        merged = union((s, e) for s, e, _ in events)
        busy.append(_length(merged))
        own = self_times(events)
        for name, t in own:
            ops[short_name(name)] += t / len(per_device)
            kernel = kernel_name(name)
            if kernel is not None:
                kernels[kernel] += t / len(per_device)
        mosaic.append(sum(t for n, t in own if _is(n, MOSAIC)))
        edges = [(w0, w0)] + merged + [(w1, w1)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps[_label((e0 + s1) / 2.0, spans)] += (s1 - e0) / len(per_device)
                longest_gap = max(longest_gap, s1 - e0)
    n = len(per_device)
    ns = 1e-9
    busy_s = sum(busy) / n * ns
    rank = lambda d: [[k, v * ns] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "devices": n,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / ((w1 - w0) * ns),
        "mosaic_s": sum(mosaic) / n * ns,
        "kernels": {k: v * ns for k, v in kernels.items()},
        "longest_gap_s": longest_gap * ns,
        "device_ops": rank(ops),
        "idle_gaps": rank(gaps),
        "host_spans": len(spans),
    }
