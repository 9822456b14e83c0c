"""The program's own spans, read out of a profiler trace.

The program marks the phases of an engine tick and of a train step with
``jax.profiler.TraceAnnotation``s whose names start with ``rlt.``
(``ray_lightning_tpu/observability/trace.py::phase_span``). In a trace they
are host events, on the plane of the host's threads, each with its length and
its arguments among its stats: a CPU trace has them as a chip's has, so a
reader that needs only their own durations reads either.

Everything here is arithmetic on those events; a program that opens no such
span (an older commit) leaves nothing to read, and every function returns an
empty list or ``None`` for it and never raises.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence

from benchmarks import stats, trace_reduce
from benchmarks.trace_reduce import kernel_of  # noqa: F401  (the readers' and the tool's name for it)

PREFIX = "rlt."
TICK = "rlt.serve.tick"
SCHEDULE = "rlt.serve.schedule"
SAMPLE_SYNC = "rlt.serve.sample_sync"
TRAIN_STEP = "rlt.train.step"
INPUT_WAIT = "rlt.train.input_wait"


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    thread: str  # the trace line the event sits on: one per host thread
    args: Dict[str, Any]

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


def spans(path: Optional[str]) -> List[Span]:
    """Every ``rlt.*`` host event of the trace at ``path``, by start."""
    if not path or not os.path.exists(path):
        return []
    st = os.stat(path)
    return list(_spans(path, st.st_mtime_ns, st.st_size))


@functools.lru_cache(maxsize=2)
def _spans(path: str, _mtime: int, _size: int) -> Sequence[Span]:
    out: List[Span] = []
    for plane in trace_reduce._load(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(
                        e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
                        f"{plane.name}/{line.name}", dict(e.stats)))
    out.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return tuple(out)


def named(all_spans: Iterable[Span], name: str) -> List[Span]:
    return [s for s in all_spans if s.name == name]


def median_ms(picked: Sequence[Span]) -> Optional[float]:
    return stats.median([s.ms for s in picked]) if picked else None


def decode_only_syncs(all_spans: Iterable[Span]) -> List[Span]:
    """The ``rlt.serve.sample_sync`` spans of ticks that ran no prefill: the
    span carries the tick's count of prefills as its argument."""
    return [s for s in named(all_spans, SAMPLE_SYNC)
            if int(s.args.get("prefills", 0)) == 0]


def per_step_ms(all_spans: Iterable[Span], name: str, step: str = TRAIN_STEP) -> Optional[float]:
    """Total length of the spans called ``name`` over the count of ``step``
    spans: a phase's cost a step."""
    all_spans = list(all_spans)
    steps = named(all_spans, step)
    if not steps:
        return None
    return sum(s.ms for s in named(all_spans, name)) / len(steps)


def children(parent: Span, all_spans: Iterable[Span]) -> List[Span]:
    """Spans of the parent's thread lying whole inside it, the parent's
    direct children only (a grandchild is inside a child)."""
    inside = [s for s in all_spans
              if s is not parent and s.thread == parent.thread
              and s.start_ns >= parent.start_ns and s.end_ns <= parent.end_ns]
    inside.sort(key=lambda s: (s.start_ns, -s.end_ns))
    out: List[Span] = []
    for s in inside:
        if out and s.end_ns <= out[-1].end_ns:
            continue
        out.append(s)
    return out


def cover_share(all_spans: Iterable[Span], parent: str = TICK) -> Optional[float]:
    """Share of the ``parent`` spans' time that their children cover."""
    all_spans = list(all_spans)
    parents = named(all_spans, parent)
    total = sum(p.end_ns - p.start_ns for p in parents)
    if not total:
        return None
    covered = sum(c.end_ns - c.start_ns for p in parents for c in children(p, all_spans))
    return covered / total


def innermost(at_ns: float, all_spans: Iterable[Span]) -> Optional[Span]:
    """The shortest span that covers the instant, or None."""
    best: Optional[Span] = None
    for s in all_spans:
        if s.start_ns <= at_ns < s.end_ns and (
                best is None or s.end_ns - s.start_ns < best.end_ns - best.start_ns):
            best = s
    return best


# ---- what the readers under layer_metrics/ share ------------------------- #
def span_median_ms(facts: Dict[str, Any], name: str) -> Optional[float]:
    return median_ms(named(spans(facts.get("trace_path")), name))


def decode_sync_ms(facts: Dict[str, Any]) -> Optional[float]:
    return median_ms(decode_only_syncs(spans(facts.get("trace_path"))))


def kernel_share_percent(facts: Dict[str, Any], kernel: str) -> Optional[float]:
    """Own time of the Mosaic custom calls named ``kernel`` over all of the
    trace's device events (the reduced trace's ``kernels``), over the
    device's busy time. A trace in which no such kernel ran leaves nothing
    to read: a kernel renamed or gone shows as a missing metric, not as 0."""
    trace = facts.get("trace")
    if not trace or not trace.get("busy_s") or kernel not in trace.get("kernels", {}):
        return None
    return 100.0 * trace["kernels"][kernel] / trace["busy_s"]


def engine_host_ms_per_tick(facts: Dict[str, Any]) -> Optional[float]:
    """The engine's own host time a tick over the whole window: the wall time
    of ``step()`` less its wait for the sampled tokens."""
    c = facts.get("counters", {})
    if not c.get("ticks") or "tick_s" not in c or "sync_wait_s" not in c:
        return None
    return 1e3 * (c["tick_s"] - c["sync_wait_s"]) / c["ticks"]


def loop_wait_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Share of the loop thread's wall time in which it had no work."""
    c = facts.get("counters", {})
    if "loop_wait_s" not in c or not c.get("tick_s"):
        return None
    return 100.0 * c["loop_wait_s"] / (c["tick_s"] + c["loop_wait_s"])
