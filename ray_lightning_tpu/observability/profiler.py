"""Fleet-wide performance profiler: HLO cost accounting, coordinated
capture, and step-time attribution.

Three layers, all riding the existing telemetry plumbing:

1. **HLO cost accounting** — ``analyze_jitted`` lowers + AOT-compiles a
   jitted program, reads XLA's ``cost_analysis()`` (analytic FLOPs and
   bytes accessed) and walks the optimized HLO text for collective ops
   (all-reduce / all-gather / reduce-scatter / ...) to get per-op counts
   and byte volumes.  ``roofline`` turns a :class:`CostReport` plus a
   measured step time into an MFU figure and a compute-vs-bandwidth
   verdict — the verdict compares arithmetic intensity against machine
   balance, so it does not trust ``RLT_PEAK_TFLOPS`` alone.

2. **Coordinated fleet capture** — :class:`FleetProfiler` lives in each
   worker's hot loop.  The driver (``cli profile --steps N``) writes an
   atomic ``profile_cmd.json`` into the shared telemetry dir naming an
   absolute global step; every rank polls the file (one throttled
   ``os.stat`` per interval) and starts ``jax.profiler`` on that same
   step, so the per-rank traces line up.  ``RLT_PROFILE_AT_STEP`` arms
   the same window from the environment for launch-time capture.

3. **Step-time attribution** — during a capture window the profiler
   blocks on the step output (honest device time), splits the mean step
   into compute / collective-wait / host-input / device-transfer
   estimates from the cost report and bandwidth tables, and ships
   ``capture`` / ``attribution`` / ``cost`` records back to the
   :class:`~.aggregator.DriverAggregator` via the heartbeat payload
   (``"p"`` key).  ``format_profile_report`` renders the folded summary
   for ``cli profile --report``.

All jax imports are lazy: importing this module must stay cheap and
safe in processes that never profile.
"""
from __future__ import annotations

import json
import os
import re
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils import fsio
from . import metrics as _metrics
from . import trace as _trace

# ------------------------------------------------------------------ #
# knobs / constants
# ------------------------------------------------------------------ #
PROFILE_CMD_FILE = "profile_cmd.json"
PROFILE_DIR = "profile"
PROFILE_AT_STEP_ENV = "RLT_PROFILE_AT_STEP"
PROFILE_STEPS_ENV = "RLT_PROFILE_STEPS"
COST_ANALYSIS_ENV = "RLT_COST_ANALYSIS"
PEAK_GBPS_ENV = "RLT_PEAK_GBPS"
DEFAULT_PROFILE_STEPS = 3
DEFAULT_LEAD_STEPS = 20
CMD_POLL_INTERVAL_S = 1.0

STEP_FLOPS_METRIC = "rlt_step_flops"
STEP_BYTES_METRIC = "rlt_step_bytes"
COLLECTIVE_BYTES_METRIC = "rlt_collective_bytes_total"
COST_MFU_METRIC = "rlt_cost_mfu"

_metrics.set_help(
    STEP_FLOPS_METRIC,
    "Analytic FLOPs per execution of the compiled program (XLA "
    "cost_analysis), labeled by program",
)
_metrics.set_help(
    STEP_BYTES_METRIC,
    "Analytic bytes accessed per execution of the compiled program "
    "(XLA cost_analysis), labeled by program",
)
_metrics.set_help(
    COLLECTIVE_BYTES_METRIC,
    "Bytes moved by collective ops per execution of the compiled "
    "program, labeled by op and program",
)
_metrics.set_help(
    COST_MFU_METRIC,
    "Model FLOPs utilization derived from cost_analysis FLOPs over "
    "measured step time, labeled by program",
)

# peak HBM bandwidth per chip, GB/s (vendor specs; keyed like the
# peak-TFLOPs table in callbacks/throughput.py, and like it an unknown
# chip is an error)
_PEAK_HBM_GBPS = {
    "v4": 1228.0,
    "v5e": 819.0,
    "v5 lite": 819.0,
    "v5p": 2765.0,
    "v6e": 1640.0,
}


def detect_peak_bandwidth_gbps() -> Optional[float]:
    """Peak HBM bandwidth (GB/s) of the local device kind, or None on the
    CPU ("not measured": callers leave roofline shares out).
    ``RLT_PEAK_GBPS`` overrides; an accelerator missing from the table
    raises."""
    env = os.environ.get(PEAK_GBPS_ENV)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = getattr(dev, "device_kind", "").lower()
    for key, gbps in _PEAK_HBM_GBPS.items():
        if key in kind:
            return gbps
    raise ValueError(
        f"unknown accelerator {kind!r}: no peak HBM bandwidth on record; "
        f"set {PEAK_GBPS_ENV} to the chip's real peak"
    )


def _detect_peaks() -> Optional[Tuple[float, float]]:
    """(peak FLOP/s, peak bytes/s) of the local chip, or None on the CPU."""
    from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

    tflops, gbps = detect_peak_tflops(), detect_peak_bandwidth_gbps()
    if tflops is None or gbps is None:
        return None
    return tflops * 1e12, gbps * 1e9


def cost_analysis_enabled() -> bool:
    """Escape hatch: ``RLT_COST_ANALYSIS=0`` skips the extra AOT compile."""
    return os.environ.get(COST_ANALYSIS_ENV, "1") != "0"


# ------------------------------------------------------------------ #
# HLO cost accounting
# ------------------------------------------------------------------ #
_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
# `%name = SHAPE all-reduce(...)` where SHAPE is a single array shape or
# a tuple (async `-start` forms). `-done` ops deliberately fail to match
# (the char after the op name is `-`, not `(`) so volumes aren't doubled.
_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _shape_bytes(shape_expr: str) -> int:
    """Total payload bytes of one HLO result shape (array or tuple)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_expr):
        if dtype not in _DTYPE_BYTES and not dtype.startswith(("f", "s", "u", "b", "p", "c")):
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def collectives_from_hlo(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Walk optimized HLO text for collective ops.

    Returns ``{op: {"count": n, "bytes": payload_bytes}}`` summed over
    all occurrences; async ``-start`` forms count once, ``-done`` forms
    are skipped."""
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape, op = m.group(1), m.group(2)
        d = out.setdefault(op, {"count": 0, "bytes": 0})
        d["count"] += 1
        d["bytes"] += _shape_bytes(shape)
    return out


@dataclass
class CostReport:
    """Analytic cost of one compiled program execution."""

    program: str
    flops: float
    bytes_accessed: float
    collectives: Dict[str, Dict[str, float]] = field(default_factory=dict)
    # from the executable's memory analysis: the bytes of donated arguments
    # it updates in place (0 for a program that donates nothing), and the
    # temporaries it allocates beside its arguments and outputs
    alias_bytes: float = 0.0
    temp_bytes: float = 0.0

    @property
    def collective_bytes(self) -> float:
        return float(sum(d.get("bytes", 0) for d in self.collectives.values()))

    def to_dict(self) -> Dict[str, Any]:
        return {
            "program": self.program,
            "step_flops": self.flops,
            "step_bytes": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "alias_bytes": self.alias_bytes,
            "temp_bytes": self.temp_bytes,
            "collectives": {
                op: dict(d) for op, d in sorted(self.collectives.items())
            },
        }


def _flatten_cost_analysis(ca: Any) -> Dict[str, float]:
    """cost_analysis() returns a dict on current jax; older jaxlibs
    returned a list with one dict per computation — merge either shape."""
    if ca is None:
        return {}
    entries = ca if isinstance(ca, (list, tuple)) else [ca]
    merged: Dict[str, float] = {}
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        for k, v in entry.items():
            try:
                merged[k] = merged.get(k, 0.0) + float(v)
            except (TypeError, ValueError):
                continue
    return merged


def analyze_compiled(compiled: Any, program: str = "program") -> CostReport:
    """Build a :class:`CostReport` from an already-compiled executable."""
    try:
        flat = _flatten_cost_analysis(compiled.cost_analysis())
    except Exception:
        flat = {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    return CostReport(
        program=program,
        flops=float(flat.get("flops", 0.0)),
        bytes_accessed=float(flat.get("bytes accessed", 0.0)),
        collectives=collectives_from_hlo(hlo),
        alias_bytes=float(getattr(mem, "alias_size_in_bytes", 0) or 0),
        temp_bytes=float(getattr(mem, "temp_size_in_bytes", 0) or 0),
    )


def analyze_jitted(fn: Any, *args: Any, program: str = "program") -> Optional[CostReport]:
    """Lower + AOT-compile a jitted callable and account its cost.

    A :class:`~ray_lightning_tpu.runtime.compile_cache.CachedProgram` (or
    anything exposing ``cached_compiled``) hands back the executable it
    already resolved, so analysis is free on a warm cache. For a raw jitted
    fn the AOT path does not share the jit dispatch cache — that is a
    second compile of the program, so route it through the shared cache;
    call it once, off the hot path, and gate behind telemetry /
    ``RLT_COST_ANALYSIS``. Lowering only reads shapes/dtypes, so passing
    live (even donated-and-reassigned) arrays is safe. Returns ``None`` on
    any failure."""
    try:
        if hasattr(fn, "cached_compiled"):
            compiled = fn.cached_compiled(*args)
        else:
            from ray_lightning_tpu.runtime import compile_cache as _cc

            if _cc.enabled():
                compiled = _cc.get_cache().get_or_compile(
                    fn, *args, program=program
                )
            else:
                compiled = fn.lower(*args).compile()
    except Exception:
        return None
    return analyze_compiled(compiled, program=program)


def roofline(
    report: CostReport,
    step_time_s: Optional[float] = None,
    peak_tflops: Optional[float] = None,
    peak_gbps: Optional[float] = None,
) -> Dict[str, Any]:
    """Roofline placement for a cost report.

    The analytic verdict compares arithmetic intensity (flops/byte)
    against machine balance (peak flops per peak byte/s); with a
    measured ``step_time_s`` it also reports MFU, achieved bandwidth,
    and which ceiling better explains the measured time."""
    if peak_tflops is None:
        from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

        peak_tflops = detect_peak_tflops()
    if peak_gbps is None:
        peak_gbps = detect_peak_bandwidth_gbps()
    intensity = (
        report.flops / report.bytes_accessed if report.bytes_accessed else float("inf")
    )
    if peak_tflops is None or peak_gbps is None:
        # no chip, no peaks: the program's own counts, no machine verdict
        return {
            "arithmetic_intensity": round(intensity, 4),
            "verdict": "not measured",
        }
    peak_flops_s = peak_tflops * 1e12
    peak_bytes_s = peak_gbps * 1e9
    balance = peak_flops_s / peak_bytes_s
    out: Dict[str, Any] = {
        "arithmetic_intensity": round(intensity, 4),
        "machine_balance": round(balance, 4),
        "verdict": "compute-bound" if intensity >= balance else "bandwidth-bound",
        "peak_tflops_assumed": peak_tflops,
        "peak_gbps_assumed": peak_gbps,
    }
    if step_time_s and step_time_s > 0:
        achieved_flops_s = report.flops / step_time_s
        achieved_bytes_s = report.bytes_accessed / step_time_s
        mfu = achieved_flops_s / peak_flops_s
        bw_util = achieved_bytes_s / peak_bytes_s
        out["step_time_s"] = round(step_time_s, 6)
        out["mfu"] = round(mfu, 6)
        out["achieved_tflops"] = round(achieved_flops_s / 1e12, 4)
        out["bandwidth_util"] = round(bw_util, 6)
        out["achieved_gbps"] = round(achieved_bytes_s / 1e9, 4)
        # which ceiling the measured run actually leaned on
        out["measured_bound"] = "compute" if mfu >= bw_util else "bandwidth"
    return out


def publish_cost_report(
    reg: Any, report: CostReport, step_time_s: Optional[float] = None,
    peak_tflops: Optional[float] = None,
) -> None:
    """Publish a cost report to a metrics registry (latest-wins)."""
    reg.gauge(STEP_FLOPS_METRIC, program=report.program).set(report.flops)
    reg.gauge(STEP_BYTES_METRIC, program=report.program).set(
        report.bytes_accessed
    )
    for op, d in report.collectives.items():
        c = reg.counter(COLLECTIVE_BYTES_METRIC, op=op, program=report.program)
        c.value = float(d.get("bytes", 0))
    if step_time_s and step_time_s > 0:
        if peak_tflops is None:
            from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

            peak_tflops = detect_peak_tflops()
        if peak_tflops:
            mfu = report.flops / step_time_s / (peak_tflops * 1e12)
            reg.gauge(COST_MFU_METRIC, program=report.program).set(round(mfu, 6))


# ------------------------------------------------------------------ #
# record queue: profiler -> heartbeat payload ("p" key)
# ------------------------------------------------------------------ #
_PENDING: List[dict] = []
_PENDING_CAP = 256


def push_record(rec: dict) -> None:
    """Queue a profile record for the next heartbeat payload."""
    _PENDING.append(rec)
    if len(_PENDING) > _PENDING_CAP:
        del _PENDING[: len(_PENDING) - _PENDING_CAP]


def drain_pending() -> List[dict]:
    out = list(_PENDING)
    _PENDING.clear()
    return out


def reset_pending() -> None:
    _PENDING.clear()


# ------------------------------------------------------------------ #
# driver side: the command file
# ------------------------------------------------------------------ #
_CMD_SEQ = 0


def write_profile_command(
    run_dir: str,
    num_steps: int = DEFAULT_PROFILE_STEPS,
    start_step: Optional[int] = None,
    note: Optional[str] = None,
) -> Dict[str, Any]:
    """Atomically write ``profile_cmd.json`` into the telemetry dir.

    Every rank polls this file and starts a capture window at
    ``start_step`` (absolute global step — all ranks share the step
    sequence, which is what makes the capture coordinated)."""
    global _CMD_SEQ
    os.makedirs(run_dir, exist_ok=True)
    _CMD_SEQ += 1
    cmd: Dict[str, Any] = {
        "id": f"{os.getpid():x}-{int(time.time() * 1000):x}-{_CMD_SEQ}",
        "num_steps": int(num_steps),
        "ts": time.time(),
    }
    if start_step is not None:
        cmd["start_step"] = int(start_step)
    if note:
        cmd["note"] = str(note)
    path = os.path.join(run_dir, PROFILE_CMD_FILE)
    fsio.atomic_write_json(path, cmd)
    return cmd


def read_profile_command(run_dir: str) -> Optional[Dict[str, Any]]:
    try:
        with open(os.path.join(run_dir, PROFILE_CMD_FILE)) as f:
            cmd = json.load(f)
    except (OSError, ValueError):
        return None
    return cmd if isinstance(cmd, dict) else None


# indirection over jax.profiler so tests can monkeypatch the backend
def _start_trace(log_dir: str) -> None:
    import jax

    jax.profiler.start_trace(log_dir)


def _stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def _batch_bytes(batch: Any) -> int:
    """Host->device payload of one batch (sum of leaf nbytes)."""
    try:
        import jax

        return int(
            sum(
                getattr(leaf, "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(batch)
            )
        )
    except Exception:
        return 0


# ------------------------------------------------------------------ #
# worker side: FleetProfiler
# ------------------------------------------------------------------ #
class FleetProfiler:
    """Per-worker coordinated capture + cost accounting + attribution.

    Lives next to the trainer hot loop; the loop pays one attribute
    check per step when no window is armed (``before_step`` short-poll,
    ``after_step`` deque append).  Never raises into training."""

    def __init__(
        self,
        run_dir: str,
        rank: int = 0,
        recorder: Optional[Any] = None,
        poll_interval: float = CMD_POLL_INTERVAL_S,
        environ: Optional[Any] = None,
    ) -> None:
        env = os.environ if environ is None else environ
        self.run_dir = run_dir
        self.rank = int(rank)
        self._recorder = recorder
        self._cmd_path = os.path.join(run_dir, PROFILE_CMD_FILE)
        self._poll_interval = float(poll_interval)
        self._next_poll = 0.0
        self._applied_id: Optional[str] = None
        self._armed: Optional[Dict[str, Any]] = None
        self._window: Optional[Dict[str, Any]] = None
        self._reports: Dict[str, CostReport] = {}
        self._step_times: deque = deque(maxlen=64)
        self._mfu_published = False
        at_step = env.get(PROFILE_AT_STEP_ENV)
        if at_step:
            try:
                self._armed = {
                    "id": "env",
                    "start": int(at_step),
                    "steps": max(
                        1, int(env.get(PROFILE_STEPS_ENV, DEFAULT_PROFILE_STEPS))
                    ),
                }
            except ValueError:
                pass

    # -------------------------------------------------------------- #
    # cost accounting hook (call once, at first dispatch)
    # -------------------------------------------------------------- #
    def analyze(
        self, program: str, fn: Any, args: Sequence[Any]
    ) -> Optional[CostReport]:
        """One-time cost analysis of a jitted program; publishes gauges
        and ships a ``cost`` record.  Never raises."""
        if not cost_analysis_enabled() or program in self._reports:
            return self._reports.get(program)
        try:
            rep = analyze_jitted(fn, *args, program=program)
        except Exception:
            rep = None
        if rep is None:
            return None
        self._reports[program] = rep
        try:
            reg = _metrics.get_registry() if _trace.enabled() else None
            if reg is not None:
                publish_cost_report(reg, rep)
            rec = {
                "kind": "cost",
                "rank": self.rank,
                "ts": time.time(),
            }
            rec.update(rep.to_dict())
            rec["roofline"] = roofline(rep)
            push_record(rec)
        except Exception:
            pass
        return rep

    # -------------------------------------------------------------- #
    # hot-loop hooks
    # -------------------------------------------------------------- #
    def _poll(self, step: int) -> None:
        now = time.monotonic()
        if now < self._next_poll:
            return
        self._next_poll = now + self._poll_interval
        try:
            os.stat(self._cmd_path)
        except OSError:
            return
        cmd = read_profile_command(self.run_dir)
        if cmd is None or cmd.get("id") == self._applied_id:
            return
        self._applied_id = cmd.get("id")
        try:
            steps = max(1, int(cmd.get("num_steps", DEFAULT_PROFILE_STEPS)))
            start = cmd.get("start_step")
            # a command with no start step means "as soon as possible"
            start = int(start) if start is not None else step + 1
        except (TypeError, ValueError):
            return
        self._armed = {"id": self._applied_id, "start": start, "steps": steps}

    def before_step(self, step: int, batch: Any = None) -> None:
        """Poll for commands and open the capture window when the armed
        global step arrives (or has already passed)."""
        self._poll(step)
        armed = self._armed
        if armed is not None and self._window is None and step >= armed["start"]:
            self._begin_window(step, armed, batch)

    def _begin_window(
        self, step: int, armed: Dict[str, Any], batch: Any
    ) -> None:
        self._armed = None
        trace_dir = os.path.join(self.run_dir, PROFILE_DIR, f"rank{self.rank}")
        active = False
        try:
            os.makedirs(trace_dir, exist_ok=True)
            _start_trace(trace_dir)
            active = True
        except Exception:
            pass
        self._window = {
            "id": armed["id"],
            "start_step": armed["start"],
            "actual_start": step,
            "stop_after": step + armed["steps"] - 1,
            "trace_dir": trace_dir,
            "samples": [],
            "batch_bytes": _batch_bytes(batch),
            "starved0": None,
            "active": active,
        }
        if self._recorder is not None:
            self._recorder.add_event(
                "profile/start", step=step, args={"trace_dir": trace_dir}
            )

    def after_step(
        self,
        step: int,
        duration_s: float,
        sync: Any = None,
        starved_s: float = 0.0,
    ) -> None:
        """Record one step.  Inside a window this blocks on ``sync``
        (honest device time), emits attribution spans, and closes the
        window at its last step."""
        w = self._window
        if w is None:
            self._step_times.append(duration_s)
            if not self._mfu_published and len(self._step_times) >= 4:
                self._publish_measured()
            return
        if sync is not None:
            t0 = time.perf_counter()
            try:
                import jax

                jax.block_until_ready(sync)
            except Exception:
                pass
            duration_s += time.perf_counter() - t0
        self._step_times.append(duration_s)
        if w["starved0"] is None:
            w["starved0"] = starved_s - 0.0
        w["samples"].append(duration_s)
        self._emit_attr_spans(step, duration_s)
        if step >= w["stop_after"]:
            self._end_window(starved_s)

    def _train_report(self):
        """The training-step cost report under whichever program name the
        trainer compiled it as ("train_step", or "zero_train_step" for the
        explicit-ZeRO path)."""
        rep = self._reports.get("train_step")
        if rep is not None:
            return rep
        for program, rep in self._reports.items():
            if program.endswith("train_step"):
                return rep
        return None

    def _emit_attr_spans(self, step: int, duration_s: float) -> None:
        """Per-step breakdown sub-spans on an "attribution" track."""
        rec = self._recorder
        rep = self._train_report()
        peaks = _detect_peaks()
        if rec is None or rep is None or duration_s <= 0 or peaks is None:
            return
        peak_flops_s, peak_bytes_s = peaks
        compute_s = min(rep.flops / peak_flops_s, duration_s)
        collective_s = min(
            rep.collective_bytes / peak_bytes_s, duration_s - compute_s
        )
        wall = time.time() - duration_s
        rec.add_span(
            "attr/compute", wall, compute_s, step=step,
            args={_trace.TRACK_ARG: "attribution"},
        )
        if collective_s > 0:
            rec.add_span(
                "attr/collective", wall + compute_s, collective_s,
                step=step, args={_trace.TRACK_ARG: "attribution"},
            )
        other = duration_s - compute_s - collective_s
        if other > 0:
            rec.add_span(
                "attr/other", wall + compute_s + collective_s, other,
                step=step, args={_trace.TRACK_ARG: "attribution"},
            )

    def attribution(
        self,
        samples: Sequence[float],
        starved_delta_s: float,
        batch_bytes: int,
    ) -> Dict[str, Any]:
        """Split the mean captured step into attributed components."""
        n = max(1, len(samples))
        mean = sum(samples) / n
        out: Dict[str, Any] = {"steps": len(samples), "step_time_s": round(mean, 6)}
        peaks = _detect_peaks()
        if peaks is None:
            # no chip: the measured step time stands alone — a split
            # against assumed peaks would be a device metric made up
            return out
        peak_flops_s, peak_bytes_s = peaks
        rep = self._train_report()
        compute_s = rep.flops / peak_flops_s if rep else 0.0
        collective_s = rep.collective_bytes / peak_bytes_s if rep else 0.0
        transfer_s = batch_bytes / peak_bytes_s
        host_s = max(0.0, starved_delta_s) / n
        attributed = compute_s + collective_s + transfer_s + host_s
        out.update(
            compute_s=round(compute_s, 6),
            collective_s=round(collective_s, 6),
            device_transfer_s=round(transfer_s, 6),
            host_input_s=round(host_s, 6),
            unattributed_s=round(max(0.0, mean - attributed), 6),
        )
        if rep is not None and rep.collectives:
            # per-op wait attribution: under explicit ZeRO the interesting
            # movement is all-gather seconds SHRINKING when the int8 gather
            # is on, not just total collective time shuffling between ops
            out["collective_breakdown"] = {
                op: round(info.get("bytes", 0) / peak_bytes_s, 6)
                for op, info in sorted(rep.collectives.items())
            }
            out["program"] = rep.program
        return out

    def _publish_measured(self) -> None:
        """Re-emit cost records with measured MFU once step times exist."""
        self._mfu_published = True
        if not self._reports or not self._step_times:
            return
        times = sorted(self._step_times)
        median = times[len(times) // 2]
        try:
            reg = _metrics.get_registry() if _trace.enabled() else None
            for program, rep in self._reports.items():
                if reg is not None:
                    publish_cost_report(reg, rep, step_time_s=median)
                rec = {"kind": "cost", "rank": self.rank, "ts": time.time()}
                rec.update(rep.to_dict())
                rec["roofline"] = roofline(rep, step_time_s=median)
                push_record(rec)
        except Exception:
            pass

    def _end_window(self, starved_s: float) -> None:
        w = self._window
        if w is None:
            return
        self._window = None
        if w["active"]:
            try:
                _stop_trace()
            except Exception:
                pass
        samples = w["samples"]
        now = time.time()
        push_record(
            {
                "kind": "capture",
                "rank": self.rank,
                "window": w["id"],
                "start_step": w["start_step"],
                "actual_start": w["actual_start"],
                "num_steps": len(samples),
                "trace_dir": w["trace_dir"],
                "ts": now,
            }
        )
        starved_delta = (
            starved_s - w["starved0"] if w["starved0"] is not None else 0.0
        )
        attr = {
            "kind": "attribution",
            "rank": self.rank,
            "window": w["id"],
            "ts": now,
        }
        attr.update(self.attribution(samples, starved_delta, w["batch_bytes"]))
        push_record(attr)
        self._mfu_published = False
        self._publish_measured()
        if self._recorder is not None:
            self._recorder.add_event(
                "profile/stop", step=w["actual_start"] + len(samples) - 1
            )

    def close(self) -> None:
        """Stop an in-flight window (fit ending / exception path)."""
        if self._window is not None:
            try:
                self._end_window(0.0)
            except Exception:
                self._window = None


# ------------------------------------------------------------------ #
# report rendering (cli profile --report)
# ------------------------------------------------------------------ #
def _fmt_num(v: float) -> str:
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def format_profile_report(summary: Optional[Dict[str, Any]]) -> str:
    """Render the ``profile`` section of summary.json as a table set."""
    prof = (summary or {}).get("profile")
    if not prof:
        return (
            "no profile data in summary.json — run with telemetry enabled "
            "and arm a window (cli profile --steps N, or RLT_PROFILE_AT_STEP)"
        )
    lines: List[str] = []
    cost = prof.get("cost") or {}
    if cost:
        lines.append("cost accounting (per program execution):")
        hdr = f"  {'program':<16} {'flops':>9} {'bytes':>9} {'coll.bytes':>10} {'mfu':>8}  verdict"
        lines.append(hdr)
        for program in sorted(cost):
            rec = cost[program]
            rl = rec.get("roofline") or {}
            mfu = rl.get("mfu")
            lines.append(
                f"  {program:<16} {_fmt_num(rec.get('step_flops', 0)):>9} "
                f"{_fmt_num(rec.get('step_bytes', 0)):>9} "
                f"{_fmt_num(rec.get('collective_bytes', 0)):>10} "
                f"{(f'{mfu:.4f}' if mfu is not None else '-'):>8}  "
                f"{rl.get('verdict', '-')}"
            )
    captures = prof.get("captures") or []
    if captures:
        lines.append("")
        lines.append("captures:")
        lines.append(f"  {'rank':>4} {'start':>6} {'actual':>6} {'steps':>5}  trace_dir")
        for rec in captures:
            lines.append(
                f"  {rec.get('rank', '?'):>4} {rec.get('start_step', '?'):>6} "
                f"{rec.get('actual_start', '?'):>6} {rec.get('num_steps', '?'):>5}  "
                f"{rec.get('trace_dir', '')}"
            )
    attr = prof.get("attribution") or {}
    if attr:
        lines.append("")
        lines.append("step-time attribution (mean over captured steps):")
        lines.append(
            f"  {'rank':>4} {'step_ms':>8} {'compute':>8} {'collect':>8} "
            f"{'h2d':>8} {'input':>8} {'other':>8}"
        )

        def pct(rec: Dict[str, Any], key: str) -> str:
            total = rec.get("step_time_s") or 0
            if not total:
                return "-"
            return f"{100.0 * rec.get(key, 0) / total:.1f}%"

        for rank in sorted(attr, key=str):
            rec = attr[rank]
            lines.append(
                f"  {rank:>4} {1000.0 * rec.get('step_time_s', 0):>8.2f} "
                f"{pct(rec, 'compute_s'):>8} {pct(rec, 'collective_s'):>8} "
                f"{pct(rec, 'device_transfer_s'):>8} "
                f"{pct(rec, 'host_input_s'):>8} {pct(rec, 'unattributed_s'):>8}"
            )
    return "\n".join(lines) if lines else "profile section is empty"
