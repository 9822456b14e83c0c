#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for, when anything compiled inside the measured window,
or when the checkout lacks the program. Otherwise the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, traced, ``breakdown``. Every
number compared for ``correct`` is printed beside its limit before it.

Nothing here names a cell, a model or a metric: the cell's files are found
by the names in ``BENCHMARK.json`` (``benchmarks/loader.py``), the model's
family by the name in the configuration file, the driver by the name in the
cell's own settings, each per-layer metric by its reader.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python gets to it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_CHIP = 3


class Context:
    """What a driver needs from the harness: the clock's origin, a scratch
    directory inside the checkout, the count of compilations inside the
    window and the device's memory reading."""

    def __init__(self, root: str, t0: float):
        self.root, self.t0 = root, t0
        self.scratch = os.path.join(root, ".bench_scratch")
        os.makedirs(self.scratch, exist_ok=True)
        self.compiles_in_window = 0
        self.cache = {"hits": 0, "misses": 0}  # of the persistent cache, the whole run
        self.marks: Dict[str, float] = {}  # set-up broken down: seconds since t0
        self._open = False
        self._listen()

    def _listen(self) -> None:
        import jax

        def on_duration(event: str, duration: float, **_kw: Any) -> None:
            if self._open and event.endswith("backend_compile_duration"):
                self.compiles_in_window += 1

        def on_event(event: str, **_kw: Any) -> None:
            if "/compilation_cache/" not in event:
                return
            for kind in self.cache:
                if event.endswith("cache_" + kind):
                    self.cache[kind] += 1
                    self.compiles_in_window += self._open

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self, name: str) -> None:
        self.marks[name] = round(time.perf_counter() - self.t0, 3)

    def window_opens(self) -> None:
        self.mark("window_opens")
        self._open = True

    def window_closes(self) -> None:
        self._open = False

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))


def find_device(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it; no TPU, or too few, ends the run."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(_refuse(f"no chip: JAX reports platform {device['platform']!r}"))
    if device["count"] < chips:
        raise SystemExit(_refuse(f"the cell needs {chips} chips, JAX reports {device['count']}"))
    return device


def _refuse(why: str) -> int:
    print(f"benchmarks/run.py: {why}", file=sys.stderr)
    return NO_CHIP


def execute(manifest, cell_name: str, seed: int, seconds: float, trace: bool,
            device: Dict[str, Any], t0: float = None) -> Dict[str, Any]:
    """Everything after the look for a chip: run the cell's driver, read the
    per-layer metrics, decide ``correct`` and build the result line."""
    from benchmarks import loader, trace_reduce

    cell = manifest.cell(cell_name)
    ctx = Context(manifest.root, T0 if t0 is None else t0)
    ctx.mark("imports_done")
    driver = manifest.driver(cell.settings["driver"])
    out = driver.run(cell, seed, seconds, trace, ctx)
    if ctx.compiles_in_window:
        raise RuntimeError(
            f"{ctx.compiles_in_window} compilation(s) or cache loads inside the "
            "measured window: a shape was not warmed up")

    check = out["check"]
    check.note("compile_cache", ctx.cache)  # a second run of a cell misses nothing
    check.print()
    line: Dict[str, Any] = {
        "correct": bool(check.ok), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": {},
        "device": dict(device, memory_peak_bytes=int(out["memory_peak_bytes"])),
    }
    if not trace:
        for m in cell.end_to_end:
            if m.name not in out["end_to_end"]:
                raise RuntimeError(f"the driver reported no {m.name}")
            line["metrics"][m.name] = {"value": out["end_to_end"][m.name], "unit": m.unit}
        return line

    facts = dict(out["facts"], end_to_end=out["end_to_end"],
                 peaks=loader.peaks(device["kind"], manifest.root))
    reduced = None
    if facts.get("trace_path"):
        reduced = trace_reduce.reduce(facts["trace_path"])
        facts["trace"] = reduced
    for m in cell.per_layer:
        value = manifest.reader(m.name)(facts)
        if value is not None:
            line["metrics"][m.name] = {"value": float(value), "unit": m.unit}
    if reduced is None:
        raise RuntimeError("the traced run took no device trace")
    line["device"]["busy_s"] = reduced["busy_s"]
    line["device"]["window_s"] = reduced["window_s"]
    line["breakdown"] = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    return line


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.exists(os.path.join(ROOT, "ray_lightning_tpu", "__init__.py")):
        print("benchmarks/run.py: this checkout holds no program to measure "
              "(ray_lightning_tpu/ is missing)", file=sys.stderr)
        return 2
    from benchmarks import loader

    manifest = loader.Manifest(ROOT)
    cell = manifest.cell(args.workload)
    device = find_device(cell.chips)
    from benchmarks import program

    program.cache_dir(ROOT)  # here and not in execute(): the tests keep JAX's cache off
    line = execute(manifest, args.workload, args.seed, args.seconds, bool(args.trace), device)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
