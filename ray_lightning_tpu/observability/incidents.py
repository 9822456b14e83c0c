"""Black-box incident recorder.

When a fault verdict, SLO breach, transfer failure, or anomaly event
fires, the evidence is scattered: ``events.jsonl`` keeps rotating,
metric snapshots are delta-based and have already moved on, and the
ledgers (arbiter / membership / request journal) only say what is true
*now*. The :class:`IncidentRecorder` freezes all of it at the moment the
event fires into one self-contained bundle under
``<telemetry>/incidents/<ts>_<kind>/``:

- ``incident.json`` — the triggering event plus capture metadata,
- ``events.jsonl`` — the trailing flight-record window, stitched across
  the writer's rotation boundary (:func:`.reqtrace.read_window`),
- ``metrics_history.json`` — the registry's ring-buffered snapshot
  history (:meth:`.metrics.MetricsRegistry.history`),
- ``trace_slice.json`` — a merged Chrome-trace slice of the recent
  per-rank trace tails,
- ``<source>.json`` — one file per registered snapshot source (arbiter
  ledger, membership ledger, request-journal summary, ...),
- optional extra text attachments.

Bundles are deduplicated per kind with a cooldown (``RLT_INCIDENT_COOLDOWN_S``)
and the directory is pruned oldest-first past ``RLT_INCIDENT_MAX_BUNDLES``,
so a crash loop cannot fill the disk with identical evidence.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

from . import reqtrace as _reqtrace

INCIDENTS_DIRNAME = "incidents"

MAX_BUNDLES_ENV = "RLT_INCIDENT_MAX_BUNDLES"
MAX_BUNDLES_DEFAULT = 16
COOLDOWN_ENV = "RLT_INCIDENT_COOLDOWN_S"
COOLDOWN_DEFAULT = 60.0
# Trailing flight-record bytes frozen into each bundle.
EVENT_WINDOW_BYTES = 256 * 1024

INCIDENTS_CAPTURED_METRIC = "rlt_incidents_captured_total"
INCIDENTS_SUPPRESSED_METRIC = "rlt_incidents_suppressed_total"

# Flight-record event kinds that trip a capture. Fault verdicts and the
# crash/relaunch path come from the supervisor/launcher; slo_breach from
# the SLO monitor; arbiter_rollback from failed chip transfers; the
# anomaly_* kinds from observability.anomaly.
INCIDENT_EVENT_KINDS = frozenset({
    "crash",
    "hang",
    "straggler",
    "slo_breach",
    "arbiter_rollback",
    "elastic_grow_failed",
    "anomaly_step_time",
    "anomaly_itl_p99",
    "anomaly_straggler",
    "anomaly_silent_goodput",
})

_BUNDLE_RE = re.compile(r"^(\d+)_(.+)$")


def max_bundles() -> int:
    try:
        return max(1, int(os.environ.get(MAX_BUNDLES_ENV, MAX_BUNDLES_DEFAULT)))
    except ValueError:
        return MAX_BUNDLES_DEFAULT


def cooldown_s() -> float:
    try:
        return max(0.0, float(os.environ.get(COOLDOWN_ENV, COOLDOWN_DEFAULT)))
    except ValueError:
        return COOLDOWN_DEFAULT


def _slug(kind: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", str(kind)) or "event"


class IncidentRecorder:
    """Rate-limited, deduplicated incident-bundle writer for one run dir."""

    def __init__(
        self,
        run_dir: str,
        registry: Optional[Any] = None,
        events_path: Optional[str] = None,
        trace_provider: Optional[Callable[[], Any]] = None,
        clock: Callable[[], float] = time.time,
        cooldown: Optional[float] = None,
        bundle_cap: Optional[int] = None,
    ) -> None:
        self.run_dir = run_dir
        self.registry = registry
        self.events_path = events_path
        self.trace_provider = trace_provider
        self._clock = clock
        self._cooldown = cooldown_s() if cooldown is None else float(cooldown)
        self._cap = max_bundles() if bundle_cap is None else int(bundle_cap)
        self._sources: Dict[str, Callable[[], Any]] = {}
        self._last_capture: Dict[str, float] = {}

    @property
    def dir(self) -> str:
        return os.path.join(self.run_dir, INCIDENTS_DIRNAME)

    def register_source(self, name: str, fn: Callable[[], Any]) -> None:
        """Register a snapshot source (e.g. ``arbiter_ledger``) whose
        return value is frozen into every future bundle as
        ``<name>.json``. Last registration per name wins."""
        self._sources[str(name)] = fn

    def maybe_capture(
        self,
        kind: str,
        event: Optional[Dict[str, Any]] = None,
        attachments: Optional[Dict[str, str]] = None,
    ) -> Optional[str]:
        """Capture a bundle for ``kind`` unless one was captured within
        the cooldown window. Returns the bundle path, or None when
        suppressed (or when writing failed — capture must never take the
        run down)."""
        now = self._clock()
        last = self._last_capture.get(kind)
        if last is not None and now - last < self._cooldown:
            if self.registry is not None:
                self.registry.counter(
                    INCIDENTS_SUPPRESSED_METRIC, kind=_slug(kind)
                ).inc()
            return None
        self._last_capture[kind] = now
        try:
            path = self._capture(kind, now, event, attachments)
        except OSError:
            return None
        if self.registry is not None:
            self.registry.counter(
                INCIDENTS_CAPTURED_METRIC, kind=_slug(kind)
            ).inc()
        self._prune()
        return path

    # -- internals -------------------------------------------------------

    def _capture(
        self,
        kind: str,
        now: float,
        event: Optional[Dict[str, Any]],
        attachments: Optional[Dict[str, str]],
    ) -> str:
        name = f"{int(now)}_{_slug(kind)}"
        path = os.path.join(self.dir, name)
        n = 1
        while os.path.exists(path):
            path = os.path.join(self.dir, f"{name}.{n}")
            n += 1
        os.makedirs(path, exist_ok=True)

        sources_written: List[str] = []
        for src_name, fn in sorted(self._sources.items()):
            try:
                obj = fn()
            except Exception as e:
                obj = {"error": f"{type(e).__name__}: {e}"}
            self._dump_json(path, f"{src_name}.json", obj)
            sources_written.append(src_name)

        if self.events_path:
            lines = _reqtrace.read_window(self.events_path, EVENT_WINDOW_BYTES)
            self._dump_text(path, "events.jsonl", "\n".join(lines) + "\n" if lines else "")

        if self.registry is not None:
            self._dump_json(path, "metrics_history.json", self.registry.history())

        if self.trace_provider is not None:
            try:
                trace_slice = self.trace_provider()
            except Exception as e:
                trace_slice = {"error": f"{type(e).__name__}: {e}"}
            self._dump_json(path, "trace_slice.json", trace_slice)

        for fname, content in (attachments or {}).items():
            self._dump_text(path, fname, content)

        self._dump_json(path, "incident.json", {
            "ts": now,
            "kind": kind,
            "event": event or {},
            "cooldown_s": self._cooldown,
            "sources": sources_written,
            "attachments": sorted((attachments or {}).keys()),
        })
        return path

    @staticmethod
    def _dump_json(bundle: str, fname: str, obj: Any) -> None:
        try:
            with open(os.path.join(bundle, fname), "w") as f:
                json.dump(obj, f, default=str, indent=1)
        except (OSError, TypeError, ValueError):
            pass

    @staticmethod
    def _dump_text(bundle: str, fname: str, content: str) -> None:
        try:
            with open(os.path.join(bundle, fname), "w", encoding="utf-8") as f:
                f.write(content)
        except OSError:
            pass

    def _prune(self) -> None:
        bundles = list_bundles(self.run_dir)
        excess = len(bundles) - self._cap
        for b in bundles[:max(0, excess)]:  # oldest first
            shutil.rmtree(b["path"], ignore_errors=True)


def list_bundles(run_dir: str) -> List[Dict[str, Any]]:
    """Incident bundles under ``run_dir``, oldest first."""
    root = os.path.join(run_dir, INCIDENTS_DIRNAME)
    out: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return out
    for name in names:
        path = os.path.join(root, name)
        if not os.path.isdir(path):
            continue
        m = _BUNDLE_RE.match(name)
        info: Dict[str, Any] = {
            "name": name,
            "path": path,
            "ts": int(m.group(1)) if m else None,
            "kind": m.group(2).split(".", 1)[0] if m else name,
        }
        try:
            info["files"] = sorted(os.listdir(path))
        except OSError:
            info["files"] = []
        out.append(info)
    out.sort(key=lambda b: (b["ts"] or 0, b["name"]))
    return out


def load_bundle(path: str) -> Dict[str, Any]:
    """Load one bundle's ``incident.json`` plus per-file summaries (line
    counts for jsonl, top-level keys for json) for CLI rendering."""
    out: Dict[str, Any] = {"path": path, "files": {}}
    try:
        with open(os.path.join(path, "incident.json")) as f:
            out["incident"] = json.load(f)
    except (OSError, ValueError):
        out["incident"] = {}
    try:
        names = sorted(os.listdir(path))
    except OSError:
        names = []
    for name in names:
        p = os.path.join(path, name)
        try:
            if name.endswith(".jsonl"):
                with open(p, encoding="utf-8") as f:
                    out["files"][name] = {
                        "lines": sum(1 for ln in f if ln.strip())
                    }
            elif name.endswith(".json"):
                with open(p) as f:
                    obj = json.load(f)
                out["files"][name] = {
                    "keys": sorted(obj.keys()) if isinstance(obj, dict)
                    else f"list[{len(obj)}]" if isinstance(obj, list) else type(obj).__name__
                }
            else:
                out["files"][name] = {"bytes": os.path.getsize(p)}
        except (OSError, ValueError):
            out["files"][name] = {"error": "unreadable"}
    return out

