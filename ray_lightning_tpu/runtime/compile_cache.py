"""Content-addressed persistent AOT executable cache.

Compile time taxes every capability the stack has: elastic resize, replica
relaunch under an open breaker and autoscaler scale-up. This module makes a fresh process skip XLA compilation entirely by
layering two caches above JAX's own ``jax_compilation_cache_dir``:

- an **in-memory layer** (key -> ``jax.stages.Compiled``) so rebuilding the
  same program inside one process — a second engine, a re-built train step
  after an elastic resize, ``cost_summary()`` — performs zero compilations;
- a **disk layer** of serialized AOT executables
  (``jax.experimental.serialize_executable``) so a relaunched or scaled-up
  process loads the program a sibling already paid to compile. When the
  backend cannot serialize executables the entry falls back to the lowered
  StableHLO text: the key/bookkeeping stay intact and the recompile still
  rides JAX's persistent cache underneath.

The cache key is content-addressed: a hash of the lowered StableHLO text
(which embeds shapes, shardings and the mesh topology), the per-argument
donation mask from ``Lowered.args_info`` (donation can be dropped by the
backend at lowering, e.g. on CPU, so the text alone is not enough), the
jax/jaxlib versions, backend platform + device kind + device count, and
``XLA_FLAGS``. Any change to any of these misses; an identical rebuild hits.

Safety: deserializing a persisted CPU executable can pin host-specific
machine features in the process (see tests/conftest.py: a later fresh
gather-heavy compile aborts the interpreter on this jaxlib). Executable
*loading* is therefore gated: always on for non-CPU backends, on for worker
actor processes (``RLT_ACTOR_PROCESS=1``, set by actor_boot/zygote — they
only load programs sibling actors wrote), and off otherwise unless
``RLT_COMPILE_CACHE_EXEC=1`` forces it. Additionally, a process attached to
a jax distributed runtime (multi-process training, or an elastic world-1
survivor holding a coordination client) never round-trips executables in
either direction — a serialized executable pins the runtime incarnation it
was compiled under, and reloading one across a gloo restart silently
diverges or hangs; those processes persist StableHLO markers and lean on
jax's own compilation cache instead. Serialization (writing) outside a
distributed runtime is safe and stays on so single-process consumers — a
serving replica, a zygote warm-start — share one another's programs. On a
TPU the round trip was checked on the chip (v5e, jaxlib 0.9.0: a Pallas
program serialized, reloaded by a fresh cache object and ran bit-equal), so
the disk layer stays on there.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ray_lightning_tpu import observability as _obs
from ray_lightning_tpu.observability import metrics as _metrics
from ray_lightning_tpu.analysis.sanitizer import rlt_lock
from ray_lightning_tpu.utils.common import rank_zero_warn
from ray_lightning_tpu.utils.fsio import atomic_writer

# Bump when the on-disk entry layout changes; skewed entries recompile.
FORMAT_VERSION = 1
_MAGIC = "rltx1"

COMPILE_CACHE_HITS_METRIC = "rlt_compile_cache_hits_total"
COMPILE_CACHE_MISSES_METRIC = "rlt_compile_cache_misses_total"
COMPILE_MS_METRIC = "rlt_compile_ms"

_metrics.set_help(
    COMPILE_CACHE_HITS_METRIC,
    "Executable-cache hits (memory or disk), by program and layer.",
)
_metrics.set_help(
    COMPILE_CACHE_MISSES_METRIC,
    "Executable-cache misses that paid an XLA compile, by program.",
)
_metrics.set_help(
    COMPILE_MS_METRIC,
    "Milliseconds spent in XLA compilation on cache misses.",
)

XLA_CACHE_DIR_ENV = "RLT_XLA_CACHE_DIR"
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
ACTOR_PROCESS_ENV = "RLT_ACTOR_PROCESS"
DISK_CAP_ENV = "RLT_XLA_CACHE_MAX_BYTES"
_DEFAULT_DISK_CAP_BYTES = 4 << 30  # 4 GiB


# --------------------------------------------------------------------- #
# cache-dir resolution + the shared jax-config stanza
# --------------------------------------------------------------------- #
def default_cache_dir() -> str:
    """``<checkout>/.xla_cache``: one fixed place for every process of a
    checkout. The path is part of JAX's cache key, so a directory that
    moves (a temp dir, a pid, a date) never hits."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".xla_cache")


def resolve_cache_dir(explicit: Optional[str] = None) -> Optional[str]:
    """Resolve the cache dir: ``JAX_COMPILATION_CACHE_DIR`` (the cache is
    placed from outside — nothing below may move it) > ctor/explicit >
    ``RLT_XLA_CACHE_DIR`` env > ``<checkout>/.xla_cache``.
    ``"0"``/``"off"``/``""`` at the explicit or ``RLT_`` level disables
    (returns None)."""
    placed = os.environ.get(JAX_CACHE_DIR_ENV)
    if placed:
        return placed
    value = explicit
    if value is None:
        value = os.environ.get(XLA_CACHE_DIR_ENV)
    if value is None:
        return default_cache_dir()
    value = str(value)
    if value.strip().lower() in ("", "0", "off", "none"):
        return None
    return value


def configure_jax_persistent_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache in the resolved dir
    (:func:`resolve_cache_dir`) and return that dir, or None when disabled.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read its own
    variable and the config is left alone; otherwise this is the one place
    that sets ``jax_compilation_cache_dir`` (worker boot paths and
    ``chip_smoke.py`` call it before the first compile).
    """
    cache_dir = resolve_cache_dir(cache_dir)
    if not cache_dir:
        return None
    import jax

    if not os.environ.get(JAX_CACHE_DIR_ENV):
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:
            return None
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def _disk_cap_bytes() -> Optional[int]:
    """Disk-layer size cap (``RLT_XLA_CACHE_MAX_BYTES``, default 4 GiB;
    ``0``/``off`` disables pruning)."""
    raw = os.environ.get(DISK_CAP_ENV)
    if raw is None:
        return _DEFAULT_DISK_CAP_BYTES
    if raw.strip().lower() in ("", "0", "off", "none"):
        return None
    try:
        return int(raw)
    except ValueError:
        return _DEFAULT_DISK_CAP_BYTES


def _prune_disk(cache_dir: str, max_bytes: Optional[int]) -> None:
    """LRU-by-mtime eviction of ``.rltx`` entries over the size cap.

    Runs once at cache construction; ``_load_disk`` touches entries it
    serves so live programs stay newest. The dir is shared across
    model/config/version churn, so without this it grows without bound.
    """
    if not max_bytes:
        return
    try:
        with os.scandir(cache_dir) as it:
            entries = [
                (e.stat().st_mtime, e.stat().st_size, e.path)
                for e in it
                if e.name.endswith(".rltx")
            ]
    except OSError:
        return
    total = sum(size for _, size, _ in entries)
    if total <= max_bytes:
        return
    entries.sort()  # oldest first
    for _, size, path in entries:
        if total <= max_bytes:
            break
        try:
            os.unlink(path)
            total -= size
        except OSError:
            pass


# --------------------------------------------------------------------- #
# key derivation
# --------------------------------------------------------------------- #
def _donation_mask(lowered) -> Tuple[Tuple[Any, bool], ...]:
    """Per-argument (shape/dtype, donated) from ``Lowered.args_info``.

    Donation must be keyed explicitly: backends may drop unusable donations
    at lowering (CPU does), leaving the StableHLO text identical between a
    donating and a non-donating build of the same program.
    """
    import jax

    flat, treedef = jax.tree_util.tree_flatten(lowered.args_info)
    parts = []
    for info in flat:
        aval = getattr(info, "aval", None) or getattr(info, "_aval", None)
        parts.append((str(aval), bool(getattr(info, "donated", False))))
    return tuple(parts) + ((str(treedef), False),)


def backend_fingerprint(backend: Optional[str] = None) -> Dict[str, Any]:
    """Versions + device topology half of the cache key."""
    import jax
    import jaxlib

    devices = jax.devices(backend) if backend else jax.devices()
    try:
        num_processes = jax.process_count()
    except Exception:
        num_processes = 1
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.version.__version__,
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "num_processes": num_processes,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def _client_token_now() -> Optional[int]:
    """Identity token of the live backend client, or None when no backend
    is up yet. ``get_or_compile`` drops its memory layer when this changes:
    an elastic reconnect tears down and rebuilds the client, and executables
    bound to the old one carry identical-looking keys but dead device
    handles. Module-level so tests can monkeypatch the token source."""
    import jax

    try:
        return id(jax.devices()[0].client)
    except (RuntimeError, IndexError, AttributeError):
        # RuntimeError: no backend initialized; IndexError: zero devices;
        # AttributeError: a device class without .client. Anything else
        # (e.g. a NameError from a refactor) must propagate, not silently
        # disable the client-change gate.
        return None


def _distributed_runtime_active() -> bool:
    """True when this process is (or has been) a member of a jax distributed
    runtime — a multi-process run, or an elastic world-1 survivor still
    holding a coordination client. Serialized executables pin the runtime
    incarnation they were compiled under, so such processes must not
    round-trip executables (they silently diverge or hang the collective
    after a reconnect); jax's own compilation cache covers their recompiles.
    """
    try:
        from jax._src import distributed as _dist

        if getattr(_dist.global_state, "client", None) is not None:
            return True
    except Exception:
        pass
    import jax

    try:
        return jax.process_count() > 1
    except Exception:
        return False


def cache_key(lowered, extra: Optional[Dict[str, Any]] = None) -> str:
    """Content-addressed key for one lowered program.

    Covers the StableHLO text (avals, shardings, mesh/axis topology and the
    computation itself), the explicit donation mask, jax/jaxlib versions,
    backend platform + device kind + device count, and ``XLA_FLAGS``.
    """
    h = hashlib.sha256()
    h.update(lowered.as_text().encode())
    h.update(repr(_donation_mask(lowered)).encode())
    h.update(
        json.dumps(backend_fingerprint(), sort_keys=True).encode()
    )
    if extra:
        h.update(json.dumps(extra, sort_keys=True, default=str).encode())
    return h.hexdigest()


def _default_allow_load() -> bool:
    """Whether deserializing persisted executables is safe in this process.

    CPU AOT loads taint the process on this jaxlib (a later fresh
    gather-heavy compile aborts — see tests/conftest.py), so on CPU only
    worker actor processes load; ``RLT_COMPILE_CACHE_EXEC`` overrides both
    ways.
    """
    env = os.environ.get("RLT_COMPILE_CACHE_EXEC")
    if env in ("0", "1"):
        return env == "1"
    import jax

    try:
        platform = jax.devices()[0].platform
    except Exception:
        return False
    if platform != "cpu":
        return True
    return os.environ.get(ACTOR_PROCESS_ENV) == "1"


def enabled() -> bool:
    """Master switch for the executable cache (``RLT_COMPILE_CACHE``,
    default on). Distinct from ``RLT_XLA_CACHE_DIR``: with persistence
    disabled the in-memory layer still dedupes in-process rebuilds."""
    return os.environ.get("RLT_COMPILE_CACHE", "1") != "0"


class CompileCache:
    """Two-layer (memory + disk) content-addressed executable cache.

    ``get_or_compile(fn, *args)`` is the whole API surface: it lowers,
    derives the key, and returns a ``jax.stages.Compiled`` from the cheapest
    layer that has it, compiling (and persisting) on miss. Thread-safe per
    key; concurrent misses for different keys compile in parallel.
    """

    def __init__(
        self,
        cache_dir: Optional[str] = None,
        allow_load: Optional[bool] = None,
        persist: Optional[bool] = None,
    ):
        self.cache_dir = resolve_cache_dir(cache_dir)
        self._allow_load = allow_load
        self._persist = persist if persist is not None else self.cache_dir is not None
        self._mem: Dict[str, Any] = {}
        self._lock = rlt_lock("runtime.compile_cache.CompileCache._lock")
        self._key_locks: Dict[str, threading.Lock] = {}
        self._client_token: Optional[int] = None
        self._warned_persist = False
        self.stats: Dict[str, Any] = {
            "hits": 0,
            "memory_hits": 0,
            "disk_hits": 0,
            "misses": 0,
            "corrupt": 0,
            "version_skew": 0,
            "stablehlo_fallbacks": 0,
            "serialize_errors": 0,
            "compile_ms_total": 0.0,
            "programs": {},
        }
        if self._persist and self.cache_dir:
            _prune_disk(self.cache_dir, _disk_cap_bytes())

    # ----------------------------------------------------------------- #
    def _entry_path(self, key: str) -> Optional[str]:
        if not self.cache_dir:
            return None
        return os.path.join(self.cache_dir, f"{key}.rltx")

    def _record(self, kind: str, program: str, layer: Optional[str] = None) -> None:
        self.stats[kind] += 1
        prog = self.stats["programs"].setdefault(
            program, {"hits": 0, "misses": 0}
        )
        reg = _obs.registry()
        if kind == "hits":
            prog["hits"] += 1
            if layer:
                self.stats[f"{layer}_hits"] += 1
            if reg:
                reg.counter(
                    COMPILE_CACHE_HITS_METRIC, program=program, layer=layer or "memory"
                ).inc()
        elif kind == "misses":
            prog["misses"] += 1
            if reg:
                reg.counter(COMPILE_CACHE_MISSES_METRIC, program=program).inc()

    # ----------------------------------------------------------------- #
    # disk layer
    # ----------------------------------------------------------------- #
    def _load_disk(self, key: str, program: str):
        path = self._entry_path(key)
        if path is None or not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                header_line = f.readline()
                header = json.loads(header_line)
                payload = f.read()
        except (OSError, ValueError):
            self.stats["corrupt"] += 1
            self._unlink(path)
            return None
        fp = backend_fingerprint()
        if (
            header.get("magic") != _MAGIC
            or header.get("format") != FORMAT_VERSION
            or header.get("jax") != fp["jax"]
            or header.get("jaxlib") != fp["jaxlib"]
            or header.get("backend") != fp["backend"]
            or header.get("device_kind") != fp["device_kind"]
        ):
            self.stats["version_skew"] += 1
            return None
        if hashlib.sha256(payload).hexdigest() != header.get("payload_sha"):
            self.stats["corrupt"] += 1
            self._unlink(path)
            return None
        try:
            os.utime(path)  # keep served entries newest for LRU pruning
        except OSError:
            pass
        if header.get("kind") != "exec":
            # StableHLO fallback entry: presence marker only; the recompile
            # below still rides jax's persistent cache when configured.
            self.stats["stablehlo_fallbacks"] += 1
            return None
        if _distributed_runtime_active():
            # A serialized executable pins the distributed-runtime
            # incarnation it was compiled under; reloading one across gloo
            # restarts silently diverges (or hangs the collective). Only
            # single-process programs round-trip.
            return None
        allow = self._allow_load
        if allow is None:
            allow = _default_allow_load()
        if not allow:
            return None
        try:
            from jax.experimental import serialize_executable as _se

            serialized, in_tree, out_tree = pickle.loads(payload)
            return _se.deserialize_and_load(serialized, in_tree, out_tree)
        except Exception:
            self.stats["corrupt"] += 1
            self._unlink(path)
            return None

    def _store_disk(self, key: str, compiled, lowered, program: str) -> None:
        path = self._entry_path(key)
        if path is None or not self._persist:
            return
        kind, payload = "exec", None
        if _distributed_runtime_active():
            # never persist executables carrying cross-process collectives
            # (see _load_disk); the marker still rides jax's compilation
            # cache for the recompile.
            try:
                kind, payload = "stablehlo", lowered.as_text().encode()
            except Exception:
                return
        else:
            try:
                from jax.experimental import serialize_executable as _se

                serialized, in_tree, out_tree = _se.serialize(compiled)
                payload = pickle.dumps((serialized, in_tree, out_tree))
            except Exception:
                self.stats["serialize_errors"] += 1
                try:
                    kind, payload = "stablehlo", lowered.as_text().encode()
                except Exception:
                    return
        fp = backend_fingerprint()
        header = {
            "magic": _MAGIC,
            "format": FORMAT_VERSION,
            "kind": kind,
            "program": program,
            "payload_sha": hashlib.sha256(payload).hexdigest(),
            **{k: fp[k] for k in ("jax", "jaxlib", "backend", "device_kind")},
        }
        try:
            os.makedirs(self.cache_dir, exist_ok=True)
            with atomic_writer(path, "wb") as f:
                f.write(json.dumps(header).encode() + b"\n")
                f.write(payload)
        except OSError as e:
            if not self._warned_persist:
                self._warned_persist = True
                rank_zero_warn("compile cache persist failed: %s", e)

    @staticmethod
    def _unlink(path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    # ----------------------------------------------------------------- #
    def get_or_compile(
        self,
        fn,
        *args,
        program: str = "program",
        lowered=None,
        extra_key: Optional[Dict[str, Any]] = None,
    ):
        """Return a ``jax.stages.Compiled`` for ``fn(*args)``, from the
        cheapest available layer. ``fn`` is a jitted function (anything with
        ``.lower``); pass ``lowered`` to reuse an existing lowering."""
        if lowered is None:
            lowered = fn.lower(*args)
        key = cache_key(lowered, extra=extra_key)
        # An elastic reconnect tears down and rebuilds the backend client;
        # executables bound to the old client carry identical-looking keys
        # (same mesh, same fingerprint) but dead device handles. Drop the
        # memory layer whenever the live client changes — the disk layer
        # deserializes against the CURRENT client, so warm starts survive.
        token = _client_token_now()
        with self._lock:
            if token != self._client_token:
                self._mem.clear()
                self._client_token = token
            compiled = self._mem.get(key)
            if compiled is None:
                key_lock = self._key_locks.setdefault(
                key, rlt_lock("runtime.compile_cache.CompileCache._key_lock")
            )
        if compiled is not None:
            self._record("hits", program, "memory")
            return compiled
        # Per-key in-flight guard: concurrent misses on the SAME key wait
        # here and find the winner's executable in the memory layer instead
        # of paying a duplicate compile; different keys proceed in parallel.
        with key_lock:
            with self._lock:
                compiled = self._mem.get(key)
            if compiled is not None:
                self._record("hits", program, "memory")
                return compiled
            compiled = self._load_disk(key, program)
            if compiled is not None:
                self._record("hits", program, "disk")
                with self._lock:
                    self._mem[key] = compiled
                return compiled
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_ms = (time.perf_counter() - t0) * 1000.0
            self._record("misses", program)
            self.stats["compile_ms_total"] += compile_ms
            reg = _obs.registry()
            if reg:
                reg.histogram(COMPILE_MS_METRIC, program=program).observe(
                    compile_ms
                )
            self._store_disk(key, compiled, lowered, program)
            with self._lock:
                self._mem[key] = compiled
        return compiled

    def clear_memory(self) -> None:
        """Drop the in-memory layer (tests use this to force disk reads)."""
        with self._lock:
            self._mem.clear()


# jax's pre-dispatch argument checks. Everything here fires BEFORE the
# executable runs, so donated buffers are still intact and a retry against a
# re-resolved executable is safe. Any other TypeError/ValueError out of a
# Compiled call is a REAL runtime failure (gloo surfaces a dead peer as a
# ValueError, see runtime/elastic.is_collective_failure) and must propagate
# untouched: re-dispatching a step whose donated inputs may already be
# consumed reads freed buffers.
_PREDISPATCH_MISMATCH_MARKERS = (
    "Compiled object called with input",      # sharding/layout (ValueError)
    "Argument types differ from the types",   # aval drift (TypeError)
    "Computation compiled for",               # arity (TypeError)
    "Function compiled with input pytree",    # pytree (TypeError)
)


def _is_signature_mismatch(exc: BaseException) -> bool:
    if not isinstance(exc, (TypeError, ValueError)):
        return False
    text = str(exc)
    return any(marker in text for marker in _PREDISPATCH_MISMATCH_MARKERS)


def _argument_shapes(args) -> tuple:
    """What an executable is specialised to, as jit is: the shape and dtype
    of every leaf of the arguments (an array, a ``ShapeDtypeStruct`` or a
    Python scalar)."""
    import jax

    return tuple(
        (getattr(a, "shape", ()), getattr(a, "dtype", type(a)))
        for a in jax.tree_util.tree_leaves(args)
    )


class CachedProgram:
    """Callable facade swapping a jitted function's first-dispatch compile
    for a cache resolution.

    Like the jitted ``fn`` it wraps, it specialises by the arguments'
    shapes: one resolved executable a shape signature, so a program called
    at a few shapes (the engine's ladder of prefill lengths) dispatches each
    to its own executable and resolves none of them twice. ``fn`` is kept
    for lowering (``.lower`` delegates, so the profiler's AOT path works
    unchanged) and as the escape hatch: if a call does not fit the
    executable resolved for its own shapes even after a second resolution,
    the wrapper permanently falls back to the jit path for correctness.
    ``_cache_size()`` mirrors jit's private counter (executables resolved)
    so ``compile_stats()``-style zero-recompile asserts keep working.
    """

    def __init__(self, fn, program: str, cache: Optional[CompileCache] = None):
        self._fn = fn
        self._program = program
        self._cache = cache or get_cache()
        self._compiled: Dict[tuple, Any] = {}  # argument shapes -> executable
        self._resolved = 0
        self._polymorphic = False

    def _resolve(self, shapes: tuple, args):
        compiled = self._compiled.get(shapes)
        if compiled is None:
            compiled = self._compiled[shapes] = self._cache.get_or_compile(
                self._fn, *args, program=self._program
            )
            self._resolved += 1
        return compiled

    def warmup(self, *args) -> "CachedProgram":
        """Resolve (compile or load) without executing; idempotent."""
        self._resolve(_argument_shapes(args), args)
        return self

    def cached_compiled(self, *args):
        """The underlying ``Compiled`` (resolving on first use) — the AOT
        handle ``cost_summary()``/``analyze_jitted`` reuse instead of paying
        a second compile."""
        return self._resolve(_argument_shapes(args), args)

    def __call__(self, *args):
        if self._polymorphic:
            return self._fn(*args)
        shapes = _argument_shapes(args)
        try:
            return self._resolve(shapes, args)(*args)
        except (TypeError, ValueError) as exc:
            # Only jax's pre-dispatch signature checks are retryable: they
            # fire before execution, so donated buffers are intact.
            # Re-resolve against the CURRENT arguments — their lowering keys
            # to the right executable (e.g. the profiler warmed the program
            # on still-unplaced params and the real step call is sharded).
            # Anything else (a gloo peer-death ValueError, a deleted-array
            # error) propagates untouched so the elastic machinery sees the
            # original failure and no step is ever dispatched twice.
            if not _is_signature_mismatch(exc):
                raise
            try:
                self._compiled.pop(shapes, None)
                return self._resolve(shapes, args)(*args)
            except (TypeError, ValueError) as exc2:
                # the re-resolution does not fit either: what differs is
                # nothing the shapes show — hand dispatch to jit permanently
                if not _is_signature_mismatch(exc2):
                    raise
                self._polymorphic = True
                return self._fn(*args)

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def _cache_size(self) -> int:
        n = self._resolved
        if self._polymorphic:
            try:
                n += self._fn._cache_size()
            except Exception:
                pass
        return n


# --------------------------------------------------------------------- #
# process-wide shared cache
# --------------------------------------------------------------------- #
_GLOBAL: Optional[CompileCache] = None
_GLOBAL_LOCK = rlt_lock("runtime.compile_cache._GLOBAL_LOCK")


def get_cache() -> CompileCache:
    """The process-wide cache every integration site shares, so the trainer,
    the engine, the profiler and ``cost_summary()`` all hit one another's
    entries."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = CompileCache()
        return _GLOBAL


def reset_cache() -> None:
    """Drop the shared cache (tests)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None


def wrap(fn, program: str, cache: Optional[CompileCache] = None):
    """Wrap a jitted fn in a :class:`CachedProgram` when the cache is
    enabled; return ``fn`` unchanged when it is not."""
    if not enabled():
        return fn
    return CachedProgram(fn, program, cache=cache)


def jit_program(fn, program: str, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the program's label, then
    :func:`wrap`. The label is the one place that names a compiled program:
    the cache's metrics carry it, and the module is ``jit_<program>``, which
    is what a device trace's ``XLA Modules`` line and the HLO show. The name
    is a constant of the call site (never a counter or an id), so the lowered
    text, and with it every cache key, is the same from run to run."""
    import jax

    @functools.wraps(fn)
    def labelled(*args):
        return fn(*args)

    labelled.__name__ = labelled.__qualname__ = program
    return wrap(jax.jit(labelled, **jit_kwargs), program)
