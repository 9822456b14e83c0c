"""Test-wide JAX config: CPU platform with 8 virtual devices.

This is the Gloo-equivalent of the reference's CI (SURVEY §4: local ray.init
"clusters" on CPU): an 8-device host mesh exercises every sharding/collective
code path that runs on a real TPU slice, compiled by the same XLA GSPMD
partitioner. Must run before jax is imported anywhere. The chip is reached
only through ``chip_smoke.py``, never from the tests.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compilation cache — WORKER PROCESSES ONLY. Within one
# suite run the slow tests spawn many actor processes compiling the same
# tiny train steps; sharing a cache across them (actor_boot/zygote resolve
# <checkout>/.xla_cache) removes that duplicate work. The MAIN pytest
# process must NOT use it: on this jaxlib, loading any cached CPU-AOT
# executable taints the process (machine-feature mismatch,
# "+prefer-no-gather"), and the next FRESH gather-heavy compile aborts the
# interpreter — reproduced 2026-07-29, warm-cache runs died at
# test_moe_llama_trains (first MoE top-k dispatch compile after cached
# loads) with glibc abort. Actors are safe because they only ever load
# programs sibling actors wrote and compile nothing gather-heavy
# afterwards. RLT_XLA_CACHE=0 disables even the worker cache.
if os.environ.get("RLT_XLA_CACHE", "1") == "0":
    os.environ["RLT_XLA_CACHE_DIR"] = "0"

# CPU is a logical scheduling resource (Ray semantics); CI containers may
# report 1 core, which would serialize every multi-actor test. The reference
# does the same thing by passing num_cpus=2/4 to ray.init in its fixtures.
os.environ.setdefault("RLT_NUM_CPUS", "64")

# Preload-fork actor spawning (runtime/zygote.py): pays the ~15-20s
# jax-import interpreter boot once instead of per worker actor — measured
# 9:44 -> 3:59 on the slow (multi-worker) test suite. Set RLT_ZYGOTE=0 to
# exercise the classic one-interpreter-per-actor path.
os.environ.setdefault("RLT_ZYGOTE", "1")

import pytest  # noqa: E402

from ray_lightning_tpu.analysis import sanitizer as _sanitizer  # noqa: E402
from ray_lightning_tpu.analysis.invariants import ThreadGuard  # noqa: E402

# Suites whose whole point is concurrent lock traffic run under the
# lock-order sanitizer (docs/development.md). Tests can also opt in
# individually with @pytest.mark.sanitize.
_SANITIZE_MARKERS = {
    "sanitize", "chaos", "elastic", "arbiter", "serving_chaos", "migration",
    "replay",
}


@pytest.fixture(autouse=True)
def _lock_sanitizer(request, monkeypatch):
    """Force RLT_SANITIZE=1 for sanitizer-marked tests and fail the test
    on any lock-order inversion observed while it ran. Locks created
    before the fixture (module-level registries) stay uninstrumented —
    only locks constructed during the test are checked, which is exactly
    the set the test exercises."""
    marked = _SANITIZE_MARKERS.intersection(
        m.name for m in request.node.iter_markers()
    )
    if not marked:
        yield
        return
    monkeypatch.setenv("RLT_SANITIZE", "1")
    _sanitizer.reset()
    yield
    inversions = _sanitizer.inversions()
    assert not inversions, (
        "lock-order inversion(s) observed during the test:\n"
        + "\n\n".join(str(i) for i in inversions)
    )


@pytest.fixture(autouse=True)
def _thread_guard(request):
    """No test may leak a non-daemon thread (it would wedge interpreter
    shutdown). Daemon pumps are exempt; so are tests that legitimately
    hand threads to a later test via module state (none today)."""
    guard = ThreadGuard.snapshot()
    yield
    leaked = guard.stragglers(grace=3.0)
    assert not leaked, (
        f"test leaked non-daemon thread(s): {[t.name for t in leaked]} — "
        "join them or make them daemons with an explicit shutdown path"
    )


@pytest.fixture
def tmp_root(tmp_path):
    return str(tmp_path)


@pytest.fixture
def no_xla_cache():
    """Compatibility no-op: the main test process never uses the
    persistent compilation cache (see the poison note above). Kept so
    MoE tests stay visibly annotated as the trigger of that failure."""
    yield
