"""Actor runtime: calls, remote errors, futures/wait, object store, queues,
cross-process handle pickling. (Role parity with what the reference assumes
of Ray core: SURVEY §2b "Ray core" row.)"""
import os

import pytest

from ray_lightning_tpu import runtime as rt


class _Counter:
    def __init__(self, start=0):
        self.x = start

    def incr(self, by=1):
        self.x += by
        return self.x

    def pid(self):
        return os.getpid()

    def boom(self):
        raise ValueError("kaboom")


@pytest.fixture(scope="module")
def counter_actor():
    rt.init()
    actor = rt.create_actor(_Counter, args=(10,), env={"JAX_PLATFORMS": "cpu"})
    yield actor
    rt.kill(actor)


def test_remote_call_and_state(counter_actor):
    assert counter_actor.incr.remote(5).result() == 15
    assert counter_actor.incr.remote().result() == 16


def test_actor_is_separate_process(counter_actor):
    assert counter_actor.pid.remote().result() != os.getpid()


def test_remote_exception_surfaces(counter_actor):
    with pytest.raises(rt.ActorError, match="kaboom"):
        counter_actor.boom.remote().result()


def test_wait_parity(counter_actor):
    futures = [counter_actor.incr.remote() for _ in range(4)]
    ready, not_ready = rt.wait(futures, num_returns=4, timeout=30)
    assert len(ready) == 4 and not not_ready


def test_object_store_roundtrip(counter_actor):
    ref = rt.put({"weights": list(range(100))})
    assert rt.get(ref)["weights"][-1] == 99
    # actor can read the driver's object and call back via a pickled handle
    class _Reader:
        def read(self, handle, ref):
            from ray_lightning_tpu import runtime as rt2

            return handle.call("incr", 0).result(), rt2.get(ref)["weights"][0]

    reader = rt.create_actor(_Reader, env={"JAX_PLATFORMS": "cpu"})
    try:
        count, first = reader.read.remote(counter_actor, ref).result()
        assert first == 0 and count >= 15
    finally:
        rt.kill(reader)


def test_queue_tunnel(counter_actor):
    q = rt.Queue()
    try:
        q.put(("metric", 1.23))
        q.put(("metric", 4.56))
        items = q.get_all()
        assert items == [("metric", 1.23), ("metric", 4.56)]
        assert q.empty()
    finally:
        q.shutdown()


def _fake_host(monkeypatch, functions, nodes):
    """A host whose PCI functions (``vendor:device``) and device nodes are
    the given lists."""
    import builtins
    import fnmatch
    import io

    from ray_lightning_tpu.runtime import api

    files = {}
    for i, function in enumerate(functions):
        vendor, device = function.split(":")
        files[f"/sys/bus/pci/devices/{i}/vendor"] = vendor
        files[f"/sys/bus/pci/devices/{i}/device"] = device

    def fake_glob(pattern):
        if pattern.startswith("/sys/bus/pci"):
            return sorted(f for f in files if f.endswith("/vendor"))
        return [n for n in nodes if fnmatch.fnmatchcase(n, pattern)]

    real_open = builtins.open
    monkeypatch.setattr(api.glob, "glob", fake_glob)
    monkeypatch.setattr(
        builtins, "open",
        lambda path, *a, **k: io.StringIO(files[path] + "\n")
        if path in files else real_open(path, *a, **k),
    )


_V5E, _V4, _GVNIC = "0x1ae0:0x0063", "0x1ae0:0x005e", "0x1ae0:0x0042"


@pytest.mark.parametrize(
    "functions,nodes,chips",
    [
        # the one-chip v5e machine: four TPU functions, one VFIO group
        ([_V5E] * 4 + ["0x8086:0x1237"], ["/dev/vfio/0", "/dev/vfio/vfio"], 1),
        ([_V5E] * 4, [f"/dev/vfio/{i}" for i in range(4)] + ["/dev/vfio/vfio"], 4),
        ([_V4] * 4, [f"/dev/accel{i}" for i in range(4)], 4),
        # VFIO groups of something else are not chips: another vendor's, or
        # a Google function that is no TPU (every GCE host has a gVNIC)
        (["0x10de:0x2330", "0x8086:0x1237"], ["/dev/vfio/0"], 0),
        ([_GVNIC], ["/dev/vfio/0"], 0),
        ([_GVNIC, _V5E], ["/dev/vfio/0", "/dev/vfio/1"], 1),
        ([], [], 0),
    ],
    ids=["v5e-1", "v5e-4-vfio", "v4-accel", "other-vfio", "gvnic-vfio",
         "gvnic-beside-a-chip", "bare"],
)
def test_tpu_chips_are_read_from_the_host_not_the_environment(
    monkeypatch, functions, nodes, chips
):
    """The TPU resource must not hinge on how JAX_PLATFORMS is spelled, nor
    on asking jax (the driver would take the chip from its workers)."""
    from ray_lightning_tpu.runtime import api

    _fake_host(monkeypatch, functions, nodes)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert api.local_tpu_chips() == chips
    assert api._local_default_resources() == ({"TPU": 1.0} if chips else {})
    # and the old spelling no longer conjures a chip
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert api.local_tpu_chips() == chips
