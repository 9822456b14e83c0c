"""Chip arbitration (ray_lightning_tpu/runtime/arbiter.py): the
SLO-driven train/serve ChipArbiter, its crash-consistent device ledger,
the ``arbiter:*`` fault family, and the satellites that ride with it
(autoscaler ``capacity_blocked``, SIGTERM weights flush, trainer
safe-boundary hooks, CLI status/force-transfer).

The acceptance bar is the slow e2e: two full borrow/return cycles over a
real LocalReplicaFleet under a sustained replica-kill loop PLUS one
arbiter crash-mid-borrow — every serve request token-identical to an
unfaulted ``generate()``, training params bitwise-identical to an
unfaulted run of the same step count, and the ledger left with no
leaked or double-assigned device.
"""
import contextlib
import dataclasses
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.observability.slo import SLOMonitor
from ray_lightning_tpu.runtime import faults
from ray_lightning_tpu.runtime.arbiter import (
    ChipArbiter,
    FleetServeHandle,
    LedgerInvariantError,
    TransferTimeout,
    read_ledger,
)
from ray_lightning_tpu.serving import (
    CapacityBlocked,
    LocalReplicaFleet,
)
from ray_lightning_tpu.serving.replica import Autoscaler
from ray_lightning_tpu.serving.resilience import install_sigterm_drain

pytestmark = pytest.mark.arbiter


# --------------------------------------------------------------------- #
# shared fakes + fixtures
# --------------------------------------------------------------------- #
def _cfg():
    # float32 so greedy argmax ties cannot fall differently between the
    # batched serving path and the sequential generate() reference
    return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return init_params(jax.random.key(0), cfg), cfg


def _reference(params, cfg, prompt, n_new):
    out = generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=n_new
    )
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@contextlib.contextmanager
def _fault_env(spec):
    """Arm RLT_FAULT with no fuse dir, so @every faults keep firing
    across relaunches (a true sustained kill loop) and arbiter
    @transferN faults rely on the ledger's persistent transfer_seq for
    their one-shot semantics. Restores env + all three parse caches."""
    old = os.environ.get(faults.FAULT_ENV)
    old_fuse = os.environ.pop(faults.FUSE_ENV, None)
    os.environ[faults.FAULT_ENV] = spec
    faults._cache = (None, [])
    faults._serve_cache = (None, [])
    faults._arbiter_cache = (None, [])
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(faults.FAULT_ENV, None)
        else:
            os.environ[faults.FAULT_ENV] = old
        if old_fuse is not None:
            os.environ[faults.FUSE_ENV] = old_fuse
        faults._cache = (None, [])
        faults._serve_cache = (None, [])
        faults._arbiter_cache = (None, [])


ENGINE_KW = dict(num_slots=4, max_prompt_len=16, max_len=32, max_queue=64)


class FakeTrain:
    """Train-side handle: a device list, shrink pops from the end."""

    def __init__(self, devs):
        self._devs = list(devs)
        self.shrinks = []
        self.grows = []

    def devices(self):
        return list(self._devs)

    def shrink(self, count):
        freed = [self._devs.pop() for _ in range(count)]
        self.shrinks.append(list(freed))
        return freed

    def grow(self, devices):
        self.grows.append(list(devices))
        for d in devices:
            if d not in self._devs:
                self._devs.append(d)


class FakeServe:
    """Serve-side handle: device -> replica index, scriptable loads."""

    def __init__(self):
        self._by_device = {}
        self._next = 0
        self.load_entries = {}
        self.spawn_error = None

    def devices(self):
        return dict(self._by_device)

    def add_replica(self, device):
        if self.spawn_error is not None:
            raise self.spawn_error
        idx = self._next
        self._next += 1
        self._by_device[str(device)] = idx
        return idx

    def remove_replica(self, index):
        for d, i in list(self._by_device.items()):
            if i == index:
                del self._by_device[d]
                return
        raise KeyError(index)

    def loads(self):
        return dict(self.load_entries)


class Burn:
    """SLO-monitor stub with a dialable fast burn / breach verdict."""

    def __init__(self, fast=0.0, breached=False):
        self.fast = fast
        self.breached_flag = breached

    def serving_fast_burn(self, now=None):
        return self.fast

    def serving_breached(self):
        return self.breached_flag


def _arbiter(tmp_path, train, serve, **kw):
    kw.setdefault("devices", train.devices())
    kw.setdefault("cooldown_s", 0.0)
    return ChipArbiter(str(tmp_path / "led"), train, serve, **kw)


def _assert_no_leaks(arb, train, serve, all_devs):
    """No device leaked or double-assigned: the ledger partitions the
    reservation and matches both handles' ground truth."""
    led = read_ledger(arb.ledger_dir)
    assert set(led["owner"]) == set(all_devs)
    t, s = set(train.devices()), set(serve.devices())
    assert not (t & s)
    assert {d for d, o in led["owner"].items() if o == "train"} == t
    assert {d for d, o in led["owner"].items() if o == "serve"} == s


# --------------------------------------------------------------------- #
# fault grammar: three families in one RLT_FAULT value (satellite 6)
# --------------------------------------------------------------------- #
def test_mixed_fault_families_parse_independently():
    mixed = (
        "rank1:crash@step5, replica0:crash@every:8,"
        "arbiter:crash-mid-borrow@transfer2, rank0:slow@step4:2.5,"
        "replica1:drop-stream@req2:4, arbiter:stall@every:3:0.5"
    )
    ranks = faults.parse_faults(mixed)
    assert [(s.rank, s.kind) for s in ranks] == [(1, "crash"), (0, "slow")]
    reps = faults.parse_serve_faults(mixed)
    assert [(s.replica, s.kind) for s in reps] == [
        (0, "crash"),
        (1, "drop-stream"),
    ]
    arbs = faults.parse_arbiter_faults(mixed)
    assert [(s.kind, s.transfer, s.every) for s in arbs] == [
        ("crash-mid-borrow", 2, None),
        ("stall", None, 3),
    ]
    assert arbs[1].arg == 0.5


def test_unknown_family_rejected_by_every_parser():
    for parser in (
        faults.parse_faults,
        faults.parse_serve_faults,
        faults.parse_arbiter_faults,
    ):
        with pytest.raises(ValueError):
            parser("gizmo0:crash@step1")


def test_bad_arbiter_specs_rejected():
    for bad in (
        "arbiter:stall@transfer1",  # stall needs a length
        "arbiter:crash-mid-borrow@every:0",
        "arbiter:crash-mid-borrow@transfer0",
        "arbiter:explode@transfer1",
        "arbiter:crash-mid-borrow",  # needs a @where
    ):
        with pytest.raises(ValueError):
            faults.parse_arbiter_faults(bad)


def test_arbiter_fuse_ids_distinct_per_firing_transfer():
    (every,) = faults.parse_arbiter_faults("arbiter:stall@every:2:0.1")
    (once,) = faults.parse_arbiter_faults("arbiter:stall@transfer2:0.1")
    assert every.fuse_id != once.fuse_id
    assert every.fuse_id_at(2) != every.fuse_id_at(4)
    assert once.fuse_id_at(2) == once.fuse_id
    assert every.matches_transfer(4) and not every.matches_transfer(3)
    assert once.matches_transfer(2) and not once.matches_transfer(4)


def test_mixed_env_fires_only_the_arbiter_family():
    with _fault_env(
        "rank0:crash@step1,replica0:crash@tick1,"
        "arbiter:crash-mid-borrow@transfer1"
    ):
        # the rank/replica specs in the same value must not perturb the
        # arbiter hook (and vice versa: parsing them out did not error)
        with pytest.raises(faults.ArbiterFault):
            faults.fire_arbiter_faults(1, "mid-borrow")
        faults.fire_arbiter_faults(2, "mid-borrow")  # wrong transfer: no-op
        faults.fire_arbiter_faults(1, "mid-return")  # wrong point: no-op


# --------------------------------------------------------------------- #
# arbiter state machine: borrow / return happy paths
# --------------------------------------------------------------------- #
def test_fresh_ledger_seeds_steady_all_train(tmp_path):
    train = FakeTrain(["t0", "t1"])
    arb = _arbiter(tmp_path, train, FakeServe())
    assert arb.state == "steady"
    assert arb.devices_by_owner() == {
        "train": ["t0", "t1"],
        "serve": [],
        "transit": [],
    }
    assert arb.tick() == "idle"  # no signals, nothing to do
    led = read_ledger(arb.ledger_dir)
    assert led["state"] == "steady" and led["transfer"] is None


def test_devices_required_without_ledger(tmp_path):
    with pytest.raises(ValueError):
        ChipArbiter(str(tmp_path), FakeTrain(["t0"]), FakeServe())


def test_slo_burn_drives_borrow_and_idle_drives_return(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    burn = Burn(fast=10.0)
    clock = [0.0]
    arb = _arbiter(
        tmp_path,
        train,
        serve,
        slo_monitor=burn,
        borrow_burn=6.0,
        idle_ticks_return=2,
        clock=lambda: clock[0],
    )
    assert arb.tick() == "borrowed"
    assert arb.state == "lent"
    assert arb.borrowed_devices() == ["t1"]
    assert serve.devices() == {"t1": 0}
    assert train.devices() == ["t0"]
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])

    # busy serving resets the idle streak; quiet ticks accumulate it
    burn.fast = 0.0
    serve.load_entries = {0: {"queue_depth": 3.0, "active": 1.0}}
    assert arb.tick() == "idle"
    serve.load_entries = {0: {"queue_depth": 0.0, "active": 0.0}}
    assert arb.tick() == "idle"  # streak 1 of 2
    assert arb.tick() == "returned"
    assert arb.state == "steady"
    assert serve.devices() == {} and set(train.devices()) == {"t0", "t1"}
    assert arb.transfers_completed == 2
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])


def test_intent_is_journaled_before_acting(tmp_path):
    """Crash-consistency contract: by the time the train handle is asked
    to shrink, the ledger on disk already names the transfer."""
    seen = {}

    class SpyTrain(FakeTrain):
        def shrink(self, count):
            led = read_ledger(os.path.dirname(seen["path"]))
            seen["state"] = led["state"]
            seen["transfer"] = led["transfer"]
            return super().shrink(count)

    train, serve = SpyTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve)
    seen["path"] = arb.ledger_path
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    assert seen["state"] == "draining"
    assert seen["transfer"]["direction"] == "borrow"
    assert seen["transfer"]["id"] == 1


def test_borrow_refused_at_min_train_floor(tmp_path):
    train = FakeTrain(["t0"])
    arb = _arbiter(tmp_path, train, FakeServe(), min_train_devices=1)
    arb.request_transfer("borrow")
    assert arb.tick() == "at_floor"  # even forced transfers honor floors
    assert arb.state == "steady" and train.devices() == ["t0"]


def test_cooldown_separates_transfers_but_force_bypasses(tmp_path):
    clock = [0.0]
    burn = Burn(fast=10.0)
    arb = _arbiter(
        tmp_path,
        FakeTrain(["t0", "t1", "t2"]),
        FakeServe(),
        slo_monitor=burn,
        cooldown_s=30.0,
        idle_ticks_return=2,
        clock=lambda: clock[0],
    )
    assert arb.tick() == "borrowed"
    burn.fast = 0.0
    assert arb.tick() == "idle"  # idle streak 1 -> wants return, but...
    clock[0] = 10.0
    assert arb.tick() == "cooldown"  # ...the do-not-thrash window holds
    arb.request_transfer("return")
    assert arb.tick() == "returned"  # operator override bypasses it
    clock[0] = 12.0
    burn.fast = 10.0
    assert arb.tick() == "cooldown"  # and the return re-armed the window
    clock[0] = 50.0
    assert arb.tick() == "borrowed"


def test_capacity_blocked_streak_is_a_borrow_signal(tmp_path):
    class Asc:
        capacity_blocked_streak = 0

    asc = Asc()
    arb = _arbiter(tmp_path, FakeTrain(["t0", "t1"]), FakeServe(), autoscaler=asc)
    assert arb.tick() == "idle"
    asc.capacity_blocked_streak = 2
    assert arb.tick() == "borrowed"
    assert arb.borrowed_devices() == ["t1"]


# --------------------------------------------------------------------- #
# SLO veto on return (satellite 3)
# --------------------------------------------------------------------- #
def test_return_vetoed_while_serving_slo_burn_active(tmp_path):
    """A real SLOMonitor on a scripted clock: bad TTFT latencies breach
    the serving objective, the arbiter refuses to repatriate the chip,
    and only after the fast window recovers does the return run."""
    clock = [1000.0]
    tick = lambda: clock[0]
    mon = SLOMonitor(fast_burn=2.0, slow_burn=1.0, clock=tick)
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(
        tmp_path,
        train,
        serve,
        slo_monitor=mon,
        idle_ticks_return=1,
        clock=tick,
    )
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"

    # ttft_p95: threshold 2.0s, budget 5% -> all-bad burns 20x
    for _ in range(10):
        mon.observe_latency("ttft_p95", 5.0)
    mon.evaluate()
    assert mon.serving_breached()
    assert arb.tick() == "vetoed"
    assert arb.tick() == "vetoed"  # stays vetoed while the burn holds
    assert arb.state == "lent" and serve.devices() == {"t1": 0}

    # recovery: the bad samples age out of the fast window and good
    # traffic replaces them; the breach clears and the veto lifts
    clock[0] += 120.0
    for _ in range(10):
        mon.observe_latency("ttft_p95", 0.01)
    mon.evaluate()
    assert not mon.serving_breached()
    assert arb.tick() == "returned"
    assert arb.state == "steady" and serve.devices() == {}


def test_force_return_overrides_the_veto(tmp_path):
    arb = _arbiter(
        tmp_path,
        FakeTrain(["t0", "t1"]),
        FakeServe(),
        slo_monitor=Burn(breached=True),
        idle_ticks_return=1,
    )
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    assert arb.tick() == "vetoed"
    arb.request_transfer("return")
    assert arb.tick() == "returned"


# --------------------------------------------------------------------- #
# failure handling: rollback, backoff, deadlines
# --------------------------------------------------------------------- #
def test_spawn_failure_cancels_borrow_cleanly_with_backoff(tmp_path):
    clock = [0.0]
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    burn = Burn(fast=10.0)
    arb = _arbiter(
        tmp_path,
        train,
        serve,
        slo_monitor=burn,
        cooldown_s=0.0,
        backoff_base_s=4.0,
        clock=lambda: clock[0],
    )
    with _fault_env("arbiter:spawn-fail@transfer1"):
        assert arb.tick() == "rolled_back"
    # clean cancel: chips back on the training side, nothing half-owned
    assert arb.state == "steady"
    assert set(train.devices()) == {"t0", "t1"} and serve.devices() == {}
    led = read_ledger(arb.ledger_dir)
    assert led["failures"] == 1 and led["transfer"] is None
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])

    clock[0] = 1.0
    assert arb.tick() == "cooldown"  # exponential backoff holds the retry
    clock[0] = 5.0
    assert arb.tick() == "borrowed"  # transfer 2: the @transfer1 fault
    assert read_ledger(arb.ledger_dir)["failures"] == 0  # misses, success resets


def test_transition_deadline_times_out_a_stuck_shrink(tmp_path):
    class StuckTrain(FakeTrain):
        def shrink(self, count):
            time.sleep(0.3)
            return []

    clock = [0.0]
    train = StuckTrain(["t0", "t1"])
    arb = _arbiter(
        tmp_path,
        train,
        FakeServe(),
        transition_timeout_s=0.05,
        clock=lambda: clock[0],
    )
    arb.request_transfer("borrow")
    assert arb.tick() == "rolled_back"
    assert arb.state == "steady"
    assert set(train.devices()) == {"t0", "t1"}
    assert read_ledger(arb.ledger_dir)["failures"] == 1


def test_failed_drain_is_retried_not_skipped(tmp_path):
    """A return whose drain fails must leave the device serve-owned with
    its replica index intact, so the retried return drains it again —
    never regrow a chip a live replica may still hold."""

    class FlakyDrainServe(FakeServe):
        def __init__(self):
            super().__init__()
            self.drain_failures_left = 1

        def remove_replica(self, index):
            if self.drain_failures_left > 0:
                self.drain_failures_left -= 1
                raise RuntimeError("drain wedged")
            super().remove_replica(index)

    clock = [0.0]
    train, serve = FakeTrain(["t0", "t1"]), FlakyDrainServe()
    arb = _arbiter(
        tmp_path, train, serve, backoff_base_s=1.0, clock=lambda: clock[0]
    )
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    arb.request_transfer("return")
    assert arb.tick() == "rolled_back"
    led = read_ledger(arb.ledger_dir)
    assert led["owner"]["t1"] == "serve"
    assert led["replicas"]["t1"] == 0  # the mapping survived the failure
    assert "t1" in serve.devices() and "t1" not in train.devices()
    assert arb.state == "lent"

    clock[0] = 10.0
    arb.request_transfer("return")
    assert arb.tick() == "returned"
    assert serve.devices() == {} and set(train.devices()) == {"t0", "t1"}
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])


def test_rollback_drain_failure_keeps_booted_replica_lent(tmp_path):
    """A borrow whose second spawn fails rolls back; if draining the
    first (already booted) replica also fails, that chip must stay
    serve-owned — the replica may still be live on it, so regrowing it
    into training would double-assign the device."""

    class Serve(FakeServe):
        def __init__(self):
            super().__init__()
            self.fail_drain = True

        def add_replica(self, device):
            if self._next >= 1:
                raise RuntimeError("second boot failed")
            return super().add_replica(device)

        def remove_replica(self, index):
            if self.fail_drain:
                raise RuntimeError("drain wedged")
            super().remove_replica(index)

    train, serve = FakeTrain(["t0", "t1", "t2"]), Serve()
    arb = _arbiter(tmp_path, train, serve, borrow_count=2)
    arb.request_transfer("borrow")
    assert arb.tick() == "rolled_back"
    led = read_ledger(arb.ledger_dir)
    # shrink freed t2 then t1; t2 booted replica 0, t1's spawn failed
    assert led["owner"]["t2"] == "serve" and led["replicas"]["t2"] == 0
    assert led["owner"]["t1"] == "train"  # the unbooted chip regrew
    assert arb.state == "lent" and serve.devices() == {"t2": 0}

    # once the drain works again, a return repatriates the stranded chip
    serve.fail_drain = False
    arb.request_transfer("return")
    assert arb.tick() == "returned"
    assert serve.devices() == {}
    assert set(train.devices()) == {"t0", "t1", "t2"}
    _assert_no_leaks(arb, train, serve, ["t0", "t1", "t2"])


class GrowFailTrain(FakeTrain):
    """Train handle whose grow can be wedged, stranding chips transit."""

    def __init__(self, devs):
        super().__init__(devs)
        self.fail_grow = False

    def grow(self, devices):
        if self.fail_grow:
            raise RuntimeError("mesh wedged")
        super().grow(devices)


def _strand_transit_chip(tmp_path, clock):
    """Drive a borrow whose spawn AND rollback regrow both fail: t1 ends
    journaled ``transit`` with ``transfer=None`` — owned by neither
    side."""
    train, serve = GrowFailTrain(["t0", "t1"]), FakeServe()
    serve.spawn_error = RuntimeError("no replica for you")
    arb = _arbiter(
        tmp_path, train, serve, backoff_base_s=1.0, clock=lambda: clock[0]
    )
    train.fail_grow = True
    arb.request_transfer("borrow")
    assert arb.tick() == "rolled_back"
    led = read_ledger(arb.ledger_dir)
    assert led["owner"]["t1"] == "transit" and led["transfer"] is None
    assert "t1" not in train.devices() and "t1" not in serve.devices()
    train.fail_grow = False
    serve.spawn_error = None
    return arb, train, serve


def test_stray_transit_chips_reclaimed_by_tick(tmp_path):
    """Chips stranded transit by a failed rollback regrow must not leak:
    the steady-state tick sweeps them back into the mesh (no force file,
    no restart needed) once the backoff expires."""
    clock = [0.0]
    arb, train, serve = _strand_transit_chip(tmp_path, clock)
    clock[0] = 10.0  # past the failure backoff
    assert arb.tick() == "returned"
    assert arb.state == "steady"
    assert set(train.devices()) == {"t0", "t1"}
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])


def test_stray_transit_chips_reclaimed_on_restart(tmp_path):
    """Restart recovery regrows stranded transit chips even though the
    ledger has no transfer record explaining them."""
    clock = [0.0]
    arb, train, serve = _strand_transit_chip(tmp_path, clock)
    arb2 = ChipArbiter(arb.ledger_dir, train, serve)
    assert arb2.recovered_action == "adopted"
    assert arb2.state == "steady"
    assert set(train.devices()) == {"t0", "t1"}
    _assert_no_leaks(arb2, train, serve, ["t0", "t1"])


def test_late_landing_shrink_is_reconciled_after_timeout(tmp_path):
    """A shrink that completes AFTER its phase deadline still frees the
    chip behind the arbiter's back. The post-timeout ground-truth
    reconcile must catch the late landing and repatriate the chip
    instead of silently leaking it with owner still 'train'."""

    class SlowTrain(FakeTrain):
        def shrink(self, count):
            time.sleep(0.2)
            return super().shrink(count)

    clock = [0.0]
    train, serve = SlowTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(
        tmp_path,
        train,
        serve,
        transition_timeout_s=0.05,
        backoff_base_s=0.01,
        clock=lambda: clock[0],
    )
    arb.request_transfer("borrow")
    assert arb.tick() == "rolled_back"  # deadline fired; freed looked empty
    time.sleep(0.4)  # the abandoned shrink lands: t1 leaves the mesh
    assert "t1" not in train.devices()
    assert read_ledger(arb.ledger_dir)["owner"]["t1"] == "train"  # diverged

    clock[0] = 10.0
    assert arb.tick() == "returned"  # reconcile -> stray -> regrown
    assert set(train.devices()) == {"t0", "t1"}
    _assert_no_leaks(arb, train, serve, ["t0", "t1"])


# --------------------------------------------------------------------- #
# crash-consistency: ledger recovery on arbiter restart
# --------------------------------------------------------------------- #
def test_crash_mid_borrow_recovery_completes_the_transfer(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve)
    arb.request_transfer("borrow")
    with _fault_env("arbiter:crash-mid-borrow@transfer1"):
        with pytest.raises(faults.ArbiterFault):
            arb.tick()
    # the arbiter died with the chip freed but no replica booted: the
    # ledger on disk names exactly that
    led = read_ledger(arb.ledger_dir)
    assert led["state"] == "resharding"
    assert led["transfer"]["direction"] == "borrow"
    assert led["transfer"]["devices"] == ["t1"]
    assert led["owner"]["t1"] == "transit"
    assert "t1" not in train.devices() and "t1" not in serve.devices()

    # restart: recovery completes the journaled intent
    arb2 = ChipArbiter(arb.ledger_dir, train, serve)
    assert arb2.recovered_action == "completed"
    assert arb2.state == "lent"
    assert arb2.borrowed_devices() == ["t1"]
    assert serve.devices() == {"t1": 0}
    assert arb2.transfers_completed == 1
    _assert_no_leaks(arb2, train, serve, ["t0", "t1"])


def test_crash_mid_borrow_recovery_rolls_back_when_spawn_fails(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve)
    arb.request_transfer("borrow")
    with _fault_env("arbiter:crash-mid-borrow@transfer1"):
        with pytest.raises(faults.ArbiterFault):
            arb.tick()

    serve.spawn_error = RuntimeError("no capacity on restart")
    arb2 = ChipArbiter(arb.ledger_dir, train, serve)
    assert arb2.recovered_action == "rolled_back"
    assert arb2.state == "steady"
    assert set(train.devices()) == {"t0", "t1"} and serve.devices() == {}
    assert arb2.transfers_completed == 0
    _assert_no_leaks(arb2, train, serve, ["t0", "t1"])


def test_crash_mid_return_recovery_regrows_training(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve, idle_ticks_return=1)
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    arb.request_transfer("return")
    with _fault_env("arbiter:crash-mid-return@transfer2"):
        with pytest.raises(faults.ArbiterFault):
            arb.tick()
    led = read_ledger(arb.ledger_dir)
    assert led["state"] == "return_pending"
    assert led["transfer"]["direction"] == "return"
    assert led["owner"]["t1"] == "transit"  # drained, not yet regrown

    arb2 = ChipArbiter(arb.ledger_dir, train, serve)
    assert arb2.recovered_action == "completed"
    assert arb2.state == "steady"
    assert set(train.devices()) == {"t0", "t1"} and serve.devices() == {}
    assert arb2.transfers_completed == 2
    _assert_no_leaks(arb2, train, serve, ["t0", "t1"])


def test_clean_ledger_adopts_landed_devices_without_transfer(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve)
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    # a clean restart over a lent ledger: nothing to repair, stays lent
    arb2 = ChipArbiter(arb.ledger_dir, train, serve)
    assert arb2.recovered_action is None
    assert arb2.state == "lent" and arb2.borrowed_devices() == ["t1"]


def test_double_assigned_device_is_an_invariant_error(tmp_path):
    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = _arbiter(tmp_path, train, serve)
    arb.request_transfer("borrow")
    assert arb.tick() == "borrowed"
    # ground truth gone insane: both handles claim t1
    train.grow(["t1"])
    with pytest.raises(LedgerInvariantError):
        ChipArbiter(arb.ledger_dir, train, serve)


# --------------------------------------------------------------------- #
# autoscaler capacity_blocked outcome (satellite 1)
# --------------------------------------------------------------------- #
class _BlockedFleet:
    num_replicas = 1

    def __init__(self):
        self.blocked = True
        self.adds = 0

    def loads(self):
        return {0: {"queue_depth": 50.0, "active": 1.0, "ttft_p95_ms": 0.0}}

    def add_replica(self):
        if self.blocked:
            raise CapacityBlocked("fleet at capacity (1/1): no free device")
        self.adds += 1
        return self.adds

    def remove_replica(self):
        pass


def test_autoscaler_reports_capacity_blocked_and_resets_on_success():
    fleet = _BlockedFleet()
    asc = Autoscaler(fleet, min_replicas=1, max_replicas=4, queue_high=4.0)
    assert asc.tick(now=0.0) == 0  # wants +1, fleet has no device
    assert asc.tick(now=1.0) == 0
    assert asc.capacity_blocked_total == 2
    assert asc.capacity_blocked_streak == 2
    assert asc.last_outcome == "capacity_blocked"
    assert asc.scale_ups == 0
    # a blocked verdict is not a scale action: no cooldown was armed,
    # so the moment a device appears the add goes through
    fleet.blocked = False
    assert asc.tick(now=1.5) == 1
    assert asc.scale_ups == 1 and fleet.adds == 1
    assert asc.capacity_blocked_streak == 0  # streak resets, total stays
    assert asc.capacity_blocked_total == 2
    assert asc.last_outcome == "scale_up"


def test_capacity_blocked_streak_clears_when_demand_subsides():
    """A stale streak would make the arbiter re-borrow a chip right
    after every idle-driven return (borrow/return thrash bounded only by
    cooldown): once the verdict stops asking for capacity, the borrow
    signal must clear."""
    fleet = _BlockedFleet()
    asc = Autoscaler(fleet, min_replicas=1, max_replicas=4, queue_high=4.0)
    assert asc.tick(now=0.0) == 0
    assert asc.capacity_blocked_streak == 1
    # the burst passes: the queue empties and no scale-up is wanted
    fleet.loads = lambda: {0: {"queue_depth": 0.0, "active": 0.0}}
    assert asc.tick(now=1.0) == 0
    assert asc.capacity_blocked_streak == 0
    assert asc.capacity_blocked_total == 1  # the counter keeps history


def test_fleet_capacity_blocks_scale_up_until_granted(model):
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=ENGINE_KW,
        initial_replicas=1,
        capacity=1,
    )
    try:
        with pytest.raises(CapacityBlocked):
            fleet.add_replica()
        assert fleet.num_replicas == 1
        fleet.grant_capacity(1)  # the arbiter lends a chip
        idx = fleet.add_replica()
        assert fleet.num_replicas == 2
        assert isinstance(idx, int)
    finally:
        fleet.shutdown()


# --------------------------------------------------------------------- #
# FleetServeHandle: the arbiter <-> LocalReplicaFleet adapter
# --------------------------------------------------------------------- #
def test_fleet_serve_handle_grants_and_revokes_capacity():
    class _Fleet:
        def __init__(self):
            self.capacity = 1
            self._draining = {}
            self._next = 0
            self.fail_add = False

        def grant_capacity(self, n=1):
            self.capacity += n

        def revoke_capacity(self, n=1):
            self.capacity = max(1, self.capacity - n)

        def add_replica(self):
            if self.fail_add:
                raise RuntimeError("boot failed")
            idx = self._next
            self._next += 1
            return idx

        def preempt_replica(self, index):
            return True

        def loads(self):
            return {}

    fleet = _Fleet()
    handle = FleetServeHandle(fleet)
    assert handle.add_replica("c3") == 0
    assert handle.devices() == {"c3": 0} and fleet.capacity == 2

    handle.remove_replica(0)
    assert handle.devices() == {} and fleet.capacity == 1

    # a failed boot must hand the capacity grant straight back
    fleet.fail_add = True
    with pytest.raises(RuntimeError):
        handle.add_replica("c4")
    assert fleet.capacity == 1 and handle.devices() == {}


def test_fleet_serve_handle_drain_timeout_settles_books_once():
    """A drain timeout removed the replica from routing irrevocably: the
    grant and device slot must be released anyway (or fleet capacity
    stays inflated by one and the autoscaler over-places), exactly once
    across however many retries, and the retried removal converges once
    the drain finally lands."""

    class _Fleet:
        def __init__(self):
            self.capacity = 2
            self._replicas = {}
            self._draining = {}
            self._next = 0

        def grant_capacity(self, n=1):
            self.capacity += n

        def revoke_capacity(self, n=1):
            self.capacity = max(1, self.capacity - n)

        def add_replica(self):
            idx = self._next
            self._next += 1
            self._replicas[idx] = object()
            return idx

        def preempt_replica(self, index):
            engine = self._replicas.pop(index, None)
            if engine is None:
                return False
            self._draining[index] = engine
            return True

        def loads(self):
            return {}

    fleet = _Fleet()
    handle = FleetServeHandle(fleet, drain_timeout_s=0.05, drain_poll_s=0.01)
    assert handle.add_replica("c0") == 0
    assert fleet.capacity == 3
    # the drain never settles: grant revoked, device slot freed, raise
    with pytest.raises(TransferTimeout):
        handle.remove_replica(0)
    assert fleet.capacity == 2 and handle.devices() == {}
    # retry while the drain is still in flight: times out again but
    # never double-revokes
    with pytest.raises(TransferTimeout):
        handle.remove_replica(0)
    assert fleet.capacity == 2
    # the drain finally lands: the retried removal converges cleanly
    del fleet._draining[0]
    handle.remove_replica(0)
    assert fleet.capacity == 2
    # a replica that never existed is still an error, never a revoke
    with pytest.raises(RuntimeError):
        handle.remove_replica(99)
    assert fleet.capacity == 2


# --------------------------------------------------------------------- #
# SIGTERM preemption drain flushes training weights (satellite 2)
# --------------------------------------------------------------------- #
def test_sigterm_drain_flushes_weights_only_checkpoint(tmp_path):
    class _Fleet:
        def __init__(self):
            self.preempted = 0

        def preempt_all(self):
            self.preempted += 1

    class _Trainer:
        def __init__(self):
            self.saved = []

        def save_checkpoint(self, path, weights_only=False):
            self.saved.append((path, weights_only))

    class _BrokenTrainer:
        def save_checkpoint(self, path, weights_only=False):
            raise RuntimeError("disk gone")

    original = signal.getsignal(signal.SIGTERM)
    try:
        fleet, tr = _Fleet(), _Trainer()
        path = str(tmp_path / "preempt.ckpt")
        handler = install_sigterm_drain(fleet, trainer=tr, checkpoint_path=path)
        handler(signal.SIGTERM, None)
        assert fleet.preempted == 1
        assert tr.saved == [(path, True)]  # weights-only, at the named path

        tr2 = _Trainer()  # default path when none is given
        install_sigterm_drain(fleet, trainer=tr2)(signal.SIGTERM, None)
        assert tr2.saved == [("rlt_preempt_weights.ckpt", True)]

        # a broken checkpoint flush must not turn the drain into a crash
        install_sigterm_drain(fleet, trainer=_BrokenTrainer())(
            signal.SIGTERM, None
        )
        assert fleet.preempted == 3

        # no trainer: the serving-only behavior is unchanged
        install_sigterm_drain(fleet)(signal.SIGTERM, None)
        assert fleet.preempted == 4
    finally:
        signal.signal(signal.SIGTERM, original)


# --------------------------------------------------------------------- #
# trainer safe-boundary hooks (the arbiter's shrink/grow anchor points)
# --------------------------------------------------------------------- #
def test_trainer_fires_safe_boundary_hooks(tmp_root):
    from tests.utils import BoringModel, get_trainer

    calls = []
    model = BoringModel()
    trainer = get_trainer(
        tmp_root, max_epochs=1, limit_train_batches=3,
        checkpoint_callback=False,
    )
    trainer.register_safe_boundary_hook(
        lambda step, boundary: calls.append((step, boundary))
    )
    # a hook that raises must be swallowed, never killing the step loop
    trainer.register_safe_boundary_hook(lambda step, boundary: 1 / 0)
    trainer.fit(model)
    kinds = [b for _, b in calls]
    assert kinds.count("step") == 3  # one per training health tick
    assert kinds.count("epoch_end") == 1
    assert trainer.state.status == "finished"


# --------------------------------------------------------------------- #
# CLI: arbiter status / force-transfer
# --------------------------------------------------------------------- #
def test_cli_arbiter_status_and_force_transfer(tmp_path, capsys):
    from ray_lightning_tpu import cli

    d = str(tmp_path / "led")
    assert cli.main(["arbiter", "status", "--ledger-dir", d]) == 1
    capsys.readouterr()

    train, serve = FakeTrain(["t0", "t1"]), FakeServe()
    arb = ChipArbiter(d, train, serve, devices=["t0", "t1"], cooldown_s=0.0)
    assert cli.main(["arbiter", "status", "--ledger-dir", d]) == 0
    out = capsys.readouterr().out
    assert "steady" in out and "t0" in out

    assert (
        cli.main(
            ["arbiter", "status", "--ledger-dir", d, "--json"]
        )
        == 0
    )
    led = json.loads(capsys.readouterr().out)
    assert led["state"] == "steady"
    assert set(led["owner"]) == {"t0", "t1"}

    # the CLI's force file is consumed by the live arbiter's next tick
    assert (
        cli.main(
            [
                "arbiter",
                "force-transfer",
                "--ledger-dir",
                d,
                "--direction",
                "borrow",
            ]
        )
        == 0
    )
    assert arb.tick() == "borrowed"
    assert arb.state == "lent"


# --------------------------------------------------------------------- #
# the chaos e2e: two borrow/return cycles under a replica kill loop
# plus one arbiter crash-mid-borrow (slow; scripts/chaos.sh runs it)
# --------------------------------------------------------------------- #
def _sim_batch(step):
    # the batch is a pure function of the step index, so params after N
    # steps are bitwise-reproducible however shrinks/grows interleave
    return jax.random.normal(jax.random.key(step), (8, 4), jnp.float32)


class SimTrain:
    """Training-side handle running a REAL jitted optimizer step: owns a
    device list, and ``grow`` immediately takes a step on the regrown
    mesh to prove training is live after every repatriation."""

    def __init__(self, devs):
        self._devs = list(devs)
        self.params = {
            "w": jnp.ones((4, 4), jnp.float32),
            "b": jnp.zeros((4,), jnp.float32),
        }
        self._opt = optax.sgd(0.05)
        self._opt_state = self._opt.init(self.params)
        self.steps = 0

        def loss(p, batch):
            return jnp.mean((batch @ p["w"] + p["b"]) ** 2)

        @jax.jit
        def step(p, s, batch):
            grads = jax.grad(loss)(p, batch)
            updates, s = self._opt.update(grads, s)
            return optax.apply_updates(p, updates), s

        self._step = step

    def devices(self):
        return list(self._devs)

    def shrink(self, count):
        return [self._devs.pop() for _ in range(count)]

    def grow(self, devices):
        for d in devices:
            if d not in self._devs:
                self._devs.append(d)
        self.run_steps(1)

    def run_steps(self, n):
        for _ in range(n):
            self.params, self._opt_state = self._step(
                self.params, self._opt_state, _sim_batch(self.steps)
            )
            self.steps += 1
        jax.block_until_ready(self.params)


def test_forced_cycle_over_a_real_fleet(model, tmp_path):
    """One forced borrow/return cycle with nothing faked on the serve side:
    `ChipArbiter` over `FleetServeHandle` over a `LocalReplicaFleet` at
    capacity, a jitted step on the train side. The borrowed chip boots a
    second replica that serves the reference's tokens; the return drains it
    and training takes a step on the regrown devices."""
    params, cfg = model
    train = SimTrain(["c0", "c1"])
    fleet = LocalReplicaFleet(
        lambda: (params, cfg), engine_kwargs=ENGINE_KW,
        initial_replicas=1, capacity=1,
    )
    try:
        serve = FleetServeHandle(fleet)
        arb = _arbiter(tmp_path, train, serve, min_train_devices=1)
        arb.request_transfer("borrow")
        assert arb.tick() == "borrowed"
        assert arb.state == "lent" and fleet.num_replicas == 2
        assert train.devices() == ["c0"] and list(serve.devices()) == ["c1"]
        prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5]]
        entries = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        for p, e in zip(prompts, entries):
            assert e.result(timeout=120) == _reference(params, cfg, p, 4)

        steps = train.steps
        arb.request_transfer("return")
        assert arb.tick() == "returned"
        assert arb.state == "steady" and arb.transfers_completed == 2
        assert fleet.num_replicas == 1 and train.steps == steps + 1
        _assert_no_leaks(arb, train, serve, ["c0", "c1"])
    finally:
        fleet.shutdown()


@pytest.mark.slow
def test_arbitration_kill_loop_e2e(model, tmp_path):
    """The PR's acceptance bar, end to end:

    - a sustained ``replica0:crash@every:N`` kill loop runs the whole
      time (no fuse: relaunched engines keep dying);
    - cycle 1's borrow is killed by ``arbiter:crash-mid-borrow`` with
      the chip freed and no replica booted; a restarted arbiter adopts
      the half-finished ledger and completes the transfer;
    - a foreign-family ``rank...`` spec rides in the same RLT_FAULT
      value to prove mixed strings parse/fire independently (satellite
      bugfix) inside a live run;
    - two full borrow/return cycles complete; every serve request is
      token-identical to an unfaulted generate(); training params are
      bitwise-identical to an unfaulted run of the same step count; and
      the ledger ends with every chip back on train, none leaked or
      double-assigned.
    """
    params, cfg = model
    every = int(os.environ.get("RLT_CHAOS_KILL_EVERY", "6"))
    spec = (
        f"rank3:crash@step7,"
        f"replica0:crash@every:{every},"
        f"arbiter:crash-mid-borrow@transfer1"
    )
    with _fault_env(spec):
        train = SimTrain(["c0", "c1", "c2"])
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=ENGINE_KW,
            initial_replicas=2,
            capacity=2,
            max_retries=6,
            breaker_threshold=2,
            breaker_cooldown_s=0.3,
        )
        try:
            serve = FleetServeHandle(fleet, drain_timeout_s=120.0)
            led_dir = str(tmp_path / "led")
            kw = dict(
                cooldown_s=0.0,
                idle_ticks_return=1,
                transition_timeout_s=120.0,
            )
            arb = ChipArbiter(
                led_dir, train, serve, devices=["c0", "c1", "c2"], **kw
            )

            rng = np.random.default_rng(7)
            reqs, entries, streams = [], [], {}

            def submit(k):
                for _ in range(k):
                    p = [int(t) for t in rng.integers(1, cfg.vocab_size, 5)]
                    n = int(rng.integers(5, 9))
                    i = len(reqs)
                    reqs.append((p, n))
                    streams[i] = []
                    entries.append(
                        fleet.submit(
                            p,
                            max_new_tokens=n,
                            on_token=lambda _rid, t, i=i: streams[i].append(t),
                        )
                    )

            submit(3)
            train.run_steps(3)

            # ---- cycle 1: borrow killed mid-transfer ---------------- #
            arb.request_transfer("borrow")
            with pytest.raises(faults.ArbiterFault):
                arb.tick()
            led = read_ledger(led_dir)
            assert led["state"] == "resharding"
            assert led["transfer"]["direction"] == "borrow"
            (orphan,) = led["transfer"]["devices"]
            assert led["owner"][orphan] == "transit"

            # restarted arbiter re-adopts the ledger, boots the replica
            arb = ChipArbiter(led_dir, train, serve, **kw)
            assert arb.recovered_action == "completed"
            assert arb.state == "lent"
            assert orphan in serve.devices()
            assert fleet.num_replicas == 3

            submit(4)
            train.run_steps(3)

            # ---- cycle 1: return ------------------------------------ #
            arb.request_transfer("return")
            assert arb.tick() == "returned"
            assert arb.state == "steady" and not arb.borrowed_devices()

            # ---- cycle 2: clean borrow/return ----------------------- #
            # transfer 3: @transfer1 cannot refire because transfer_seq
            # persisted in the ledger across the arbiter restart
            arb.request_transfer("borrow")
            assert arb.tick() == "borrowed"
            submit(4)
            train.run_steps(3)
            arb.request_transfer("return")
            assert arb.tick() == "returned"

            assert arb.transfers_completed == 4
            assert arb.transfer_seq == 4

            # zero dropped or duplicated serve tokens across the cycles
            for i, ((p, n), e) in enumerate(zip(reqs, entries)):
                want = _reference(params, cfg, p, n)
                assert e.result(timeout=300) == want
                assert streams[i] == want
            stats = fleet.stats()
            assert stats["completed"] == len(reqs)
            assert stats["failed"] == 0 and stats["shed"] == 0
            assert fleet.relaunches_total >= 1  # the kill loop fired

            # no leaked or double-assigned devices anywhere
            led = read_ledger(led_dir)
            assert set(led["owner"]) == {"c0", "c1", "c2"}
            assert all(side == "train" for side in led["owner"].values())
            assert set(train.devices()) == {"c0", "c1", "c2"}
            assert serve.devices() == {}

            # training params bitwise-identical to an unfaulted run of
            # the same step count
            ref = SimTrain(["c0", "c1", "c2"])
            ref.run_steps(train.steps)
            got = jax.tree_util.tree_leaves(train.params)
            want = jax.tree_util.tree_leaves(ref.params)
            for a, b in zip(got, want):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        finally:
            fleet.shutdown()
