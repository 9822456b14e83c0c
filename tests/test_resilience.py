"""Serving resilience (ray_lightning_tpu/serving/resilience.py + the
LocalReplicaFleet recovery pump): request journal, circuit breakers,
deadline shedding, and the serving-path fault points.

The acceptance bar: a fleet under a sustained replica-kill loop
(``RLT_FAULT=replica0:crash@every:N`` with no fuse, so relaunched
engines keep dying) completes 100% of non-shed requests token-identical
to an unfaulted sequential ``generate()``, and an open circuit breaker
receives ZERO routed requests until its half-open probe succeeds.

Unit tests (no model) run first; the model-backed e2es reuse the
module-scoped tiny-Llama fixture from test_serving.py's idiom.
"""
import contextlib
import dataclasses
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.runtime import faults
from ray_lightning_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    LocalReplicaFleet,
    RequestShed,
)
from ray_lightning_tpu.serving.resilience import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    CircuitBreaker,
    RequestJournal,
    ShedPolicy,
    install_sigterm_drain,
)

pytestmark = pytest.mark.serving_chaos


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
def _fault_env(spec, fuse=None):
    """Arm RLT_FAULT with a serving spec; by default no fuse dir, so
    @every faults keep firing across same-index relaunches (a true
    sustained kill loop). Pass ``fuse`` (a directory) to make each spec
    fire exactly ONCE across relaunches instead. Restores the env and
    both parse caches (engine serving + migration) on exit."""
    old = os.environ.get(faults.FAULT_ENV)
    old_fuse = os.environ.pop("RLT_FAULT_FUSE", None)
    os.environ[faults.FAULT_ENV] = spec
    if fuse is not None:
        os.environ["RLT_FAULT_FUSE"] = str(fuse)
    faults._serve_cache = (None, [])
    faults._migration_cache = (None, [])
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(faults.FAULT_ENV, None)
        else:
            os.environ[faults.FAULT_ENV] = old
        os.environ.pop("RLT_FAULT_FUSE", None)
        if old_fuse is not None:
            os.environ["RLT_FAULT_FUSE"] = old_fuse
        faults._serve_cache = (None, [])
        faults._migration_cache = (None, [])


ENGINE_KW = dict(num_slots=4, max_prompt_len=16, max_len=32, max_queue=64)


# --------------------------------------------------------------------- #
# circuit breaker (pure host, scripted clock)
# --------------------------------------------------------------------- #
def test_breaker_closed_open_halfopen_cycle():
    clock = [0.0]
    b = CircuitBreaker(
        failure_threshold=3, open_cooldown_s=5.0, clock=lambda: clock[0]
    )
    assert b.state == BREAKER_CLOSED and b.allow_request()

    # failures below the threshold keep it closed; a success resets the
    # consecutive count
    b.record_failure()
    b.record_failure()
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == BREAKER_CLOSED

    b.record_failure()  # third consecutive: open
    assert b.state == BREAKER_OPEN
    assert not b.allow_request()  # cooldown not elapsed: refuse everything

    clock[0] = 4.9
    assert not b.allow_request()
    clock[0] = 5.1
    assert b.allow_request()  # THE half-open probe
    assert b.state == BREAKER_HALF_OPEN
    assert not b.allow_request()  # one probe at a time

    b.record_failure()  # failed probe: straight back to open
    assert b.state == BREAKER_OPEN
    assert not b.allow_request()

    clock[0] = 11.0  # fresh cooldown from the re-open
    assert b.allow_request()
    b.record_success()  # probe passed: closed, traffic resumes
    assert b.state == BREAKER_CLOSED and b.allow_request()

    arcs = [(frm, to) for _, frm, to in b.transitions]
    assert arcs == [
        (BREAKER_CLOSED, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_CLOSED),
    ]
    assert b.failures_total == 6 and b.successes_total == 2
    # gauge encoding is stable (dashboards key on it)
    assert b.state_value() == 0


def test_breaker_validates_threshold():
    with pytest.raises(ValueError, match="failure_threshold"):
        CircuitBreaker(failure_threshold=0)


# --------------------------------------------------------------------- #
# shed policy
# --------------------------------------------------------------------- #
def test_shed_policy_protects_priority_zero():
    policy = ShedPolicy(queue_watermark=0.5, shed_priority_floor=1)
    # priority 0 is never shed, even with the queue melting down AND the
    # SLO alert firing — it only ever sees queue-full back-pressure
    assert not policy.should_shed(0, 100, 100, slo_breached=True)
    # sheddable work: rejected past the watermark...
    assert policy.should_shed(1, 50, 100)
    assert not policy.should_shed(1, 49, 100)
    # ...or while the burn-rate alert is firing, regardless of depth
    assert policy.should_shed(1, 0, 100, slo_breached=True)


# --------------------------------------------------------------------- #
# request journal (no engine: scripted attempts)
# --------------------------------------------------------------------- #
class _FakeCompletion:
    def __init__(self):
        self.done = False
        self.finish_reason = None
        self.error = None


def test_journal_resume_math_and_stream_guard():
    journal = RequestJournal()
    seen = []
    entry = journal.open(
        (5, 6, 7), 8, on_token=lambda rid, t: seen.append((rid, t))
    )

    rid1, prompt1, budget1 = journal.begin_attempt(entry, replica=0)
    assert rid1 == entry.request_id
    assert prompt1 == (5, 6, 7) and budget1 == 8
    journal.bind(entry, _FakeCompletion())
    assert journal.retries_total == 0  # first attempt is not a retry

    guard1 = journal.stream_guard(entry, rid1)
    guard1(rid1, 11)
    guard1(rid1, 12)
    assert entry.delivered == [11, 12] and entry.ttft_s is not None

    # replica 0 dies; attempt 2 resumes from prompt + delivered with the
    # remaining budget — the bitwise-resume contract
    rid2, prompt2, budget2 = journal.begin_attempt(entry, replica=1)
    assert rid2 == f"{entry.request_id}~r1"
    assert prompt2 == (5, 6, 7, 11, 12) and budget2 == 6
    journal.bind(entry, _FakeCompletion())
    assert journal.retries_total == 1 and entry.retries == 1

    # the zombie replica keeps calling the OLD guard: dropped, not duped
    guard1(rid1, 99)
    assert entry.delivered == [11, 12]

    guard2 = journal.stream_guard(entry, rid2)
    guard2(rid2, 13)
    journal.finish(entry, "completed", finish_reason="length")
    guard2(rid2, 14)  # post-finish tokens land nowhere
    assert entry.delivered == [11, 12, 13]
    assert entry.done and entry.result() == [11, 12, 13]

    # the client callback saw the journal rid throughout, exactly once
    # per delivered token
    assert seen == [(entry.request_id, t) for t in (11, 12, 13)]

    journal.finish(entry, "failed")  # idempotent: first finish wins
    assert entry.disposition == "completed"
    assert entry.replica_history == [0, 1]
    stats = journal.stats()
    assert stats["completed"] == 1 and stats["failed"] == 0
    assert stats["retries"] == 1 and stats["open"] == 0


def test_journal_abort_attempt_rolls_back():
    journal = RequestJournal()
    entry = journal.open((1, 2), 4)
    journal.begin_attempt(entry, replica=0)
    # dispatch never reached an engine (queue full / engine closed):
    # rolling back must not count as a retry on the next attempt
    journal.abort_attempt(entry)
    assert entry.attempts == 0 and entry.attempt_rid is None
    rid, _, _ = journal.begin_attempt(entry, replica=1)
    assert rid == entry.request_id  # still the FIRST attempt
    journal.bind(entry, _FakeCompletion())
    assert journal.retries_total == 0


def test_journal_rejects_duplicate_request_id():
    journal = RequestJournal()
    journal.open((1,), 2, request_id="dup")
    with pytest.raises(ValueError, match="duplicate"):
        journal.open((1,), 2, request_id="dup")


# --------------------------------------------------------------------- #
# serving fault grammar
# --------------------------------------------------------------------- #
def test_serve_fault_grammar():
    specs = faults.parse_serve_faults(
        "rank0:crash@step3,replica1:crash@every:6,"
        "replica0:slow-decode@tick4:0.25,replica2:drop-stream@req2:4"
    )
    # training (rank...) specs coexist and are skipped here
    assert [(s.replica, s.kind) for s in specs] == [
        (1, "crash"), (0, "slow-decode"), (2, "drop-stream")
    ]
    assert specs[0].every == 6 and specs[0].matches_tick(12)
    assert not specs[0].matches_tick(0)  # tick 0 never fires @every
    assert specs[1].tick == 4 and specs[1].arg == 0.25
    assert specs[2].req == 2 and specs[2].arg == 4.0
    assert faults.parse_serve_faults(None) == []

    for bad in (
        "replica0:explode@tick3",           # unknown kind
        "replica0:crash@every:0",           # @every needs N >= 1
        "replica0:drop-stream@tick3",       # drop-stream targets a request
        "replica0:hang@req2",               # hang is a tick fault
        "replica0:slow-decode@every:4",     # slow-decode needs a stall arg
    ):
        with pytest.raises(ValueError):
            faults.parse_serve_faults(bad)


# --------------------------------------------------------------------- #
# SIGTERM preemption drain
# --------------------------------------------------------------------- #
def test_sigterm_drain_handler_prefers_preempt_all():
    class _Fleet:
        def __init__(self):
            self.preempted = 0

        def preempt_all(self):
            self.preempted += 1

    class _Engine:
        def __init__(self):
            self.drained = 0

        def drain(self):
            self.drained += 1

    original = signal.getsignal(signal.SIGTERM)
    try:
        fleet = _Fleet()
        handler = install_sigterm_drain(fleet)
        assert signal.getsignal(signal.SIGTERM) is handler
        handler(signal.SIGTERM, None)
        assert fleet.preempted == 1

        engine = _Engine()  # no preempt_all: falls back to drain()
        install_sigterm_drain(engine)(signal.SIGTERM, None)
        assert engine.drained == 1
    finally:
        signal.signal(signal.SIGTERM, original)


# --------------------------------------------------------------------- #
# deadlines: engine-level TTL expiry
# --------------------------------------------------------------------- #
def test_engine_expires_queued_request_past_deadline(model):
    params, cfg = model
    engine = InferenceEngine(
        params, cfg,
        EngineConfig(num_slots=1, max_prompt_len=8, max_len=32),
    )
    engine.start()
    try:
        # A holds the single slot through first-step compilation, far
        # longer than B's TTL; the scheduler sweeps B from the queue
        a = engine.submit([3, 1, 4], max_new_tokens=12)
        b = engine.submit([2, 7], max_new_tokens=4, deadline_ms=30.0)
        assert a.result(timeout=180) == _reference(params, cfg, [3, 1, 4], 12)
        deadline = time.time() + 30
        while not b.done and time.time() < deadline:
            time.sleep(0.01)
        assert b.finish_reason == "expired" and b.error is None
    finally:
        engine.shutdown(drain=False)


def test_fleet_expires_dead_on_arrival_deadline(model):
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg), engine_kwargs=ENGINE_KW, initial_replicas=1
    )
    try:
        entry = fleet.submit([1, 2, 3], max_new_tokens=4, deadline_ms=0.0)
        assert entry.done and entry.disposition == "expired"
        assert entry.result() == []  # expired, not errored: partial stream
        assert fleet.stats()["expired"] == 1
    finally:
        fleet.shutdown()


# --------------------------------------------------------------------- #
# load shedding at the fleet front door
# --------------------------------------------------------------------- #
def test_fleet_sheds_low_priority_on_slo_burn(model):
    params, cfg = model

    class _BurningMonitor:
        def serving_breached(self):
            return True

    fleet = LocalReplicaFleet(
        lambda: (params, cfg), engine_kwargs=ENGINE_KW, initial_replicas=1
    )
    try:
        fleet._replicas[0].slo_monitor = _BurningMonitor()
        with pytest.raises(RequestShed):
            fleet.submit([1, 2], max_new_tokens=4, priority=1)
        assert fleet.stats()["shed"] == 1
        # priority 0 rides through the same burn untouched
        entry = fleet.submit([1, 2], max_new_tokens=4, priority=0)
        assert entry.result(timeout=180) == _reference(
            params, cfg, [1, 2], 4
        )
    finally:
        fleet.shutdown()


# --------------------------------------------------------------------- #
# circuit breaker wired into fleet routing
# --------------------------------------------------------------------- #
def test_open_breaker_routes_zero_until_probe_succeeds(model):
    """The routing acceptance criterion: while replica 0's breaker is
    open it receives ZERO routed requests; the first submit after
    cooldown becomes the half-open probe, and its success re-admits the
    replica to routing."""
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=ENGINE_KW,
        initial_replicas=2,
        breaker_threshold=2,
        breaker_cooldown_s=0.5,
    )
    try:
        b0 = fleet._breaker(0)
        b0.record_failure()
        b0.record_failure()
        assert b0.state == BREAKER_OPEN
        routed_before = fleet.routed_total[0]

        prompts = [[7, i + 1, 3] for i in range(6)]
        entries = [fleet.submit(p, max_new_tokens=5) for p in prompts]
        for p, e in zip(prompts, entries):
            assert e.result(timeout=180) == _reference(params, cfg, p, 5)
        # every request routed around the ejected replica
        assert fleet.routed_total[0] == routed_before
        assert all(e.replica_history == [1] for e in entries)

        time.sleep(0.6)  # cooldown elapses; next submit IS the probe
        probe = fleet.submit([9, 9, 2], max_new_tokens=5)
        assert probe.result(timeout=180) == _reference(
            params, cfg, [9, 9, 2], 5
        )
        assert probe.replica_history == [0]
        assert fleet.routed_total[0] == routed_before + 1

        deadline = time.time() + 10  # the pump settles the probe outcome
        while b0.state != BREAKER_CLOSED and time.time() < deadline:
            time.sleep(0.02)
        assert b0.state == BREAKER_CLOSED
        arcs = [(frm, to) for _, frm, to in b0.transitions]
        assert arcs == [
            (BREAKER_CLOSED, BREAKER_OPEN),
            (BREAKER_OPEN, BREAKER_HALF_OPEN),
            (BREAKER_HALF_OPEN, BREAKER_CLOSED),
        ]
    finally:
        fleet.shutdown()


# --------------------------------------------------------------------- #
# scripted stream death: resume without a dropped or duplicated token
# --------------------------------------------------------------------- #
def test_drop_stream_fault_resumes_bitwise_identical(model):
    params, cfg = model
    with _fault_env("replica0:drop-stream@req1:2"):
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=ENGINE_KW,
            initial_replicas=1,
            max_retries=3,
        )
        try:
            streamed = []
            prompt, n_new = [4, 8, 15], 8
            entry = fleet.submit(
                prompt, max_new_tokens=n_new,
                on_token=lambda rid, t: streamed.append(t),
            )
            want = _reference(params, cfg, prompt, n_new)
            assert entry.result(timeout=180) == want
            # the client stream is the merge of both attempts: the 2
            # tokens that survived the drop plus the resumed remainder,
            # each exactly once and in order
            assert streamed == want
            assert entry.retries == 1
            assert entry.replica_history == [0, 0]  # same engine, req 2
            assert fleet.stats()["failed"] == 0
        finally:
            fleet.shutdown()


# --------------------------------------------------------------------- #
# graceful preemption + scale-down: the backlog migrates, nothing drops
# --------------------------------------------------------------------- #
def test_preempt_replica_migrates_backlog_zero_drop(model):
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=dict(ENGINE_KW, num_slots=2),
        initial_replicas=2,
    )
    try:
        rng = np.random.default_rng(11)
        reqs = [
            (
                [int(t) for t in rng.integers(1, cfg.vocab_size, 4)],
                int(rng.integers(4, 7)),
            )
            for _ in range(10)
        ]
        entries = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
        assert fleet.preempt_replica(0)  # SIGTERM-style notice mid-burst
        assert fleet.num_replicas == 1

        for (p, n), e in zip(reqs, entries):
            assert e.result(timeout=180) == _reference(params, cfg, p, n)
        stats = fleet.stats()
        assert stats["completed"] == 10
        assert stats["failed"] == 0 and stats["shed"] == 0
    finally:
        fleet.shutdown()


def test_scale_down_drain_timeout_hands_back_queue(model):
    """Satellite regression: remove_replica on a WEDGED engine (decode
    loop hung forever) must hand its queued backlog back after the drain
    timeout (cancelled -> the pump migrates it) and fail its admitted
    work over to a healthy replica — not silently drop the requests with
    the engine object."""
    params, cfg = model
    with _fault_env("replica0:hang@tick1"):
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=dict(ENGINE_KW, num_slots=1),
            initial_replicas=2,
            max_retries=3,
            drain_timeout=2.0,
        )
        try:
            # single slot per engine: most of the burst sits QUEUED on
            # its replica, which is exactly what a wedged drain used to
            # drop on the floor
            prompts = [[6, i + 1] for i in range(6)]
            entries = [fleet.submit(p, max_new_tokens=4) for p in prompts]
            time.sleep(0.2)  # let replica 0 wedge with work in hand
            assert fleet.remove_replica(0) == 0
            for p, e in zip(prompts, entries):
                assert e.result(timeout=180) == _reference(
                    params, cfg, p, 4
                )
            stats = fleet.stats()
            assert stats["completed"] == 6
            assert stats["failed"] == 0 and stats["shed"] == 0
        finally:
            fleet.shutdown()


# --------------------------------------------------------------------- #
# THE acceptance e2e: sustained kill loop, 100% goodput, exact tokens
# --------------------------------------------------------------------- #
def test_kill_loop_completes_all_requests_token_identical(model):
    """RLT_FAULT crashes replica 0 every N ticks with no fuse: the
    relaunched engine dies again and again. The journal + breaker +
    relaunch stack must still complete EVERY request with the exact
    token stream of an unfaulted sequential decode."""
    params, cfg = model
    every = int(os.environ.get("RLT_CHAOS_KILL_EVERY", "6"))
    with _fault_env(f"replica0:crash@every:{every}"):
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=ENGINE_KW,
            initial_replicas=2,
            max_retries=6,
            breaker_threshold=2,
            breaker_cooldown_s=0.3,
        )
        try:
            rng = np.random.default_rng(23)
            reqs = [
                (
                    [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
                    int(rng.integers(5, 9)),
                )
                for _ in range(12)
            ]
            streams = {}
            entries = []
            for i, (p, n) in enumerate(reqs):
                streams[i] = []
                entries.append(
                    fleet.submit(
                        p, max_new_tokens=n,
                        on_token=lambda _rid, t, i=i: streams[i].append(t),
                    )
                )
            for i, ((p, n), e) in enumerate(zip(reqs, entries)):
                want = _reference(params, cfg, p, n)
                assert e.result(timeout=300) == want
                assert streams[i] == want  # stream: no dup, no gap

            stats = fleet.stats()
            assert stats["completed"] == len(reqs)
            assert stats["failed"] == 0 and stats["shed"] == 0
            # the kill loop provably fired: engines died and attempts
            # were resubmitted (crash cadence guarantees both)
            assert fleet.relaunches_total >= 1
            assert stats["retries"] >= 1
            # the crash-looping replica's breaker opened at least once
            b0 = fleet.breakers[0]
            assert (BREAKER_CLOSED, BREAKER_OPEN) in [
                (frm, to) for _, frm, to in b0.transitions
            ]
        finally:
            fleet.shutdown()


# --------------------------------------------------------------------- #
# disaggregated serving: a decode-replica death MID-MIGRATION is just
# another replica death — the journal resumes, nothing drops
# --------------------------------------------------------------------- #
def test_decode_replica_kill_mid_migration_token_identical(model, tmp_path):
    """1 prefill + 1 decode replica. The request prefills on replica 0,
    its KV ships to replica 1, and replica 1 CRASHES a few decode steps
    in (fused tick fault: fires exactly once, so the relaunch stays up).
    The journal must re-dispatch prompt + delivered through the prefill
    pool and finish the request token-identical to generate(), with
    exactly one charged retry and zero dropped requests."""
    params, cfg = model
    ekw = dict(ENGINE_KW, block_size=4)
    with _fault_env("replica1:crash@tick4", fuse=tmp_path):
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=ekw,
            initial_replicas=2,
            prefill_replicas=1,
            max_retries=4,
            breaker_threshold=3,
        )
        try:
            streamed = []
            prompt, n_new = [4, 8, 15, 16], 8
            entry = fleet.submit(
                prompt, max_new_tokens=n_new,
                on_token=lambda rid, t: streamed.append(t),
            )
            want = _reference(params, cfg, prompt, n_new)
            assert entry.result(timeout=300) == want
            # the client stream merges both attempts: the tokens that
            # landed before the decode replica died plus the resumed
            # remainder, each exactly once and in order
            assert streamed == want
            assert entry.retries == 1
            # first attempt went prefill-pool first, then the handoff
            assert entry.replica_history[:2] == [0, 1]
            stats = fleet.stats()
            assert stats["completed"] == 1
            assert stats["failed"] == 0 and stats["shed"] == 0
            # the first migration landed before the kill; the resumed
            # attempt re-enters through the prefill pool (a later
            # migration may land OR gracefully fall back to colocated
            # decode while replica 1 relaunches — both are valid; a
            # dropped request is not)
            assert stats["migration"]["migrated"] >= 1
        finally:
            fleet.shutdown()
