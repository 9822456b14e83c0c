"""Continuous-batching serving (ray_lightning_tpu/serving/): the pool's slots,
scheduler policy, the two-program engine, and the replica front door.

The acceptance bar: >= 8 concurrent requests with staggered arrival and
mixed lengths, served by a 2-slot pool — completions token-identical to
sequential ``generate()``, slots visibly recycled, and ZERO steady-state
recompiles (jit cache sizes flat after warmup).
"""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.serving import (
    Autoscaler,
    ContinuousBatchScheduler,
    EngineClosed,
    EngineConfig,
    InferenceEngine,
    LocalReplicaFleet,
    PagedKVPool,
    Request,
    RequestQueueFull,
    autoscale_decision,
    needs_relaunch,
    pick_least_loaded,
)
from ray_lightning_tpu.serving.paged_kv import TRASH_BLOCK

pytestmark = pytest.mark.serving


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


# --------------------------------------------------------------------- #
# the pool's slots (rows of the decode batch)
# --------------------------------------------------------------------- #
def test_pool_acquire_release_cycle(model):
    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=2, max_len=16)
    a = pool.acquire("a", prompt_len=3, max_new_tokens=4)
    b = pool.acquire("b", prompt_len=5, max_new_tokens=2)
    assert a.index != b.index and pool.occupancy == 2
    assert pool.acquire("c", 2, 2) is None  # full -> None, not an error
    assert [s.request_id for s in pool.active_slots()] == ["a", "b"]

    pool.release(a.index)
    assert pool.free_count == 1 and not a.occupied
    c = pool.acquire("c", 2, 2)
    assert c.index == a.index  # recycled row
    assert pool.admitted_total == 3 and pool.recycled_total == 1
    assert pool.tenancies[c.index] == ["a", "c"]
    assert pool.highwater == 2

    pool.release(c.index)
    with pytest.raises(ValueError, match="already free"):
        pool.release(c.index)


def test_pool_validates_lengths(model):
    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=1, max_len=8, block_size=4)
    with pytest.raises(ValueError, match="max_len=8"):
        pool.acquire("a", prompt_len=6, max_new_tokens=3)
    with pytest.raises(ValueError, match="prompt_len"):
        pool.acquire("a", prompt_len=0, max_new_tokens=3)


def test_pool_rejects_sliding_window():
    cfg = dataclasses.replace(_cfg(), sliding_window=8)
    with pytest.raises(ValueError, match="sliding"):
        PagedKVPool(cfg, num_slots=2, max_len=16)


# --------------------------------------------------------------------- #
# scheduler
# --------------------------------------------------------------------- #
def test_scheduler_fifo_admission_and_interleave(model):
    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=2, max_len=16)
    sched = ContinuousBatchScheduler(pool, max_queue=8, max_prefills_per_tick=1)
    for name in ("a", "b", "c"):
        sched.submit(Request(name, (1, 2, 3), max_new_tokens=2))
    assert sched.queue_depth == 3

    plan = sched.tick()  # admits ONE (prefill/decode interleave knob)
    assert [r.request_id for r, _ in plan.prefills] == ["a"]
    # the just-admitted slot decodes in the same iteration
    assert [s.request_id for s in plan.decode_slots] == ["a"]

    plan = sched.tick()
    assert [r.request_id for r, _ in plan.prefills] == ["b"]
    assert sched.queue_depth == 1  # "c" waits: pool is full

    plan = sched.tick()
    assert plan.prefills == [] and len(plan.decode_slots) == 2

    pool.release(plan.decode_slots[0].index)
    plan = sched.tick()
    assert [r.request_id for r, _ in plan.prefills] == ["c"]
    assert sched.has_work()


def test_scheduler_bounded_queue_backpressure(model):
    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=1, max_len=16)
    sched = ContinuousBatchScheduler(pool, max_queue=2)
    sched.submit(Request("a", (1,), 1))
    sched.submit(Request("b", (1,), 1))
    with pytest.raises(RequestQueueFull):
        sched.submit(Request("c", (1,), 1))
    assert sched.rejected_total == 1
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request("d", tuple(range(15)), 5))
    assert [r.request_id for r in sched.drain_queue()] == ["a", "b"]
    assert not sched.has_work()


# --------------------------------------------------------------------- #
# engine: the acceptance e2e
# --------------------------------------------------------------------- #
def test_engine_continuous_batching_matches_sequential_generate(model):
    """8 staggered mixed-length requests through a 2-slot pool: every
    completion token-identical to sequential generate(), slots recycled
    across multiple tenants, and the jit caches FLAT after warmup (zero
    steady-state recompiles — the whole point of the fixed shapes)."""
    params, cfg = model
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
    )
    rng = np.random.default_rng(0)
    reqs = [
        (
            [int(t) for t in rng.integers(1, cfg.vocab_size, rng.integers(3, 8))],
            int(rng.integers(4, 9)),
        )
        for _ in range(8)
    ]

    # staggered arrival: 3 land before serving starts, the rest arrive
    # while the first wave is mid-decode
    completions = [engine.submit(p, max_new_tokens=n) for p, n in reqs[:3]]
    for _ in range(4):
        engine.step()
    warm = engine.compile_stats()  # both programs compiled by now
    assert warm == {"prefill_compiles": 1, "decode_compiles": 1}
    completions += [engine.submit(p, max_new_tokens=n) for p, n in reqs[3:]]
    engine.run_until_idle()

    for (prompt, n_new), comp in zip(reqs, completions):
        assert comp.finish_reason == "length"
        assert comp.result(timeout=1) == _reference(params, cfg, prompt, n_new)

    # continuous batching actually happened: every slot served several
    # tenants and the pool is empty again
    assert engine.pool.recycled_total == 8
    assert all(len(v) > 1 for v in engine.pool.tenancies.values())
    assert engine.pool.occupancy == 0
    # zero steady-state recompiles: cache sizes unchanged since warmup
    assert engine.compile_stats() == warm
    assert engine.slot_utilization() > 0.5


def test_engine_eos_recycles_slot_early(model):
    """A request whose greedy first token IS its eos finishes with reason
    'eos' after one token; its slot frees for the next tenant."""
    params, cfg = model
    prompt = [5, 6, 7]
    first = _reference(params, cfg, prompt, 1)[0]
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=1, max_prompt_len=8, max_len=32)
    )
    c1 = engine.submit(prompt, max_new_tokens=8, eos_id=first)
    c2 = engine.submit(prompt, max_new_tokens=2, eos_id=None)
    engine.run_until_idle()
    assert c1.finish_reason == "eos" and c1.result(timeout=1) == [first]
    assert c2.finish_reason == "length" and len(c2.result(timeout=1)) == 2
    assert engine.pool.tenancies[0] == [c1.request_id, c2.request_id]


def test_engine_threaded_loop_stream_and_drain(model):
    """The loop-thread path: submits from the caller thread, streaming
    on_token callbacks in order, graceful drain, EngineClosed after."""
    params, cfg = model
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
    )
    engine.start()
    streamed = []
    lock = threading.Lock()

    def on_token(rid, tok):
        with lock:
            streamed.append(tok)

    prompt = [9, 8, 7, 6]
    comp = engine.submit(prompt, max_new_tokens=5, on_token=on_token)
    got = comp.result(timeout=60)
    assert got == _reference(params, cfg, prompt, 5)
    with lock:
        assert streamed == got  # streamed in generation order
    engine.drain(timeout=30)
    with pytest.raises(EngineClosed):
        engine.submit([1], max_new_tokens=1)


def test_engine_rejects_bad_submissions(model):
    params, cfg = model
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=1, max_prompt_len=4, max_len=8, block_size=4)
    )
    with pytest.raises(ValueError, match="non-empty"):
        engine.submit([], max_new_tokens=1)
    with pytest.raises(ValueError, match="max_prompt_len"):
        engine.submit([1, 2, 3, 4, 5], max_new_tokens=1)
    with pytest.raises(ValueError, match="max_len"):
        engine.submit([1, 2, 3], max_new_tokens=6)  # 3 + 6 > 8
    engine.submit([1], max_new_tokens=1, request_id="dup")
    with pytest.raises(ValueError, match="duplicate"):
        engine.submit([1], max_new_tokens=1, request_id="dup")
    with pytest.raises(ValueError, match="max_prompt_len"):
        EngineConfig(num_slots=1, max_prompt_len=8, max_len=8).validate()


def test_engine_config_refuses_the_removed_slot_layout_by_name():
    """``kv_layout`` has one value left (the benchmark's data files still
    pass it); any other is refused where the settings are checked."""
    EngineConfig(kv_layout="paged").validate()
    with pytest.raises(ValueError, match="kv_layout='slot'.*removed in PR 28"):
        EngineConfig(kv_layout="slot").validate()


def test_engine_publishes_serving_metrics(model):
    """With telemetry on, the serving path lands its gauges/counters/
    latency histograms in the process registry."""
    from ray_lightning_tpu import observability as obs

    params, cfg = model
    obs.reset()  # another test may have left telemetry (and counts) behind
    obs.enable()
    try:
        engine = InferenceEngine(
            params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
        )
        cs = [engine.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
        engine.run_until_idle()
        assert all(c.done for c in cs)
        reg = obs.registry()
        assert reg.counter("rlt_serve_requests_total").value == 3
        assert reg.counter("rlt_serve_tokens_total").value == 9
        assert reg.counter("rlt_serve_completions_total", reason="length").value == 3
        assert reg.gauge("rlt_serve_slot_occupancy").value == 0
        assert reg.gauge("rlt_serve_slot_highwater").value == 2
        assert reg.get("rlt_serve_ttft_seconds").count == 3
        assert reg.get("rlt_serve_itl_seconds").count == 6  # 3 x (3 - 1)
        text = reg.prometheus_text()
        assert "rlt_serve_queue_depth" in text
    finally:
        obs.reset()


# --------------------------------------------------------------------- #
# replica front door: pure policy (no actors)
# --------------------------------------------------------------------- #
def test_pick_least_loaded_routes_and_breaks_ties():
    loads = {0: {"queue_depth": 3, "active": 1}, 1: {"queue_depth": 0, "active": 1}}
    assert pick_least_loaded(loads, 2, rr_counter=0) == 1
    # unreported replicas count as empty and attract traffic
    assert pick_least_loaded({0: {"queue_depth": 9}}, 2, 0) == 1
    # ties rotate round-robin instead of piling on replica 0
    picks = {pick_least_loaded({}, 3, i) for i in range(3)}
    assert picks == {0, 1, 2}
    with pytest.raises(ValueError):
        pick_least_loaded({}, 0, 0)


def test_needs_relaunch_policy():
    # monitor-only: never condemn
    assert not needs_relaunch(10.0, 0.0, now=100.0, hang_timeout=None)
    # silent past hang_timeout -> relaunch
    assert needs_relaunch(10.0, 0.0, now=100.0, hang_timeout=5.0)
    assert not needs_relaunch(98.0, 0.0, now=100.0, hang_timeout=5.0)
    # pre-first-beat silence tolerated unless startup_timeout bounds it
    assert not needs_relaunch(None, 0.0, now=100.0, hang_timeout=5.0)
    assert needs_relaunch(
        None, 0.0, now=100.0, hang_timeout=5.0, startup_timeout=50.0
    )


# --------------------------------------------------------------------- #
# replica front door: live actors (slow)
# --------------------------------------------------------------------- #
def _tiny_builder():
    import dataclasses as _dc
    import os as _os

    _os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax as _jax
    import jax.numpy as _jnp

    from ray_lightning_tpu.models.llama import LlamaConfig as _LC
    from ray_lightning_tpu.models.llama import init_params as _init

    cfg = _dc.replace(_LC.tiny(), dtype=_jnp.float32)
    return _init(_jax.random.PRNGKey(0), cfg), cfg


@pytest.mark.slow
def test_replica_group_serves_and_balances(model):
    """2 live replica actors: routed traffic reaches both, completions
    match the sequential reference, health check passes, clean shutdown."""
    from ray_lightning_tpu.serving import ReplicaGroup

    params, cfg = model
    group = ReplicaGroup(
        _tiny_builder,
        engine_kwargs={"num_slots": 2, "max_prompt_len": 8, "max_len": 32},
        num_replicas=2,
        env={"JAX_PLATFORMS": "cpu"},
    ).start()
    try:
        rng = np.random.default_rng(1)
        reqs = [
            (
                [int(t) for t in rng.integers(1, cfg.vocab_size, rng.integers(3, 8))],
                int(rng.integers(3, 6)),
            )
            for _ in range(6)
        ]
        futures = [group.submit(p, max_new_tokens=n) for p, n in reqs]
        for (prompt, n_new), fut in zip(reqs, futures):
            assert fut.result(timeout=120) == _reference(params, cfg, prompt, n_new)
        assert {f.replica for f in futures} == {0, 1}
        assert group.check() == {0: "ok", 1: "ok"}
    finally:
        group.shutdown()


# --------------------------------------------------------------------- #
# the paged pool: parity, prefix sharing, block back-pressure
# --------------------------------------------------------------------- #
def test_paged_engine_matches_generate(model):
    """The staggered acceptance e2e with a tiny block size: the same 8
    requests as the e2e above at the default block size, every completion
    token-identical to sequential generate(). Block growth happens
    mid-decode (grown_total), and the jit caches stay FLAT across
    admit/recycle/growth."""
    params, cfg = model
    engine = InferenceEngine(
        params,
        cfg,
        EngineConfig(
            num_slots=2, max_prompt_len=8, max_len=32,
            block_size=4,
        ),
    )
    rng = np.random.default_rng(0)
    reqs = [
        (
            [int(t) for t in rng.integers(1, cfg.vocab_size, rng.integers(3, 8))],
            int(rng.integers(4, 9)),
        )
        for _ in range(8)
    ]

    completions = [engine.submit(p, max_new_tokens=n) for p, n in reqs[:3]]
    for _ in range(4):
        engine.step()
    warm = engine.compile_stats()
    assert warm == {"prefill_compiles": 1, "decode_compiles": 1}
    completions += [engine.submit(p, max_new_tokens=n) for p, n in reqs[3:]]
    engine.run_until_idle()

    for (prompt, n_new), comp in zip(reqs, completions):
        assert comp.finish_reason == "length"
        assert comp.result(timeout=1) == _reference(params, cfg, prompt, n_new)

    alloc = engine.pool.kinds["full"].allocator
    assert alloc.grown_total > 0  # decode crossed block boundaries
    assert alloc.used_blocks == 0  # every request released its blocks
    assert engine.pool.recycled_total == 8
    assert engine.pool.occupancy == 0
    # zero steady-state recompiles under admission, recycling AND growth
    assert engine.compile_stats() == warm
    assert "kv_layout" not in engine.describe()
    assert engine.describe()["block_utilization"] == 0.0


def test_paged_shared_prefix_bitwise_identical(model):
    """Two requests with a common system prompt: the shared full blocks
    are prefilled once and HIT by the second admission, and both
    continuations are bitwise-identical to the prefix-cache-off run and
    to the sequential reference — sharing changes allocation, not math."""
    params, cfg = model
    system = [3, 1, 4, 1, 5, 9, 2, 6]  # two full 4-token blocks
    prompts = [system + [11, 12], system + [21, 22, 23]]
    n_new = 6

    def run(prefix_cache):
        engine = InferenceEngine(
            params,
            cfg,
            EngineConfig(
                num_slots=2, max_prompt_len=12, max_len=32,
                block_size=4, prefix_cache=prefix_cache,
            ),
        )
        comps = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
        engine.run_until_idle()
        return engine, [c.result(timeout=1) for c in comps]

    shared_engine, shared = run(prefix_cache=True)
    # both leading system-prompt blocks were served from the chain cache
    assert shared_engine.pool.kinds["full"].allocator.prefix_hits_total == 2
    unshared_engine, unshared = run(prefix_cache=False)
    assert unshared_engine.pool.kinds["full"].allocator.prefix_hits_total == 0
    for prompt, a, b in zip(prompts, shared, unshared):
        ref = _reference(params, cfg, prompt, n_new)
        assert a == ref  # shared run matches sequential generate()
        assert b == ref  # and so does the unshared run: bitwise equal


def test_paged_pool_write_redirect_and_growth(model):
    """Pool-level contract: the second tenant of a shared prefix gets a
    write table that redirects the already-written blocks to TRASH
    (written exactly once), gathers the same physical blocks, and grows
    its private tail on demand from the reservation."""
    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=2, max_len=16, block_size=4)
    s1 = pool.acquire("a", prompt_len=9, max_new_tokens=6,
                      prompt_tokens=[7] * 9)
    wt1 = pool.prompt_write_table(s1.index, 3)
    assert TRASH_BLOCK not in wt1  # first tenant writes all its blocks
    s2 = pool.acquire("b", prompt_len=9, max_new_tokens=6,
                      prompt_tokens=[7] * 9)
    assert pool.shared_blocks(s2.index) == 2
    wt2 = pool.prompt_write_table(s2.index, 3)
    # shared leading blocks are NOT rewritten; only the private write
    # frontier (the block decode mutates) lands in the cache
    assert list(wt2[:2]) == [TRASH_BLOCK, TRASH_BLOCK]
    assert wt2[2] not in (TRASH_BLOCK, wt1[2])
    # both block tables gather the same physical prefix blocks
    assert list(pool.kinds["full"].block_tables[s1.index][:2]) == \
        list(pool.kinds["full"].block_tables[s2.index][:2])
    # decode reaching position 12 pulls block 3 from the reservation
    assert pool.kinds["full"].block_tables[s1.index][3] == TRASH_BLOCK
    s1.pos = 12
    pool.ensure_writable(s1)
    assert pool.kinds["full"].block_tables[s1.index][3] != TRASH_BLOCK
    assert pool.kinds["full"].allocator.grown_total == 1
    pool.release(s1.index)
    pool.release(s2.index)
    assert pool.kinds["full"].allocator.used_blocks == 0


def test_scheduler_defers_on_block_exhaustion_fifo(model):
    """Admission is gated by BLOCK availability, not just free slots: a
    big tenant exhausts the pool, later small requests wait in strict
    FIFO (no skip-ahead), and the head admits as soon as blocks free."""
    _, cfg = model
    # 4 slots but only 4 data blocks: blocks are the scarce resource
    pool = PagedKVPool(cfg, num_slots=4, max_len=16, block_size=4,
                       num_blocks=5, prefix_cache=False)
    sched = ContinuousBatchScheduler(pool, max_queue=8,
                                     max_prefills_per_tick=4)
    sched.submit(Request("big", tuple(range(1, 9)), max_new_tokens=8))
    sched.submit(Request("tiny1", (1, 2, 3), max_new_tokens=1))
    sched.submit(Request("tiny2", (4, 5, 6), max_new_tokens=1))

    plan = sched.tick()  # big takes every block; tinies defer
    assert [r.request_id for r, _ in plan.prefills] == ["big"]
    assert sched.queue_depth == 2
    assert sched.deferred_total == 1
    assert pool.kinds["full"].allocator.available() == 0
    sched.tick()
    assert sched.deferred_total == 2  # still waiting, still queued

    pool.release(plan.prefills[0][1].index)
    plan = sched.tick()  # head-of-line order preserved on admission
    assert [r.request_id for r, _ in plan.prefills] == ["tiny1", "tiny2"]
    assert sched.queue_depth == 0


# --------------------------------------------------------------------- #
# autoscaler: pure policy + threads-as-replicas e2e
# --------------------------------------------------------------------- #
def test_autoscale_decision_policy():
    busy = {0: {"queue_depth": 9, "active": 2}}
    assert autoscale_decision(busy, 1, 1, 4) == 1
    assert autoscale_decision(busy, 4, 1, 4) == 0  # at the ceiling
    # TTFT latency trips scale-up even when queues look shallow
    slow = {0: {"queue_depth": 0, "active": 1, "ttft_p95_ms": 900.0}}
    assert autoscale_decision(slow, 1, 1, 4, ttft_high_ms=500.0) == 1
    assert autoscale_decision(slow, 1, 1, 4) == 0  # signal off by default
    # scale down only when the WHOLE fleet is idle, and never below min
    idle = {0: {"queue_depth": 0, "active": 0}, 1: {}}
    assert autoscale_decision(idle, 2, 1, 4) == -1
    assert autoscale_decision(idle, 1, 1, 4) == 0
    assert autoscale_decision({0: {"queue_depth": 0, "active": 1}}, 2, 1, 4) == 0
    with pytest.raises(ValueError):
        autoscale_decision({}, 1, 0, 4)


def test_pick_least_loaded_sparse_indices():
    loads = {3: {"queue_depth": 2}, 7: {"queue_depth": 0}}
    assert pick_least_loaded(loads, 0, 0, indices=[3, 7]) == 7
    # a draining replica leaves the routable set; traffic falls back
    assert pick_least_loaded(loads, 0, 0, indices=[3]) == 3
    with pytest.raises(ValueError, match="no routable"):
        pick_least_loaded(loads, 0, 0, indices=[])


class _FakeFleet:
    def __init__(self, n=2):
        self.n = n
        self.load_reports = {}

    @property
    def num_replicas(self):
        return self.n

    def loads(self):
        return self.load_reports

    def add_replica(self):
        self.n += 1

    def remove_replica(self):
        self.n -= 1


def test_autoscaler_hysteresis_cooldown_and_idle_ticks():
    fleet = _FakeFleet(n=2)
    scaler = Autoscaler(fleet, min_replicas=1, max_replicas=4,
                        queue_high=1.0, cooldown_s=10.0, idle_ticks_down=2)
    fleet.load_reports = {0: {"queue_depth": 8}}
    assert scaler.tick(now=0.0) == 1 and fleet.n == 3
    # cooldown suppresses the immediate follow-up...
    assert scaler.tick(now=1.0) == 0 and fleet.n == 3
    # ...but not the next eligible tick
    assert scaler.tick(now=11.0) == 1 and fleet.n == 4
    # one quiet beat between bursts must not shed capacity: the first
    # idle verdict only arms, the second fires
    fleet.load_reports = {0: {"queue_depth": 0, "active": 0}}
    assert scaler.tick(now=30.0) == 0 and fleet.n == 4
    assert scaler.tick(now=41.0) == -1 and fleet.n == 3
    assert scaler.scale_ups == 2 and scaler.scale_downs == 1


def test_local_fleet_autoscales_up_and_drains_down(model):
    """Autoscaler e2e on the threads-as-replicas fleet: an over-offered
    burst scales the fleet up, every completion still matches the
    sequential reference (zero dropped requests, including those owned
    by later-drained replicas), and an idle fleet drains back to the
    floor gracefully."""
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs={"num_slots": 2, "max_prompt_len": 8, "max_len": 32},
        initial_replicas=1,
    )
    scaler = Autoscaler(fleet, min_replicas=1, max_replicas=3,
                        queue_high=2.0, idle_ticks_down=2)
    try:
        rng = np.random.default_rng(7)
        reqs = [
            (
                [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
                int(rng.integers(4, 7)),
            )
            for _ in range(12)
        ]
        comps = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
        # the burst all routed to replica 0 (the only one): its queue
        # depth trips the scaler. Stop ticking at the first scale-up:
        # with a warm executable cache the replicas serve immediately,
        # so further quiet ticks would (correctly) start draining the
        # capacity this assertion is about to observe.
        for _ in range(3):
            scaler.tick()
            if scaler.scale_ups:
                break
        assert fleet.num_replicas >= 2 and scaler.scale_ups >= 1

        for (prompt, n_new), comp in zip(reqs, comps):
            assert comp.result(timeout=180) == _reference(
                params, cfg, prompt, n_new
            )
        assert all(c.finish_reason == "length" for c in comps)
        # zero-drop: scale-down must never lose a request to any
        # non-completed disposition (shed / expired / failed)
        stats = fleet.stats()
        assert stats["completed"] == len(reqs)
        assert stats["failed"] == 0 and stats["shed"] == 0
        assert stats["expired"] == 0

        # idle: consecutive quiet ticks drain the fleet back to one
        deadline = time.time() + 60
        while fleet.num_replicas > 1 and time.time() < deadline:
            scaler.tick()
            time.sleep(0.05)
        assert fleet.num_replicas == 1
        assert scaler.scale_downs >= 1
        assert fleet.removed_total == fleet.added_total - 1
    finally:
        fleet.shutdown()


# --------------------------------------------------------------------- #
# request-scoped tracing: the observability acceptance e2e
# --------------------------------------------------------------------- #
def test_engine_request_tracing_e2e(model, tmp_path):
    """With telemetry on, every request lands a requests.jsonl record and
    its own Perfetto track (queue/prefill/decode spans) in trace.json —
    and the two-program zero-recompile contract holds with tracing on."""
    import json
    import os

    from ray_lightning_tpu import observability as obs
    from ray_lightning_tpu.observability import reqtrace
    from ray_lightning_tpu.observability.aggregator import (
        REQUESTS_FILE, TRACE_FILE, write_local_dump,
    )

    params, cfg = model
    obs.reset()
    obs.enable()
    try:
        engine = InferenceEngine(
            params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
        )
        prompts = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10]]
        cs = [
            engine.submit(p, max_new_tokens=2 + i % 3, request_id=f"rq{i}")
            for i, p in enumerate(prompts)
        ]
        engine.run_until_idle()
        assert all(c.done for c in cs)
        # tracing must not perturb the compiled-program contract
        assert engine.compile_stats() == {
            "prefill_compiles": 1, "decode_compiles": 1,
        }
        run_dir = write_local_dump(
            str(tmp_path / "t"), obs.get_recorder(), obs.registry(),
            requests=engine.drain_request_records(),
        )
        records = reqtrace.read_requests(os.path.join(run_dir, REQUESTS_FILE))
        by_id = {r["request_id"]: r for r in records}
        assert set(by_id) == {f"rq{i}" for i in range(4)}
        for i, rec in ((i, by_id[f"rq{i}"]) for i in range(4)):
            assert rec["prompt_len"] == len(prompts[i])
            assert rec["tokens_out"] == 2 + i % 3
            assert rec["finish_reason"] == "length"
            assert rec["queue_wait_s"] >= 0
            assert rec["prefill_s"] > 0
            assert rec["ttft_s"] > 0
            assert rec["slot"] in (0, 1)

        trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
        threads = {
            e["args"]["name"]: e["tid"]
            for e in trace["traceEvents"] if e.get("name") == "thread_name"
        }
        for i in range(4):
            tid = threads.get(f"req rq{i}")
            assert tid is not None and tid > 0, threads
            spans = {
                e["name"] for e in trace["traceEvents"]
                if e["ph"] == "X" and e.get("tid") == tid
            }
            assert {"req/queue_wait", "req/prefill", "req/decode"} <= spans
        # ttft histogram exemplars name the requests in their buckets
        exemplars = obs.registry().get(
            "rlt_serve_ttft_seconds"
        ).bucket_exemplars()
        assert set(exemplars) <= {f"rq{i}" for i in range(4)}
        assert exemplars
    finally:
        obs.reset()


def test_engine_tracing_off_is_attribute_check_only(model):
    """Telemetry off: no tracer object exists and request/slot trace
    attributes stay None — the per-token cost is one attribute check."""
    params, cfg = model
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=1, max_prompt_len=8, max_len=16)
    )
    assert engine._tracer is None
    c = engine.submit([1, 2, 3], max_new_tokens=2)
    engine.run_until_idle()
    assert c.done
    assert all(s.trace is None for s in engine.pool.slots)
    assert engine.drain_request_records() == []


def test_engine_tracing_head_sampling_drops(model, monkeypatch):
    """RLT_TRACE_SAMPLE=0: telemetry on but every request unsampled —
    no records, no per-request spans, same completions."""
    from ray_lightning_tpu import observability as obs
    from ray_lightning_tpu.observability import reqtrace

    monkeypatch.setenv(reqtrace.SAMPLE_ENV, "0")
    params, cfg = model
    obs.reset()
    obs.enable()
    try:
        engine = InferenceEngine(
            params, cfg, EngineConfig(num_slots=1, max_prompt_len=8, max_len=16)
        )
        c = engine.submit([1, 2, 3], max_new_tokens=2)
        engine.run_until_idle()
        assert c.done
        assert engine._tracer is not None
        assert engine._tracer.started_total == 1
        assert engine._tracer.sampled_total == 0
        assert engine.drain_request_records() == []
    finally:
        obs.reset()


def test_scheduler_deferral_stamps_trace(model):
    """A queued request that waits for capacity accumulates deferred
    ticks on its trace and records the wait on admission."""
    from ray_lightning_tpu.observability import reqtrace

    _, cfg = model
    pool = PagedKVPool(cfg, num_slots=1, max_len=16)
    sched = ContinuousBatchScheduler(pool, max_queue=4)
    a = Request("a", (1, 2), 2)
    b = Request("b", (1, 2), 2, trace=reqtrace.RequestTrace("b", 2, 2))
    sched.submit(a)
    sched.submit(b)
    sched.tick()  # admits "a" (one prefill per tick)
    sched.tick()  # "b" defers against the full pool
    sched.tick()
    assert b.trace.deferred_ticks == 2  # one per tick while blocked
    assert b.trace.queue_wait_s is None
    pool.release(0)
    plan = sched.tick()
    assert [r.request_id for r, _ in plan.prefills] == ["b"]
    assert b.trace.slot == 0
    assert b.trace.queue_wait_s > 0
    assert plan.prefills[0][1].trace is b.trace
    rec = b.trace.record("eos")
    assert rec["deferred_ticks"] == 2 and rec["deferred_wait_s"] > 0


# --------------------------------------------------------------------- #
# head-of-line aging: the skip-ahead window is BOUNDED
# --------------------------------------------------------------------- #
def test_scheduler_head_aging_closes_skip_window(model):
    """``head_skip_limit`` lets small requests jump a deferred head, but
    only until the head has waited ``head_aging_ticks`` — past that the
    window closes and nothing may pass it, even work that would fit.
    Regression for unbounded starvation of long prompts."""
    _, cfg = model
    # 6 data blocks (one is the trash block): a 4-block hog in residence
    # leaves 2 free — the 4-block head cannot admit, 1-block tinies can
    pool = PagedKVPool(cfg, num_slots=4, max_len=16, block_size=4,
                       num_blocks=7, prefix_cache=False)
    sched = ContinuousBatchScheduler(pool, max_queue=8,
                                     max_prefills_per_tick=4,
                                     head_skip_limit=2, head_aging_ticks=3)
    sched.submit(Request("hog", tuple(range(1, 9)), max_new_tokens=8))
    plan = sched.tick()
    assert [r.request_id for r, _ in plan.prefills] == ["hog"]
    hog_slot = plan.prefills[0][1]

    sched.submit(Request("big", tuple(range(1, 9)), max_new_tokens=8))
    sched.submit(Request("tiny1", (1, 2, 3), max_new_tokens=1))
    sched.submit(Request("tiny2", (4, 5, 6), max_new_tokens=1))
    sched.submit(Request("tiny3", (7, 8, 9), max_new_tokens=1))

    plan = sched.tick()  # the window is open: two tinies jump the head
    assert [r.request_id for r, _ in plan.prefills] == ["tiny1", "tiny2"]
    assert sched.skipped_total == 2
    tiny1_slot = plan.prefills[0][1]

    for _ in range(3):  # the head keeps deferring against 0 free blocks
        assert sched.tick().prefills == []

    # head now aged past head_aging_ticks: tiny3 FITS in the freed
    # block, but the closed window refuses to let it jump the queue
    pool.release(tiny1_slot.index)
    assert sched.tick().prefills == []
    assert sched.skipped_total == 2
    assert sched.queue_depth == 2

    # capacity for the head itself: strict order resumes behind it
    pool.release(hog_slot.index)
    plan = sched.tick()
    assert [r.request_id for r, _ in plan.prefills] == ["big", "tiny3"]
    assert sched.queue_depth == 0


# --------------------------------------------------------------------- #
# shutdown vs streaming: the re-entrant race stays idempotent
# --------------------------------------------------------------------- #
def test_shutdown_mid_stream_suppresses_late_tokens(model):
    """shutdown(drain=False) fired from INSIDE an on_token callback (the
    engine loop thread): the completion finishes exactly once, tokens
    already delivered stay readable, and nothing streams after the
    shutdown — no duplicate delivery, no exception out of the loop."""
    params, cfg = model
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
    )
    engine.start()
    streamed = []

    def kill_switch(rid, tok):
        streamed.append(tok)
        engine.shutdown(drain=False)  # re-entrant from the loop thread

    comp = engine.submit([2, 3, 5], max_new_tokens=8, on_token=kill_switch)
    deadline = time.time() + 120
    while not comp.done and time.time() < deadline:
        time.sleep(0.01)
    assert comp.done and comp.finish_reason == "error"
    assert isinstance(comp.error, EngineClosed)
    # exactly the one pre-shutdown token, delivered exactly once, and it
    # is the true greedy token (the stream died clean, not corrupted)
    assert streamed == _reference(params, cfg, [2, 3, 5], 1)
    assert comp.tokens == streamed
    time.sleep(0.3)
    assert streamed == comp.tokens and len(streamed) == 1  # nothing late
    assert not engine.alive
    with pytest.raises(EngineClosed):
        engine.submit([1, 2], max_new_tokens=2)
    engine.shutdown(drain=False)  # second shutdown: idempotent no-op


# --------------------------------------------------------------------- #
# a tick that admits a prompt is ONE program where the family's prefill
# rides its decode step (PR 43): the same streams as the two programs
# --------------------------------------------------------------------- #
def _tick_programs_model(kind):
    base = LlamaConfig.tiny() if kind == "dense" else LlamaConfig.tiny_moe()
    cfg = dataclasses.replace(base, dtype=jnp.float32)
    return init_params(jax.random.key(2), cfg), cfg


def _staggered_streams(params, cfg, **engine):
    """Nine requests of mixed lengths behind two shared prefixes through
    three slots, four before the first tick and the rest while those decode:
    (the engine, each request's tokens)."""
    settings = dict(num_slots=3, max_prompt_len=12, max_len=32, block_size=4)
    engine = InferenceEngine(params, cfg, EngineConfig(**dict(settings, **engine)))
    rng = np.random.default_rng(5)
    prefixes = ([7, 3, 9, 4], [2, 8, 6, 5, 1, 1, 3, 2], [])
    reqs = [
        (prefixes[i % 3]
         + [int(t) for t in rng.integers(1, cfg.vocab_size, rng.integers(1, 5))],
         int(rng.integers(1, 9)))
        for i in range(9)
    ]
    done = [engine.submit(p, max_new_tokens=n) for p, n in reqs[:4]]
    for _ in range(5):
        engine.step()
    done += [engine.submit(p, max_new_tokens=n) for p, n in reqs[4:]]
    engine.run_until_idle()
    assert engine._inflight is None and engine.pool.occupancy == 0
    return engine, reqs, [c.result(timeout=1) for c in done]


@pytest.mark.parametrize("prefills_a_tick", [1, 2], ids=["one-a-tick", "two-a-tick"])
@pytest.mark.parametrize("kind", ["dense", "experts"])
def test_the_fused_tick_serves_the_two_program_ticks_streams(
    kind, prefills_a_tick, monkeypatch
):
    """Staggered requests, shared prefixes among them, through an engine
    whose tick that admits a prompt is one program, and through the same
    engine forced onto two programs a tick (the family's method taken away;
    ``speculate_k`` and ``role`` untouched): the same token streams,
    ``generate()``'s. ``fused_prefill_steps`` says how often it engaged:
    every prefill where a tick admits one prompt, every TICK with prefills
    where it admits two (the first goes out with no row, the last with the
    rows); 0 on two programs. Every other counter reads the same."""
    from ray_lightning_tpu.models.generation import LlamaServing

    params, cfg = _tick_programs_model(kind)
    fused, reqs, streams = _staggered_streams(
        params, cfg, max_prefills_per_tick=prefills_a_tick)
    assert fused._fused_rung == 12  # the one rung of a short ladder
    monkeypatch.delattr(LlamaServing, "prefill_decode_paged")
    plain, _, want = _staggered_streams(
        params, cfg, max_prefills_per_tick=prefills_a_tick)
    assert plain._fused_rung is None
    assert streams == want
    assert streams == [_reference(params, cfg, p, n) for p, n in reqs]
    s, t = fused.stats, plain.stats
    assert t["fused_prefill_steps"] == 0 and s["prefills"] == t["prefills"] == 9
    if prefills_a_tick == 1:
        assert s["fused_prefill_steps"] == s["prefills"]
    else:
        assert 5 <= s["fused_prefill_steps"] < s["prefills"]
    same = ("decode_steps", "busy_slot_steps", "overlapped_steps", "tokens_out",
            "prefill_positions", "prefill_tokens", "completed",
            "dropped_row_steps", *fused._model.counters)
    assert {k: s[k] for k in same} == {k: t[k] for k in same}
    assert fused.compile_stats() == plain.compile_stats() == {
        "prefill_compiles": 1, "decode_compiles": 1}
    assert fused.pool.stats()["prefix_hits_total"] == plain.pool.stats()[
        "prefix_hits_total"] > 0


@pytest.mark.parametrize("setting", [
    {"speculate_k": 2}, {"role": "prefill"}, {"role": "decode"}],
    ids=lambda s: "-".join(f"{k}-{v}" for k, v in s.items()))
def test_an_engine_that_cannot_fuse_keeps_two_programs_a_tick(model, setting):
    """A speculating engine reads a tick's tokens before it dispatches the
    next and a prefill replica parks the slot it filled: their prefill
    program is the prompt's alone, whatever the family provides. A decode
    replica's prompts (a migration's fallback) go the same way."""
    params, cfg = model
    engine = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, max_prompt_len=8, max_len=32, block_size=4, **setting))
    assert engine._fused_rung is None
    comp = engine.submit([5, 9, 5, 9, 5], max_new_tokens=4)
    for _ in range(8):
        engine.step()
    assert engine.stats["prefills"] == 1 and engine.stats["fused_prefill_steps"] == 0
    if setting.get("role") != "prefill":
        assert comp.result(timeout=1) == _reference(params, cfg, [5, 9, 5, 9, 5], 4)


@pytest.mark.parametrize("family", ["deepseek", "cohere"])
def test_a_family_without_the_fused_step_reads_no_fused_tick(family):
    """The engine asks the family's serving object and nothing else: a model
    that provides no ``prefill_decode_paged`` runs its two programs a tick
    and counts no fused one."""
    if family == "deepseek":
        from ray_lightning_tpu.models import deepseek as ds

        cfg = ds.DeepseekConfig(
            vocab_size=97, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, ffn_dim=96, moe_ffn_dim=32,
            n_experts=8, expert_top_k=2, max_seq=64, dtype=jnp.float32,
            remat=False)
        params, extra = ds.init_params(jax.random.key(0), cfg), {}
    else:
        from ray_lightning_tpu.models import cohere as co

        cfg = co.CohereConfig(
            vocab_size=97, dim=64, n_layers=8, period=4, n_heads=8,
            n_kv_heads=2, head_dim=16, sliding_window=12, ffn_dim=32,
            n_experts=16, experts_held=4, first_expert=4, n_shared_experts=2,
            expert_top_k=4, max_seq=64, dtype=jnp.float32)
        params, extra = co.init_params(jax.random.key(0), cfg), {"prefix_cache": False}
    engine = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, max_prompt_len=16, max_len=32, block_size=4, **extra))
    assert engine._fused_rung is None and not hasattr(cfg.serving(), "prefill_decode_paged")
    done = [engine.submit(p, max_new_tokens=5) for p in ([5, 9, 2, 7, 1], [8, 4])]
    engine.run_until_idle()
    assert all(len(c.result(timeout=1)) == 5 for c in done)
    assert engine.stats["prefills"] == 2 and engine.stats["fused_prefill_steps"] == 0
