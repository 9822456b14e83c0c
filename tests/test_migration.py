"""Disaggregated prefill/decode serving (ray_lightning_tpu/serving/
migration.py + the engine export/import surface + the LocalReplicaFleet
migration pump).

The acceptance bar: a prefill-pool request's KV blocks ship to a decode
replica as a checksummed, versioned :class:`KVShipment`; the receiver
verifies BEFORE any payload touches its device cache and resumes through
the journal so the completion is token-identical to a sequential
``generate()``; every scripted transport fault (dropped, corrupt,
stalled shipment, receiver crash mid-admit) is retried under the
migration policy's bounded budget and degrades — never drops — to
colocated decode on the prefill replica; and the homogeneous single-pool
configuration stays byte-identical to the colocated path (same tokens,
flat jit caches).

Unit tests (no model) run first; the model-backed e2es reuse the
module-scoped tiny-Llama fixture from test_serving.py's idiom.
"""
import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.runtime import faults
from ray_lightning_tpu.serving import (
    Autoscaler,
    BlockAllocator,
    EngineConfig,
    InferenceEngine,
    LocalReplicaFleet,
    MigrationPolicy,
    ShipmentCorrupt,
    ShipmentMismatch,
    autoscale_decision,
    build_shipment,
    kv_fingerprint,
    pick_least_loaded,
    verify_shipment,
)
from ray_lightning_tpu.serving import migration as migration_mod

pytestmark = pytest.mark.migration


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
    """Arm RLT_FAULT with a migration/serving spec; no fuse dir, so
    @every faults keep firing across same-index relaunches. Restores the
    env and BOTH parse caches on exit."""
    old = os.environ.get(faults.FAULT_ENV)
    old_fuse = os.environ.pop("RLT_FAULT_FUSE", None)
    os.environ[faults.FAULT_ENV] = spec
    faults._serve_cache = (None, [])
    faults._migration_cache = (None, [])
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(faults.FAULT_ENV, None)
        else:
            os.environ[faults.FAULT_ENV] = old
        if old_fuse is not None:
            os.environ["RLT_FAULT_FUSE"] = old_fuse
        faults._serve_cache = (None, [])
        faults._migration_cache = (None, [])


ENGINE_KW = dict(
    num_slots=4, max_prompt_len=16, max_len=32, max_queue=64,
    block_size=4,
)


def _blocks(n, seed=0, shape=(2, 2, 4, 3)):
    rng = np.random.default_rng(seed)
    ks = tuple(rng.standard_normal(shape).astype(np.float32) for _ in range(n))
    vs = tuple(rng.standard_normal(shape).astype(np.float32) for _ in range(n))
    return ks, vs


def _ship(n=3, prompt=(5, 6, 7, 8, 9), fp="f" * 16):
    ks, vs = _blocks(n)
    return build_shipment("r0", prompt, fp, 4, ks, vs)


# --------------------------------------------------------------------- #
# shipment format: checksums, fingerprint, digest (pure host)
# --------------------------------------------------------------------- #
def test_shipment_roundtrip_verifies():
    ship = _ship()
    assert verify_shipment(ship, "f" * 16) == ship.nbytes()
    assert ship.num_blocks == 3
    assert ship.version == migration_mod.SHIPMENT_VERSION


def test_corrupt_shipment_detected_original_untouched():
    ship = _ship()
    bad = migration_mod.corrupt_copy(ship)
    with pytest.raises(ShipmentCorrupt, match="checksum"):
        verify_shipment(bad, "f" * 16)
    # the clean original survives for the retry resend
    assert verify_shipment(ship, "f" * 16) == ship.nbytes()


def test_fingerprint_or_version_mismatch_rejected_before_checksums():
    ship = _ship()
    with pytest.raises(ShipmentMismatch, match="fingerprint"):
        verify_shipment(ship, "0" * 16)
    stale = dataclasses.replace(ship, version=ship.version + 1)
    with pytest.raises(ShipmentMismatch, match="version"):
        verify_shipment(stale, "f" * 16)


def test_digest_seals_header_not_just_payloads():
    # a swapped prompt with intact block payloads must still fail: the
    # whole-shipment digest covers the header fields
    ship = _ship()
    forged = dataclasses.replace(ship, prompt=(1, 2, 3, 4, 5))
    with pytest.raises(ShipmentCorrupt, match="digest"):
        verify_shipment(forged, "f" * 16)


def test_kv_fingerprint_covers_every_layout_property():
    base = dict(
        block_size=4, block_shape=(2, 2, 4, 3), dtype="float32", max_len=32,
    )
    fp = kv_fingerprint(**base)
    assert fp == kv_fingerprint(**base)  # deterministic
    for key, bad in [
        ("block_size", 8), ("block_shape", (2, 2, 8, 3)),
        ("dtype", "bfloat16"), ("max_len", 64),
    ]:
        assert fp != kv_fingerprint(**{**base, key: bad}), key


def test_migration_policy_backoff_is_exponential_and_capped():
    p = MigrationPolicy(backoff_base_s=0.1, backoff_factor=2.0,
                        backoff_max_s=0.3)
    assert p.backoff(0) == 0.0
    assert p.backoff(1) == pytest.approx(0.1)
    assert p.backoff(2) == pytest.approx(0.2)
    assert p.backoff(3) == pytest.approx(0.3)  # capped
    assert p.backoff(9) == pytest.approx(0.3)


# --------------------------------------------------------------------- #
# migration fault grammar
# --------------------------------------------------------------------- #
def test_migration_fault_grammar():
    specs = faults.parse_migration_faults(
        "replica0:drop-shipment@req1,replica1:corrupt-shipment@every:3,"
        "replica2:stall-shipment@req2:0.5,replica0:crash-mid-admit@req4"
    )
    assert [s.kind for s in specs] == [
        "drop-shipment", "corrupt-shipment", "stall-shipment",
        "crash-mid-admit",
    ]
    assert specs[0].matches_seq(1) and not specs[0].matches_seq(2)
    assert specs[1].matches_seq(3) and specs[1].matches_seq(6)
    assert specs[2].arg == 0.5

    # the migration parser and the engine serving parser skip each
    # other's specs, so one RLT_FAULT string can script both layers
    mixed = "replica0:crash@tick3,replica1:corrupt-shipment@req1"
    assert [s.kind for s in faults.parse_migration_faults(mixed)] == [
        "corrupt-shipment"
    ]
    assert [s.kind for s in faults.parse_serve_faults(mixed)] == ["crash"]

    for bad in [
        "replica0:corrupt-shipment",          # needs a trigger
        "replica0:drop-shipment@req0",        # shipments are 1-based
        "replica0:stall-shipment@req1",       # stall needs a length
        "replica0:corrupt-shipment@every:0",  # every needs N >= 1
    ]:
        with pytest.raises(ValueError):
            faults.parse_migration_faults(bad)


# --------------------------------------------------------------------- #
# satellite: shipment pins close the shared-prefix eviction race
# --------------------------------------------------------------------- #
def test_pinned_chain_blocks_survive_eviction_pressure():
    """The regression: request A's prefix chain is referenced by an
    in-flight shipment when a sibling release drops its refcount to 0.
    Without the pin, allocation pressure LRU-evicts and REWRITES those
    physical blocks while the shipment still needs their bytes."""
    a = BlockAllocator(num_blocks=9, block_size=4)  # 8 usable blocks
    # 9 tokens: blocks 0 and 1 are full AND before the write frontier
    # (decode rewrites position 8 in block 2), so exactly those two are
    # chain-registered — the shareable prefix a shipment references
    prompt = list(range(1, 10))
    alloc = a.admit("mig", prompt_len=9, max_new_tokens=1,
                    prompt_tokens=prompt)
    chain_blocks = set(alloc.blocks[:2])
    pinned = a.pin_request("mig")
    assert len(pinned) == 2 and a.stats()["chains_pinned"] == 2

    # the owner releases mid-transfer: chains idle but PINNED — they are
    # neither claimable supply nor eviction victims
    a.release("mig")
    assert a.stats()["chains_pinned"] == 2
    # soak up the whole free list with 1-block tenants (no growth
    # reservation: 4 tokens total fit one block)
    taken = set()
    for i in range(a.available()):
        got = a.admit(f"g{i}", prompt_len=3, max_new_tokens=1,
                      prompt_tokens=[50 + i] * 3)
        assert got is not None
        taken.update(got.blocks)
    assert a.evictions_total == 0
    assert not chain_blocks.intersection(taken)  # bytes untouched
    # the next tenant WOULD need the pinned blocks: refused (deferred),
    # never served by rewriting them out from under the shipment
    assert a.admit("over", prompt_len=3, max_new_tokens=1,
                   prompt_tokens=[7] * 3) is None
    assert a.deferred_total == 1

    # unpin: the idle chains become ordinary eviction victims again
    a.unpin(pinned)
    assert a.stats()["chains_pinned"] == 0
    over = a.admit("over", prompt_len=3, max_new_tokens=1,
                   prompt_tokens=[7] * 3)
    assert over is not None
    assert a.evictions_total > 0

    with pytest.raises(KeyError):
        a.pin_request("never-admitted")


# --------------------------------------------------------------------- #
# satellite: pool-aware routing + per-pool autoscaling signals
# --------------------------------------------------------------------- #
def test_pick_least_loaded_filters_by_role():
    loads = {
        0: {"queue_depth": 0, "role": "prefill"},
        1: {"queue_depth": 5, "role": "decode"},
        2: {"queue_depth": 1, "role": "decode"},
        3: {"queue_depth": 0, "role": "both"},
    }
    # homogeneous default: role=None is the pre-disaggregation behavior
    assert pick_least_loaded(loads, 4, 0) in (0, 3)
    assert pick_least_loaded(loads, 4, 0, role="prefill") == 0
    # "both" replicas are members of every pool (and 3 is the idlest)
    assert pick_least_loaded(loads, 4, 0, role="decode") == 3
    assert pick_least_loaded(
        loads, 0, 0, role="decode", indices=[1, 2]
    ) == 2
    with pytest.raises(ValueError, match="pool"):
        pick_least_loaded(loads, 0, 0, role="prefill", indices=[1, 2])


def test_autoscale_decision_role_scoped_and_itl_signal():
    loads = {
        0: {"queue_depth": 9, "active": 2, "role": "prefill"},
        1: {"queue_depth": 0, "active": 1, "itl_p99_ms": 80.0,
            "role": "decode"},
    }
    # queue depth drives the prefill pool...
    assert autoscale_decision(loads, 1, 1, 4, role="prefill") == 1
    # ...and is invisible to the decode pool, whose signal is ITL p99
    assert autoscale_decision(loads, 1, 1, 4, role="decode") == 0
    assert autoscale_decision(
        loads, 1, 1, 4, role="decode", itl_high_ms=50.0
    ) == 1
    assert autoscale_decision(
        loads, 1, 1, 4, role="decode", itl_high_ms=200.0
    ) == 0
    # scale-down stays pool-scoped: an idle decode pool drains even
    # while the prefill pool is burning
    idle = {
        0: {"queue_depth": 7, "active": 2, "role": "prefill"},
        1: {"queue_depth": 0, "active": 0, "role": "decode"},
        2: {"queue_depth": 0, "active": 0, "role": "decode"},
    }
    assert autoscale_decision(idle, 2, 1, 4, role="decode") == -1


class _FakePooledFleet:
    def __init__(self):
        self.load_reports = {}
        self.added = []
        self.removed = []

    @property
    def num_replicas(self):
        return len(self.load_reports)

    def loads(self):
        return self.load_reports

    def add_replica(self, role=None):
        self.added.append(role)

    def remove_replica(self, role=None):
        self.removed.append(role)
        return 0


def test_autoscaler_scales_only_its_own_pool():
    fleet = _FakePooledFleet()
    fleet.load_reports = {
        0: {"queue_depth": 9, "role": "prefill"},
        1: {"queue_depth": 0, "active": 1, "itl_p99_ms": 120.0,
            "role": "decode"},
        2: {"queue_depth": 0, "active": 1, "role": "decode"},
    }
    pf = Autoscaler(fleet, min_replicas=1, max_replicas=4,
                    queue_high=1.0, role="prefill")
    dec = Autoscaler(fleet, min_replicas=1, max_replicas=4,
                     queue_high=1.0, itl_high_ms=50.0, role="decode")
    assert pf.tick(now=0.0) == 1 and fleet.added == ["prefill"]
    assert dec.tick(now=0.0) == 1 and fleet.added == ["prefill", "decode"]
    # the decode pool going idle drains a DECODE replica, regardless of
    # the prefill pool's backlog
    fleet.load_reports[1] = {"queue_depth": 0, "active": 0,
                             "role": "decode"}
    fleet.load_reports[2] = {"queue_depth": 0, "active": 0,
                             "role": "decode"}
    assert dec.tick(now=10.0) == 0  # idle_ticks_down arms first
    assert dec.tick(now=20.0) == -1 and fleet.removed == ["decode"]


# --------------------------------------------------------------------- #
# engine-to-engine handoff: token identity, flat caches, pin lifecycle
# --------------------------------------------------------------------- #
def test_engine_migration_token_identical_and_caches_flat(model):
    params, cfg = model
    src = InferenceEngine(
        params, cfg, EngineConfig(role="prefill", **ENGINE_KW)
    )
    dst = InferenceEngine(
        params, cfg, EngineConfig(role="decode", **ENGINE_KW)
    )
    dst.start()
    try:
        assert src.kv_fingerprint() == dst.kv_fingerprint()
        prompt, n_new = [3, 1, 4, 1, 5], 6
        comp_src = src.submit(prompt, max_new_tokens=n_new)
        src.step()  # prefill runs; the slot parks export-pending
        [rid] = src.drain_ready_exports()
        assert src.pool.kinds["full"].allocator.stats()["chains_pinned"] > 0

        ship = src.export_shipment(rid)
        assert verify_shipment(ship, dst.kv_fingerprint()) == ship.nbytes()
        comp = dst.import_shipment(ship, max_new_tokens=n_new,
                                   request_id=rid)
        src.finish_export(rid)
        src.step()

        want = _reference(params, cfg, prompt, n_new)
        # the receiver resumes from prompt[-1] at pos len(prompt)-1 — an
        # idempotent KV rewrite — so EVERY token comes out of the decode
        # pool and the stream is bitwise what the colocated path emits
        assert comp.result(timeout=60) == want
        assert comp_src.finish_reason == "migrated"
        # admitting a shipment is install-and-resume: the receiver's
        # prefill program never compiles, its decode program exactly once
        warm_dst = dst.compile_stats()
        assert warm_dst == {"prefill_compiles": 0, "decode_compiles": 1}
        # pins released with the export record on both outcomes
        assert src.pool.kinds["full"].allocator.stats()["chains_pinned"] == 0
        assert src.pool.occupancy == 0

        # steady state: a second handoff (different length) recompiles
        # NOTHING on either side
        warm_src = src.compile_stats()
        prompt2, n2 = [2, 7, 1, 8, 2, 8, 1], 5
        src.submit(prompt2, max_new_tokens=n2)
        src.step()
        [rid2] = src.drain_ready_exports()
        comp2 = dst.import_shipment(src.export_shipment(rid2),
                                    max_new_tokens=n2, request_id=rid2)
        src.finish_export(rid2)
        src.step()
        assert comp2.result(timeout=60) == _reference(
            params, cfg, prompt2, n2
        )
        assert dst.compile_stats() == warm_dst
        assert src.compile_stats() == warm_src
    finally:
        dst.shutdown()
        src.shutdown()


def test_engine_rejects_corrupt_shipment_then_admits_clean_resend(model):
    params, cfg = model
    src = InferenceEngine(
        params, cfg, EngineConfig(role="prefill", **ENGINE_KW)
    )
    dst = InferenceEngine(
        params, cfg, EngineConfig(role="decode", **ENGINE_KW)
    )
    dst.start()
    try:
        prompt, n_new = [2, 7, 1, 8], 5
        src.submit(prompt, max_new_tokens=n_new)
        src.step()
        [rid] = src.drain_ready_exports()
        ship = src.export_shipment(rid)

        before = dst.pool.occupancy
        with pytest.raises(ShipmentCorrupt):
            dst.import_shipment(migration_mod.corrupt_copy(ship),
                                max_new_tokens=n_new)
        # never decoded, never admitted: no slot, no blocks, no garbage
        assert dst.pool.occupancy == before

        comp = dst.import_shipment(ship, max_new_tokens=n_new,
                                   request_id=rid)
        src.finish_export(rid)
        src.step()
        assert comp.result(timeout=60) == _reference(
            params, cfg, prompt, n_new
        )
    finally:
        dst.shutdown()
        src.shutdown()


def test_engine_cancel_export_decodes_in_place(model):
    """The fallback leg: cancel_export un-parks the slot and the prefill
    replica finishes the request itself, token-identical."""
    params, cfg = model
    src = InferenceEngine(
        params, cfg, EngineConfig(role="prefill", **ENGINE_KW)
    )
    try:
        prompt, n_new = [1, 6, 1, 8], 5
        comp = src.submit(prompt, max_new_tokens=n_new)
        src.step()
        [rid] = src.drain_ready_exports()
        src.cancel_export(rid)
        src.run_until_idle()
        assert comp.result(timeout=60) == _reference(
            params, cfg, prompt, n_new
        )
        assert comp.finish_reason == "length"
        assert src.pool.kinds["full"].allocator.stats()["chains_pinned"] == 0
    finally:
        src.shutdown()


# --------------------------------------------------------------------- #
# fleet e2e: disaggregated pools, affinity, fault ladder, fallback
# --------------------------------------------------------------------- #
def _disagg_fleet(params, cfg, replicas=2, prefill=1, **kw):
    return LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=ENGINE_KW,
        initial_replicas=replicas,
        prefill_replicas=prefill,
        max_retries=kw.pop("max_retries", 4),
        **kw,
    )


def test_disaggregated_fleet_token_identical(model):
    params, cfg = model
    fleet = _disagg_fleet(params, cfg, replicas=3, prefill=1)
    try:
        assert fleet.stats()["roles"] == {
            0: "prefill", 1: "decode", 2: "decode"
        }
        rng = np.random.default_rng(5)
        reqs = [
            (
                [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
                int(rng.integers(4, 8)),
            )
            for _ in range(6)
        ]
        entries = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
        for (p, n), e in zip(reqs, entries):
            assert e.result(timeout=180) == _reference(params, cfg, p, n)
            # prefilled on the prefill pool, finished on the decode pool
            assert e.replica_history[0] == 0
            assert e.retries == 0  # a clean migration is routing, not
            # failure recovery
        stats = fleet.stats()
        assert stats["completed"] == 6 and stats["failed"] == 0
        m = stats["migration"]
        assert m["migrated"] == 6 and m["verified"] == 6
        assert m["corrupt"] == 0 and m["fallbacks"] == 0
        assert m["bytes_shipped"] > 0
    finally:
        fleet.shutdown()


def test_same_burst_colocated_and_disaggregated_token_identical(model):
    """One burst through two replicas either way, two colocated and then one
    prefill + one decode: the handoff changes no token, every request
    migrates once and none falls back."""
    params, cfg = model
    rng = np.random.default_rng(7)
    reqs = [[int(t) for t in rng.integers(1, cfg.vocab_size, 6)] for _ in range(8)]
    streams, stats = {}, {}
    for prefill in (0, 1):
        fleet = _disagg_fleet(params, cfg, replicas=2, prefill=prefill)
        try:
            assert fleet.disaggregated == bool(prefill)
            entries = [fleet.submit(p, max_new_tokens=8) for p in reqs]
            streams[prefill] = [e.result(timeout=180) for e in entries]
            stats[prefill] = fleet.stats()
        finally:
            fleet.shutdown()
    assert streams[1] == streams[0]
    assert stats[0]["completed"] == stats[1]["completed"] == 8
    assert "migration" not in stats[0]
    m = stats[1]["migration"]
    assert (m["attempts"], m["migrated"], m["fallbacks"]) == (8, 8, 0)


def test_warm_chain_affinity_routes_repeat_prefix_to_same_replica(model):
    params, cfg = model
    fleet = _disagg_fleet(params, cfg, replicas=4, prefill=2)
    try:
        prompt = [9, 9, 9, 9, 2, 4]  # first block_size tokens = the key
        first = fleet.submit(prompt, max_new_tokens=4)
        first.result(timeout=180)
        warm = first.replica_history[0]
        # same prefix, different tail: lands on the SAME prefill replica
        # whose chain cache already holds the shared blocks
        again = fleet.submit(prompt[:4] + [7, 7], max_new_tokens=4)
        assert again.result(timeout=180) == _reference(
            params, cfg, prompt[:4] + [7, 7], 4
        )
        assert again.replica_history[0] == warm
    finally:
        fleet.shutdown()


def test_fleet_corrupt_shipment_checksum_retry(model):
    """A corrupt delivery is detected by the receiver's checksum gate
    (never decoded), counted, and the CLEAN original resent — to the
    same receiver, which proved itself healthy by rejecting garbage."""
    params, cfg = model
    with _fault_env("replica0:corrupt-shipment@req1"):
        fleet = _disagg_fleet(params, cfg)
        try:
            prompt, n_new = [3, 1, 4, 1], 6
            e = fleet.submit(prompt, max_new_tokens=n_new)
            assert e.result(timeout=180) == _reference(
                params, cfg, prompt, n_new
            )
            assert e.retries == 0  # transport retries never charge the
            # request's journal attempts
            m = fleet.stats()["migration"]
            assert m["corrupt"] == 1 and m["retries"] == 1
            assert m["migrated"] == 1 and m["verified"] == 1
        finally:
            fleet.shutdown()


def test_fleet_drop_and_stall_shipment_retry(model):
    params, cfg = model
    with _fault_env("replica0:drop-shipment@req1"):
        fleet = _disagg_fleet(params, cfg)
        try:
            prompt, n_new = [2, 7, 1], 6
            e = fleet.submit(prompt, max_new_tokens=n_new)
            assert e.result(timeout=180) == _reference(
                params, cfg, prompt, n_new
            )
            m = fleet.stats()["migration"]
            assert m["retries"] == 1 and m["migrated"] == 1
            assert m["corrupt"] == 0
        finally:
            fleet.shutdown()
    # a stalled send that blows the policy's send timeout is a retry too
    with _fault_env("replica0:stall-shipment@req1:0.3"):
        fleet = _disagg_fleet(
            params, cfg,
            migration_policy=MigrationPolicy(send_timeout_s=0.1),
        )
        try:
            prompt, n_new = [1, 6, 1, 8], 5
            e = fleet.submit(prompt, max_new_tokens=n_new)
            assert e.result(timeout=180) == _reference(
                params, cfg, prompt, n_new
            )
            m = fleet.stats()["migration"]
            assert m["retries"] == 1 and m["migrated"] == 1
        finally:
            fleet.shutdown()


def test_fleet_crash_mid_admit_falls_back_to_colocated_decode(model):
    """Every import into the only decode replica dies mid-admit: after
    max_attempts the request un-parks and decodes on the PREFILL replica
    — graceful degradation, counted, token-identical, never dropped."""
    params, cfg = model
    with _fault_env("replica1:crash-mid-admit@every:1"):
        fleet = _disagg_fleet(
            params, cfg, max_retries=6,
            breaker_threshold=2, breaker_cooldown_s=0.2,
        )
        try:
            prompt, n_new = [5, 9, 2, 6], 6
            e = fleet.submit(prompt, max_new_tokens=n_new)
            assert e.result(timeout=180) == _reference(
                params, cfg, prompt, n_new
            )
            stats = fleet.stats()
            assert stats["failed"] == 0
            m = stats["migration"]
            assert m["fallbacks"] == 1 and m["migrated"] == 0
            assert m["verified"] == 0  # garbage never decoded, and the
            # crashed admits never count as landed
        finally:
            fleet.shutdown()


def test_fallback_decode_beside_parked_slots_token_identical(model):
    """Regression: a parked (export-pending) slot rides the fixed-shape
    decode program as a padding row, and its row of the block table must
    be trash-masked — otherwise the padding write (token 0, pos 0) lands
    in the parked request's first prompt block and corrupts the KV its
    fallback decode (or shipment) depends on. Saturate the decode pool
    so fallbacks decode on the prefill replica WHILE sibling slots are
    still parked, the exact mixed regime that exposed the clobber."""
    params, cfg = model
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=dict(ENGINE_KW, num_slots=2),
        initial_replicas=2,
        prefill_replicas=1,
        max_retries=4,
        migration_policy=migration_mod.MigrationPolicy(
            max_attempts=2, backoff_base_s=0.01, backoff_max_s=0.05
        ),
    )
    try:
        rng = np.random.default_rng(7)
        reqs = [
            [int(t) for t in rng.integers(1, cfg.vocab_size, 6)]
            for _ in range(8)
        ]
        entries = [fleet.submit(p, max_new_tokens=16) for p in reqs]
        for p, e in zip(reqs, entries):
            assert e.result(timeout=300) == _reference(params, cfg, p, 16)
        stats = fleet.stats()
        assert stats["completed"] == 8 and stats["failed"] == 0
        m = stats["migration"]
        assert m["corrupt"] == 0
        # the 2-slot decode pool cannot hold the burst: some requests
        # must have migrated and some fallen back to colocated decode
        assert m["migrated"] >= 1 and m["fallbacks"] >= 1
    finally:
        fleet.shutdown()


def test_fleet_sustained_migration_kill_loop_zero_drop(model):
    """THE acceptance e2e: drop-shipment + corrupt-shipment + repeated
    receiver crash-mid-admit, sustained across relaunches (no fuse).
    Every request completes token-identical to generate(), every corrupt
    shipment is caught by checksum, zero dropped requests."""
    params, cfg = model
    spec = (
        "replica0:drop-shipment@every:5,"
        "replica0:corrupt-shipment@every:3,"
        "replica1:crash-mid-admit@every:4"
    )
    with _fault_env(spec):
        fleet = _disagg_fleet(
            params, cfg, replicas=3, prefill=1, max_retries=8,
            breaker_threshold=3, breaker_cooldown_s=0.3,
        )
        try:
            rng = np.random.default_rng(23)
            reqs = [
                (
                    [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
                    int(rng.integers(4, 8)),
                )
                for _ in range(10)
            ]
            entries = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
            for (p, n), e in zip(reqs, entries):
                assert e.result(timeout=300) == _reference(
                    params, cfg, p, n
                )
            stats = fleet.stats()
            assert stats["completed"] == len(reqs)
            assert stats["failed"] == 0 and stats["shed"] == 0
            m = stats["migration"]
            # the fault matrix provably fired, and every corrupt
            # delivery was caught by the checksum gate (corrupt counts
            # only increment on ShipmentCorrupt from verify — i.e.
            # BEFORE any payload reached a device cache)
            assert m["corrupt"] >= 1 and m["retries"] >= 2
            assert m["migrated"] + m["fallbacks"] >= 1
        finally:
            fleet.shutdown()


# --------------------------------------------------------------------- #
# the regression floor: a single homogeneous pool is byte-identical to
# the colocated path — same tokens, flat jit caches
# --------------------------------------------------------------------- #
def test_homogeneous_single_pool_identical_to_colocated(model):
    params, cfg = model
    ekw = dict(num_slots=4, max_prompt_len=16, max_len=32, max_queue=64,
               block_size=4)
    fleet = LocalReplicaFleet(
        lambda: (params, cfg), engine_kwargs=ekw, initial_replicas=1,
    )
    try:
        assert not fleet.disaggregated
        assert "migration" not in fleet.stats()
        eng = fleet._replicas[0]
        assert eng.load()["role"] == "both"
        rng = np.random.default_rng(3)
        reqs = [
            (
                [int(t) for t in rng.integers(1, cfg.vocab_size, 5)],
                int(rng.integers(4, 8)),
            )
            for _ in range(4)
        ]
        entries = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
        got = [e.result(timeout=180) for e in entries]
        warm = eng.compile_stats()
        assert warm == {"prefill_compiles": 1, "decode_compiles": 1}
        for (p, n), g in zip(reqs, got):
            assert g == _reference(params, cfg, p, n)
        # steady state: more traffic, zero recompiles
        more = [fleet.submit(p, max_new_tokens=n) for p, n in reqs]
        for (p, n), e in zip(reqs, more):
            assert e.result(timeout=180) == _reference(params, cfg, p, n)
        assert eng.compile_stats() == warm
    finally:
        fleet.shutdown()
