"""Trace-driven replay (ray_lightning_tpu/workloads/): seeded generator
determinism, the JSONL recorded-trace round-trip, and the ReplayDriver
verdict against a live fleet.

The acceptance bar (ISSUE: million-user scenario harness): a seeded
flash-crowd trace replayed at 10x virtual time against a 2-replica
fleet with an RLT_FAULT chaos fault yields a verdict whose goodput
sections sum to wall time, whose ``guaranteed`` tenants attain at least
the ``best_effort`` SLO attainment, and in which zero quota-conformant
requests starve — and ``cli replay`` reproduces the same verdict as an
artifact.

Generator/format tests run without a model; driver tests reuse the
tiny-Llama fixture idiom; the chaos e2e and the CLI run are slow.
"""
import contextlib
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.runtime import faults
from ray_lightning_tpu.serving import LocalReplicaFleet, TenantRegistry, TenantSpec
from ray_lightning_tpu.workloads import (
    ArrivalEvent,
    ReplayDriver,
    bursty_trace,
    diurnal_trace,
    flash_crowd_trace,
    heavy_tail_prompt_len,
    read_trace,
    write_trace,
)
from ray_lightning_tpu.workloads.replay import VERDICT_KIND

pytestmark = pytest.mark.replay


# --------------------------------------------------------------------- #
# generators: determinism, shape, bounds
# --------------------------------------------------------------------- #
def test_generators_are_seed_deterministic():
    mix = {"gold": 3.0, "free": 1.0}
    for gen in (
        lambda seed: diurnal_trace(30.0, 4.0, tenants=mix, seed=seed),
        lambda seed: bursty_trace(30.0, 2.0, tenants=mix, seed=seed),
        lambda seed: flash_crowd_trace(
            30.0, 2.0, crowd_tenant="free", crowd_at_s=10.0,
            tenants=mix, seed=seed,
        ),
    ):
        a, b, c = gen(7), gen(7), gen(8)
        assert a == b  # byte-for-byte reproducible
        assert a != c  # and the seed actually matters
        assert a, "trace generated zero arrivals"
        assert all(0.0 <= ev.t < 30.0 for ev in a)
        assert [ev.t for ev in a] == sorted(ev.t for ev in a)
        assert all(ev.tenant in mix for ev in a)


def test_diurnal_rate_follows_the_cycle():
    # amplitude 1: the first half-period peaks, the second bottoms out
    events = diurnal_trace(60.0, 8.0, seed=3, amplitude=1.0)
    first = sum(1 for ev in events if ev.t < 30.0)
    second = len(events) - first
    assert first > 2 * second, (first, second)
    with pytest.raises(ValueError):
        diurnal_trace(10.0, 1.0, amplitude=1.5)


def test_flash_crowd_spikes_one_tenant():
    events = flash_crowd_trace(
        20.0, 2.0, crowd_tenant="free", crowd_at_s=10.0, crowd_mult=10.0,
        tenants={"gold": 1.0}, seed=5,
    )
    before = [ev for ev in events if ev.t < 10.0]
    spike = [ev for ev in events if 10.0 <= ev.t < 13.0]
    assert len(spike) > 2 * len(before) / 10.0 * 3.0  # crowd density jump
    crowd_share = sum(1 for ev in spike if ev.tenant == "free") / len(spike)
    assert crowd_share > 0.7, crowd_share


def test_heavy_tail_prompt_lens_are_clipped_and_skewed():
    import random

    rng = random.Random(0)
    lens = [heavy_tail_prompt_len(rng, 4, 64) for _ in range(2000)]
    assert min(lens) >= 4 and max(lens) <= 64
    assert max(lens) > 48  # the tail actually reaches
    # skew: the median sits far below the midpoint of the range
    assert sorted(lens)[len(lens) // 2] < 20


def test_trace_jsonl_round_trip(tmp_path):
    events = diurnal_trace(15.0, 3.0, tenants={"a": 1.0, "b": 2.0}, seed=1)
    path = str(tmp_path / "trace.jsonl")
    write_trace(path, events, generator="diurnal", seed=1)
    header, back = read_trace(path)
    assert back == events
    assert header["kind"] == "rlt-trace" and header["generator"] == "diurnal"
    # wrong kind / empty file fail loudly, not silently
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "other"}\n')
    with pytest.raises(ValueError):
        read_trace(str(bad))
    (tmp_path / "empty.jsonl").write_text("")
    with pytest.raises(ValueError):
        read_trace(str(tmp_path / "empty.jsonl"))


# --------------------------------------------------------------------- #
# ReplayDriver against a live fleet
# --------------------------------------------------------------------- #
def _cfg():
    return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return init_params(jax.random.key(0), cfg), cfg


ENGINE_KW = dict(num_slots=4, max_prompt_len=8, max_len=32, max_queue=256)


def _registry(free_rate=None):
    return TenantRegistry([
        TenantSpec("gold", tenant_class="guaranteed", weight=4.0,
                   ttft_slo_ms=30_000.0),
        TenantSpec("free", tenant_class="best_effort", weight=1.0,
                   rate=free_rate, ttft_slo_ms=30_000.0),
    ])


def _fleet(model, registry, replicas=2, **kw):
    params, cfg = model
    return LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=ENGINE_KW,
        initial_replicas=replicas,
        tenants=registry,
        **kw,
    )


@contextlib.contextmanager
def _fault_env(spec):
    old = os.environ.get(faults.FAULT_ENV)
    os.environ[faults.FAULT_ENV] = spec
    faults._serve_cache = (None, [])
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(faults.FAULT_ENV, None)
        else:
            os.environ[faults.FAULT_ENV] = old
        faults._serve_cache = (None, [])


_QUICK_TRACES = {
    "diurnal": lambda: diurnal_trace(
        4.0, 3.0, tenants={"gold": 3.0, "free": 1.0}, seed=2,
        prompt_len=(2, 6), max_new_tokens=3,
    ),
    # a crowd of the best-effort tenant a third of the way in
    "flash_crowd": lambda: flash_crowd_trace(
        4.0, 3.0, crowd_tenant="free", crowd_at_s=4.0 / 3,
        tenants={"gold": 1.0}, seed=2, prompt_len=(2, 6), max_new_tokens=3,
    ),
}


@pytest.mark.parametrize("generator,replicas", [("diurnal", 1), ("flash_crowd", 2)])
def test_replay_driver_verdict_quick(model, tmp_path, generator, replicas):
    registry = _registry()
    fleet = _fleet(model, registry, replicas=replicas)
    artifact = str(tmp_path / "verdict.json")
    try:
        # warm the step executable so compile time is not in the TTFTs
        fleet.submit([1, 2], max_new_tokens=2).result(timeout=180)
        events = _QUICK_TRACES[generator]()
        verdict = ReplayDriver(
            fleet, events, tenants=registry, speed=8.0, seed=2,
            vocab=int(model[1].vocab_size), max_prompt_len=8,
            artifact_path=artifact, trace_meta={"generator": generator},
        ).run()
    finally:
        fleet.shutdown()
    assert verdict["passed"], verdict["failures"]
    assert verdict["goodput"]["sums_to_wall"]
    assert verdict["requests"]["submitted"] == len(events)
    assert verdict["requests"]["dispatched"] == len(events)
    assert verdict["starvation"]["unterminated"] == []
    for name in ("gold", "free"):
        assert verdict["tenants"][name]["completed"] > 0
        assert verdict["tenants"][name]["slo_attainment"] == 1.0
    # the artifact is the same verdict, atomically written
    with open(artifact) as fh:
        on_disk = json.load(fh)
    assert on_disk["kind"] == VERDICT_KIND and on_disk["passed"]


def test_replay_driver_accounts_quota_refusals(model):
    # rate=0, burst=2: exactly two free-tenant arrivals clear the bucket
    registry = _registry(free_rate=0.0)
    registry.register(
        TenantSpec("free", tenant_class="best_effort", weight=1.0,
                   rate=0.0, burst=2.0, ttft_slo_ms=30_000.0)
    )
    fleet = _fleet(model, registry, replicas=1)
    try:
        fleet.submit([1, 2], max_new_tokens=2).result(timeout=180)
        events = [
            ArrivalEvent(t=0.05 * i, tenant="free", prompt_len=3,
                         max_new_tokens=2)
            for i in range(5)
        ]
        verdict = ReplayDriver(
            fleet, events, tenants=registry, speed=4.0, seed=0,
            vocab=int(model[1].vocab_size), max_prompt_len=8,
        ).run()
    finally:
        fleet.shutdown()
    # refusals are quota_rejected — never shed, never starvation
    assert verdict["requests"]["quota_rejected"] == 3
    assert verdict["requests"]["shed"] == 0
    assert verdict["tenants"]["free"]["quota_rejected"] == 3
    assert verdict["quota"]["ok"] and verdict["quota"]["checked"]
    assert verdict["passed"], verdict["failures"]


@pytest.mark.slow
def test_flash_crowd_replay_survives_chaos_kill_loop(model, tmp_path):
    """The ISSUE acceptance run: seeded flash crowd, 2 replicas, a
    sustained replica-0 crash loop underneath — the verdict must still
    show goodput summing to wall, guaranteed attainment >= best_effort,
    and zero quota-conformant starvation."""
    registry = _registry()
    events = flash_crowd_trace(
        10.0, 2.0, crowd_tenant="free", crowd_at_s=4.0, crowd_mult=8.0,
        tenants={"gold": 1.0}, seed=11, prompt_len=(2, 6),
        max_new_tokens=3, heavy_tail=True,
    )
    artifact = str(tmp_path / "chaos-verdict.json")
    with _fault_env("replica0:crash@every:40"):
        fleet = _fleet(
            model, registry, replicas=2, max_retries=8,
            breaker_threshold=2, breaker_cooldown_s=0.2,
        )
        try:
            fleet.submit([1, 2], max_new_tokens=2).result(timeout=180)
            verdict = ReplayDriver(
                fleet, events, tenants=registry, speed=10.0, seed=11,
                vocab=int(model[1].vocab_size), max_prompt_len=8,
                drain_timeout_s=180.0, artifact_path=artifact,
                trace_meta={"generator": "flash-crowd", "seed": 11},
            ).run()
        finally:
            fleet.shutdown()
    assert verdict["passed"], verdict["failures"]
    assert verdict["chaos"] == "replica0:crash@every:40"
    assert verdict["goodput"]["sums_to_wall"]
    assert verdict["starvation"]["ok"]
    assert verdict["starvation"]["unterminated"] == []
    att = verdict["slo"]["min_attainment_by_class"]
    assert att["guaranteed"] >= att["best_effort"]
    crowd = verdict["tenants"]["free"]
    assert crowd["dispatched"] > verdict["tenants"]["gold"]["dispatched"]
    assert verdict["tenants"]["gold"]["completed"] > 0
    with open(artifact) as fh:
        assert json.load(fh)["passed"]


@pytest.mark.slow
def test_cli_replay_writes_passing_verdict(tmp_path, capsys):
    from ray_lightning_tpu import cli

    out = str(tmp_path / "cli-verdict.json")
    rc = cli.main([
        "replay", "--trace", "flash-crowd", "--duration", "6",
        "--rps", "3", "--speed", "8", "--replicas", "2",
        "--seed", "11", "--out", out, "--json",
    ])
    assert rc == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["kind"] == VERDICT_KIND and verdict["passed"]
    with open(out) as fh:
        on_disk = json.load(fh)
    assert on_disk["passed"] and on_disk["trace"]
    assert on_disk["slo"]["min_attainment_by_class"]
