"""Fleet-wide performance profiler: HLO cost accounting, coordinated
capture, and step-time attribution.

Unit layer: collective extraction from canned and real (shard_map) HLO,
analytic FLOPs/bytes for a tiny jitted matmul step, roofline verdicts,
the driver command file, FleetProfiler window arming (env and command
paths), aggregator profile ingestion and rank eviction, and the
docs->code direction of scripts/check_metrics_docs.py. E2E layer: an
in-process fit with an armed window producing a ``profile`` section in
summary.json, plus a slow 2-worker coordinated capture where both ranks
start at the same global step.
"""
from __future__ import annotations

import json
import os
import time

import pytest

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.observability import metrics as obs_metrics
from ray_lightning_tpu.observability import profiler as prof
from ray_lightning_tpu.observability.aggregator import (
    EVENTS_FILE,
    SUMMARY_FILE,
    DriverAggregator,
    telemetry_dir,
    write_local_dump,
)
from ray_lightning_tpu.runtime.supervisor import Supervisor
from tests.utils import BoringModel, get_trainer

pytestmark = pytest.mark.profiling


@pytest.fixture(autouse=True)
def profiler_reset():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture
def fake_trace(monkeypatch):
    """Replace the jax.profiler indirection with a call log so window
    tests never start a real device trace."""
    calls = []
    monkeypatch.setattr(prof, "_start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(prof, "_stop_trace", lambda: calls.append(("stop",)))
    return calls


# --------------------------------------------------------------------- #
# HLO collective extraction
# --------------------------------------------------------------------- #
_CANNED_HLO = """
HloModule jit_step
ENTRY main {
  %p0 = f32[8,128]{1,0} parameter(0)
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %p0), replica_groups={}
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %x), dimensions={0}
  %agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ags)
  %rs = bf16[16]{0} reduce-scatter(bf16[32]{0} %y), dimensions={0}
}
"""


def test_collectives_from_canned_hlo():
    out = prof.collectives_from_hlo(_CANNED_HLO)
    # f32[8,128] = 8*128*4 bytes
    assert out["all-reduce"] == {"count": 1, "bytes": 4096}
    # async pair counts ONCE (the -start; -done is bookkeeping), with the
    # tuple result's total bytes: f32[4] + f32[8] = 16 + 32
    assert out["all-gather"] == {"count": 1, "bytes": 48}
    # bf16 is 2 bytes/elem
    assert out["reduce-scatter"] == {"count": 1, "bytes": 32}
    assert "all-to-all" not in out


def test_collectives_from_hlo_ignores_pointwise_ops():
    assert prof.collectives_from_hlo("%a = f32[4]{0} add(f32[4] %x, f32[4] %y)") == {}
    assert prof.collectives_from_hlo("") == {}


def test_collectives_from_real_shard_map_program():
    import jax
    import jax.numpy as jnp
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices for a real collective")
    mesh = Mesh(jax.devices()[:2], ("dp",))

    def psum_step(x):
        return jax.lax.psum(x, "dp")

    fn = jax.jit(
        shard_map(psum_step, mesh=mesh, in_specs=P("dp"), out_specs=P())
    )
    x = jnp.ones((2, 16), jnp.float32)
    rep = prof.analyze_jitted(fn, x, program="psum")
    assert rep is not None
    assert rep.collectives.get("all-reduce", {}).get("count", 0) >= 1
    assert rep.collective_bytes > 0


# --------------------------------------------------------------------- #
# analytic cost of a tiny jitted step
# --------------------------------------------------------------------- #
def test_analyze_jitted_tiny_matmul():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(a, b):
        return a @ b

    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 32), jnp.float32)
    rep = prof.analyze_jitted(step, a, b, program="matmul")
    assert rep is not None
    assert rep.program == "matmul"
    # XLA counts 2*M*N*K for the matmul, plus possible fusion noise
    analytic = 2 * 8 * 32 * 16
    assert analytic <= rep.flops <= analytic * 2
    # reads a + b, writes out, all f32; allow layout/padding slack
    io = (8 * 16 + 16 * 32 + 8 * 32) * 4
    assert io <= rep.bytes_accessed <= io * 2
    assert rep.collectives == {}
    d = rep.to_dict()
    assert d["step_flops"] == rep.flops
    assert d["step_bytes"] == rep.bytes_accessed
    assert d["collective_bytes"] == 0


def test_cost_analysis_env_kill_switch(monkeypatch):
    monkeypatch.setenv(prof.COST_ANALYSIS_ENV, "0")
    assert not prof.cost_analysis_enabled()
    p = prof.FleetProfiler("/nonexistent", environ={})
    assert p.analyze("x", None, ()) is None
    monkeypatch.setenv(prof.COST_ANALYSIS_ENV, "1")
    assert prof.cost_analysis_enabled()


# --------------------------------------------------------------------- #
# roofline
# --------------------------------------------------------------------- #
def _report(flops, nbytes):
    return prof.CostReport(program="p", flops=flops, bytes_accessed=nbytes)


def test_roofline_analytic_verdicts():
    # peaks: 1 TFLOP/s, 1 GB/s -> machine balance 1000 flops/byte
    compute = prof.roofline(_report(1e9, 1e6), peak_tflops=1.0, peak_gbps=1.0)
    assert compute["arithmetic_intensity"] == pytest.approx(1000.0)
    assert compute["machine_balance"] == pytest.approx(1000.0)
    assert compute["verdict"] == "compute-bound"
    memory = prof.roofline(_report(1e9, 1e9), peak_tflops=1.0, peak_gbps=1.0)
    assert memory["arithmetic_intensity"] == pytest.approx(1.0)
    assert memory["verdict"] == "bandwidth-bound"
    # analytic-only: no measured fields
    assert "mfu" not in compute and "step_time_s" not in compute


def test_roofline_measured_mfu_and_bandwidth():
    # 1e9 flops in 0.01s at 1 TFLOP/s peak -> 10% MFU
    out = prof.roofline(
        _report(1e9, 1e6), step_time_s=0.01, peak_tflops=1.0, peak_gbps=1.0
    )
    assert out["mfu"] == pytest.approx(0.1)
    assert out["achieved_tflops"] == pytest.approx(0.1)
    assert out["bandwidth_util"] == pytest.approx(1e6 / 0.01 / 1e9)
    assert out["measured_bound"] == "compute"
    assert out["step_time_s"] == 0.01


def test_detect_peak_bandwidth_override(monkeypatch):
    monkeypatch.setenv(prof.PEAK_GBPS_ENV, "1234.5")
    assert prof.detect_peak_bandwidth_gbps() == 1234.5
    monkeypatch.setenv(prof.PEAK_GBPS_ENV, "junk")
    # falls back to detection, and the CPU is "not measured"
    assert prof.detect_peak_bandwidth_gbps() is None


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "kind,tflops,gbps",
    [("TPU v5 lite", 197.0, 819.0), ("TPU v4", 275.0, 1228.0),
     ("TPU v6e", 918.0, 1640.0)],
)
def test_peak_tables_know_the_chip_by_its_device_kind(monkeypatch, kind, tflops, gbps):
    import jax

    from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice("tpu", kind)])
    assert detect_peak_tflops() == tflops
    assert prof.detect_peak_bandwidth_gbps() == gbps


def test_unknown_device_kind_is_an_error_not_a_default(monkeypatch):
    import jax

    from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v9x")])
    with pytest.raises(ValueError, match="unknown accelerator 'tpu v9x'"):
        detect_peak_tflops()
    with pytest.raises(ValueError, match="unknown accelerator 'tpu v9x'"):
        prof.detect_peak_bandwidth_gbps()
    # the override is the way out for a chip the tables do not know
    monkeypatch.setenv("RLT_PEAK_TFLOPS", "500")
    monkeypatch.setenv(prof.PEAK_GBPS_ENV, "2000")
    assert detect_peak_tflops() == 500.0
    assert prof.detect_peak_bandwidth_gbps() == 2000.0


def test_cpu_roofline_is_not_measured():
    """No chip, no peaks: counts from the program, no share of a machine
    nobody deploys (and no MFU gauge against an assumed 0.1 TFLOP/s)."""
    out = prof.roofline(_report(1e9, 1e6), step_time_s=0.01)
    assert out == {"arithmetic_intensity": 1000.0, "verdict": "not measured"}
    reg = obs_metrics.MetricsRegistry()
    prof.publish_cost_report(reg, _report(1e9, 1e6), step_time_s=0.01)
    assert "rlt_cost_mfu" not in reg.prometheus_text()


# --------------------------------------------------------------------- #
# metrics publication
# --------------------------------------------------------------------- #
def test_publish_cost_report_gauges_and_counter():
    reg = obs_metrics.MetricsRegistry()
    rep = prof.CostReport(
        program="train_step",
        flops=1000.0,
        bytes_accessed=500.0,
        collectives={"all-reduce": {"count": 2, "bytes": 64}},
    )
    prof.publish_cost_report(reg, rep, step_time_s=0.001, peak_tflops=0.1)
    text = reg.prometheus_text()
    assert 'rlt_step_flops{program="train_step"} 1000' in text
    assert 'rlt_step_bytes{program="train_step"} 500' in text
    assert 'op="all-reduce"' in text and "rlt_collective_bytes_total" in text
    assert "rlt_cost_mfu" in text


# --------------------------------------------------------------------- #
# driver command file
# --------------------------------------------------------------------- #
def test_profile_command_roundtrip(tmp_path):
    run_dir = str(tmp_path)
    assert prof.read_profile_command(run_dir) is None
    written = prof.write_profile_command(run_dir, num_steps=5, start_step=40, note="x")
    assert os.path.isfile(os.path.join(run_dir, prof.PROFILE_CMD_FILE))
    cmd = prof.read_profile_command(run_dir)
    assert cmd == written
    assert cmd["num_steps"] == 5 and cmd["start_step"] == 40
    first_id = cmd["id"]
    prof.write_profile_command(run_dir, num_steps=1)
    assert prof.read_profile_command(run_dir)["id"] != first_id


def test_read_profile_command_tolerates_garbage(tmp_path):
    (tmp_path / prof.PROFILE_CMD_FILE).write_text("{not json")
    assert prof.read_profile_command(str(tmp_path)) is None


# --------------------------------------------------------------------- #
# FleetProfiler windows
# --------------------------------------------------------------------- #
def _run_steps(p, n, dt=0.01):
    import jax.numpy as jnp

    batch = jnp.ones((4, 8), jnp.float32)
    for step in range(n):
        p.before_step(step, batch)
        p.after_step(step, dt)


def test_fleet_profiler_env_armed_window(tmp_path, fake_trace):
    p = prof.FleetProfiler(
        str(tmp_path),
        rank=1,
        environ={prof.PROFILE_AT_STEP_ENV: "3", prof.PROFILE_STEPS_ENV: "2"},
    )
    _run_steps(p, 7)
    # exactly one start/stop pair, rank-suffixed trace dir
    assert [c[0] for c in fake_trace] == ["start", "stop"]
    assert fake_trace[0][1].endswith(os.path.join(prof.PROFILE_DIR, "rank1"))
    recs = prof.drain_pending()
    kinds = [r["kind"] for r in recs]
    assert "capture" in kinds and "attribution" in kinds
    cap = next(r for r in recs if r["kind"] == "capture")
    assert cap["start_step"] == 3
    assert cap["actual_start"] == 3
    assert cap["num_steps"] == 2
    assert cap["rank"] == 1
    attr = next(r for r in recs if r["kind"] == "attribution")
    assert attr["steps"] == 2
    assert attr["step_time_s"] == pytest.approx(0.01, rel=0.5)
    # on the CPU the split against the chip's peaks is "not measured"
    assert "compute_s" not in attr and "unattributed_s" not in attr


def test_fleet_profiler_command_polling_and_dedup(tmp_path, fake_trace):
    p = prof.FleetProfiler(str(tmp_path), rank=0, poll_interval=0.0, environ={})
    prof.write_profile_command(str(tmp_path), num_steps=1, start_step=2)
    _run_steps(p, 5)
    assert [c[0] for c in fake_trace] == ["start", "stop"]
    recs = prof.drain_pending()
    cap = next(r for r in recs if r["kind"] == "capture")
    assert cap["start_step"] == 2 and cap["actual_start"] == 2
    # the same command id must not re-arm on continued polling
    _run_steps(p, 5)
    assert [c[0] for c in fake_trace] == ["start", "stop"]
    assert not any(r["kind"] == "capture" for r in prof.drain_pending())


def test_fleet_profiler_late_command_starts_asap(tmp_path, fake_trace):
    """An armed start step already in the past opens the window on the
    next step instead of never firing."""
    p = prof.FleetProfiler(str(tmp_path), environ={prof.PROFILE_AT_STEP_ENV: "1"})
    for step in range(5, 9):
        p.before_step(step)
        p.after_step(step, 0.01)
    assert fake_trace and fake_trace[0][0] == "start"
    cap = next(r for r in prof.drain_pending() if r["kind"] == "capture")
    assert cap["actual_start"] == 5


def test_fleet_profiler_close_mid_window_stops_trace(tmp_path, fake_trace):
    p = prof.FleetProfiler(str(tmp_path), environ={prof.PROFILE_AT_STEP_ENV: "0"})
    p.before_step(0)
    assert fake_trace == [("start", fake_trace[0][1])]
    p.close()
    assert fake_trace[-1] == ("stop",)
    p.close()  # idempotent
    assert [c[0] for c in fake_trace].count("stop") == 1


def test_fleet_profiler_never_armed_is_cheap(tmp_path, fake_trace):
    p = prof.FleetProfiler(str(tmp_path), environ={}, poll_interval=3600.0)
    _run_steps(p, 20)
    assert fake_trace == []
    assert not any(
        r["kind"] in ("capture", "attribution") for r in prof.drain_pending()
    )


# --------------------------------------------------------------------- #
# beat payload plumbing
# --------------------------------------------------------------------- #
def test_collect_beat_payload_carries_profile_records():
    obs.enable()
    prof.push_record({"kind": "cost", "program": "train_step"})
    payload = obs.collect_beat_payload()
    assert payload is not None
    assert payload["p"] == [{"kind": "cost", "program": "train_step"}]
    # drained: a second beat has nothing new
    again = obs.collect_beat_payload()
    assert again is None or "p" not in again


def test_collect_beat_payload_profile_without_recorder():
    """An env-armed profile on a telemetry-off run still ships records."""
    assert obs.get_recorder() is None
    prof.push_record({"kind": "capture", "rank": 0})
    payload = obs.collect_beat_payload()
    assert payload == {"p": [{"kind": "capture", "rank": 0}]}
    assert obs.collect_beat_payload() is None


def test_obs_reset_clears_pending_profile_records():
    prof.push_record({"kind": "cost"})
    obs.reset()
    assert prof.drain_pending() == []


# --------------------------------------------------------------------- #
# aggregator: profile ingestion + summary + report rendering
# --------------------------------------------------------------------- #
def _cost_rec(rank=0, mfu=None):
    roof = {"verdict": "compute-bound"}
    if mfu is not None:
        roof["mfu"] = mfu
    return {
        "kind": "cost",
        "rank": rank,
        "program": "train_step",
        "step_flops": 1e9,
        "step_bytes": 1e6,
        "collective_bytes": 64,
        "collectives": {"all-reduce": {"count": 1, "bytes": 64}},
        "roofline": roof,
        "ts": time.time(),
    }


def test_aggregator_profile_summary_and_events(tmp_path):
    run_dir = str(tmp_path / "telemetry")
    agg = DriverAggregator(run_dir, num_workers=2)
    cap = {
        "kind": "capture",
        "rank": 1,
        "window": "env",
        "start_step": 3,
        "actual_start": 3,
        "num_steps": 2,
        "trace_dir": "/x/profile/rank1",
    }
    attr = {
        "kind": "attribution",
        "rank": 1,
        "steps": 2,
        "step_time_s": 0.01,
        "compute_s": 0.004,
        "collective_s": 0.001,
        "device_transfer_s": 0.0,
        "host_input_s": 0.0,
        "unattributed_s": 0.005,
    }
    agg.on_beat(1, 5, time.time(), payload={"p": [_cost_rec(1), cap, attr]})
    # measured (mfu-bearing) cost replaces the analytic one, not vice versa
    agg.ingest_profile(0, _cost_rec(0, mfu=0.42))
    agg.ingest_profile(0, _cost_rec(0))
    summary = agg.summary()
    profile = summary["profile"]
    assert profile["cost"]["train_step"]["roofline"]["mfu"] == 0.42
    assert profile["captures"][0]["trace_dir"] == "/x/profile/rank1"
    assert profile["attribution"]["1"]["compute_s"] == 0.004
    report = prof.format_profile_report(summary)
    assert "train_step" in report
    assert "rank1" in report  # trace dir shows up in the captures table
    agg.finalize()
    events = [
        json.loads(line)
        for line in open(os.path.join(run_dir, EVENTS_FILE))
    ]
    assert any(e["event"] == "profile_capture" and e["rank"] == 1 for e in events)


def test_format_profile_report_without_data():
    assert "no profile data" in prof.format_profile_report(None)
    assert "no profile data" in prof.format_profile_report({"cluster": {}})


def test_write_local_dump_includes_profile(tmp_path):
    run_dir = str(tmp_path / "telemetry")
    rec = obs.enable()
    write_local_dump(
        run_dir, rec, obs_metrics.get_registry(), profile=[_cost_rec()]
    )
    summary = json.load(open(os.path.join(run_dir, SUMMARY_FILE)))
    assert summary["profile"]["cost"]["train_step"]["step_flops"] == 1e9


# --------------------------------------------------------------------- #
# rank eviction (elastic shrink -> telemetry eviction)
# --------------------------------------------------------------------- #
def _beat(agg, rank, step=5):
    reg = obs_metrics.MetricsRegistry()
    reg.histogram("rlt_step_time_seconds").observe(0.1 * (rank + 1))
    reg.gauge("rlt_samples_per_sec").set(100.0 * (rank + 1))
    agg.on_beat(rank, step, time.time(), payload={"m": reg.snapshot(delta=True)})


def test_drop_rank_evicts_all_per_rank_state(tmp_path):
    agg = DriverAggregator(str(tmp_path / "t"), num_workers=2)
    _beat(agg, 0)
    _beat(agg, 1)
    agg.ingest_profile(1, {"kind": "capture", "rank": 1, "window": "w"})
    assert "1" in agg.summary()["per_rank"]
    agg.drop_rank(1)
    summary = agg.summary()
    assert "1" not in summary["per_rank"]
    assert "0" in summary["per_rank"]  # survivor untouched
    assert 'rank="1"' not in agg.registry.prometheus_text()
    assert 'rank="0"' in agg.registry.prometheus_text()
    assert not summary.get("profile", {}).get("captures")
    # the eviction is visible in the event log (read back after finalize)
    agg.finalize()
    lines = [
        json.loads(line)
        for line in open(os.path.join(str(tmp_path / "t"), EVENTS_FILE))
    ]
    assert any(e["event"] == "rank_dropped" and e["rank"] == 1 for e in lines)


def test_registry_drop_series():
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("rlt_worker_step", rank=0).set(1)
    reg.gauge("rlt_worker_step", rank=1).set(2)
    reg.counter("rlt_x_total", rank=1, op="a").inc(3)
    reg.gauge("rlt_unlabeled").set(9)
    assert reg.drop_series(rank=1) == 2
    text = reg.prometheus_text()
    assert 'rank="1"' not in text
    assert 'rank="0"' in text and "rlt_unlabeled" in text
    assert reg.drop_series(rank=7) == 0


def test_supervisor_forget_rank_drop_telemetry():
    class _Agg:
        dropped = []

        def drop_rank(self, rank):
            self.dropped.append(rank)

    agg = _Agg()
    sup = Supervisor(num_workers=2, drain=list, hang_timeout=5.0, aggregator=agg)
    sup.track_rank(0)
    sup.track_rank(1)
    sup.forget_rank(1)  # transient: telemetry kept
    assert agg.dropped == []
    sup.forget_rank(0, drop_telemetry=True)  # permanent eviction
    assert agg.dropped == [0]


# --------------------------------------------------------------------- #
# ProfilerCallback hardening
# --------------------------------------------------------------------- #
class _Strategy:
    global_rank = 3


class _Trainer:
    def __init__(self, root):
        self.default_root_dir = root
        self.strategy = _Strategy()
        self.global_step = 0


def test_profiler_callback_rank_suffix_and_exception_stop(tmp_path, monkeypatch):
    import jax

    from ray_lightning_tpu.callbacks.profiler import ProfilerCallback

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    cb = ProfilerCallback(start_step=0, num_steps=2)
    trainer = _Trainer(str(tmp_path))
    cb.setup(trainer, None, "fit")
    assert cb.log_dir.endswith("rank3")
    cb.setup(trainer, None, "fit")  # re-setup must not double-suffix
    assert not cb.log_dir.endswith(os.path.join("rank3", "rank3"))
    cb.on_train_batch_start(trainer, None, None, 0)
    assert calls == [("start", cb.log_dir)]
    # crash mid-window: the tracer stops exactly once, even with teardown
    cb.on_exception(trainer, None, RuntimeError("boom"))
    cb.teardown(trainer, None, "fit")
    cb.on_train_end(trainer, None)
    assert calls == [("start", cb.log_dir), ("stop",)]


def test_profiler_callback_stop_swallows_backend_errors(tmp_path, monkeypatch):
    import jax

    from ray_lightning_tpu.callbacks.profiler import ProfilerCallback

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def _boom():
        raise RuntimeError("no trace running")

    monkeypatch.setattr(jax.profiler, "stop_trace", _boom)
    cb = ProfilerCallback(start_step=0)
    trainer = _Trainer(str(tmp_path))
    cb.setup(trainer, None, "fit")
    cb.on_train_batch_start(trainer, None, None, 0)
    cb.on_exception(trainer, None, RuntimeError("boom"))  # must not raise
    assert cb._active is False


# --------------------------------------------------------------------- #
# docs gate: docs->code direction
# --------------------------------------------------------------------- #
def test_check_metrics_docs_rows_direction(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metrics_docs",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "check_metrics_docs.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    doc = tmp_path / "obs.md"
    doc.write_text(
        "| metric | type |\n|---|---|\n"
        "| `rlt_step_flops` | gauge |\n"
        "| `rlt_ghost_metric` | gauge |\n"
        "prose mention of `rlt_other_thing` only\n"
    )
    rows = mod.documented_rows(doc)
    assert rows == {"rlt_step_flops", "rlt_ghost_metric"}
    # repo state is clean in both directions
    assert mod.main() == 0
    # and the new profiler metrics are emission-visible to the checker
    emitted = mod.emitted_metrics()
    for name in (
        prof.STEP_FLOPS_METRIC,
        prof.STEP_BYTES_METRIC,
        prof.COLLECTIVE_BYTES_METRIC,
        prof.COST_MFU_METRIC,
    ):
        assert name in emitted


# --------------------------------------------------------------------- #
# serving cost summary
# --------------------------------------------------------------------- #
def test_engine_cost_summary_both_programs():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving.engine import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=16)
    )
    out = engine.cost_summary()
    assert set(out) == {"serve_prefill", "serve_decode"}
    for name, rep in out.items():
        assert rep is not None, name
        assert rep["step_flops"] > 0
        assert rep["step_bytes"] > 0
        assert rep["roofline"]["verdict"] == "not measured"  # no chip here


# --------------------------------------------------------------------- #
# e2e: in-process fit with an armed window
# --------------------------------------------------------------------- #
def test_inprocess_fit_profile_section(tmp_root, monkeypatch, fake_trace):
    import ray_lightning_tpu as rlt

    monkeypatch.setenv(prof.PROFILE_AT_STEP_ENV, "2")
    monkeypatch.setenv(prof.PROFILE_STEPS_ENV, "1")
    trainer = get_trainer(
        tmp_root,
        strategy=rlt.XLAStrategy(devices=2, telemetry=True),
        limit_train_batches=6,
    )
    trainer.fit(BoringModel())
    assert [c[0] for c in fake_trace] == ["start", "stop"]
    summary = json.load(
        open(os.path.join(telemetry_dir(tmp_root), SUMMARY_FILE))
    )
    profile = summary["profile"]
    assert profile["cost"]["train_step"]["step_flops"] > 0
    assert profile["cost"]["train_step"]["roofline"]["verdict"] == "not measured"
    cap = profile["captures"][0]
    assert cap["start_step"] == 2 and cap["num_steps"] == 1
    assert "0" in profile["attribution"]


@pytest.mark.slow
def test_two_worker_coordinated_capture(tmp_root, monkeypatch):
    """Acceptance e2e: both ranks of a 2-worker CPU fit open their
    jax.profiler window at the SAME armed global step and the driver
    aggregator collects both capture records."""
    import ray_lightning_tpu as rlt

    monkeypatch.setenv(prof.PROFILE_AT_STEP_ENV, "3")
    monkeypatch.setenv(prof.PROFILE_STEPS_ENV, "2")
    trainer = get_trainer(
        tmp_root,
        strategy=rlt.RayStrategy(
            num_workers=2,
            platform="cpu",
            devices_per_worker=2,
            telemetry=True,
            heartbeat_interval=0.1,
        ),
        limit_train_batches=8,
    )
    trainer.fit(BoringModel())
    summary = json.load(
        open(os.path.join(telemetry_dir(tmp_root), SUMMARY_FILE))
    )
    profile = summary["profile"]
    captures = profile["captures"]
    assert {c["rank"] for c in captures} == {0, 1}
    assert {c["actual_start"] for c in captures} == {3}
    for c in captures:
        assert os.path.isdir(c["trace_dir"]), c["trace_dir"]
    assert profile["cost"]["train_step"]["step_flops"] > 0
