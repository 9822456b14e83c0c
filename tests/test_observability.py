"""Distributed flight recorder: spans, metrics registry, driver aggregation.

Unit layer: ring bounds / no-op guarantees, snapshot-delta semantics, the
clock-skew estimator and Chrome trace merge, Prometheus exposition, and the
supervisor's telemetry tap. E2E layer: a worker fit with ``telemetry=True``
producing the full artifact set (trace.json with per-rank tracks, per-rank
step-time histograms, events.jsonl, summary.json) on the driver.
"""
from __future__ import annotations

import json
import os
import time

import pytest

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.observability import metrics as obs_metrics
from ray_lightning_tpu.observability.aggregator import (
    EVENTS_FILE,
    METRICS_FILE,
    PROM_FILE,
    STEP_TIME_METRIC,
    SUMMARY_FILE,
    TRACE_FILE,
    DriverAggregator,
    render_top,
    step_time_stats,
    telemetry_dir,
    write_local_dump,
)
from ray_lightning_tpu.runtime.supervisor import Supervisor
from tests.utils import BoringModel, get_trainer

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def obs_reset():
    obs.reset()
    yield
    obs.reset()


# --------------------------------------------------------------------- #
# trace recorder
# --------------------------------------------------------------------- #
def test_disabled_is_noop_singleton():
    """Off by default: span() hands back ONE shared object (no per-call
    allocation) and event() records nothing."""
    assert not obs.enabled()
    s1 = obs.span("anything", step=3, foo="bar")
    s2 = obs.span("else")
    assert s1 is s2 is obs.NOOP_SPAN
    with s1:
        pass
    obs.event("ignored", step=1)
    assert obs.get_recorder() is None
    assert obs.registry() is None
    assert obs.collect_beat_payload() is None


def test_span_nesting_and_ring_bounds():
    rec = obs.enable(capacity=32)
    with obs.span("outer", step=1):
        with obs.span("inner", step=1, detail="x"):
            pass
    events = rec.drain()
    # inner closes first; both are complete "X" spans with ordered walls
    assert [e[1] for e in events] == ["inner", "outer"]
    assert all(e[0] == "X" for e in events)
    inner, outer = events
    assert outer[2] <= inner[2]  # outer started first
    assert outer[3] >= inner[3]  # and lasted at least as long
    assert inner[5] == {"detail": "x"}

    for i in range(100):
        rec.add_event(f"e{i}")
    kept = rec.drain()
    assert len(kept) == 32  # ring drops oldest, never grows
    assert kept[0][1] == "e68" and kept[-1][1] == "e99"


def test_enable_is_idempotent_and_env_driven(monkeypatch):
    rec = obs.enable()
    assert obs.enable() is rec
    obs.reset()
    monkeypatch.delenv("RLT_TELEMETRY", raising=False)
    assert obs.maybe_enable_from_env() is None
    assert not obs.enabled()
    monkeypatch.setenv("RLT_TELEMETRY", "yes")
    assert obs.maybe_enable_from_env() is not None
    assert obs.enabled()


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_metrics_snapshot_delta_and_merge():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("saves_total").inc()
    reg.counter("saves_total").inc(2)
    reg.gauge("mfu", rank=0).set(0.41)
    h = reg.histogram("step_seconds")
    for v in (0.01, 0.02, 0.3):
        h.observe(v)

    delta = reg.snapshot(delta=True)
    assert ["saves_total", [], 3.0] in delta["counters"]
    assert ["mfu", [("rank", "0")], 0.41] in delta["gauges"]
    (name, labels, hist), = delta["histograms"]
    assert name == "step_seconds" and hist["count"] == 3
    assert hist["samples"] == [0.01, 0.02, 0.3]
    # the delta drained the raw samples; cumulative state remains
    assert reg.snapshot(delta=True)["histograms"][0][2]["samples"] == []
    assert reg.snapshot()["histograms"][0][2]["count"] == 3

    # driver side: merge with rank relabelling
    driver = obs_metrics.MetricsRegistry()
    driver.merge_snapshot(delta, extra_labels={"rank": 1})
    assert driver.get("saves_total", rank=1).value == 3.0
    merged_h = driver.get("step_seconds", rank=1)
    assert merged_h.count == 3 and merged_h.recent[-1] == 0.3
    # cumulative snapshots overwrite, not double-count
    driver.merge_snapshot(reg.snapshot(), extra_labels={"rank": 1})
    assert driver.get("step_seconds", rank=1).count == 3


def test_merge_snapshot_rank_label_collision():
    """A worker series already labelled rank=... must not crash the merge —
    the driver's label wins."""
    src = obs_metrics.MetricsRegistry()
    src.gauge("g", rank=9).set(1.0)
    dst = obs_metrics.MetricsRegistry()
    dst.merge_snapshot(src.snapshot(), extra_labels={"rank": 2})
    assert dst.get("g", rank=2).value == 1.0


def test_histogram_kind_conflict_raises():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_prometheus_text_golden():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("rlt_saves_total", format="orbax").inc(2)
    reg.gauge("rlt_mfu").set(0.5)
    h = reg.histogram("rlt_lat", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert reg.prometheus_text() == (
        "# HELP rlt_lat rlt lat\n"
        "# TYPE rlt_lat histogram\n"
        'rlt_lat_bucket{le="0.1"} 1\n'
        'rlt_lat_bucket{le="1"} 2\n'
        'rlt_lat_bucket{le="+Inf"} 3\n'
        "rlt_lat_sum 5.55\n"
        "rlt_lat_count 3\n"
        "# HELP rlt_mfu rlt mfu\n"
        "# TYPE rlt_mfu gauge\n"
        "rlt_mfu 0.5\n"
        "# HELP rlt_saves_total rlt saves total\n"
        "# TYPE rlt_saves_total counter\n"
        'rlt_saves_total{format="orbax"} 2\n'
    )


def test_prometheus_text_escapes_label_values():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("rlt_odd_total", path='a\\b"c\nd').inc()
    text = reg.prometheus_text()
    assert 'path="a\\\\b\\"c\\nd"' in text
    # the emitted line itself holds no raw newline inside the label value
    assert 'rlt_odd_total{path="a\\\\b\\"c\\nd"} 1' in text.splitlines()


def test_prometheus_help_registry():
    obs_metrics.set_help("rlt_custom_total", "my help text")
    try:
        reg = obs_metrics.MetricsRegistry()
        reg.counter("rlt_custom_total").inc()
        assert "# HELP rlt_custom_total my help text" in reg.prometheus_text()
    finally:
        obs_metrics.HELP.pop("rlt_custom_total", None)


def test_collect_beat_payload_roundtrip():
    obs.enable()
    reg = obs.registry()
    reg.histogram(STEP_TIME_METRIC).observe(0.1)
    with obs.span("step", step=1):
        pass
    payload = obs.collect_beat_payload()
    assert payload is not None
    assert [e[1] for e in payload["t"]] == ["step"]
    # nothing new -> cumulative-only beat still carries the histogram shell
    again = obs.collect_beat_payload()
    assert again is None or again["t"] == []
    final = obs.collect_beat_payload(final=True)
    assert final["m"]["histograms"][0][2]["count"] == 1


# --------------------------------------------------------------------- #
# skew + trace merge
# --------------------------------------------------------------------- #
def test_estimate_skew_recovers_offset():
    """A rank whose clock runs 5s behind the driver: every beat's
    send_wall lags recv_wall by 5s plus latency; the max over beats
    recovers -5s to within the latency floor."""
    skewed = [(1000.0 - 5.0 + i - lat, 1000.0 + i) for i, lat in
              enumerate((0.04, 0.002, 0.08))]
    est = obs.estimate_skew(skewed)
    assert est == pytest.approx(-5.0, abs=0.01)
    assert obs.estimate_skew([]) == 0.0


def test_merge_traces_aligns_skewed_ranks():
    t0 = 1000.0
    events_by_rank = {
        obs.DRIVER: [("X", "boot/setup_workers", t0, 1.0, None, None)],
        0: [("X", "step", t0 + 1.0, 0.5, 7, None)],
        # rank 1's clock is 5s behind: same true instant, wall reads t0-4
        1: [("X", "step", t0 - 4.0, 0.5, 7, None)],
    }
    merged = obs.merge_traces(events_by_rank, {0: 0.0, 1: -5.0})
    assert merged["displayTimeUnit"] == "ms"
    meta = [e for e in merged["traceEvents"] if e["ph"] == "M"
            and e["name"] == "process_name"]
    assert {m["args"]["name"] for m in meta} == {"driver", "rank 0", "rank 1"}
    assert {m["pid"] for m in meta} == {0, 1, 2}
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"
             and e["name"] == "step"]
    ts = {e["pid"]: e["ts"] for e in spans}
    # skew-corrected: both rank steps land on the same driver-clock instant
    assert ts[1] == pytest.approx(ts[2], abs=1.0)
    assert ts[1] == pytest.approx((t0 + 1.0) * 1e6, abs=1.0)
    assert spans[0]["args"] == {"step": 7}


def test_step_time_stats_single_and_multi_rank():
    assert step_time_stats({}) == {}
    single = step_time_stats({0: [0.1, 0.2, 0.3]})
    assert single["step_time_p50"] == pytest.approx(0.2)
    assert single["step_time_max_skew"] == pytest.approx(0.2)  # max - min
    multi = step_time_stats({0: [0.1, 0.1, 0.1], 1: [0.3, 0.3, 0.3]})
    # cross-rank skew = spread of per-rank medians: the straggler signal
    assert multi["step_time_max_skew"] == pytest.approx(0.2)
    assert multi["step_time_p90"] == pytest.approx(0.3)


# --------------------------------------------------------------------- #
# driver aggregator
# --------------------------------------------------------------------- #
def _beat_payload(step_samples, extra_gauges=()):
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram(STEP_TIME_METRIC)
    for v in step_samples:
        h.observe(v)
    for name, value in extra_gauges:
        reg.gauge(name).set(value)
    return {
        "m": reg.snapshot(delta=True),
        "t": [("X", "step", time.time(), 0.01, 1, None)],
    }


def test_driver_aggregator_end_to_end(tmp_path):
    run_dir = str(tmp_path / "telemetry")
    agg = DriverAggregator(run_dir, num_workers=2)
    now = time.time()
    for rank, lag in ((0, 0.001), (1, 2.0)):
        agg.on_beat(
            rank, 5, now - lag,
            payload=_beat_payload(
                [0.1 + rank * 0.1] * 4,
                extra_gauges=[("rlt_samples_per_sec", 100.0 * (rank + 1))],
            ),
            recv_wall=now,
        )
    agg.record_event("straggler", rank=1, silent_s=2.0)
    agg.record_event("run_finished", fn="fit")
    out = agg.finalize(
        driver_events=[("X", "boot/setup_workers", now - 5, 1.0, None, None)]
    )
    assert out == run_dir

    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"driver", "rank 0", "rank 1"}

    metrics_doc = json.load(open(os.path.join(run_dir, METRICS_FILE)))
    per_rank = metrics_doc["summary"]["per_rank"]
    assert per_rank["0"]["step_time_p50"] == pytest.approx(0.1)
    assert per_rank["1"]["step_time_p50"] == pytest.approx(0.2)
    assert per_rank["1"]["samples_per_sec"] == pytest.approx(200.0)
    cluster = metrics_doc["summary"]["cluster"]
    assert cluster["step_time_max_skew"] == pytest.approx(0.1)
    assert cluster["samples_per_sec"] == pytest.approx(300.0)
    hists = metrics_doc["per_rank_histograms"][STEP_TIME_METRIC]
    assert {'{rank="0"}', '{rank="1"}'} <= set(hists)

    prom = open(os.path.join(run_dir, PROM_FILE)).read()
    assert 'rlt_heartbeat_latency_seconds{rank="1"} 2' in prom
    assert f"# TYPE {STEP_TIME_METRIC} histogram" in prom

    events = [json.loads(l) for l in open(os.path.join(run_dir, EVENTS_FILE))]
    assert [e["event"] for e in events] == ["straggler", "run_finished"]
    assert events[0]["rank"] == 1


def test_aggregator_flight_record_survives_disabled_telemetry(tmp_path):
    """full=False (RLT_TELEMETRY off): no trace/metrics artifacts, but
    verdicts still land in events.jsonl — the always-on flight record."""
    run_dir = str(tmp_path / "t")
    agg = DriverAggregator(run_dir, num_workers=1, full=False)
    agg.on_beat(0, 3, time.time())
    agg.record_event("hang", ranks=[0])
    assert agg.finalize() is None
    assert not os.path.exists(os.path.join(run_dir, TRACE_FILE))
    events = [json.loads(l) for l in open(os.path.join(run_dir, EVENTS_FILE))]
    assert events[0]["event"] == "hang"
    # post-finalize events (fatal crash after the run) reopen the record
    agg.record_event("crash", fatal=True)
    events = [json.loads(l) for l in open(os.path.join(run_dir, EVENTS_FILE))]
    assert [e["event"] for e in events] == ["hang", "crash"]


def test_telemetry_dir_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv("RLT_TELEMETRY_DIR", raising=False)
    assert telemetry_dir("/runs/x") == os.path.join("/runs/x", "telemetry")
    monkeypatch.setenv("RLT_TELEMETRY_DIR", str(tmp_path / "override"))
    assert telemetry_dir("/runs/x") == str(tmp_path / "override")


def test_render_top_reads_summary(tmp_path):
    run_dir = str(tmp_path / "t")
    agg = DriverAggregator(run_dir, num_workers=1)
    agg.on_beat(0, 9, time.time(), payload=_beat_payload([0.05] * 3))
    agg.record_event("run_started", fn="fit")
    agg.finalize()
    lines = []
    assert render_top(run_dir, _print=lambda *a, **k: lines.append(a[0])) == 0
    text = "\n".join(lines)
    assert "1 worker(s)" in text and "run_started" in text
    assert render_top(str(tmp_path / "missing"),
                      _print=lambda *a, **k: None) == 1


def test_cli_top_subcommand(tmp_path):
    from ray_lightning_tpu import cli

    run_dir = str(tmp_path / "t")
    agg = DriverAggregator(run_dir, num_workers=1)
    agg.on_beat(0, 1, time.time())
    agg.finalize()
    assert cli.main(["top", "--dir", run_dir]) == 0


# --------------------------------------------------------------------- #
# supervisor tap
# --------------------------------------------------------------------- #
def test_supervisor_monitor_only_forwards_beats(tmp_path):
    """hang_timeout=None: the supervisor never classifies, but beats (and
    their telemetry payloads) still reach the aggregator — how a
    telemetry-only run reuses the heartbeat channel."""
    agg = DriverAggregator(str(tmp_path / "t"), num_workers=1)
    sup = Supervisor(
        num_workers=1, drain=list, hang_timeout=None, aggregator=agg
    )
    assert sup.hang_timeout is None
    wall = time.time()
    sup.ingest((0, 4, wall, _beat_payload([0.2, 0.2])))
    sup.ingest((0, 5, wall))  # plain 3-tuple beats still work
    sup.ingest("garbage")  # malformed: dropped, not raised
    assert sup.check() == {0: "ok"}  # never classifies
    assert agg.registry.get("rlt_worker_step", rank=0).value == 5.0
    assert agg.registry.get("rlt_heartbeat_age_seconds", rank=0) is not None
    assert agg.step_samples_by_rank() == {0: [0.2, 0.2]}


def test_supervisor_straggler_verdict_hits_flight_record(tmp_path):
    run_dir = str(tmp_path / "t")
    agg = DriverAggregator(run_dir, num_workers=1, full=False)
    sup = Supervisor(
        num_workers=1, drain=list, hang_timeout=10.0, aggregator=agg
    )
    sup.observe(0, step=3, wall_time=time.time())
    sup.check(now=sup.health[0].last_beat + 6.0)
    events = [json.loads(l) for l in open(os.path.join(run_dir, EVENTS_FILE))]
    assert events[0]["event"] == "straggler"
    assert events[0]["rank"] == 0 and events[0]["last_step"] == 3


# --------------------------------------------------------------------- #
# satellites: throughput + peak-tflops override
# --------------------------------------------------------------------- #
def test_detect_peak_tflops_env_override(monkeypatch):
    from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops

    monkeypatch.setenv("RLT_PEAK_TFLOPS", "123.5")
    assert detect_peak_tflops() == 123.5
    # a bad override is ignored, and the CPU has no peak: "not measured"
    monkeypatch.setenv("RLT_PEAK_TFLOPS", "not-a-number")
    assert detect_peak_tflops() is None
    monkeypatch.setenv("RLT_PEAK_TFLOPS", "-3")
    assert detect_peak_tflops() is None


def test_throughput_monitor_publishes_gauges(monkeypatch):
    from ray_lightning_tpu.callbacks.throughput import ThroughputMonitor

    obs.enable()
    assert ThroughputMonitor(flops_per_sample=1e9).summary(None) == {}
    mon = ThroughputMonitor(flops_per_sample=1e9)
    mon._times = [0.1]
    mon._batch_size = 8

    class _T:
        world_size = 1

    # on the CPU a utilization is not measured: no MFU key, no gauge
    assert "train_mfu" not in mon.summary(_T())
    monkeypatch.setenv("RLT_PEAK_TFLOPS", "197")
    mon = ThroughputMonitor(flops_per_sample=1e9)
    mon._times = [0.1]
    mon._batch_size = 8

    class _T:
        world_size = 1

    mon._publish_telemetry(_T())
    reg = obs.registry()
    assert reg.get("rlt_samples_per_sec").value == pytest.approx(80.0)
    assert reg.get("rlt_train_mfu").value > 0


def test_write_local_dump(tmp_path):
    obs.enable()
    with obs.span("compile", step=0):
        pass
    reg = obs.registry()
    reg.histogram(STEP_TIME_METRIC).observe(0.01)
    run_dir = write_local_dump(
        str(tmp_path / "t"), obs.get_recorder(), reg
    )
    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    assert any(e.get("name") == "compile" for e in trace["traceEvents"])
    assert os.path.exists(os.path.join(run_dir, METRICS_FILE))


# --------------------------------------------------------------------- #
# e2e: worker fit with telemetry
# --------------------------------------------------------------------- #
def _assert_run_artifacts(run_dir, expect_ranks):
    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("name") == "process_name"}
    for r in expect_ranks:
        assert f"rank {r}" in tracks, tracks
    assert "driver" in tracks
    span_names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "boot/setup_workers" in span_names  # driver boot phase
    assert "boot/payload_load" in span_names  # worker boot phase
    assert "compile" in span_names and "step" in span_names

    metrics_doc = json.load(open(os.path.join(run_dir, METRICS_FILE)))
    per_rank = metrics_doc["summary"]["per_rank"]
    for r in expect_ranks:
        assert per_rank[str(r)]["n_step_samples"] > 0, per_rank
        assert per_rank[str(r)]["step_time_p50"] > 0
    hists = metrics_doc["per_rank_histograms"][STEP_TIME_METRIC]
    for r in expect_ranks:
        assert hists['{rank="%d"}' % r]["count"] > 0
    assert os.path.exists(os.path.join(run_dir, PROM_FILE))
    assert os.path.exists(os.path.join(run_dir, SUMMARY_FILE))
    events = [json.loads(l) for l in open(os.path.join(run_dir, EVENTS_FILE))]
    kinds = [e["event"] for e in events]
    assert "run_started" in kinds and "run_finished" in kinds


def test_ray_fit_telemetry_one_worker(tmp_root):
    """Fast tier-1 e2e: one worker, full artifact chain — worker spans
    cross the heartbeat channel, the driver merges them with its own boot
    spans and per-rank step histograms."""
    import ray_lightning_tpu as rlt

    strategy = rlt.RayStrategy(
        num_workers=1,
        platform="cpu",
        devices_per_worker=2,
        telemetry=True,
        heartbeat_interval=0.1,
    )
    trainer = get_trainer(tmp_root, strategy=strategy, limit_train_batches=6)
    trainer.fit(BoringModel())
    assert trainer.state.status == "finished"
    _assert_run_artifacts(os.path.join(tmp_root, "telemetry"), [0])


@pytest.mark.slow
def test_ray_fit_telemetry_two_workers(tmp_root):
    """The acceptance scenario: 2 ranks, merged trace has two distinct
    worker tracks and the driver saw per-rank step metrics."""
    import ray_lightning_tpu as rlt

    strategy = rlt.RayStrategy(
        num_workers=2,
        platform="cpu",
        devices_per_worker=2,
        telemetry=True,
        heartbeat_interval=0.1,
    )
    trainer = get_trainer(tmp_root, strategy=strategy, limit_train_batches=6)
    trainer.fit(BoringModel())
    assert trainer.state.status == "finished"
    _assert_run_artifacts(os.path.join(tmp_root, "telemetry"), [0, 1])


def test_local_fit_telemetry_dump(tmp_root):
    """In-process strategy (no launcher): the trainer dumps its own
    single-track artifact set at the end of fit."""
    import ray_lightning_tpu as rlt

    trainer = get_trainer(
        tmp_root,
        strategy=rlt.XLAStrategy(devices=2, telemetry=True),
        limit_train_batches=6,
    )
    trainer.fit(BoringModel())
    run_dir = os.path.join(tmp_root, "telemetry")
    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    span_names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert "fit/setup" in span_names
    assert "compile" in span_names and "step" in span_names
    metrics_doc = json.load(open(os.path.join(run_dir, METRICS_FILE)))
    hists = metrics_doc["per_rank_histograms"][STEP_TIME_METRIC]
    assert hists['{rank="0"}']["count"] > 0


# --------------------------------------------------------------------- #
# request-scoped tracing: sampling, jsonl plumbing, per-request tracks
# --------------------------------------------------------------------- #
def test_head_sampling_deterministic_and_env_rate(monkeypatch):
    from ray_lightning_tpu.observability import reqtrace

    assert reqtrace.head_sampled("anything", 1.0)
    assert not reqtrace.head_sampled("anything", 0.0)
    # same id -> same verdict every time (a request is all-or-nothing)
    verdicts = {reqtrace.head_sampled("req-7", 0.5) for _ in range(10)}
    assert len(verdicts) == 1
    # ~half of a large id population at rate 0.5
    kept = sum(reqtrace.head_sampled(f"req-{i}", 0.5) for i in range(1000))
    assert 350 < kept < 650
    monkeypatch.setenv(reqtrace.SAMPLE_ENV, "2.5")
    assert reqtrace.sample_rate() == 1.0  # clamped
    monkeypatch.setenv(reqtrace.SAMPLE_ENV, "junk")
    assert reqtrace.sample_rate() == 1.0
    monkeypatch.setenv(reqtrace.SAMPLE_ENV, "0.25")
    assert reqtrace.sample_rate() == 0.25


def test_jsonl_writer_rotation_and_read_requests(tmp_path):
    from ray_lightning_tpu.observability import reqtrace

    path = str(tmp_path / "requests.jsonl")
    w = reqtrace.JsonlWriter(path, max_bytes=200)
    for i in range(20):
        w.write({"request_id": f"r{i}", "pad": "x" * 40})
    w.close()
    assert w.rotations >= 1
    assert os.path.exists(path + ".1")
    records = reqtrace.read_requests(path)
    # rotation keeps at most two generations but never loses the newest
    assert records[-1]["request_id"] == "r19"
    assert reqtrace.read_requests(path, limit=3) == records[-3:]
    assert reqtrace.read_requests(str(tmp_path / "missing.jsonl")) == []


def test_histogram_pending_cap_and_exemplars():
    h = obs_metrics.Histogram(bounds=(0.1, 1.0), pending_cap=5)
    for i in range(50):
        h.observe(0.05)
    assert len(h.pending) == 5  # capped; cumulative state still full
    assert h.count == 50
    h.observe(0.5, exemplar="mid")
    for i in range(5):
        h.observe(2.0, exemplar=f"slow-{i}")
    # per-bucket exemplars keep the last few ids only
    assert h.bucket_exemplars(lower_than=1.0) == ["slow-4", "slow-3", "slow-2"]
    assert "mid" in h.bucket_exemplars()
    # exemplars survive the snapshot -> merge round trip with rank labels
    reg = obs_metrics.MetricsRegistry()
    reg._metrics[("rlt_lat", ())] = h
    driver = obs_metrics.MetricsRegistry()
    driver.merge_snapshot(
        json.loads(json.dumps(reg.snapshot())), extra_labels={"rank": 0}
    )
    merged = driver.get("rlt_lat", rank=0)
    assert merged.bucket_exemplars(lower_than=1.0) == [
        "slow-4", "slow-3", "slow-2"
    ]


def test_request_trace_record_fields():
    from ray_lightning_tpu.observability import reqtrace

    tr = reqtrace.RequestTrace("r1", prompt_len=3, max_new_tokens=4)
    tr.deferred()
    tr.deferred()
    tr.admitted(slot=2)
    tr.prefilled(0.01)
    for _ in range(3):
        tr.token()
    rec = tr.record("length")
    assert rec["request_id"] == "r1"
    assert rec["prompt_len"] == 3 and rec["tokens_out"] == 3
    assert rec["finish_reason"] == "length"
    assert rec["deferred_ticks"] == 2 and rec["slot"] == 2
    assert rec["queue_wait_s"] >= 0 and rec["ttft_s"] >= 0
    assert rec["total_s"] >= rec["ttft_s"]
    assert "itl_p50_ms" in rec and "itl_max_ms" in rec


def test_request_tracer_sampling_and_drain(tmp_path):
    from ray_lightning_tpu.observability import reqtrace

    t = reqtrace.RequestTracer(out_dir=str(tmp_path), rate=0.0)
    assert t.start("r1") is None  # unsampled -> one attribute check per tick
    t = reqtrace.RequestTracer(out_dir=str(tmp_path), rate=1.0)
    tr = t.start("r2", prompt_len=2, max_new_tokens=2)
    tr.admitted(slot=0)
    tr.token()
    t.finish(tr, "eos")
    t.close()
    drained = t.drain()
    assert [r["request_id"] for r in drained] == ["r2"]
    assert t.drain() == []  # drain pops
    on_disk = reqtrace.read_requests(t.path)
    assert [r["request_id"] for r in on_disk] == ["r2"]


def test_request_tracks_roundtrip_trace_json(tmp_path):
    """Per-request spans tagged with the track arg render as their own
    named Perfetto thread rows after a full write-to-disk round trip."""
    from ray_lightning_tpu.observability import reqtrace

    obs.enable()
    tracer = reqtrace.RequestTracer()
    tr = tracer.start("r9", prompt_len=4, max_new_tokens=3)
    tr.deferred()
    tr.admitted(slot=1)
    tr.prefilled(0.002)
    for _ in range(3):
        tr.token()
    tracer.finish(tr, "length")
    run_dir = write_local_dump(
        str(tmp_path / "t"), obs.get_recorder(), obs.registry()
    )
    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    threads = {
        e["args"]["name"]: e["tid"]
        for e in trace["traceEvents"]
        if e.get("name") == "thread_name"
    }
    assert "req r9" in threads and threads["req r9"] > 0
    req_spans = {
        e["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "X" and e.get("tid") == threads["req r9"]
    }
    assert {
        "req/queue_wait", "req/deferred_block_wait", "req/prefill",
        "req/decode",
    } <= req_spans
    decode = next(
        e for e in trace["traceEvents"]
        if e["ph"] == "X" and e["name"] == "req/decode"
    )
    assert decode["args"]["tokens"] == 3
    assert decode["args"]["reason"] == "length"
    assert "ttft_ms" in decode["args"]


def test_aggregator_negative_skew_alignment_with_tracks(tmp_path):
    """A rank whose clock runs AHEAD of the driver (negative correction)
    still lands its spans — including per-request tracks — on the driver
    timeline next to a well-synced rank's."""
    run_dir = str(tmp_path / "telemetry")
    agg = DriverAggregator(run_dir, num_workers=2)
    now = time.time()
    track_args = {"track": "req rA"}
    for i in range(3):
        # rank 0's wall clock reads 5s in the future at the same instant
        agg.on_beat(
            0, i, now + 5.0 + i * 0.01, recv_wall=now + i * 0.01,
            payload={
                "t": [("X", "req/decode", now + 5.0, 0.5, None, track_args)],
                "m": None,
            },
        )
        agg.on_beat(
            1, i, now + i * 0.01, recv_wall=now + i * 0.01,
            payload={
                "t": [("X", "req/decode", now, 0.5, None, dict(track_args))],
                "m": None,
            },
        )
    skews = agg.skew_by_rank()
    assert skews[0] == pytest.approx(5.0, abs=0.02)
    assert skews[1] == pytest.approx(0.0, abs=0.02)
    agg.finalize()
    trace = json.load(open(os.path.join(run_dir, TRACE_FILE)))
    spans = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e["name"] == "req/decode"]
    ts_by_pid = {}
    for e in spans:
        ts_by_pid.setdefault(e["pid"], e["ts"])
    a, b = list(ts_by_pid.values())[:2]
    # skew-corrected: both ranks' spans land on the same driver instant
    assert a == pytest.approx(b, abs=0.05 * 1e6)
    # each rank got its own named request track
    names = [e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "thread_name"]
    assert names.count("req rA") == 2


def test_device_memory_gauges(monkeypatch):
    fake = [
        {"device": "tpu:0", "bytes_in_use": 100, "peak_bytes": 200,
         "bytes_limit": 1000},
        {"device": "tpu:1", "bytes_in_use": 50, "peak_bytes": 300,
         "bytes_limit": 1000},
    ]
    monkeypatch.setattr(obs_metrics, "device_memory_stats", lambda: fake)
    obs.enable()
    reg = obs.registry()
    obs.sample_device_memory(force=True)
    assert reg.get(
        obs_metrics.HBM_IN_USE_METRIC, device="tpu:0"
    ).value == 100
    assert reg.get(obs_metrics.HBM_PEAK_METRIC, device="tpu:1").value == 300
    # throttle: within the interval the cache answers, no device poll
    calls = []
    monkeypatch.setattr(
        obs_metrics, "device_memory_stats",
        lambda: calls.append(1) or fake,
    )
    obs.sample_device_memory()
    assert calls == []
    assert obs_metrics.last_device_memory() == fake
    assert calls == []  # admission-path read never touches the device


def test_aggregator_request_records_and_hbm_fold(tmp_path):
    from ray_lightning_tpu.observability.aggregator import REQUESTS_FILE
    from ray_lightning_tpu.observability import reqtrace

    run_dir = str(tmp_path / "telemetry")
    agg = DriverAggregator(run_dir, num_workers=1)
    reg = obs_metrics.MetricsRegistry()
    reg.gauge(obs_metrics.HBM_IN_USE_METRIC, device="tpu:0").set(100)
    reg.gauge(obs_metrics.HBM_IN_USE_METRIC, device="tpu:1").set(900)
    agg.on_beat(
        0, 1, time.time(),
        payload={
            "m": reg.snapshot(),
            "r": [{"request_id": "r1", "ttft_s": 0.5,
                   "finish_reason": "eos"}],
        },
    )
    summary = agg.summary()
    assert summary["per_rank"]["0"]["hbm_bytes_in_use"] == 900  # worst device
    assert summary["cluster"]["requests_total"] == 1
    agg.finalize()
    records = reqtrace.read_requests(os.path.join(run_dir, REQUESTS_FILE))
    assert records[0]["request_id"] == "r1" and records[0]["rank"] == 0


def test_check_metrics_docs_script():
    """The docs-drift gate: every emitted rlt_* metric is documented."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "check_metrics_docs.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_requests_subcommand(tmp_path, capsys):
    from ray_lightning_tpu.cli import main
    from ray_lightning_tpu.observability import reqtrace

    w = reqtrace.JsonlWriter(str(tmp_path / reqtrace.REQUESTS_FILE))
    w.write({"request_id": "fast", "ttft_s": 0.1, "total_s": 0.2,
             "prompt_len": 2, "tokens_out": 4, "finish_reason": "eos"})
    w.write({"request_id": "slow", "ttft_s": 1.5, "total_s": 2.0,
             "prompt_len": 8, "tokens_out": 16, "finish_reason": "length"})
    w.close()
    assert main(["requests", "--dir", str(tmp_path), "--limit", "1"]) == 0
    out = capsys.readouterr().out
    assert "slow" in out and "fast" not in out  # sorted by ttft desc
    assert main(["requests", "--dir", str(tmp_path), "--json"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [r["request_id"] for r in lines] == ["slow", "fast"]
    assert main(["requests", "--dir", str(tmp_path / "empty")]) == 1
    capsys.readouterr()
