"""Goodput ledger, black-box incident recorder, anomaly detection.

Unit layer: the wall-time ledger's sum-to-wall invariant (every second
lands in exactly one category), the fleet fold, rotation-stitched event
windows, incident capture/dedup/prune, detector firing on injected
regressions (and staying silent on clean streams), and the scrape
endpoints. E2E layer: a 2-worker CPU chaos run whose crash produces a
goodput section and an incident bundle covering the fault (slow;
scripts/chaos.sh runs it).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
import urllib.request

import pytest

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.observability import (
    aggregator as agg_mod,
    anomaly as anomaly_mod,
    goodput as goodput_mod,
    incidents as incidents_mod,
    metrics as metrics_mod,
    reqtrace as reqtrace_mod,
)
from ray_lightning_tpu.observability.aggregator import DriverAggregator

pytestmark = pytest.mark.goodput


@pytest.fixture(autouse=True)
def obs_reset():
    obs.reset()
    yield
    obs.reset()


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# --------------------------------------------------------------------- #
# goodput ledger
# --------------------------------------------------------------------- #
def test_ledger_categories_sum_to_wall_time():
    clk = FakeClock()
    led = goodput_mod.GoodputLedger(src="train", clock=clk, category="idle")
    clk.advance(2.0)
    led.enter("compile")
    clk.advance(3.0)
    led.enter("productive_compute")
    clk.advance(5.0)
    snap = led.snapshot()
    assert snap == {"idle": 2.0, "compile": 3.0, "productive_compute": 5.0}
    assert sum(snap.values()) == pytest.approx(led.wall_s())
    assert led.fraction() == pytest.approx(0.5)


def test_ledger_sum_to_wall_under_real_clock():
    """The acceptance invariant with the real monotonic clock: category
    totals track wall time within 2% (by construction — transitions are
    edges on one clock, there is no sampling gap to drift through)."""
    led = goodput_mod.GoodputLedger(src="train")
    t0 = time.monotonic()
    for cat in ("compile", "productive_compute", "input_wait", "idle"):
        led.enter(cat)
        time.sleep(0.01)
    wall = time.monotonic() - t0
    total = sum(led.snapshot().values())
    assert abs(total - led.wall_s()) <= 0.02 * max(led.wall_s(), 1e-9)
    assert total == pytest.approx(wall, rel=0.25)


def test_ledger_phase_restores_previous_category():
    clk = FakeClock()
    led = goodput_mod.GoodputLedger(clock=clk, category="productive_compute")
    clk.advance(1.0)
    with led.phase("checkpoint"):
        clk.advance(4.0)
        assert led.current == "checkpoint"
    assert led.current == "productive_compute"
    clk.advance(1.0)
    snap = led.snapshot()
    assert snap["checkpoint"] == pytest.approx(4.0)
    assert snap["productive_compute"] == pytest.approx(2.0)


def test_new_ledger_adopts_predecessor_totals():
    clk = FakeClock()
    first = goodput_mod.GoodputLedger(src="serve0", clock=clk)
    clk.advance(3.0)
    first.enter("productive_compute")
    goodput_mod._LEDGERS["serve0"] = first  # register under src
    second = goodput_mod.new_ledger("serve0")
    snap = second.snapshot()
    # predecessor's 3 idle seconds carried: published counters never regress
    assert snap["idle"] >= 3.0
    assert second.wall_s() >= 3.0
    assert goodput_mod.get_ledger("serve0") is second
    assert goodput_mod.ensure_ledger("serve0") is second  # no restart


def test_publish_and_fold():
    clk = FakeClock()
    led = goodput_mod.GoodputLedger(src="train", clock=clk, category="compile")
    clk.advance(2.0)
    led.enter("productive_compute")
    clk.advance(8.0)
    reg = metrics_mod.MetricsRegistry()
    led.publish(reg)
    values = {
        labels[0][1]: m.value
        for (name, labels), m in reg.items()
        if name == goodput_mod.GOODPUT_SECONDS_METRIC
    }
    assert values["compile"] == pytest.approx(2.0)
    assert values["productive_compute"] == pytest.approx(8.0)

    folded = goodput_mod.fold({
        "0": {"productive_compute": 8.0, "compile": 2.0},
        "1": {"productive_compute": 4.0, "fault_recovery": 6.0},
    })
    assert folded["total_s"] == pytest.approx(20.0)
    assert folded["fraction"] == pytest.approx(12.0 / 20.0)
    assert folded["per_rank"]["1"]["fraction"] == pytest.approx(0.4)
    assert folded["per_rank"]["1"]["wall_s"] == pytest.approx(10.0)


# --------------------------------------------------------------------- #
# rotation-stitched event windows
# --------------------------------------------------------------------- #
def test_read_window_stitches_across_rotation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = reqtrace_mod.JsonlWriter(path, max_bytes=400)
    for i in range(30):
        w.write({"seq": i, "pad": "x" * 40})
    w.close()
    assert w.rotations >= 1
    assert os.path.exists(path + ".1")

    lines = reqtrace_mod.read_window(path, max_bytes=1 << 20)
    seqs = [json.loads(ln)["seq"] for ln in lines]
    # oldest-first, contiguous, and spanning BOTH generations
    assert seqs == sorted(seqs)
    assert seqs[-1] == 29
    live_first = json.loads(open(path).readline())["seq"]
    assert seqs[0] < live_first, "window must reach into the rotated file"

    # a small budget trims from the OLD side, never the new
    small = reqtrace_mod.read_window(path, max_bytes=120)
    small_seqs = [json.loads(ln)["seq"] for ln in small]
    assert small_seqs and small_seqs[-1] == 29
    assert len(small_seqs) < len(seqs)

    # writer method delegates
    w2 = reqtrace_mod.JsonlWriter(path, max_bytes=400)
    assert [json.loads(ln)["seq"] for ln in w2.read_window(1 << 20)] == seqs


# --------------------------------------------------------------------- #
# incident recorder
# --------------------------------------------------------------------- #
def _recorder(tmp_path, clk, **kw):
    run_dir = str(tmp_path)
    reg = metrics_mod.MetricsRegistry()
    events_path = os.path.join(run_dir, "events.jsonl")
    w = reqtrace_mod.JsonlWriter(events_path)
    w.write({"ts": clk(), "event": "run_started"})
    w.close()
    rec = incidents_mod.IncidentRecorder(
        run_dir, registry=reg, events_path=events_path, clock=clk,
        trace_provider=lambda: {"traceEvents": []}, **kw
    )
    return rec, reg


def test_incident_capture_bundle_contents(tmp_path):
    clk = FakeClock(1000.0)
    rec, reg = _recorder(tmp_path, clk)
    reg.counter("rlt_serve_requests_total").inc(7)
    reg.push_history(now=clk())
    rec.register_source("arbiter_ledger", lambda: {"state": "steady"})

    path = rec.maybe_capture(
        "crash", event={"ts": clk(), "event": "crash", "rank": 0},
        attachments={"probe_log.txt": "tail line\n"},
    )
    assert path is not None and os.path.isdir(path)
    files = sorted(os.listdir(path))
    assert files == [
        "arbiter_ledger.json", "events.jsonl", "incident.json",
        "metrics_history.json", "probe_log.txt", "trace_slice.json",
    ]
    meta = json.load(open(os.path.join(path, "incident.json")))
    assert meta["kind"] == "crash" and meta["event"]["rank"] == 0
    window = open(os.path.join(path, "events.jsonl")).read()
    assert "run_started" in window
    history = json.load(open(os.path.join(path, "metrics_history.json")))
    assert any(
        c[0] == "rlt_serve_requests_total" for e in history for c in e["counters"]
    )
    assert json.load(open(os.path.join(path, "arbiter_ledger.json"))) == {
        "state": "steady"
    }

    # listing + loading (what `cli incidents` renders)
    bundles = incidents_mod.list_bundles(str(tmp_path))
    assert len(bundles) == 1 and bundles[0]["kind"] == "crash"
    detail = incidents_mod.load_bundle(bundles[0]["path"])
    assert detail["incident"]["kind"] == "crash"
    assert detail["files"]["events.jsonl"]["lines"] >= 1


def test_incident_cooldown_dedup_and_prune(tmp_path):
    clk = FakeClock(1000.0)
    rec, reg = _recorder(tmp_path, clk, cooldown=60.0, bundle_cap=3)
    assert rec.maybe_capture("crash", event={}) is not None
    assert rec.maybe_capture("crash", event={}) is None  # inside cooldown
    # a DIFFERENT kind is not suppressed by crash's cooldown
    assert rec.maybe_capture("slo_breach", event={}) is not None
    counts = {
        (name, labels): m.value
        for (name, labels), m in reg.items()
        if name.startswith("rlt_incidents_")
    }
    assert sum(
        v for (n, l), v in counts.items()
        if n == incidents_mod.INCIDENTS_CAPTURED_METRIC
    ) == 2
    assert sum(
        v for (n, l), v in counts.items()
        if n == incidents_mod.INCIDENTS_SUPPRESSED_METRIC
    ) == 1

    for i in range(4):
        clk.advance(100.0)
        rec.maybe_capture("crash", event={"seq": i})
    bundles = incidents_mod.list_bundles(str(tmp_path))
    assert len(bundles) == 3  # pruned oldest-first to the cap
    assert bundles[-1]["kind"] == "crash"


# --------------------------------------------------------------------- #
# anomaly detection
# --------------------------------------------------------------------- #
def test_step_time_detector_fires_on_slow_fault_not_on_clean():
    mon = anomaly_mod.AnomalyMonitor(clock=FakeClock())
    for _ in range(40):
        mon.observe_step(0, 0.10)
    assert mon.evaluate() == []  # clean stream: silent

    for _ in range(3):  # injected `slow` fault: sustained 5x regression
        mon.observe_step(0, 0.50)
    events = mon.evaluate()
    assert [e["event"] for e in events] == ["anomaly_step_time"]
    assert events[0]["z"] >= mon.step.threshold
    # latched: the same sustained condition emits no second event
    mon.observe_step(0, 0.50)
    assert mon.evaluate() == []


def test_single_spike_does_not_fire():
    mon = anomaly_mod.AnomalyMonitor()
    for _ in range(40):
        mon.observe_step(0, 0.10)
    mon.observe_step(0, 0.50)  # one outlier < consecutive threshold
    assert mon.evaluate() == []


def test_itl_detector_and_score_gauges():
    mon = anomaly_mod.AnomalyMonitor()
    reg = metrics_mod.MetricsRegistry()
    for _ in range(40):
        mon.observe_itl(0.02)
    for _ in range(3):
        mon.observe_itl(0.20)
    events = mon.evaluate(reg=reg)
    assert [e["event"] for e in events] == ["anomaly_itl_p99"]
    gauges = {
        labels[0][1]: m.value
        for (name, labels), m in reg.items()
        if name == anomaly_mod.ANOMALY_SCORE_METRIC
    }
    assert gauges["itl_p99"] >= mon.itl.threshold
    counters = {
        labels[0][1]: m.value
        for (name, labels), m in reg.items()
        if name == anomaly_mod.ANOMALY_EVENTS_METRIC
    }
    assert counters == {"itl_p99": 1}


def test_straggler_drift_detector():
    mon = anomaly_mod.AnomalyMonitor()
    for _ in range(10):
        mon.observe_step(0, 0.10)
        mon.observe_step(1, 0.10)
    fired = []
    for _ in range(8):
        for _ in range(3):
            mon.observe_step(0, 0.30)  # rank 0 drifts to 3x its peer
            mon.observe_step(1, 0.10)
        fired.extend(mon.evaluate())
    stragglers = [e for e in fired if e["event"] == "anomaly_straggler"]
    assert len(stragglers) == 1  # latched after firing
    assert stragglers[0]["rank"] == 0 and stragglers[0]["ratio"] >= 1.75
    mon.drop_rank(0)
    assert 0 not in mon._rank_recent


def test_silent_goodput_fires_only_without_recent_fault():
    clk = FakeClock(1000.0)
    mon = anomaly_mod.AnomalyMonitor(clock=clk, fault_quiet_s=120.0)
    for _ in range(10):
        assert mon.evaluate(goodput_fraction=0.8, now=clk.advance(5)) == []

    # same drop, but a fault fired 10s ago -> explained, stays silent
    events = mon.evaluate(
        goodput_fraction=0.3, last_fault_ts=clk() - 10.0, now=clk.advance(5)
    )
    assert events == []

    # fault is now outside the quiet window -> silent degradation alarm
    events = mon.evaluate(
        goodput_fraction=0.3,
        last_fault_ts=clk() - 500.0,
        now=clk.advance(5),
    )
    assert [e["event"] for e in events] == ["anomaly_silent_goodput"]
    assert events[0]["drop"] == pytest.approx(0.5)
    # degraded fractions never feed the baseline, so recovery re-arms
    events = mon.evaluate(goodput_fraction=0.8, now=clk.advance(5))
    assert events == []


# --------------------------------------------------------------------- #
# driver aggregator integration
# --------------------------------------------------------------------- #
def _goodput_beat(seconds_by_cat, src="train"):
    reg = metrics_mod.MetricsRegistry()
    for cat, secs in seconds_by_cat.items():
        reg.counter(
            goodput_mod.GOODPUT_SECONDS_METRIC, category=cat, src=src
        ).value = secs
    return {"m": reg.snapshot(delta=False)}


def test_aggregator_folds_goodput_beats(tmp_path):
    obs.enable()
    agg = DriverAggregator(str(tmp_path), num_workers=2, full=True)
    agg.ingest_payload(0, _goodput_beat({"productive_compute": 9.0, "compile": 1.0}))
    agg.ingest_payload(1, _goodput_beat({"productive_compute": 5.0, "fault_recovery": 5.0}))
    summary = agg.summary()
    gp = summary["goodput"]
    assert gp["by_category"]["productive_compute"] == pytest.approx(14.0)
    assert gp["fraction"] == pytest.approx(0.7)
    assert gp["per_rank"]["0"]["fraction"] == pytest.approx(0.9)
    # fault recovery on rank 1 dips its fraction and the fleet's
    assert gp["per_rank"]["1"]["fraction"] == pytest.approx(0.5)
    # categories sum to the per-rank wall within 2% (exact here)
    for info in gp["per_rank"].values():
        assert sum(info["seconds"].values()) == pytest.approx(
            info["wall_s"], rel=0.02
        )
    # fleet counters + fraction gauge published for the prom surfaces
    gauge = agg.registry.gauge(goodput_mod.GOODPUT_FRACTION_METRIC)
    assert gauge.value == pytest.approx(0.7)
    # latest-wins per counter key: rank 1's next beat updates its
    # productive total in place rather than double-counting it
    agg.ingest_payload(1, _goodput_beat({"productive_compute": 12.0}))
    gp = agg.goodput_summary()
    assert gp["per_rank"]["1"]["seconds"]["productive_compute"] == pytest.approx(12.0)
    assert gp["per_rank"]["1"]["wall_s"] == pytest.approx(17.0)
    agg.finalize()


def test_aggregator_fault_event_triggers_incident(tmp_path):
    obs.enable()
    agg = DriverAggregator(str(tmp_path), num_workers=1, full=True)
    agg.register_incident_source("membership_ledger", lambda: {"epoch": 3})
    agg.record_event("crash", rank=0, error="boom")
    bundles = incidents_mod.list_bundles(str(tmp_path))
    assert len(bundles) == 1 and bundles[0]["kind"] == "crash"
    window = open(os.path.join(bundles[0]["path"], "events.jsonl")).read()
    assert "boom" in window  # the trigger itself is inside its own window
    assert json.load(
        open(os.path.join(bundles[0]["path"], "membership_ledger.json"))
    ) == {"epoch": 3}
    # an uninteresting event kind does not capture
    agg.record_event("run_finished")
    assert len(incidents_mod.list_bundles(str(tmp_path))) == 1
    agg.finalize()


def test_aggregator_runs_anomaly_and_routes_events(tmp_path):
    obs.enable()
    agg = DriverAggregator(str(tmp_path), num_workers=1, full=True)
    assert agg.anomaly is not None
    for _ in range(40):
        agg.anomaly.observe_step(0, 0.1)
    for _ in range(3):
        agg.anomaly.observe_step(0, 0.5)
    agg._summary_written = 0.0  # force the throttled path to run now
    agg._maybe_write_summary(time.time())
    events = [
        json.loads(ln) for ln in open(os.path.join(str(tmp_path), "events.jsonl"))
    ]
    kinds = [e["event"] for e in events]
    assert "anomaly_step_time" in kinds
    # the anomaly event is an incident trigger too
    kinds_captured = [b["kind"] for b in incidents_mod.list_bundles(str(tmp_path))]
    assert "anomaly_step_time" in kinds_captured
    agg.finalize()


def test_metrics_history_ring_cap(monkeypatch):
    monkeypatch.setenv(metrics_mod.HISTORY_ENV, "4")
    reg = metrics_mod.MetricsRegistry()
    for i in range(10):
        reg.counter("rlt_serve_requests_total").inc()
        reg.push_history(now=float(i))
    hist = reg.history()
    assert len(hist) == 4
    assert [e["ts"] for e in hist] == [6.0, 7.0, 8.0, 9.0]
    assert hist[-1]["counters"][0][2] == 10

    monkeypatch.setenv(metrics_mod.HISTORY_ENV, "0")
    reg2 = metrics_mod.MetricsRegistry()
    reg2.push_history(now=1.0)
    assert reg2.history() == []


def test_trace_peek_is_non_destructive():
    obs.enable()
    rec = obs.get_recorder()
    with obs.span("step"):
        pass
    peeked = rec.peek()
    assert len(peeked) >= 1
    assert rec.peek(limit=1) == peeked[-1:]
    assert len(rec.peek()) == len(peeked)  # still there: drain untouched


# --------------------------------------------------------------------- #
# prometheus scrape endpoints
# --------------------------------------------------------------------- #
def test_prom_server_serves_live_registry():
    reg = metrics_mod.MetricsRegistry()
    reg.counter("rlt_serve_requests_total").inc(3)
    srv = metrics_mod.PromServer(reg.prometheus_text, port=0)
    port = srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "rlt_serve_requests_total 3" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        srv.stop()
    srv.stop()  # idempotent


def test_prom_port_from_env(monkeypatch):
    monkeypatch.delenv(metrics_mod.PROM_PORT_ENV, raising=False)
    assert metrics_mod.prom_port_from_env() is None
    monkeypatch.setenv(metrics_mod.PROM_PORT_ENV, "0")
    assert metrics_mod.prom_port_from_env() == 0
    monkeypatch.setenv(metrics_mod.PROM_PORT_ENV, "9400")
    assert metrics_mod.prom_port_from_env() == 9400
    monkeypatch.setenv(metrics_mod.PROM_PORT_ENV, "not-a-port")
    assert metrics_mod.prom_port_from_env() is None


def test_aggregator_prom_endpoint_env(tmp_path, monkeypatch):
    obs.enable()
    monkeypatch.setenv(metrics_mod.PROM_PORT_ENV, "0")
    agg = DriverAggregator(str(tmp_path), num_workers=1, full=True)
    assert agg._prom is not None and agg._prom.port
    agg.registry.counter("rlt_serve_requests_total").inc()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{agg._prom.port}/metrics", timeout=5
    ).read().decode()
    assert "rlt_serve_requests_total" in body
    events = [
        json.loads(ln) for ln in open(os.path.join(str(tmp_path), "events.jsonl"))
    ]
    assert any(e["event"] == "prom_endpoint" for e in events)
    agg.finalize()
    assert agg._prom is None  # stopped


def test_top_serve_port_serves_metrics_prom(tmp_path):
    (tmp_path / "metrics.prom").write_text("rlt_worker_step 5\n")
    srv = agg_mod.start_prom_file_server(str(tmp_path), 0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert body == "rlt_worker_step 5\n"
    finally:
        srv.stop()


# --------------------------------------------------------------------- #
# cli
# --------------------------------------------------------------------- #
def test_cli_goodput_renders_summary(tmp_path, capsys):
    from ray_lightning_tpu import cli

    summary = {"goodput": {
        "fraction": 0.61, "total_s": 100.0,
        "by_category": {"productive_compute": 61.0, "fault_recovery": 39.0},
        "per_rank": {"0": {
            "seconds": {"productive_compute": 61.0, "fault_recovery": 39.0},
            "wall_s": 100.0, "fraction": 0.61,
        }},
    }}
    (tmp_path / "summary.json").write_text(json.dumps(summary))
    assert cli.main(["goodput", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "goodput fraction: 0.6100" in out
    assert "fault_recovery" in out and "61.0%" in out
    assert cli.main(["goodput", "--dir", str(tmp_path), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["fraction"] == 0.61
    assert cli.main(["goodput", "--dir", str(tmp_path / "missing")]) == 1


def test_cli_incidents_lists_and_shows(tmp_path, capsys):
    from ray_lightning_tpu import cli

    clk = FakeClock(1722800000.0)
    rec, _ = _recorder(tmp_path, clk)
    path = rec.maybe_capture("slo_breach", event={"objective": "ttft_p95"})
    name = os.path.basename(path)
    assert cli.main(["incidents", "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "slo_breach" in out and name in out
    assert cli.main(["incidents", "--dir", str(tmp_path), "--show", name]) == 0
    out = capsys.readouterr().out
    assert "ttft_p95" in out and "events.jsonl" in out
    assert cli.main(["incidents", "--dir", str(tmp_path), "--show", "no"]) == 1
    capsys.readouterr()
    assert cli.main(["incidents", "--dir", str(tmp_path / "empty")]) == 1


# --------------------------------------------------------------------- #
# metrics/docs contract (scripts/check_metrics_docs.py)
# --------------------------------------------------------------------- #
def _load_checker():
    repo = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "check_metrics_docs",
        os.path.join(repo, "scripts", "check_metrics_docs.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_metrics_docs_both_directions(tmp_path):
    checker = _load_checker()
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'FOO_METRIC = "rlt_foo_total"\n'
        'reg.counter("rlt_bar_seconds").inc()\n'
        'log.info("rlt_not_an_emission failed")\n'
    )
    docs = tmp_path / "docs.md"
    docs.write_text(
        "| `rlt_foo_total` | counter | | test |\n"
        "| `rlt_gone_metric` | gauge | | stale row |\n"
    )
    emitted = checker.emitted_metrics(pkg)
    assert emitted == {"rlt_foo_total", "rlt_bar_seconds"}
    # code -> docs: rlt_bar_seconds is emitted but undocumented
    assert sorted(emitted - checker.documented_metrics(docs)) == [
        "rlt_bar_seconds"
    ]
    # docs -> code: rlt_gone_metric is a table row with no emission site
    assert sorted(checker.documented_rows(docs) - emitted) == [
        "rlt_gone_metric"
    ]


def test_new_observability_metrics_have_doc_rows():
    checker = _load_checker()
    rows = checker.documented_rows()
    emitted = checker.emitted_metrics()
    for name in (
        goodput_mod.GOODPUT_SECONDS_METRIC,
        goodput_mod.GOODPUT_FRACTION_METRIC,
        anomaly_mod.ANOMALY_SCORE_METRIC,
        anomaly_mod.ANOMALY_EVENTS_METRIC,
        incidents_mod.INCIDENTS_CAPTURED_METRIC,
        incidents_mod.INCIDENTS_SUPPRESSED_METRIC,
    ):
        assert name in rows, f"{name} missing from the docs metric table"
        assert name in emitted, f"{name} lost its emission site"
        assert name in metrics_mod.HELP, f"{name} missing a HELP entry"


def test_local_fit_summary_holds_goodput(tmp_root):
    """An in-process fit with telemetry on (no launcher, no worker) lands a
    goodput section in its summary: every second of the fit in one category,
    some of them productive, the fraction their share."""
    import ray_lightning_tpu as rlt
    from tests.utils import BoringModel, get_trainer

    trainer = get_trainer(
        tmp_root,
        strategy=rlt.XLAStrategy(devices=1, telemetry=True),
        limit_train_batches=6,
    )
    trainer.fit(BoringModel())
    gp = agg_mod._read_summary(os.path.join(tmp_root, "telemetry"))["goodput"]
    seconds = gp["by_category"]
    assert set(seconds) <= set(goodput_mod.CATEGORIES)
    assert seconds[goodput_mod.PRODUCTIVE] > 0
    assert sum(seconds.values()) == pytest.approx(gp["total_s"], rel=0.02)
    assert gp["fraction"] == pytest.approx(
        seconds[goodput_mod.PRODUCTIVE] / gp["total_s"], rel=0.02)


# --------------------------------------------------------------------- #
# e2e: chaos run produces goodput + an incident bundle (chaos.sh)
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_two_worker_chaos_goodput_and_incident(tmp_root, monkeypatch):
    """The acceptance scenario: a 2-worker CPU fit with an injected crash
    finishes, the summary carries a goodput section whose per-rank
    categories sum to the reported wall time, and the crash froze >= 1
    incident bundle whose event window covers the fault itself."""
    import ray_lightning_tpu as rlt
    from tests.utils import BoringModel, get_trainer

    monkeypatch.setenv("RLT_FAULT", "rank0:crash@step3")
    monkeypatch.setenv("RLT_FAULT_FUSE", os.path.join(tmp_root, "fuses"))

    strategy = rlt.RayStrategy(
        num_workers=2, platform="cpu", devices_per_worker=1,
        max_failures=1, telemetry=True, heartbeat_interval=0.1,
    )
    trainer = get_trainer(tmp_root, strategy=strategy, limit_train_batches=6)
    trainer.fit(BoringModel())
    assert trainer.state.status == "finished"

    run_dir = os.path.join(tmp_root, "telemetry")
    summary = agg_mod._read_summary(run_dir)
    assert summary is not None
    gp = summary["goodput"]
    assert gp["total_s"] > 0 and 0.0 <= gp["fraction"] <= 1.0
    assert gp["by_category"].get("productive_compute", 0.0) > 0
    for key, info in gp["per_rank"].items():
        assert sum(info["seconds"].values()) == pytest.approx(
            info["wall_s"], rel=0.02
        ), key

    events = [json.loads(ln) for ln in open(os.path.join(run_dir, "events.jsonl"))]
    crash_ts = [e["ts"] for e in events if e["event"] == "crash"]
    assert crash_ts, "injected crash never hit the flight record"

    bundles = [
        b for b in incidents_mod.list_bundles(run_dir) if b["kind"] == "crash"
    ]
    assert len(bundles) >= 1
    window = open(os.path.join(bundles[0]["path"], "events.jsonl")).read()
    assert window.strip(), "bundle event window is empty"
    assert '"crash"' in window, "bundle window does not cover the fault"
    assert bundles[0]["ts"] >= int(min(crash_ts)) - 1
