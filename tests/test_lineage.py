"""Cross-replica request lineage (observability/lineage.py + the
hop-carrying TraceContext threaded through reqtrace/engine/fleet).

Unit layer: the telescoping TTFT decomposition (components sum exactly
to the measured TTFT, across hops, nothing double-counted), rid-grammar
parent inference, rotation-stitched read_window, clock-skew-corrected
stitching, the SLO burn attribution, and the per-pool autoscale signal.
E2E layer (tiny model): a migrated request's recorded components sum to
the client-measured TTFT within 5%, and ``cli lineage <rid>`` renders
the prefill -> shipment -> decode hops with a retry branch under an
injected corrupt-shipment fault. The slow chaos e2e sustains a
corrupt-shipment kill loop and asserts every completed rid still
stitches a complete lineage.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time

import pytest

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.observability import lineage as lineage_mod
from ray_lightning_tpu.observability import metrics as obs_metrics
from ray_lightning_tpu.observability import reqtrace, slo
from ray_lightning_tpu.observability.aggregator import DriverAggregator

pytestmark = pytest.mark.observability


@pytest.fixture(autouse=True)
def obs_reset():
    obs.reset()
    yield
    obs.reset()


# --------------------------------------------------------------------- #
# telescoping TTFT decomposition (pure reqtrace, no model)
# --------------------------------------------------------------------- #
def test_local_components_sum_exactly_to_ttft():
    tr = reqtrace.RequestTrace("r1", prompt_len=4)
    time.sleep(0.002)
    tr.admitted(slot=0)
    time.sleep(0.002)
    tr.prefilled(0.002)
    time.sleep(0.002)
    tr.token()
    comps = tr.ttft_components()
    assert set(comps) == {"queue_wait", "prefill", "decode"}
    assert sum(comps.values()) == pytest.approx(tr.ttft_s, abs=1e-9)


def test_components_telescope_across_a_migration_hop():
    """export_context -> receiving trace: the cumulative decomposition on
    the first-token hop sums to end-to-end submit -> first-token wall
    time, with the in-flight gap charged to ``transfer``."""
    src = reqtrace.RequestTrace("req-7", prompt_len=4)
    t_submit = src.submitted_wall
    time.sleep(0.002)
    src.admitted(slot=0)
    time.sleep(0.002)
    src.prefilled(0.002)
    time.sleep(0.002)
    ctx = src.export_context()
    assert ctx.hop == 1 and ctx.rid == "req-7"
    assert ctx.gap_component == "transfer"
    assert "export_wait" in ctx.components
    time.sleep(0.003)  # the shipment's time on the wire
    dst = reqtrace.RequestTrace("req-7~m1", prompt_len=4, ctx=ctx)
    assert dst.hop == 1 and dst.parent_rid == "req-7"
    time.sleep(0.002)
    dst.token()
    comps = dst.ttft_components()
    assert comps["transfer"] >= 0.003
    end_to_end = (
        dst.submitted_wall + dst.ttft_s
    ) - t_submit
    # exact up to float rounding of epoch-sized wall stamps
    assert sum(comps.values()) == pytest.approx(end_to_end, abs=1e-5)
    rec = dst.record("length")
    assert rec["ttft_total_s"] == pytest.approx(sum(comps.values()), abs=5e-6)
    assert rec["parent_rid"] == "req-7" and rec["hop"] == 1
    assert rec["base_rid"] == "req-7"
    assert rec["transfer_s"] == pytest.approx(comps["transfer"], abs=5e-6)


def test_hop0_context_means_no_parent():
    ctx = reqtrace.TraceContext(
        rid="a", base_rid="a", hop=0, sent_wall=time.time()
    )
    tr = reqtrace.RequestTrace("a", ctx=ctx)
    assert tr.parent_rid is None and tr.hop == 0


def test_rid_grammar_and_dispositions():
    assert reqtrace.base_rid("jreq-3~m2") == "jreq-3"
    assert reqtrace.base_rid("jreq-3~r1") == "jreq-3"
    assert reqtrace.disposition_for("migrated") == "migrated"
    assert lineage_mod._implied_parent("r~r2") == "r~r1"
    assert lineage_mod._implied_parent("r~r1") == "r"
    assert lineage_mod._implied_parent("r~m1") is None
    assert lineage_mod._implied_parent("r") is None
    assert lineage_mod._migration_number("r~m3") == 3
    assert lineage_mod._migration_number("r~r1") is None


def test_head_sampling_keys_on_base_rid():
    """Every hop of one request shares the keep/drop verdict, so a
    lineage is whole or absent — never a partial chain."""
    tracer = reqtrace.RequestTracer(rate=0.5)
    for base in ("x-%d" % i for i in range(64)):
        verdicts = {
            tracer.start(rid) is not None
            for rid in (base, base + "~m1", base + "~r1", base + "~m2")
        }
        assert len(verdicts) == 1


# --------------------------------------------------------------------- #
# read_window: rotation stitching (the JsonlWriter regression)
# --------------------------------------------------------------------- #
def _write_lines(path, lines):
    with open(path, "w", encoding="utf-8") as fh:
        for ln in lines:
            fh.write(ln + "\n")


def test_read_window_reserves_rotated_floor(tmp_path):
    """Regression: a live file larger than the window must NOT starve the
    rotated generation — half the budget is reserved for the ``.1`` tail
    so records straddling a rotation stay visible together."""
    path = str(tmp_path / "requests.jsonl")
    old = ["old-%04d" % i for i in range(20)]
    new = ["new-%04d" % i for i in range(200)]
    _write_lines(path + ".1", old)
    _write_lines(path, new)
    budget = 400  # far smaller than the live file
    lines = reqtrace.read_window(path, budget)
    assert any(ln.startswith("old-") for ln in lines)
    assert any(ln.startswith("new-") for ln in lines)
    # oldest-first: every rotated line precedes every live line
    last_old = max(i for i, ln in enumerate(lines) if ln.startswith("old-"))
    first_new = min(i for i, ln in enumerate(lines) if ln.startswith("new-"))
    assert last_old < first_new
    # a partially-included first line is dropped, never returned corrupt
    assert all(len(ln) == 8 for ln in lines)
    # single-generation files still spend the whole budget on the tail
    assert reqtrace.read_window(path + ".1", budget) == old[-20:]


def test_lineage_survives_rotation_split(tmp_path):
    """One request's hop records split across requests.jsonl/.1 by a
    rotation mid-burst still stitch into one complete lineage."""
    path = str(tmp_path / "requests.jsonl")
    t0 = 1000.0
    hop0 = {
        "request_id": "q-0", "ts": t0 + 0.5, "start_ts": t0, "hop": 0,
        "finish_reason": "migrated", "disposition": "migrated",
        "pool": "prefill", "replica": 0, "total_s": 0.5,
    }
    hop1 = {
        "request_id": "q-0~m1", "base_rid": "q-0", "parent_rid": "q-0",
        "ts": t0 + 1.0, "start_ts": t0 + 0.6, "hop": 1,
        "finish_reason": "length", "disposition": "completed",
        "pool": "decode", "replica": 1, "total_s": 0.4,
    }
    filler = {"request_id": "other", "ts": t0, "finish_reason": "length",
              "pad": "x" * 300}
    # threshold sized so the filler (not either hop) trips the rotation:
    # hop 0 + filler land in .1, hop 1 starts the fresh live generation
    line_len = lambda r: len(json.dumps(r, sort_keys=True)) + 1
    writer = reqtrace.JsonlWriter(
        path, max_bytes=max(line_len(hop0), line_len(hop1)) + 2
    )
    writer.write(hop0)
    assert writer.rotations == 0
    writer.write(filler)
    assert writer.rotations == 1  # hop 0 now lives in the .1 generation
    writer.write(hop1)
    assert writer.rotations == 1
    writer.close()
    lins = lineage_mod.lineages_from_window(path, max_bytes=64 * 1024)
    lin = lins["q-0"]
    assert [h.rid for h in lin.hops] == ["q-0", "q-0~m1"]
    assert lin.complete and not lin.orphan_hops()
    # reading ONLY the live generation would orphan the decode hop
    live_only = lineage_mod.build_lineages([
        json.loads(ln)
        for ln in open(path).read().splitlines() if ln.strip()
    ])
    assert not live_only["q-0"].complete
    assert live_only["q-0"].orphan_hops() == ["q-0~m1"]


# --------------------------------------------------------------------- #
# clock-skew round-trip: two replicas, injected skew, stitched timeline
# --------------------------------------------------------------------- #
def test_clock_skew_roundtrip_stitches_non_negative_hops(tmp_path):
    """A decode replica whose wall clock runs 5 s ahead: the aggregator's
    heartbeat skew estimate corrects its records, so the stitched
    timeline has non-negative hop durations and spans exactly the
    journal's wall time."""
    skew = 5.0
    t0 = 2000.0
    journal_wall = 1.0  # true submit -> finish span
    recs = [
        {   # prefill hop, rank 0, honest clock
            "request_id": "s-0", "rank": 0, "hop": 0,
            "start_ts": t0, "ts": t0 + 0.5, "total_s": 0.5,
            "finish_reason": "migrated", "disposition": "migrated",
            "pool": "prefill", "replica": 0,
        },
        {   # decode hop, rank 1, clock runs +5s fast
            "request_id": "s-0~m1", "base_rid": "s-0",
            "parent_rid": "s-0", "rank": 1, "hop": 1,
            "start_ts": t0 + 0.6 + skew, "ts": t0 + journal_wall + skew,
            "total_s": 0.4, "finish_reason": "length",
            "disposition": "completed", "pool": "decode", "replica": 1,
        },
    ]
    agg = DriverAggregator(str(tmp_path / "t"), num_workers=2)
    # heartbeats: rank 0 in sync, rank 1's send stamps run `skew` ahead
    for beat in range(3):
        recv = 100.0 + beat
        agg.on_beat(0, beat, send_wall=recv, recv_wall=recv)
        agg.on_beat(1, beat, send_wall=recv + skew, recv_wall=recv)
    est = agg.skew_by_rank()
    assert est[0] == pytest.approx(0.0, abs=1e-9)
    assert est[1] == pytest.approx(skew, abs=1e-9)

    lins = lineage_mod.build_lineages(recs, skew_by_rank=est)
    lin = lins["s-0"]
    assert lin.complete
    h0, h1 = lin.hops
    assert h0.duration_s >= 0 and h1.duration_s >= 0
    # corrected: the decode hop starts AFTER the prefill hop started and
    # the stitched end-to-end span equals the journal wall time
    assert h1.start_ts >= h0.start_ts
    assert h1.end_ts - h0.start_ts == pytest.approx(journal_wall, abs=1e-6)
    # uncorrected, the same records claim a 5s-longer request
    raw = lineage_mod.build_lineages(recs)["s-0"]
    assert raw.hops[-1].end_ts - raw.hops[0].start_ts > journal_wall + skew - 0.1


# --------------------------------------------------------------------- #
# lineage summaries, chrome flow events, incident slice
# --------------------------------------------------------------------- #
def _two_hop_records(base="w-0", t0=3000.0):
    return [
        {
            "request_id": base, "hop": 0, "start_ts": t0, "ts": t0 + 0.3,
            "total_s": 0.3, "finish_reason": "migrated",
            "disposition": "migrated", "pool": "prefill", "replica": 0,
            "queue_wait_s": 0.05, "prefill_s": 0.1,
        },
        {
            "request_id": base + "~m2", "base_rid": base,
            "parent_rid": base, "hop": 1, "start_ts": t0 + 0.4,
            "ts": t0 + 0.8, "total_s": 0.4, "finish_reason": "length",
            "disposition": "completed", "pool": "decode", "replica": 1,
            "transfer_s": 0.1, "ttft_s": 0.05,
            "ttft_components": {
                "dispatch": 0.01, "queue_wait": 0.05, "prefill": 0.1,
                "export_wait": 0.04, "transfer": 0.1, "decode": 0.05,
            },
            "ttft_total_s": 0.35,
        },
    ]


def test_summary_and_render_with_retry_branch():
    lins = lineage_mod.build_lineages(_two_hop_records())
    s = lineage_mod.summary(lins["w-0"])
    assert s["complete"] and s["migrations"] == 1 and s["retries"] == 0
    assert s["disposition"] == "completed"
    assert s["ttft_total_s"] == pytest.approx(0.35)
    assert sum(s["ttft_components"].values()) == pytest.approx(0.35)
    text = lineage_mod.render(lins["w-0"])
    assert "hop 0" in text and "hop 1" in text
    assert "pool prefill" in text and "pool decode" in text
    # ~m2 survived => the ~m1 shipment attempt failed: a retry branch
    assert "retry branch: 1 failed shipment attempt(s)" in text
    assert "TTFT" in text


def test_orphan_hop_detection():
    # decode hop only: its recorded parent left no record
    lins = lineage_mod.build_lineages(_two_hop_records()[1:])
    lin = lins["w-0"]
    assert not lin.complete
    assert lin.orphan_hops() == ["w-0~m2"]
    assert "INCOMPLETE" in lineage_mod.render(lin)


def test_chrome_events_flow_pair_between_hops():
    lins = lineage_mod.build_lineages(_two_hop_records())
    evs = lineage_mod.chrome_events(lins)
    slices = [e for e in evs if e.get("ph") == "X"]
    assert len(slices) == 2
    assert {e["tid"] for e in slices} == {lineage_mod.LINEAGE_TID}
    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    assert finishes[0]["bp"] == "e"
    # the arrow crosses process tracks (replica 0 -> replica 1)
    assert starts[0]["pid"] != finishes[0]["pid"]


def test_write_lineage_and_load_roundtrip(tmp_path):
    lins = lineage_mod.build_lineages(_two_hop_records())
    path = str(tmp_path / "lineage.jsonl")
    assert lineage_mod.write_lineage(path, lins) == 1
    [line] = [json.loads(ln) for ln in open(path)]
    assert line["base_rid"] == "w-0" and line["complete"]
    names = [s["name"] for s in line["hops"][1]["spans"]]
    assert names[0] == "transfer"  # migrated-in hop leads with the wire


def test_incident_lineage_slice_prefers_exemplar_rids(tmp_path, monkeypatch):
    monkeypatch.setenv(lineage_mod.LINEAGE_WINDOW_ENV, "65536")
    assert lineage_mod.lineage_window_bytes() == 65536
    agg = DriverAggregator(str(tmp_path / "t"), num_workers=1)
    for rec in _two_hop_records("inc-0") + _two_hop_records("inc-1", 3100.0):
        agg.record_request(rec, rank=0)
    # exemplar on the TTFT histogram names inc-1 as the offender
    agg.registry.histogram("rlt_serve_ttft_seconds").observe(
        5.0, exemplar="inc-1~m2"
    )
    sl = agg._lineage_slice()
    assert [l["base_rid"] for l in sl["lineages"]] == ["inc-1"]
    assert sl["lineages"][0]["complete"]
    # finalize lands lineage.jsonl + flow events in trace.json
    run_dir = agg.finalize()
    lines = open(os.path.join(run_dir, lineage_mod.LINEAGE_FILE)).readlines()
    assert len(lines) == 2
    trace_doc = json.load(open(os.path.join(run_dir, "trace.json")))
    assert any(e.get("cat") == "lineage" for e in trace_doc["traceEvents"])


# --------------------------------------------------------------------- #
# SLO burn attribution + per-pool autoscale signal
# --------------------------------------------------------------------- #
def _component_reg(observations):
    reg = obs_metrics.MetricsRegistry()
    for component, pool, secs in observations:
        reg.histogram(
            obs_metrics.SERVE_TTFT_COMPONENT_METRIC,
            bounds=obs_metrics.TTFT_COMPONENT_BOUNDS,
            component=component, pool=pool,
        ).observe(secs)
    return reg


def test_ttft_burn_attribution_names_dominant_component():
    reg = _component_reg([
        ("queue_wait", "decode", 0.9),  # emitted by the first-token hop,
        ("queue_wait", "decode", 0.7),  # but the seconds charge PREFILL
        ("decode", "decode", 0.1),
        ("transfer", "decode", 0.05),
    ])
    attr = slo.ttft_burn_attribution(reg)
    assert attr["dominant_component"] == "queue_wait"
    assert attr["dominant_pool"] == "prefill"
    assert attr["component_share"] == pytest.approx(1.6 / 1.75, abs=1e-3)
    assert slo.ttft_burn_attribution(obs_metrics.MetricsRegistry()) is None


def test_ttft_breach_verdict_carries_attribution():
    class _Clock:
        t = 0.0
        def __call__(self):
            return self.t
    clock = _Clock()
    mon = slo.SLOMonitor(clock=clock)
    for _ in range(20):
        mon.observe_latency("ttft_p95", 100.0)
        clock.t += 1.0
    reg = _component_reg([("decode", "decode", 2.0)])
    [verdict] = [
        v for v in mon.evaluate(reg=reg) if v["event"] == "slo_breach"
    ]
    assert verdict["dominant_component"] == "decode"
    assert verdict["dominant_pool"] == "decode"
    assert verdict["component_share"] == 1.0


def test_autoscaler_component_signal_windowed_mean():
    from ray_lightning_tpu.serving import Autoscaler

    class _Fleet:
        num_replicas = 1
        def loads(self):
            return {0: {"role": "decode", "queue_depth": 0, "active": 0}}
        def add_replica(self):
            return 1
        def remove_replica(self):
            return 0

    scaler = Autoscaler(
        _Fleet(), role="decode", ttft_component_high_s=0.05,
    )
    reg = _component_reg([
        ("decode", "decode", 0.2), ("decode", "decode", 0.4),
        ("queue_wait", "decode", 9.0),  # other pool's component: ignored
    ])
    assert scaler._component_signal(reg) == pytest.approx(0.3)
    # no new samples since the snapshot -> no signal (not a stale mean)
    assert scaler._component_signal(reg) is None
    reg.histogram(
        obs_metrics.SERVE_TTFT_COMPONENT_METRIC,
        bounds=obs_metrics.TTFT_COMPONENT_BOUNDS,
        component="decode", pool="decode",
    ).observe(0.6)
    assert scaler._component_signal(reg) == pytest.approx(0.6)
    # prefill pool keys on queue_wait; disabled watermark -> None
    assert Autoscaler(
        _Fleet(), role="prefill", ttft_component_high_s=None,
    )._component_signal(reg) is None


def test_autoscale_decision_component_watermark():
    from ray_lightning_tpu.serving import autoscale_decision

    loads = {0: {"role": "decode", "queue_depth": 0, "active": 1}}
    common = dict(num_replicas=1, min_replicas=1, max_replicas=4, role="decode")
    assert autoscale_decision(
        loads, ttft_component_s=0.2, ttft_component_high_s=0.05, **common
    ) == 1
    assert autoscale_decision(
        loads, ttft_component_s=0.01, ttft_component_high_s=0.05, **common
    ) == 0
    assert autoscale_decision(loads, ttft_component_s=None,
                              ttft_component_high_s=0.05, **common) == 0


# --------------------------------------------------------------------- #
# cli: lineage rendering + requests hop/pool columns
# --------------------------------------------------------------------- #
def _requests_dir(tmp_path):
    d = str(tmp_path / "tel")
    writer = reqtrace.JsonlWriter(
        os.path.join(d, reqtrace.REQUESTS_FILE), max_bytes=0
    )
    for rec in _two_hop_records("c-0"):
        writer.write(rec)
    writer.close()
    return d


def test_cli_lineage_renders_hops(tmp_path, capsys):
    from ray_lightning_tpu import cli

    d = _requests_dir(tmp_path)
    assert cli.main(["lineage", "--dir", d, "c-0~m2"]) == 0
    out = capsys.readouterr().out
    assert "hop 0" in out and "hop 1" in out and "retry branch" in out
    # list mode + json mode
    assert cli.main(["lineage", "--dir", d]) == 0
    assert "c-0" in capsys.readouterr().out
    assert cli.main(["lineage", "--dir", d, "c-0", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["base_rid"] == "c-0" and doc["complete"]
    assert cli.main(["lineage", "--dir", d, "nope"]) == 1
    capsys.readouterr()


def test_cli_requests_shows_hop_and_pool_columns(tmp_path, capsys):
    from ray_lightning_tpu import cli

    d = _requests_dir(tmp_path)
    assert cli.main(["requests", "--dir", d, "--sort", "total_s"]) == 0
    out = capsys.readouterr().out
    header = out.splitlines()[0]
    assert "hop" in header and "pool" in header
    migrated = next(l for l in out.splitlines() if "migrated" in l)
    finished = next(l for l in out.splitlines() if "c-0~m2" in l)
    assert "prefill" in migrated and "decode" in finished


# --------------------------------------------------------------------- #
# model-backed e2e: disaggregated fleet, migration fault, full lineage
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    return init_params(jax.random.key(0), cfg), cfg


@contextlib.contextmanager
def _fault_env(spec):
    """Arm RLT_FAULT (no fuse dir so @every keeps firing); restores the
    env and both parse caches on exit — test_migration.py's idiom."""
    from ray_lightning_tpu.runtime import faults

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


def _disagg_fleet(params, cfg, **kw):
    from ray_lightning_tpu.serving import LocalReplicaFleet

    return LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=ENGINE_KW,
        initial_replicas=kw.pop("replicas", 2),
        prefill_replicas=kw.pop("prefill", 1),
        max_retries=kw.pop("max_retries", 4),
        **kw,
    )


@pytest.mark.migration
def test_migrated_ttft_components_sum_and_cli_renders_retry_branch(
    model, tmp_path, capsys
):
    """THE acceptance e2e: under an injected corrupt-shipment fault a
    migrated request's recorded TTFT components sum to the
    client-measured TTFT within 5%, and ``cli lineage <rid>`` renders
    the prefill -> shipment -> decode hops with the retry branch."""
    from ray_lightning_tpu import cli

    params, cfg = model
    obs.enable()
    with _fault_env("replica0:corrupt-shipment@req1"):
        fleet = _disagg_fleet(params, cfg)
        try:
            e = fleet.submit([3, 1, 4, 1], max_new_tokens=6)
            e.result(timeout=180)
            measured_ttft = e.ttft_s
            assert fleet.stats()["migration"]["retries"] == 1
            records = fleet.drain_request_records()
        finally:
            fleet.shutdown()

    lins = lineage_mod.build_lineages(records)
    lin = lins[e.request_id]
    assert lin.complete and lin.migrations == 1
    # prefill hop on the prefill pool, decode hop parented on it; the
    # corrupt first shipment attempt surfaces as the ~m2 attempt suffix
    assert lin.hops[0].pool == "prefill"
    final = lin.final_hop
    assert final.pool == "decode" and final.parent_rid == e.request_id
    assert lineage_mod._migration_number(final.rid) == 2
    comps = final.record["ttft_components"]
    assert {"queue_wait", "prefill", "export_wait", "transfer", "decode"} \
        <= set(comps)
    total = final.record["ttft_total_s"]
    assert total == pytest.approx(sum(comps.values()), abs=1e-4)
    assert total == pytest.approx(measured_ttft, rel=0.05)
    # the component histograms landed with per-request exemplars
    reg = obs.registry()
    hists = [
        (dict(labels), m) for (name, labels), m in reg.items()
        if name == obs_metrics.SERVE_TTFT_COMPONENT_METRIC
    ]
    assert {l["component"] for l, _ in hists} >= set(comps)
    assert all(l["pool"] == "decode" for l, _ in hists)

    # cli round-trip through requests.jsonl
    d = str(tmp_path / "tel")
    writer = reqtrace.JsonlWriter(
        os.path.join(d, reqtrace.REQUESTS_FILE), max_bytes=0
    )
    for rec in records:
        writer.write(rec)
    writer.close()
    assert cli.main(["lineage", "--dir", d, e.request_id]) == 0
    out = capsys.readouterr().out
    assert "pool prefill" in out and "pool decode" in out
    assert "-> migrated" in out and "transfer" in out
    assert "retry branch: 1 failed shipment attempt(s)" in out


@pytest.mark.migration
@pytest.mark.serving_chaos
@pytest.mark.slow
def test_lineage_complete_under_corrupt_shipment_kill_loop(model):
    """scripts/chaos.sh stanza: every other shipment off the prefill
    pool is poisoned, sustained; every completed rid must still stitch a
    complete lineage (no orphan hops) and the poisoned requests carry
    their retry branches."""
    import numpy as np

    params, cfg = model
    obs.enable()
    with _fault_env("replica0:corrupt-shipment@every:2"):
        fleet = _disagg_fleet(params, cfg, max_retries=6)
        try:
            rng = np.random.default_rng(11)
            reqs = [
                [int(t) for t in rng.integers(1, cfg.vocab_size, 5)]
                for _ in range(8)
            ]
            entries = [fleet.submit(p, max_new_tokens=6) for p in reqs]
            for e in entries:
                e.result(timeout=300)
            stats = fleet.stats()
            assert stats["completed"] == len(reqs) and stats["failed"] == 0
            assert stats["migration"]["corrupt"] >= 2
            records = fleet.drain_request_records()
        finally:
            fleet.shutdown()

    lins = lineage_mod.build_lineages(records)
    assert set(lins) == {e.request_id for e in entries}
    retry_branches = 0
    for e in entries:
        lin = lins[e.request_id]
        assert lin.complete, (
            f"{e.request_id}: orphan hops {lin.orphan_hops()}"
        )
        assert lin.final_hop.disposition == "completed"
        retry_branches += sum(
            1 for h in lin.hops
            if (lineage_mod._migration_number(h.rid) or 0) > 1
        )
    # every other shipment was poisoned: retry branches must be present
    assert retry_branches >= 2
