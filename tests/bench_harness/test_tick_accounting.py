"""The readers of the engine's starvation probe and tick-cycle counters
(``benchmarks/tick_readers.py`` and the four ``layer_metrics`` files on it) on
counters made by hand and in a traced tiny run, and
``benchmarks/tools/tick_accounting.py`` on spans and gaps worked out by hand
and on the small engine traces recorded on the chip."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, program_trace as pt, run, tick_readers, trace_reduce  # noqa: E402
from benchmarks.tools import tick_accounting as ta  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# a dozen ticks of a tiny paged engine on a TPU v5 lite: the older recording,
# whose spans carry no starved= / rung= and prefills= on the sync alone, and
# one of an engine that carries them (``record_engine_trace.py``, PR 36)
OLDER_TRACE = os.path.join(HERE, "data", "tiny_engine_tpu.xplane.pb")
TICK_TRACE = os.path.join(HERE, "data", "tiny_tick_engine_tpu.xplane.pb")
CLOSED = ["serve-moe-batch", "serve-mla-moe-reason", "serve-swa-moe-doc"]
ENTRIES = {
    "device_starved_share.chat": ("entry points", "itl_p99_ms", ["serve-dense-chat"]),
    "device_starved_share.closed": ("entry points", "serve_tokens_per_s", CLOSED),
    "prefill_window_share.chat": ("model step", "itl_p99_ms", ["serve-dense-chat"]),
    "prefill_window_share.closed": ("model step", "serve_tokens_per_s", CLOSED),
}
# 400 decode programs, 12 of them behind a device that had run dry; 300
# cycles of 10 ms without a prefill, 50 of 50 ms with one
COUNTERS = {"decode_steps": 400.0, "starved_steps": 12.0, "starved_s": 0.004,
            "decode_cycles": 300.0, "decode_cycle_s": 3.0,
            "prefill_cycles": 50.0, "prefill_cycle_s": 2.5}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_does_the_stated_arithmetic_or_reads_nothing(name):
    read = loader.Manifest().reader(name)
    if name.startswith("device_starved_share"):
        want, needs = 100.0 * 12 / 400, ("starved_steps", "decode_steps")
    else:  # (2.5 - 50 x 0.010) / (2.5 + 3.0)
        want, needs = 100.0 * 2.0 / 5.5, ("prefill_cycle_s", "decode_cycles")
    assert read({"counters": COUNTERS}) == pytest.approx(want)
    for key in needs:  # a parent has no such counter: no reading, never 0
        assert read({"counters": {k: v for k, v in COUNTERS.items() if k != key}}) is None
    assert read({"counters": dict(COUNTERS, decode_steps=0, decode_cycles=0)}) is None
    assert read({}) is None


def test_no_prefill_in_the_window_reads_zero_and_none_dry_reads_zero():
    quiet = dict(COUNTERS, starved_steps=0.0, prefill_cycles=0.0, prefill_cycle_s=0.0)
    assert tick_readers.device_starved_share({"counters": quiet}) == 0.0
    assert tick_readers.prefill_window_share({"counters": quiet}) == 0.0


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_manifest_holds_the_entry_and_only_its_cells_report_it(name):
    manifest = loader.Manifest()
    layer, moves, cells = ENTRIES[name]
    entry = next(m for m in manifest.raw["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": layer, "moves": moves,
                     "workloads": cells}
    reported = [c for c in manifest.cells
                if any(m.name == name for m in manifest.cell(c).per_layer)]
    assert reported == cells  # the long cell has no entry: PERF.md section 7
    assert manifest.raw["per_layer"][-4:] == [
        m for m in manifest.raw["per_layer"] if m["name"] in ENTRIES]


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return loader.Manifest(tiny.make_root(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,suffix", [("chat-tiny", "chat"), ("batch-tiny", "closed")])
def test_a_traced_tiny_run_reports_the_entries_from_the_engines_counters(
        manifest, monkeypatch, cell, suffix):
    recorded = trace_reduce.reduce(OLDER_TRACE)
    monkeypatch.setattr(trace_reduce, "reduce", lambda path, top=10: recorded)
    line = run.execute(manifest, cell, 59, 1.0, True, tiny.DEVICE)
    assert line["correct"] is True
    starved = line["metrics"][f"device_starved_share.{suffix}"]
    prefill = line["metrics"][f"prefill_window_share.{suffix}"]
    assert starved["unit"] == prefill["unit"] == "%"
    assert 0.0 <= starved["value"] <= 100.0
    assert prefill["value"] < 100.0  # of cycles timed on the CPU: no more is promised here


# --------------------------------------------------------------------- #
# the tool, on spans and gaps worked out by hand (nanoseconds)
# --------------------------------------------------------------------- #
def _span(name, start, end, **args):
    return pt.Span(name, float(start), float(end), "t", args)


US = 1e3
# the device idles 100-400 us and 5,000-5,300 us, and for 5 us at 2,000 us
GAPS = [(100 * US, 400 * US), (2000 * US, 2005 * US), (5000 * US, 5300 * US)]
SPANS = [
    _span("rlt.serve.decode_prep", 50 * US, 350 * US, rows=2, prefills=0),
    # its program starts where the 300 us gap ends: the probe said so
    _span(ta.DISPATCH, 350 * US, 450 * US, prefills=0, starved=1),
    # its program starts behind the 5 us between two queued programs: no gap
    _span(ta.DISPATCH, 1990 * US, 2050 * US, prefills=0, starved=0),
    # said dry, and its program started with the device busy: a disagreement
    _span(ta.DISPATCH, 3000 * US, 3100 * US, prefills=0, starved=1),
    # said busy, and the device idled until its program: the other one
    _span(ta.PREFILL, 5050 * US, 5150 * US, prompt_len=300, rung=512, starved=0),
    _span(ta.PREFILL, 5150 * US, 5250 * US, prompt_len=90, rung=256),  # not a first dispatch
    _span(ta.DISPATCH, 5250 * US, 5290 * US, prefills=2),
    _span(pt.SAMPLE_SYNC, 5290 * US, 5400 * US, prefills=0),
    _span(pt.SAMPLE_SYNC, 6000 * US, 6400 * US, prefills=2),
]
# a prefill enqueued before the trace began comes first and has no span
RUNS = {
    "jit_serve_prefill": [(4000 * US, 4900 * US), (5300 * US, 5800 * US), (5800 * US, 6000 * US)],
    "jit_serve_decode": [(400 * US, 900 * US), (2005 * US, 2500 * US), (3150 * US, 3600 * US),
                         (6000 * US, 6500 * US)],
}


def test_spans_are_laid_against_their_programs_runs_in_order():
    prefills = pt.named(SPANS, ta.PREFILL)
    assert ta.lay(prefills, RUNS["jit_serve_prefill"]) == list(
        zip(prefills, RUNS["jit_serve_prefill"][1:]))
    assert ta.lay(prefills, RUNS["jit_serve_prefill"][:2]) == [
        (prefills[0], RUNS["jit_serve_prefill"][1])]  # the second span's run lies behind the trace
    assert ta.lay(prefills, []) == [] and ta.lay([], RUNS["jit_serve_prefill"]) == []
    assert ta.idle_before(GAPS, 400 * US) == 300 * US
    assert ta.idle_before(GAPS, 3150 * US) == 0.0


def test_the_probe_table_counts_agreement_both_ways():
    p = ta.probe_table(SPANS, GAPS, RUNS)
    assert p["probed"] == 4
    assert (p["starved_1"], p["starved_1_and_a_gap_before_its_program"]) == (2, 1)
    assert (p["starved_0"], p["starved_0_and_no_gap_before_its_program"]) == (2, 1)
    assert p["gap_us_median_where_starved_1"] == pytest.approx(150.0)  # 300 and none
    assert p["gap_us_median_where_starved_0"] == pytest.approx(152.5)  # 5 and 300
    assert ta.probe_table([s for s in SPANS if "starved" not in s.args], GAPS, RUNS)["probed"] == 0
    assert ta.probe_table(SPANS, GAPS, {})["probed"] == 0  # no run to lay a span against


def test_the_idle_table_splits_a_phases_idle_by_its_ticks_prefills():
    idle = ta.idle_table(SPANS + [_span("rlt.serve.decode_prep", 0, 10 * US, rows=1)], GAPS)
    assert idle["rlt.serve.decode_prep prefills=0"]["idle_ms"] == pytest.approx(0.25)
    assert idle["rlt.serve.decode_prep ?"] == {
        "spans": 1, "span_ms": pytest.approx(0.01), "idle_ms": 0.0, "idle_ms_a_span": 0.0}
    d0, d2 = (idle[f"{ta.DISPATCH} prefills{c}"] for c in ("=0", ">0"))
    assert (d0["spans"], d0["idle_ms"]) == (3, pytest.approx(0.055))  # 50 us and the 5
    assert (d2["spans"], d2["idle_ms"]) == (1, pytest.approx(0.040))
    assert idle[f"{pt.SAMPLE_SYNC} prefills=0"]["idle_ms"] == pytest.approx(0.010)
    assert idle[f"{pt.SAMPLE_SYNC} prefills>0"]["idle_ms"] == 0.0
    assert not any(k.startswith(ta.PREFILL) for k in idle)  # the three phases only


def test_the_rung_table_names_each_run_by_the_rung_that_enqueued_it():
    r = ta.rung_table(SPANS, RUNS["jit_serve_prefill"])
    assert (r["runs"], r["spans"], r["runs_without_a_span"]) == (3, 2, 1)
    assert r["by_rung"] == {
        "256": {"runs": 1, "median_ms": pytest.approx(0.2), "total_ms": pytest.approx(0.2)},
        "512": {"runs": 1, "median_ms": pytest.approx(0.5), "total_ms": pytest.approx(0.5)}}
    assert ta.rung_table(SPANS, [])["by_rung"] == {}
    assert ta.rung_table([], RUNS["jit_serve_prefill"])["runs_without_a_span"] == 3


def test_the_tool_prints_its_three_tables_for_an_older_programs_trace(capsys):
    """The older recording's spans carry neither ``starved=`` nor ``rung=``:
    the tool reads what is there and raises for nothing."""
    assert ta.main([OLDER_TRACE]) == 0
    text = capsys.readouterr().out
    assert "probe: 0 dispatches carry starved=" in text
    assert "rlt.serve.decode_prep ?" in text and "rlt.serve.sample_sync prefills>0" in text
    assert "rungs: 2 runs of jit_serve_prefill, 2 rlt.serve.prefill spans, 0 runs without" in text
    assert ta.main([OLDER_TRACE, "--json"]) == 0
    a = json.loads(capsys.readouterr().out)
    assert set(a) >= {"probe", "idle", "rungs"} and a["idle_s"] < a["window_s"]
    assert ta.main([]) == 2


def test_the_recorded_engines_probe_agrees_with_the_recorded_device():
    """A tiny engine stepped by hand on the chip: its programs take
    microseconds, so every dispatch behind the first found the tick in flight
    complete, and the device's own record says the same of each: idle for a
    millisecond before the program the dispatch enqueued."""
    a = ta.accounting(TICK_TRACE)
    p = a["probe"]
    assert p["probed"] == p["starved_1"] == p["starved_1_and_a_gap_before_its_program"] == 10
    assert p["starved_0"] == 0 and p["gap_us_median_where_starved_1"] > 1000.0
    # every phase span says whose tick it is: none falls under "?"
    assert set(a["idle"]) == {f"{phase} prefills{c}" for phase in ta.PHASES for c in ("=0", ">0")}
    assert a["idle"][f"{ta.DISPATCH} prefills>0"]["spans"] == 2
    assert a["rungs"]["by_rung"]["64"]["runs"] == 2 and a["rungs"]["runs_without_a_span"] == 0
    ticks = pt.named(pt.spans(TICK_TRACE), pt.TICK)
    assert [t.args["prefills"] for t in ticks] == [1, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0, 0]
    assert [t.args["retired_prefills"] for t in ticks] == [0, 1, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0]
