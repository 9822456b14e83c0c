"""The readers of the program's own spans and counters
(``benchmarks/program_trace.py`` and the ``layer_metrics`` files on it) on
events worked out by hand, and ``benchmarks/tools/program_breakdown.py`` on a
small engine trace recorded on the chip
(``benchmarks/tools/record_engine_trace.py``; a TPU v5 lite, a tiny paged
engine, a dozen ticks with two prefills)."""
import os

import pytest

from benchmarks import loader, program_trace as pt, trace_reduce
from benchmarks.tools import program_breakdown as pb

HERE = os.path.dirname(os.path.abspath(__file__))
ENGINE_TRACE = os.path.join(HERE, "data", "tiny_engine_tpu.xplane.pb")
KERNEL_TRACE = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")
KERNELS = {"paged_decode_attention", "fused_sample", "fused_argmax",
           "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rmsnorm"}
NEW_READERS = [
    "paged_attn_time_share.chat", "paged_attn_time_share.batch",
    "decode_sync_ms.chat", "decode_sync_ms.batch",
    "schedule_ms.chat", "schedule_ms.batch",
    "engine_host_ms_per_tick.chat", "engine_host_ms_per_tick.batch",
    "loop_wait_share.batch", "input_wait_ms.train",
]


def _span(name, start, end, thread="t", **args):
    return pt.Span(name, float(start), float(end), thread, args)


# a tick with a prefill and a tick without, a wait between them, and a span
# of another thread inside the first tick
TICK_A = _span(pt.TICK, 0, 1000, tick=1)
TICK_B = _span(pt.TICK, 1500, 2100, tick=2)
HAND = [
    TICK_A,
    _span(pt.SCHEDULE, 10, 30),
    _span("rlt.serve.prefill", 30, 230, prompt_len=7),
    _span("rlt.serve.decode_prep", 240, 300, rows=2),
    _span("rlt.serve.decode_dispatch", 300, 320),
    _span(pt.SAMPLE_SYNC, 330, 930, prefills=1),
    _span("rlt.serve.deliver", 930, 990, rows=2),
    _span("rlt.other_thread", 100, 200, thread="u"),
    _span("rlt.serve.wait_work", 1000, 1500),
    TICK_B,
    _span(pt.SCHEDULE, 1510, 1560),
    _span("rlt.serve.decode_prep", 1560, 1600, rows=2),
    _span("rlt.serve.decode_dispatch", 1600, 1610),
    _span(pt.SAMPLE_SYNC, 1610, 2010, prefills=0),
    _span("rlt.serve.deliver", 2010, 2090, rows=2),
]


def test_named_and_median():
    assert [s.start_ns for s in pt.named(HAND, pt.SCHEDULE)] == [10, 1510]
    assert pt.median_ms(pt.named(HAND, pt.SCHEDULE)) == pytest.approx(35e-6)
    assert pt.median_ms([]) is None


def test_decode_only_syncs_are_those_of_ticks_without_a_prefill():
    picked = pt.decode_only_syncs(HAND)
    assert [(s.start_ns, s.end_ns) for s in picked] == [(1610, 2010)]
    assert pt.median_ms(picked) == pytest.approx(400e-6)


def test_children_are_direct_and_of_the_parents_thread():
    kids = pt.children(TICK_A, HAND + [_span("rlt.grandchild", 40, 50)])
    assert [k.name.rsplit(".", 1)[1] for k in kids] == [
        "schedule", "prefill", "decode_prep", "decode_dispatch", "sample_sync", "deliver"]
    assert pt.children(_span(pt.TICK, 5000, 6000), HAND) == []


def test_cover_share_of_the_ticks():
    covered = (20 + 200 + 60 + 20 + 600 + 60) + (50 + 40 + 10 + 400 + 80)
    assert pt.cover_share(HAND) == pytest.approx(covered / (1000 + 600))
    assert pt.cover_share([]) is None


def test_innermost_is_the_shortest_span_over_the_instant():
    assert pt.innermost(150, HAND).name == "rlt.other_thread"
    assert pt.innermost(235, HAND) is TICK_A
    assert pt.innermost(1200, HAND).name == "rlt.serve.wait_work"
    assert pt.innermost(9999, HAND) is None


def test_per_step_ms_is_the_phases_total_over_the_count_of_steps():
    spans = [_span(pt.INPUT_WAIT, 0, 100), _span(pt.TRAIN_STEP, 100, 900, step=0),
             _span(pt.INPUT_WAIT, 900, 1200), _span(pt.TRAIN_STEP, 1200, 2000, step=1),
             _span(pt.INPUT_WAIT, 2000, 2050)]
    assert pt.per_step_ms(spans, pt.INPUT_WAIT) == pytest.approx(450e-6 / 2)
    assert pt.per_step_ms([_span(pt.INPUT_WAIT, 0, 100)], pt.INPUT_WAIT) is None


@pytest.mark.parametrize("short,kernel", [
    ("custom-call %paged_decode_attention.23", "paged_decode_attention"),
    ("custom-call %flash_fwd", "flash_fwd"),
    ("custom-call %jvp_flash_fwd_.1", "flash_fwd"),
    ("custom-call %transpose_jvp_flash_bwd_dkv__.4", "flash_bwd_dkv"),
    ("custom-call %closed_call.39", "closed_call"),
    ("fusion %fusion.3 kOutput", None),
])
def test_kernel_of_an_instructions_short_name(short, kernel):
    assert pt.kernel_of(short) == kernel


def test_spans_of_no_trace_are_none_to_read():
    assert pt.spans(None) == []
    assert pt.spans("/nonexistent/trace.xplane.pb") == []


@pytest.fixture(scope="module")
def engine_reduced():
    return trace_reduce.reduce(ENGINE_TRACE)


@pytest.mark.parametrize("kernel", ["paged_decode_attention", "rmsnorm", "flash_fwd", "fused_argmax"])
def test_kernel_share_is_the_kernels_own_time_over_all_events(engine_reduced, broken_down, kernel):
    """On the recorded engine trace: what the tool reads from all events,
    whether or not the kernel is among the ten largest operations."""
    share = pt.kernel_share_percent({"trace": engine_reduced}, kernel)
    assert share == pytest.approx(100.0 * broken_down["kernels"][kernel] / broken_down["busy_s"])
    assert share > 0.0
    among_the_ten = any(pt.kernel_of(name) == kernel for name, _ in engine_reduced["device_ops"])
    assert among_the_ten == (kernel == "paged_decode_attention")
    assert len(engine_reduced["device_ops"]) == 10  # the breakdown stays the ten largest


def test_counter_readers():
    c = {"ticks": 100.0, "tick_s": 10.0, "sync_wait_s": 9.0, "loop_wait_s": 2.5}
    assert pt.engine_host_ms_per_tick({"counters": c}) == pytest.approx(10.0)
    assert pt.loop_wait_share_percent({"counters": c}) == pytest.approx(20.0)
    assert pt.engine_host_ms_per_tick({"counters": dict(c, ticks=0.0)}) is None


@pytest.fixture(scope="module")
def manifest():
    return loader.Manifest()


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("facts", [
    {},
    {"counters": {"prefills": 3.0, "decode_steps": 9.0}, "trace_path": None, "trace": None},
], ids=["empty", "parent"])
def test_new_readers_find_nothing_in_facts_of_a_program_without_them(manifest, metric, facts):
    """No trace, no spans, none of the new counters (the parent commit's
    facts): every reader returns None and none raises."""
    assert manifest.reader(metric)(facts) is None


@pytest.mark.parametrize("metric", NEW_READERS[:2])
def test_paged_share_reads_the_recorded_kernel_and_nothing_where_there_is_none(
        manifest, engine_reduced, metric):
    read = manifest.reader(metric)
    assert read({"trace": engine_reduced}) == pytest.approx(100.0 * 96.859e-6 / 377.077e-6)
    flash_only = trace_reduce.reduce(KERNEL_TRACE)  # a flash kernel, no paged one
    assert flash_only["kernels"] and read({"trace": flash_only}) is None  # never 0.0


def test_span_readers_read_the_recorded_engine_trace(manifest):
    facts = {"trace_path": ENGINE_TRACE}
    sync = manifest.reader("decode_sync_ms.chat")(facts)
    sched = manifest.reader("schedule_ms.batch")(facts)
    assert 0.0 < sched < sync < 50.0
    assert manifest.reader("input_wait_ms.train")(facts) is None  # no train step in it


# ---- the tool on the recorded engine trace ------------------------------- #
@pytest.fixture(scope="module")
def broken_down():
    return pb.breakdown(ENGINE_TRACE)


def test_tool_names_every_mosaic_call_and_their_times_sum_to_mosaic_s(broken_down):
    reduced = trace_reduce.reduce(ENGINE_TRACE)
    assert set(broken_down["kernels"]) <= KERNELS
    assert {"paged_decode_attention", "rmsnorm", "fused_argmax"} <= set(broken_down["kernels"])
    # what else trace_reduce counts as Mosaic is the compiler's own custom
    # calls (AllocateBuffer), next to nothing here
    rest = sum(broken_down["mosaic_other"].values())
    assert all(k.startswith("custom-call %custom-call") for k in broken_down["mosaic_other"])
    assert sum(broken_down["kernels"].values()) + rest == pytest.approx(reduced["mosaic_s"], rel=1e-9)
    assert rest < 0.001 * reduced["mosaic_s"]
    assert broken_down["mosaic_s"] == pytest.approx(reduced["mosaic_s"], rel=1e-9)
    assert broken_down["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-9)


def test_tool_finds_both_serving_programs(broken_down):
    modules = broken_down["modules"]
    assert modules["jit_serve_prefill"]["count"] == 2
    assert modules["jit_serve_decode"]["count"] == 12
    assert not any("wrapped" in name for name in modules)
    assert 0 < modules["jit_serve_decode"]["median_ms"] < modules["jit_serve_prefill"]["median_ms"] * 50


def test_tool_gaps_sum_to_window_less_busy_and_carry_the_programs_names(broken_down):
    idle = broken_down["window_s"] - broken_down["busy_s"]
    assert sum(broken_down["gaps"].values()) == pytest.approx(idle, rel=1e-6)
    named = {k for k in broken_down["gaps"] if k != pb.NO_SPAN}
    assert named and all(k.startswith("rlt.serve.") for k in named)


def test_tool_ticks_are_covered_by_their_children(broken_down):
    cover = broken_down["tick_cover"]
    assert cover["ticks"] >= 10  # those lying whole inside the device window
    assert cover["share"] >= 0.95
    assert cover["share"] == pytest.approx(sum(cover["children"].values()))
    assert set(cover["children"]) == {
        "rlt.serve.schedule", "rlt.serve.prefill", "rlt.serve.decode_prep",
        "rlt.serve.decode_dispatch", "rlt.serve.sample_sync", "rlt.serve.deliver"}


def test_tool_moves_spans_by_the_margin_host_spans_applies():
    moved = [s for s in trace_reduce.host_spans(ENGINE_TRACE) if s[2] == trace_reduce.SYNC_SPAN]
    shift = pb.clock_shift_ns(ENGINE_TRACE)
    starts = sorted(float(e.start_ns) for plane in trace_reduce._load(ENGINE_TRACE).planes
                    if not trace_reduce.DEVICE_PLANE.match(plane.name)
                    for line in plane.lines for e in line.events
                    if e.name == trace_reduce.SYNC_SPAN)
    assert [s - shift for s in starts] == pytest.approx([m[0] for m in moved])
    assert pb.clock_shift_ns(KERNEL_TRACE) != 0.0  # that trace has its probes too


def test_tool_renders_and_runs_from_the_command_line(broken_down, capsys):
    text = pb.render(broken_down)
    assert "jit_serve_decode" in text and "paged_decode_attention" in text
    assert pb.main([ENGINE_TRACE, "--json"]) == 0
    assert '"tick_cover"' in capsys.readouterr().out
    assert pb.main([]) == 2
