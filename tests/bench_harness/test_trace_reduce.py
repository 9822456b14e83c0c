"""trace_reduce.py on a small trace recorded on the chip
(``benchmarks/tools/record_trace.py``; a TPU v5 lite, four steps of a tiny
program with one Mosaic kernel) and on intervals worked out by hand."""
import os

import pytest

from benchmarks import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE)


def test_recorded_trace_has_one_device_and_its_ops(reduced):
    assert reduced["devices"] == 1
    names = [n for n, _ in reduced["device_ops"]]
    assert any(n.startswith("custom-call ") for n in names)  # the flash kernel
    assert any(n.startswith("fusion ") for n in names)
    assert all(len(n) < 90 for n in names)
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10


def test_recorded_trace_busy_and_idle_add_up(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(1 - reduced["busy_s"] / reduced["window_s"])
    assert sum(s for _, s in reduced["device_ops"]) == pytest.approx(reduced["busy_s"], rel=1e-6)
    gaps = sum(s for _, s in reduced["idle_gaps"])
    assert gaps == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert 0 < reduced["mosaic_s"] < reduced["busy_s"]


def test_recorded_trace_gaps_are_named_by_the_benchmarks_spans(reduced):
    assert reduced["host_spans"] >= 8  # four bench.step, four bench.wait
    assert {n for n, _ in reduced["idle_gaps"]} <= {
        "bench.step", "bench.wait", "bench.sync_probe", "no_bench_span"}


def test_short_name_of_an_hlo_instruction():
    hlo = ("%fusion.3 = bf16[512,512]{1,0:T(8,128)(2,1)} fusion(bf16[512,512]{1,0:T(8,128)(2,1)S(1)} "
           "%copy-done, bf16[512,512]{1,0} %x.1), kind=kOutput, calls=%fused_computation.1")
    assert tr.short_name(hlo) == "fusion %fusion.3 kOutput"
    call = ('%step.1 = (bf16[1,2,512,128]{3,2,1,0:T(8,128)(2,1)}, f32[1,2,512,1]{3,2,1,0}) '
            'custom-call(bf16[1,2,512,128]{3,2,1,0} %q.1), custom_call_target="tpu_custom_call"')
    assert tr.short_name(call) == "custom-call %step.1"
    assert tr.short_name("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}") \
        == "all-reduce %all-reduce.7"
    assert tr.short_name("not hlo at all") == "not hlo at all"


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.union([]) == []


def test_self_time_takes_nested_events_off_their_parent():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"), (200, 210, "d")]
    own = dict(tr.self_times(events))
    assert own == {"while": 30, "a": 20, "b": 40, "c": 10, "d": 10}
    assert sum(own.values()) == 110  # the union's length: nothing counted twice


def test_gap_label_prefers_a_working_span_to_a_waiting_one():
    spans = [(0, 100, "bench.wait_request"), (10, 60, "bench.tick"), (20, 30, "bench.tick.schedule")]
    assert tr._label(25, spans) == "bench.tick.schedule"
    assert tr._label(50, spans) == "bench.tick"
    assert tr._label(80, spans) == "bench.wait_request"
    assert tr._label(500, spans) == "no_bench_span"


def test_a_trace_without_device_ops_is_an_error(tmp_path):
    import jax

    with tr.Tracer(str(tmp_path / "t")) as tracer:
        jax.block_until_ready(jax.numpy.ones(4) + 1)
    with pytest.raises(RuntimeError, match="no 'XLA Ops' line"):
        tr.reduce(tracer.path)  # a CPU trace: never a device number


def test_span_is_a_no_op_outside_a_trace():
    with tr.span("bench.x") as got:
        assert got is None
