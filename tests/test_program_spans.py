"""The measurement the program carries inside itself: jitted programs under
their labels, ``rlt.*`` phase spans on the profiler's clock (and in the ring
when telemetry is on), and the engine's loop-time counters. All on the CPU: a
CPU trace holds the ``TraceAnnotation``s as a chip's does.
"""
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import program_trace as pt
from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.runtime import compile_cache as cc
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

from tests.utils import BoringModel, get_trainer

SERVE_PHASES = [
    "rlt.serve.schedule", "rlt.serve.prefill", "rlt.serve.decode_prep",
    "rlt.serve.decode_dispatch", "rlt.serve.sample_sync", "rlt.serve.deliver",
]


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def model():
    # wide enough that a tick on the CPU takes milliseconds, so that the
    # microseconds between two spans are a small share of it (twice as wide
    # since a call no longer waits for the program it dispatched: the call
    # got shorter and the code between its spans did not)
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), dim=512, ffn_dim=2048, n_layers=4,
        dtype=jnp.float32,
    )
    return init_params(jax.random.key(0), cfg), cfg


def _engine(model, **kw):
    params, cfg = model
    kw = dict(dict(num_slots=4, max_prompt_len=16, max_len=32), **kw)
    return InferenceEngine(params, cfg, EngineConfig(**kw))


def _traced(tmp_path, work):
    """Run ``work()`` under ``jax.profiler``; the trace's ``rlt.*`` spans."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    return pt.spans(found[0])


# --------------------------------------------------------------------- #
# (b) programs are jitted under their labels; the names are constants
# --------------------------------------------------------------------- #
def _lowered_texts(engine):
    return {name: fn.lower(*args).as_text()
            for name, fn, args in engine._program_specs()}


def test_engine_programs_lower_to_modules_named_by_their_labels(model):
    texts = _lowered_texts(_engine(model))
    assert texts["serve_prefill"].startswith("module @jit_serve_prefill ")
    assert texts["serve_decode"].startswith("module @jit_serve_decode ")


def test_two_builds_of_one_engine_lower_to_the_same_text(model):
    """The names are constants of the call site, so the lowered text, which
    the compile cache's keys are made of, does not move from run to run."""
    first, second = _lowered_texts(_engine(model)), _lowered_texts(_engine(model))
    assert first == second
    assert "jit_wrapped" not in first["serve_decode"]


def test_jit_program_names_the_module_and_keeps_jit_options():
    def fn(a, b):
        return a + b, b

    prog = cc.jit_program(fn, "my_label", donate_argnums=(0,))
    a = jnp.ones((4,), jnp.float32)
    text = prog.lower(a, a).as_text()
    assert text.startswith("module @jit_my_label ")
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text
    assert fn.__name__ == "fn"  # the caller's function keeps its own name
    out, _ = prog(a + 1, a)
    np.testing.assert_array_equal(np.asarray(out), 3.0)


def test_trainer_step_lowers_to_jit_train_step(tmp_root):
    trainer = get_trainer(tmp_root, max_epochs=1, limit_train_batches=2)
    build, seen = trainer._build_train_step, {}

    def spying_build():
        step = build()

        def spy(*args):
            # lowered before the call: the call donates its first arguments
            seen.setdefault("text", step.lower(*args).as_text())
            return step(*args)
        return spy

    trainer._build_train_step = spying_build
    trainer.fit(BoringModel())
    assert seen["text"].startswith("module @jit_train_step ")


# --------------------------------------------------------------------- #
# (c) spans of a tick nest, in order, and cover it. A call of step()
# dispatches its own decode program and then retires the one before it, so
# the phases keep their order inside a call, and the sync and the deliver
# of a call are the LAST call's tick's: the first call of a run has none,
# the call after the last dispatch has nothing else.
# --------------------------------------------------------------------- #
def test_tick_spans_nest_in_order_and_cover_the_tick(model, tmp_path):
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=2)
    engine.run_until_idle()  # both programs compiled before the trace

    def work():
        engine.submit([1, 2, 3, 4], max_new_tokens=8)
        engine.step()
        engine.step()
        engine.submit([5, 6, 7], max_new_tokens=8)
        engine.run_until_idle()

    spans = _traced(tmp_path, work)
    ticks = pt.named(spans, pt.TICK)
    assert len(ticks) >= 8
    numbers = [t.args["tick"] for t in ticks]
    assert numbers == list(range(numbers[0], numbers[0] + len(ticks)))
    order = {name: i for i, name in enumerate(SERVE_PHASES)}
    with_prefill = 0
    enqueued = 0  # prefills the call before this one enqueued
    for i, tick in enumerate(ticks):
        kids = pt.children(tick, spans)
        names = [k.name for k in kids]
        assert set(names) <= set(SERVE_PHASES)
        assert [order[n] for n in names] == sorted(order[n] for n in names)
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
        assert names[0] == "rlt.serve.schedule"
        if i == 0:  # nothing was in flight: this call retires nothing
            assert names[-1] == "rlt.serve.decode_dispatch"
        else:
            assert names[-1] == "rlt.serve.deliver"
            sync = next(k for k in kids if k.name == pt.SAMPLE_SYNC)
            # the sync is the retired tick's: it names that tick's prefills
            assert sync.args["prefills"] == enqueued
            with_prefill += sync.args["prefills"]
        enqueued = names.count("rlt.serve.prefill")
    # the last call retires the last tick and dispatches nothing
    assert [k.name for k in pt.children(ticks[-1], spans)] == [
        "rlt.serve.schedule", "rlt.serve.sample_sync", "rlt.serve.deliver"]
    assert with_prefill == 2
    # one decode program a sync, one sync a call but the first
    assert len(pt.named(spans, pt.SAMPLE_SYNC)) == len(ticks) - 1 == len(
        pt.named(spans, "rlt.serve.decode_dispatch"))
    assert len(pt.decode_only_syncs(spans)) == len(ticks) - 3
    first = pt.named(spans, "rlt.serve.prefill")[0]
    assert (first.args["prompt_len"], first.args["rung"]) == (4, 16)
    assert pt.cover_share(spans) >= 0.95


def test_a_step_that_raises_half_way_leaves_no_span_open(model, tmp_path):
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=2)
    engine.run_until_idle()
    calls = {"n": 0}

    def failing(program):
        def call(*args):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("boom")
            return program(*args)

        return call

    # the first call's prompt and its row go out as one program, the second
    # call's decode program is the one that raises
    assert engine._fused_rung
    engine._prefill_fn = failing(engine._prefill_fn)
    engine._decode_fn = failing(engine._decode_fn)
    base = engine.stats["ticks"]

    def work():
        engine.submit([1, 2, 3, 4], max_new_tokens=8)
        engine.step()
        with pytest.raises(RuntimeError, match="boom"):
            engine.step()
        engine.step()

    spans = _traced(tmp_path, work)
    ticks = pt.named(spans, pt.TICK)
    assert len(ticks) == 3  # the tick that raised closed its span too
    failed = [k.name for k in pt.children(ticks[1], spans)]
    assert failed[-1] == "rlt.serve.decode_dispatch"  # closed by the raise
    after = [k.name for k in pt.children(ticks[2], spans)]
    assert after[0] == "rlt.serve.schedule" and after[-1] == "rlt.serve.deliver"
    # nothing of the third tick sits inside a span left open by the second
    assert all(s.start_ns >= ticks[1].end_ns for s in [ticks[2]] + pt.children(ticks[2], spans))
    assert engine.stats["ticks"] == base + 3  # a tick that raises counts


def test_train_loop_spans_every_step(tmp_root, tmp_path):
    trainer = get_trainer(tmp_root, max_epochs=1, limit_train_batches=4,
                          limit_val_batches=0)
    spans = _traced(tmp_path, lambda: trainer.fit(BoringModel()))
    steps = pt.named(spans, pt.TRAIN_STEP)
    assert [s.args["step"] for s in steps] == [0, 1, 2, 3]
    waits = pt.named(spans, pt.INPUT_WAIT)
    assert len(waits) >= 4  # one pull a step, and the pull that found the end
    hooks = [s.args["hook"] for s in pt.named(spans, "rlt.train.callbacks")]
    assert hooks == ["batch_start", "batch_end"] * 4
    assert pt.per_step_ms(spans, pt.INPUT_WAIT) >= 0.0
    for step, wait in zip(steps, waits):
        assert wait.end_ns <= step.start_ns  # the batch is pulled before its step


# --------------------------------------------------------------------- #
# (d) loop-time counters: always on, sums only
# --------------------------------------------------------------------- #
def test_counters_after_n_ticks(model):
    engine = _engine(model)
    assert engine.stats["ticks"] == 0 and engine.stats["tick_s"] == 0.0
    engine.submit([1, 2, 3], max_new_tokens=6)
    t0 = time.perf_counter()
    n = 0
    while engine.scheduler.has_work():
        engine.step()
        n += 1
    wall = time.perf_counter() - t0
    s = engine.stats
    # six calls dispatch the six steps, each but the first retiring the step
    # before it; the seventh retires the last
    assert s["ticks"] == n == 7
    assert (s["decode_steps"], s["overlapped_steps"]) == (6, 5)
    assert wall >= s["tick_s"] >= s["sync_wait_s"] >= 0.0
    assert s["tick_s"] > 0.9 * wall  # the loop above does nothing but tick
    assert s["loop_wait_s"] == 0.0  # no loop thread ran


def test_loop_thread_time_is_ticks_plus_waits(model):
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=2)
    engine.run_until_idle()
    base = dict(engine.stats)
    t0 = time.perf_counter()
    engine.start()
    done = engine.submit([1, 2, 3, 4], max_new_tokens=12)
    done.result(timeout=60)
    time.sleep(0.3)  # the loop waits for work
    engine.drain()
    wall = time.perf_counter() - t0
    grew = {k: engine.stats[k] - base[k] for k in ("ticks", "tick_s", "loop_wait_s")}
    assert grew["ticks"] == 13  # twelve dispatches and the last retire
    assert grew["loop_wait_s"] >= 0.25
    assert grew["tick_s"] + grew["loop_wait_s"] == pytest.approx(wall, rel=0.05)


# --------------------------------------------------------------------- #
# (e) the ring gets the same names; off, span() is still the no-op
# --------------------------------------------------------------------- #
def test_ring_gets_the_same_names_when_telemetry_is_on(model):
    rec = obs.enable()
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run_until_idle()
    events = [e for e in rec.drain() if e[1].startswith("rlt.")]
    names = [e[1] for e in events]
    assert names.count("rlt.serve.tick") == 4  # three steps, the last retire
    assert set(names) == set(SERVE_PHASES) | {"rlt.serve.tick"}
    assert "serve_prefill" not in names and "serve_decode" not in names
    prefill = next(e for e in events if e[1] == "rlt.serve.prefill")
    assert prefill[0] == "X" and prefill[5] == {"prompt_len": 3, "rung": 16}
    tick = next(e for e in events if e[1] == "rlt.serve.tick")
    assert tick[5]["tick"] >= 1 and tick[3] > 0.0


def test_phase_span_off_is_an_inert_annotation_and_span_the_singleton():
    assert not obs.enabled()
    assert obs.span("rlt.serve.tick") is obs.NOOP_SPAN
    s = obs.phase_span("rlt.serve.tick", tick=1)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass
    assert obs.get_recorder() is None


def test_phase_span_never_imports_jax(monkeypatch):
    """A launcher's parent stays off JAX: without it in ``sys.modules`` the
    span is the ring's (or the no-op) and nothing is imported."""
    import sys

    from ray_lightning_tpu.observability import trace

    saved = sys.modules["jax"]
    monkeypatch.delitem(sys.modules, "jax")
    try:
        assert trace.phase_span("rlt.x", a=1) is trace.NOOP_SPAN
        rec = trace.enable()
        with trace.phase_span("rlt.x", a=1):
            pass
        assert "jax" not in sys.modules
    finally:
        sys.modules["jax"] = saved
    assert [(e[1], e[5]) for e in rec.drain()] == [("rlt.x", {"a": 1})]


# --------------------------------------------------------------------- #
# the prefill's duration in the request trace ends where the host first
# knows it is done: the tick's sampling sync
# --------------------------------------------------------------------- #
def test_prefill_duration_ends_at_the_ticks_sync(model):
    obs.enable()
    engine = _engine(model)
    done = engine.submit([1, 2, 3, 4], max_new_tokens=2)
    t0 = time.perf_counter()
    engine.step()  # enqueues the prefill and the tick's decode program
    trace = next(s.trace for s in engine.pool.slots if s.occupied)
    assert trace.prefill_s is None and engine.stats["sync_wait_s"] == 0.0
    engine.step()  # retires that tick: its sync is where the host knows
    tick_s = time.perf_counter() - t0
    assert trace.prefill_synced is True
    # enqueue to the end of the sync: all of the tick's wait is inside it
    assert tick_s >= trace.prefill_s >= engine.stats["sync_wait_s"] > 0.0
    engine.run_until_idle()
    record = next(r for r in engine.drain_request_records()
                  if r["request_id"] == done.request_id)
    assert sum(record["ttft_components"].values()) == pytest.approx(
        record["ttft_s"], rel=1e-3)
    assert record["ttft_components"]["prefill"] >= record["prefill_s"]


def test_a_tick_without_a_sync_keeps_the_enqueue_time_and_says_so(model):
    rec = obs.enable()
    engine = _engine(model, role="prefill")
    engine.submit([1, 2, 3, 4], max_new_tokens=2)
    out = engine.step()  # the fresh prefill is parked: nothing decodes
    assert out == {"prefills": 1, "decoded": 0, "completed": []}
    assert engine.stats["sync_wait_s"] == 0.0
    trace = next(s.trace for s in engine.pool.slots if s.occupied)
    assert trace.prefill_synced is False and trace.prefill_s > 0.0
    names = [e[1] for e in rec.drain() if e[1].startswith("rlt.")]
    assert "rlt.serve.prefill" in names and "rlt.serve.sample_sync" not in names
    trace.emit_spans(rec, "length")
    prefill = next(e for e in rec.drain() if e[1] == "req/prefill")
    assert prefill[5]["synced"] is False
