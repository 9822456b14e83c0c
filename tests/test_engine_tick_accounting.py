"""What the engine says of every tick (``serving/engine.py``, "what ``stats``
says of the ticks"): the starvation probe's two counters, the four tick-cycle
counters, and the arguments on the phase spans that say whose tick a span
belongs to. All on the CPU at a tiny size, with the readiness of the array in
flight stubbed where a case needs it one way or the other.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.models.generation import LlamaServing
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

pytestmark = pytest.mark.serving

NEW = ("starved_steps", "starved_s", "decode_cycles", "decode_cycle_s",
       "prefill_cycles", "prefill_cycle_s")


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    return init_params(jax.random.key(0), cfg), cfg


@pytest.fixture(params=["one_program", "two_programs"])
def fused(request, monkeypatch):
    """Whether a tick that admits a prompt is one program (the family's
    prefill rides its decode step) or the two it was: the family's method
    taken away, which is all an engine looks at."""
    if request.param == "two_programs":
        monkeypatch.delattr(LlamaServing, "prefill_decode_paged")
    return request.param == "one_program"


def _engine(model, **kw):
    params, cfg = model
    kw = dict(dict(num_slots=2, max_prompt_len=12, max_len=32, block_size=4), **kw)
    return InferenceEngine(params, cfg, EngineConfig(**kw))


class _Output:
    """A decode program's first output that answers ``is_ready()`` as told."""

    def __init__(self, array, ready):
        self.array, self._ready = array, ready

    def is_ready(self):
        return self._ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.array)


def _stub_ready(engine, ready):
    """Every decode step's output says ``ready`` when asked, whichever
    program carried the step: ``_decode_fn``, or ``_prefill_fn`` where a tick
    that admits a prompt is one program (its last argument is then the
    output before it, as the decode program's is)."""

    def stubbed(fn):
        def call(params, cache, *rest):
            if engine._speculate_k == 0:
                rest = rest[:-1] + (getattr(rest[-1], "array", rest[-1]),)
            sampled, cache = fn(params, cache, *rest)
            return _Output(sampled, ready), cache

        return call

    engine._decode_fn = stubbed(engine._decode_fn)
    if engine._fused_rung:
        engine._prefill_fn = stubbed(engine._prefill_fn)


def _run(engine, requests=(([1, 2, 3], 6), ([4, 5, 6, 7], 4))):
    """Two requests, the second admitted while the first decodes: two ticks
    with a prefill, the rest without."""
    done = [engine.submit(requests[0][0], max_new_tokens=requests[0][1])]
    engine.step()
    engine.step()
    done.append(engine.submit(requests[1][0], max_new_tokens=requests[1][1]))
    engine.run_until_idle()
    assert all(len(c.result(timeout=1)) == n for c, (_, n) in zip(done, requests))
    return engine.stats


def test_a_fresh_engine_has_the_counters_at_zero(model):
    stats = _engine(model).stats
    assert {k: stats[k] for k in NEW} == dict.fromkeys(NEW, 0)


def test_cycles_are_at_most_the_decode_programs_and_inside_the_calls_time(model, fused):
    """A tick whose prompt went out with its rows is one decode step and one
    cycle, and the cycle is a prefill's."""
    s = _run(_engine(model))
    n = s["decode_steps"]
    assert n == 6  # the first request's six steps; the second rides four of them
    assert s["fused_prefill_steps"] == (s["prefills"] if fused else 0)
    assert (s["prefills"], s["overlapped_steps"]) == (2, n - 1)
    # the first retire of a run has no sync before it to start a cycle from
    assert s["decode_cycles"] + s["prefill_cycles"] == n - 1
    # the first tick's prefill is that retire's; the second request's counts
    assert (s["prefill_cycles"], s["decode_cycles"]) == (1, n - 2)
    assert s["decode_cycle_s"] > 0.0 and s["prefill_cycle_s"] > 0.0
    assert s["decode_cycle_s"] + s["prefill_cycle_s"] <= s["tick_s"] + s["loop_wait_s"]


@pytest.mark.parametrize("case", ["ready", "unready", "speculating"])
def test_the_probe_counts_a_dispatch_whose_tick_in_flight_was_complete(model, case, fused):
    """Stubbed ready, every overlapped dispatch found the device with
    nothing queued (a tick of one program counts once, as one of two does);
    stubbed unready, none did; a speculating engine has nothing in flight to
    ask, and runs two programs a tick whatever the family provides."""
    engine = _engine(model, speculate_k=2 if case == "speculating" else 0)
    assert (engine._fused_rung == 12) == (fused and case != "speculating")
    _stub_ready(engine, case != "unready")
    s = _run(engine, requests=(([5, 9, 5, 9, 5], 6), ([4, 4, 4, 4], 4)))
    if case == "ready":
        # the run's first call has nothing in flight; every other call that
        # dispatches does, the one whose first dispatch is a prefill too
        assert s["starved_steps"] == s["overlapped_steps"] == s["decode_steps"] - 1
        assert s["starved_s"] > 0.0
    else:
        assert (s["starved_steps"], s["starved_s"]) == (0, 0.0)
    if case == "speculating":
        assert s["overlapped_steps"] == 0 and engine._inflight is None


def test_the_first_call_and_a_call_that_dispatches_nothing_count_nothing(model):
    engine = _engine(model)
    _stub_ready(engine, True)
    engine.submit([1, 2, 3], max_new_tokens=2)
    engine.step()  # nothing in flight
    assert engine.stats["starved_steps"] == 0
    engine.step()  # in flight, complete, and a second step to dispatch
    assert engine.stats["starved_steps"] == 1
    engine.step()  # retires the last tick and dispatches nothing
    assert engine.stats["starved_steps"] == 1 and engine._inflight is None


def test_the_probes_own_array_answers_without_a_wait(model):
    """Unstubbed: the array in flight is JAX's and the probe asks it; once
    the device is given time to finish, the next dispatch finds it ready."""
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=4)
    engine.step()
    jax.block_until_ready(engine._inflight.sampled)
    engine.step()
    assert engine.stats["starved_steps"] == 1
    assert 0.0 < engine.stats["starved_s"] < engine.stats["tick_s"]
    engine.run_until_idle()


def test_a_cycle_across_a_wait_for_work_is_dropped(model):
    engine = _engine(model)
    engine.submit([1, 2, 3], max_new_tokens=3)
    engine.run_until_idle()  # compiled, and a sync to start a cycle from
    base = dict(engine.stats)
    engine.start()
    while engine._cycle_from is not None:  # until the loop waits for work
        time.sleep(0.01)
    try:
        engine.submit([1, 2, 3, 4], max_new_tokens=5).result(timeout=60)
        time.sleep(0.3)  # the loop waits for work
        engine.submit([4, 5, 6], max_new_tokens=5).result(timeout=60)
    finally:
        engine.drain()
    grew = {k: engine.stats[k] - base[k] for k in engine.stats}
    assert grew["loop_wait_s"] >= 0.25
    # of each request's five retires the first ends no cycle: the wait before
    # it is no tick's time
    assert grew["decode_steps"] == 10
    assert grew["decode_cycles"] + grew["prefill_cycles"] == 8
    assert grew["decode_cycle_s"] + grew["prefill_cycle_s"] < grew["tick_s"]


def test_a_tick_of_prefills_alone_starts_no_cycle(model):
    """A prefill-role replica parks every slot it fills: its ticks dispatch
    no decode program and wait for nothing, so they end no cycle."""
    engine = _engine(model, role="prefill")
    engine.submit([1, 2, 3], max_new_tokens=3)
    engine.step()
    engine.step()
    s = engine.stats
    assert s["prefills"] == 1 and s["decode_steps"] == 0
    assert (s["decode_cycles"], s["prefill_cycles"]) == (0, 0)
    assert engine._cycle_from is None


def test_the_spans_say_whose_tick_they_are(model, fused):
    """Through the ring recorder: ``prefills=`` on the tick, the prepare and
    the dispatch is what the call enqueued, on the sync what the retired
    tick had; ``starved=`` sits on a call's first dispatch where a tick was
    in flight, and nowhere else: the prefill's, or the one dispatch of a tick
    whose prompt and rows are one program, which says ``fused=1``."""
    rec = obs.enable()
    engine = _engine(model)
    _stub_ready(engine, True)
    _run(engine)
    calls, call = [], None
    for e in (e for e in rec.drain() if e[1].startswith("rlt.serve.")):
        # children close before their tick: a tick's event ends its call
        call = call or []
        call.append((e[1].split(".")[-1], e[5] or {}))
        if e[1] == "rlt.serve.tick":
            calls.append(dict(call))
            assert [n for n, _ in call].count("prefill") <= 1
            call = None
    enqueued = [c["tick"]["prefills"] for c in calls]
    assert enqueued == [1, 0, 1, 0, 0, 0, 0]
    assert [c["tick"]["retired_prefills"] for c in calls] == [0] + enqueued[:-1]
    assert [c["tick"]["fused"] for c in calls] == [n * fused for n in enqueued]
    assert engine.stats["fused_prefill_steps"] == 2 * fused
    for i, c in enumerate(calls):
        for phase in ("decode_prep", "decode_dispatch"):
            if phase in c:
                assert c[phase]["prefills"] == enqueued[i]
        if "sample_sync" in c:
            assert c["sample_sync"]["prefills"] == c["tick"]["retired_prefills"]
        first = "prefill" if enqueued[i] and not fused else "decode_dispatch"
        if i == 0 or first not in c:  # nothing in flight, or no dispatch
            assert all("starved" not in args for args in c.values())
        else:
            assert c[first]["starved"] == 1
            assert ["starved" in c[p] for p in c if p != first] == [False] * (len(c) - 1)
    assert engine.stats["starved_steps"] == 5
