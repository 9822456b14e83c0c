"""Drives a serving cell: ``InferenceEngine`` with its own loop thread under
an open or a closed loop of requests from the traffic file.

One process, one sender thread (this one); the engine's loop thread calls
``on_token`` for every sampled token and the benchmark stamps its own clock
there. Open loop: requests are submitted when they are due whether or not
earlier ones have finished, and every latency is taken from the due time.
Closed loop: ``clients`` callers, each sends its next request when its last
has finished. Load runs for ``ramp_s`` before the window opens (set-up the
traffic needs: an empty engine is no steady state) and, in the open loop,
goes on after it closes until the counted requests have finished.

After the window the engine is shut down and freed, and the plain reference
is run over a seeded sample of the finished requests (the longest among
them): prompt and served tokens teacher-forced, every served token's
reference logit held against the reference's best at its position.
"""
from __future__ import annotations

import functools
import gc
import itertools
import math
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import program, reference, stats, trace_reduce, traffic
from benchmarks.correct import Check


class _Rec:
    __slots__ = ("req", "due", "submitted", "times", "tokens", "refused", "in_window")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.submitted: Optional[float] = None
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.refused: Optional[str] = None
        self.in_window = False

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.new_tokens


def run(cell, seed: int, seconds: float, trace: bool, ctx) -> Dict[str, Any]:
    import jax

    sizes, mix, settings, family = cell.config, cell.traffic, cell.settings, cell.family
    ecfg = dict(settings["engine"])
    ramp_s = float(mix.get("ramp_s", 0.0))
    drain_s = float(settings.get("drain_s", 60.0))
    vocab = sizes["vocab_size"]
    cfg = family.program.model_config(sizes, max_seq=ecfg["max_len"], remat=False)
    ctx.mark("program_imported")
    params = family.program.engine_params(sizes, seed)
    ctx.mark("weights_dispatched")
    jax.block_until_ready(params)
    ctx.mark("weights_made")
    engine = program.make_engine(cfg, params, ecfg)
    del params
    ctx.mark("engine_built")
    engine.warmup()
    ctx.mark("programs_resolved")

    recs: Dict[str, _Rec] = {}
    finished: "queue.Queue[str]" = queue.Queue()

    def on_token(rid: str, tok: int) -> None:
        rec = recs[rid]
        rec.times.append(time.perf_counter())
        rec.tokens.append(int(tok))
        if rec.done:
            finished.put(rid)

    def submit(req, due: float) -> _Rec:
        rid = f"r{req.index}-{len(recs)}"
        rec = recs[rid] = _Rec(req, due)
        rec.submitted = time.perf_counter()
        try:
            engine.submit(req.prompt, max_new_tokens=req.new_tokens, request_id=rid,
                          eos_id=None, on_token=on_token)
        except Exception as err:  # refused, shed, closed: the request failed
            rec.refused = f"{type(err).__name__}: {err}"
        return rec

    def engine_died() -> None:
        if engine.failed is not None:
            raise RuntimeError(f"the engine's loop died: {engine.failed!r}") from engine.failed

    engine.start()
    try:
        # every shape the window uses, executed once: the longest prompt the
        # prefill program takes and a decode tick
        longest = min(int(mix["prompt_len"]["max"]), ecfg["max_prompt_len"])
        warm = traffic.Request(-1, 0.0, tuple([1] * longest), 4, False)
        w = submit(warm, time.perf_counter())
        _wait(lambda: engine_died() or w.done or w.refused, 300.0, "the warm-up request")
        if w.refused:
            raise RuntimeError(f"warm-up request refused: {w.refused}")
        recs.clear()
        ctx.mark("warm_request_done")
        base = dict(program.engine_counters(engine))

        ticks: List[tuple] = []
        if trace:
            _instrument(engine, ticks)
        tracer = trace_reduce.Tracer(os.path.join(ctx.scratch, "trace")) if trace else None

        loop = _open_loop if mix["kind"] == "open_loop" else _closed_loop
        t_open, t_close = loop(
            mix, seed, seconds, ramp_s, drain_s, vocab, submit, finished, recs, ctx, tracer)
        engine_died()
        counters = {k: v - base.get(k, 0) if k in engine.stats else v
                    for k, v in program.engine_counters(engine).items()}
    finally:
        engine.shutdown(drain=False)
    peak_bytes = ctx.memory_peak_bytes()

    counted = [r for r in recs.values() if r.in_window]
    failed = [r for r in counted if not r.done]
    ttft, itl, lags = stats.request_latencies(
        [(r.due, r.submitted, r.times) for r in counted], t_close + drain_s)
    in_window_tokens = sum(
        1 for r in recs.values() for t in r.times if t_open <= t < t_close)
    # every serving number, whatever the loop: the manifest says which of
    # them a cell is judged on, and run.py reports those
    e2e = {
        "setup_s": t_open - ctx.t0,
        "ttft_p50_ms": stats.percentile(ttft, 50.0) * 1e3 if ttft else math.inf,
        "itl_p99_ms": stats.percentile(itl, 99.0) * 1e3 if itl else math.inf,
        "serve_tokens_per_s": in_window_tokens / (t_close - t_open),
    }

    del engine
    gc.collect()
    done = [r for r in counted if r.done]
    check = served_check(cell, seed, done)
    check.require("some_finished", len(done) > 0, f"{len(done)} of {len(counted)}")
    check.note("setup_marks_s", ctx.marks)
    check.note("serving", {k: round(v, 3) for k, v in e2e.items()})
    half = len(ttft) // 2  # a backlog that grows shows as a second half that waits longer
    if half and itl:
        check.note("latency_ms", {
            "first_token_p50_by_half": [round(stats.median(h) * 1e3, 3)
                                        for h in (ttft[:half], ttft[half:])],
            "first_token_p90": round(stats.percentile(ttft, 90.0) * 1e3, 3),
            "sender_late_p99": round(stats.percentile(lags, 99.0) * 1e3, 3),
            "gap_p50": round(stats.median(itl) * 1e3, 3),
            "gap_max": round(max(itl) * 1e3, 3)})  # one stall of seconds hides from a p99
    check.note("engine", {k: counters.get(k) for k in (
        "prefills", "decode_steps", "busy_slot_steps", "pool.deferred_total",
        "pool.blocks_highwater")})

    facts: Dict[str, Any] = {
        "counters": counters, "ticks": ticks,
        "generator_lag_s": lags, "ttft_s": ttft, "itl_s": itl,
        "prompt_lens": [len(r.req.prompt) for r in recs.values() if r.submitted],
        "window_s": t_close - t_open, "tokens_in_window": in_window_tokens,
        "decode_tick_bytes": functools.partial(family.counts.decode_tick_bytes, sizes),
        "trace_path": tracer.path if tracer is not None else None,
        "finished": done,
    }
    return {
        "attempted": len(counted), "failed": len(failed), "check": check,
        "end_to_end": e2e, "facts": facts, "memory_peak_bytes": peak_bytes,
    }


def _wait(cond, timeout: float, what: str) -> None:
    end = time.perf_counter() + timeout
    while not cond():
        if time.perf_counter() > end:
            raise RuntimeError(f"{what} did not finish in {timeout}s")
        time.sleep(0.005)


def _sleep_until(t: float) -> None:
    with trace_reduce.span("bench.wait_request"):
        while True:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.05))


def _tracing(tracer, t_open: float, seconds: float):
    """Trace a few seconds from the middle of the window, from a thread of
    its own so that the sender keeps its schedule."""
    if tracer is None:
        return None
    span_s = min(3.0, seconds / 3.0)

    def work():
        _sleep = t_open + 0.4 * seconds - time.perf_counter()
        if _sleep > 0:
            time.sleep(_sleep)
        tracer.start()
        time.sleep(span_s)
        tracer.stop()

    th = threading.Thread(target=work, name="bench-tracer", daemon=True)
    th.start()
    return th


def _open_loop(mix, seed, seconds, ramp_s, drain_s, vocab, submit, finished, recs, ctx, tracer):
    plan = traffic.open_loop(mix, seed, seconds, vocab, ramp_s)
    t_open = time.perf_counter() + 0.05 + ramp_s
    opened = False
    th = None
    counted: List[_Rec] = []
    for req in plan:
        due = t_open + req.due_s
        if not opened and req.due_s >= 0.0:
            _sleep_until(t_open)
            ctx.window_opens()
            th = _tracing(tracer, t_open, seconds)
            opened = True
        if not req.counted and req.due_s > 0 and all(r.done or r.refused for r in counted):
            break  # the tail keeps the load up only while counted ones run
        if time.perf_counter() > t_open + seconds + drain_s:
            break
        _sleep_until(due)
        rec = submit(req, due)
        if req.counted:
            rec.in_window = True
            counted.append(rec)
    t_close = t_open + seconds
    _wait_done(counted, t_close + drain_s)
    ctx.window_closes()
    if th is not None:
        th.join()
    return t_open, t_close


def _closed_loop(mix, seed, seconds, ramp_s, drain_s, vocab, submit, finished, recs, ctx, tracer):
    plan = traffic.closed_loop(mix, seed, vocab)
    nxt = itertools.count()
    send = lambda: submit(plan[next(nxt) % len(plan)], time.perf_counter())
    t_open = time.perf_counter() + ramp_s
    t_close = t_open + seconds
    for _ in range(int(mix["clients"])):
        send()
    opened = False
    th = None
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            ctx.window_opens()
            t_open = time.perf_counter()
            t_close = t_open + seconds
            th = _tracing(tracer, t_open, seconds)
            opened = True
        if now >= t_close:
            break
        try:
            with trace_reduce.span("bench.wait_request"):
                finished.get(timeout=min(0.05, max(t_close - now, 0.001)))
        except queue.Empty:
            continue
        rec = send()  # the client whose request finished sends its next
        rec.in_window = opened
    # requests sent inside the window are the ones attempted; let them finish
    counted = [r for r in recs.values() if r.in_window]
    _wait_done(counted, t_close + drain_s)
    ctx.window_closes()
    if th is not None:
        th.join()
    return t_open, t_close


def _wait_done(counted: List[_Rec], deadline: float) -> None:
    with trace_reduce.span("bench.drain"):
        while time.perf_counter() < deadline:
            if all(r.done or r.refused for r in counted):
                return
            time.sleep(0.01)


def _instrument(engine, ticks: List[tuple]) -> None:
    """The traced run's wrappers: a tick's wall time with what it did, and
    host spans round its scheduling and its two enqueues."""
    step, sched_tick = engine.step, engine.scheduler.tick
    prefill, decode = engine._prefill_fn, engine._decode_fn

    def spanned(fn, name):
        def call(*a, **k):
            with trace_reduce.span(name):
                return fn(*a, **k)
        return call

    def timed_step():
        live = sum(s.pos + 1 for s in engine.pool.slots if s.occupied)
        t0 = time.perf_counter()
        with trace_reduce.span("bench.tick"):
            out = step()
        ticks.append((t0, time.perf_counter(), out["prefills"], out["decoded"], live))
        return out

    engine.step = timed_step
    engine.scheduler.tick = spanned(sched_tick, "bench.tick.schedule")
    engine._prefill_fn = spanned(prefill, "bench.tick.prefill_enqueue")
    engine._decode_fn = spanned(decode, "bench.tick.decode_enqueue")


def sample_finished(done: List[_Rec], seed: int, k: int) -> List[_Rec]:
    """k finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    order = sorted(done, key=lambda r: (len(r.req.prompt) + len(r.tokens), r.req.index))
    longest = order[-1]
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x5A])
    picks = rng.permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + [rest[i] for i in sorted(picks)]


def served_check(cell, seed: int, done: List[_Rec], quant=None) -> Check:
    settings = cell.settings["correct"]
    limits = settings["limits"]
    width = int(cell.settings["engine"]["max_len"])
    check = Check()
    sample = sample_finished(done, seed, int(settings["sample_requests"]))
    if not sample:
        return check
    t0 = time.perf_counter()
    tokens = np.zeros((len(sample), width), np.int32)
    plens, totals = [], []
    for i, r in enumerate(sample):
        seq = list(r.req.prompt) + list(r.tokens)
        tokens[i, : len(seq)] = seq
        plens.append(len(r.req.prompt))
        totals.append(len(seq))
    logits_of = cell.family.reference.teacher_forced_logits
    logits = logits_of(cell.config, seed, tokens)
    if quant is None:
        gaps = reference.served_token_gaps(logits, tokens, plens, totals)
    else:  # the control: the lower precision in the program's place
        low = logits_of(cell.config, seed, tokens, quant=quant)
        gaps = reference.first_choice_gaps(logits, low, plens, totals)
        del low
    del logits
    gc.collect()
    check.note("reference_s", time.perf_counter() - t0)
    check.note("served_tokens_compared", int(gaps.size))
    check.note("gap_max", float(gaps.max()))
    check.note("gap_p99", float(np.percentile(gaps, 99)))
    check.note("gap_mean", float(gaps.mean()))
    check.note("gap_share_over_0.25", float(np.mean(gaps > 0.25)))
    readings = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
                "gap_p99": float(np.percentile(gaps, 99))}
    for name, limit in limits.items():
        check.hold(name, readings[name], limit,
                   f"{gaps.size} served tokens of {len(sample)} requests")
    return check
