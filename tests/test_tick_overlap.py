"""The engine dispatches decode tick n+1 before it reads tick n's tokens
(``serving/engine.py``, "the order of a tick"): the host's part of a tick runs
under the device's. What has to hold, all on the CPU at a tiny size: every
request's tokens are the ones sequential ``generate()`` gives, whatever the
arrivals, the sharing and the stops; no row is ever stepped past its length;
a stop learnt at the read drops the one step the row rode since and nothing
else; the order really is dispatch-then-read (and read-then-dispatch for a
speculating engine, which needs the tokens); ``step()`` reports the tick it
retired; every end leaves nothing in flight; and the benchmark's readers of
``tick_overlap_share.*`` read the engine's counters.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader, program
from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.serving import EngineClosed, EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.paged_kv import TRASH_BLOCK
from ray_lightning_tpu.serving.resilience import RequestJournal

pytestmark = pytest.mark.serving

# the two entries on the engine's counter: one a judged end-to-end metric
ENTRIES = {
    "chat": (["serve-dense-chat"], "itl_p99_ms"),
    "closed": (["serve-moe-batch", "serve-mla-moe-reason", "serve-swa-moe-doc"],
               "serve_tokens_per_s"),
}


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def model():
    # float32 so greedy argmax ties cannot fall differently between the
    # batched serving path and the sequential generate() reference
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    return init_params(jax.random.key(0), cfg), cfg


def _reference(model, prompt, n_new, eos_id=None):
    params, cfg = model
    out = generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=n_new
    )
    tokens = [int(t) for t in np.asarray(out)[0, len(prompt):]]
    if eos_id in tokens:
        tokens = tokens[: tokens.index(eos_id) + 1]
    return tokens


def _engine(model, **kw):
    params, cfg = model
    kw = dict(dict(num_slots=2, max_prompt_len=12, max_len=32, block_size=4), **kw)
    return InferenceEngine(params, cfg, EngineConfig(**kw))


def _requests(model, n, seed=0, prefix=()):
    """``n`` (prompt, new tokens) of mixed lengths behind ``prefix``."""
    vocab = model[1].vocab_size
    rng = np.random.default_rng(seed)
    return [
        (
            list(prefix)
            + [int(t) for t in rng.integers(1, vocab, rng.integers(2, 5))],
            int(rng.integers(3, 9)),
        )
        for _ in range(n)
    ]


class _Noting:
    """A decode program's first output that notes when the host reads it."""

    def __init__(self, array, note):
        self.array, self._note = array, note

    def __array__(self, dtype=None, copy=None):
        self._note()
        return np.asarray(self.array)

    def is_ready(self):  # the engine's starvation probe asks; no read
        return self.array.is_ready()


class _Recorder:
    """A stand-in for the engine's decode step in whichever program carries
    it (``engine._decode_fn``, and ``engine._prefill_fn`` where a tick that
    admits a prompt is ONE program): the real program, with every dispatch
    and every read of a result noted in order, and what each dispatch fed
    every occupied slot."""

    def __init__(self, engine, fail_at=None):
        self.engine, self.fn, self.fail_at = engine, engine._decode_fn, fail_at
        self.with_prompt = engine._prefill_fn
        self.events, self.fed, self.uploads = [], [], []
        engine._decode_fn = self
        if engine._fused_rung:
            engine._prefill_fn = lambda params, cache, row, where, *rows: self(
                params, cache, *rows, prompt=(row, where))

    def __call__(self, params, cache, token, pos, tables, key, *prev, prompt=()):
        n = len(self.fed) + 1
        if n == self.fail_at:
            raise RuntimeError("boom")
        token_h, pos_h = np.asarray(token), np.asarray(pos)
        tables_h = {k: np.array(t) for k, t in tables.items()}
        self.uploads.append((tables, tables_h))
        self.fed.append([
            (s.index, s.prompt_len, s.max_new_tokens, int(pos_h[s.index]),
             token_h[s.index].tolist(),
             all((t[s.index] == TRASH_BLOCK).all() for t in tables_h.values()))
            for s in self.engine.pool.slots if s.occupied
        ])
        # the output of the program before, as the engine kept it
        prev = tuple(getattr(p, "array", p) for p in prev)
        fn = self.with_prompt if prompt else self.fn
        sampled, cache = fn(params, cache, *prompt, token, pos, tables, key, *prev)
        self.events.append(("dispatch", n))
        return _Noting(sampled, lambda: self.events.append(("read", n))), cache


# --------------------------------------------------------------------- #
# the tokens are generate()'s, whatever the traffic
# --------------------------------------------------------------------- #
def _staggered(model, engine):
    """Eight mixed requests through two slots, three before the first tick,
    the rest while the first wave decodes: every slot is reused."""
    reqs = _requests(model, 8)
    done = [engine.submit(p, max_new_tokens=n) for p, n in reqs[:3]]
    for _ in range(4):
        engine.step()
    done += [engine.submit(p, max_new_tokens=n) for p, n in reqs[3:]]
    engine.run_until_idle()
    assert engine.pool.recycled_total == 8
    return reqs, done, {}


def _shared_prefix(model, engine):
    """Six requests behind one prefix of two whole blocks, prefix cache on:
    the blocks are shared, and a slot that waits for its last token rides a
    tick as a padding row whose write must not reach them."""
    reqs = _requests(model, 6, seed=1, prefix=[7, 3, 9, 4, 2, 8, 6, 5])
    done = [engine.submit(p, max_new_tokens=n) for p, n in reqs]
    engine.run_until_idle()
    assert engine.pool.stats()["prefix_hits_total"] >= 8
    return reqs, done, {}


def _eos(model, engine):
    """Every request stops on the token generate() gives it third."""
    reqs = [(p, 8) for p, _ in _requests(model, 5, seed=2)]
    eos = {i: _reference(model, p, n)[2] for i, (p, n) in enumerate(reqs)}
    done = [engine.submit(p, max_new_tokens=n, eos_id=eos[i])
            for i, (p, n) in enumerate(reqs)]
    engine.run_until_idle()
    return reqs, done, eos


def _threaded(model, engine):
    """The loop thread's path, submits from this one, then a drain."""
    reqs = _requests(model, 6, seed=3)
    engine.start()
    done = []
    for p, n in reqs:
        done.append(engine.submit(p, max_new_tokens=n))
        time.sleep(0.005)
    for c in done:
        c.result(timeout=120)
    engine.drain(timeout=60)
    return reqs, done, {}


@pytest.mark.parametrize(
    "traffic", [_staggered, _shared_prefix, _eos, _threaded],
    ids=lambda f: f.__name__.strip("_"),
)
def test_every_requests_tokens_are_generates(model, traffic):
    engine = _engine(model)
    reqs, done, eos = traffic(model, engine)
    for i, ((prompt, n_new), comp) in enumerate(zip(reqs, done)):
        want = _reference(model, prompt, n_new, eos.get(i))
        assert comp.result(timeout=1) == want
        assert comp.finish_reason == ("eos" if want[-1] == eos.get(i) else "length")
    s = engine.stats
    assert engine._inflight is None and engine.pool.occupancy == 0
    assert s["tokens_out"] == sum(len(c.tokens) for c in done)
    # a row step gave a token or was dropped, and only a stop by value drops
    assert s["busy_slot_steps"] == s["tokens_out"] + s["dropped_row_steps"]
    assert (s["dropped_row_steps"] > 0) == bool(eos)
    assert s["overlapped_steps"] > 0.6 * s["decode_steps"]
    assert engine.compile_stats() == {"prefill_compiles": 1, "decode_compiles": 1}


def test_sampling_at_a_temperature_takes_one_key_a_dispatch_in_order(model):
    """Two engines of one seed under one order of arrivals give one stream."""
    streams = []
    for _ in range(2):
        engine = _engine(model, temperature=0.8, seed=11)
        done = [engine.submit(p, max_new_tokens=n) for p, n in _requests(model, 4)]
        engine.run_until_idle()
        streams.append([c.result(timeout=1) for c in done])
    assert streams[0] == streams[1]


# --------------------------------------------------------------------- #
# stops: by length at dispatch, by value at the read
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("new_tokens", [1, 5, 29], ids=lambda n: f"new{n}")
def test_no_row_is_stepped_past_its_length(model, new_tokens):
    """Three prompts of three tokens: no dispatch feeds a row a position
    past ``prompt + max_new_tokens - 2`` (the last step's; 29 new tokens end
    at ``max_len - 2``), and a slot that is occupied and not stepped rides
    with a table that is all trash block."""
    engine = _engine(model)
    rec = _Recorder(engine)
    done = [engine.submit([3 + i, 5, 8], max_new_tokens=new_tokens) for i in range(3)]
    engine.run_until_idle()
    stepped = 0
    for rows in rec.fed:
        for _index, prompt_len, max_new, pos, token, trashed in rows:
            if trashed:  # spent: it waits for the read of its last step
                assert (pos, token) == (0, 0)
                continue
            stepped += 1
            assert prompt_len - 1 <= pos <= prompt_len + max_new - 2
            assert pos <= engine.engine_config.max_len - 2
    assert stepped == 3 * new_tokens == engine.stats["busy_slot_steps"]
    assert [len(c.result(timeout=1)) for c in done] == [new_tokens] * 3
    assert engine.stats["dropped_row_steps"] == 0


def test_a_stop_by_eos_drops_the_one_step_the_row_rode_since(model):
    """The token of the step a row rode after its eos is never delivered,
    never journaled and never counted; ``dropped_row_steps`` counts it. The
    last tick in flight holds nothing but that row: it is retired too."""
    prompt = [5, 6, 7]
    want = _reference(model, prompt, 8)
    eos = want[3]
    assert eos not in want[:3]
    engine = _engine(model, num_slots=1)
    journal = RequestJournal()
    entry = journal.open(tuple(prompt), 8, eos_id=eos)
    rid, _, budget = journal.begin_attempt(entry, replica=0)
    streamed = []
    guard = journal.stream_guard(entry, rid)

    def on_token(r, tok):
        streamed.append(tok)
        guard(r, tok)

    comp = engine.submit(prompt, max_new_tokens=budget, request_id=rid,
                         eos_id=eos, on_token=on_token)
    steps = 0
    while engine.scheduler.has_work():
        engine.step()
        steps += 1
    # the slot is free, and the step dispatched before the eos was read is
    # still in flight: nothing but the loop's own check says so
    assert engine._inflight is not None and engine.pool.occupancy == 0
    engine.run_until_idle()
    assert engine._inflight is None
    assert comp.finish_reason == "eos"
    assert comp.result(timeout=1) == streamed == entry.delivered == want[:4]
    s = engine.stats
    assert (s["tokens_out"], s["dropped_row_steps"]) == (4, 1)
    assert (s["decode_steps"], s["busy_slot_steps"], s["ticks"]) == (5, 5, steps + 1)
    # the next tenant of the slot starts clean
    again = engine.submit(prompt, max_new_tokens=3, request_id=rid)
    engine.run_until_idle()
    assert again.result(timeout=1) == want[:3]


def test_a_callback_that_shuts_the_engine_down_ends_its_request_there(model):
    """shutdown(drain=False) from inside ``on_token``: the request ends on
    the token that called it, the tick dispatched meanwhile is never read,
    and nothing streams after."""
    engine = _engine(model)
    streamed = []

    def kill_switch(rid, tok):
        streamed.append(tok)
        engine.shutdown(drain=False)

    comp = engine.submit([2, 3, 5], max_new_tokens=8, on_token=kill_switch)
    other = engine.submit([4, 4, 9], max_new_tokens=8)
    engine.step()
    engine.step()  # dispatches the second step, then reads the first
    assert comp.done and other.done and engine._inflight is None
    assert comp.finish_reason == other.finish_reason == "error"
    assert isinstance(comp.error, EngineClosed)
    assert streamed == comp.tokens == _reference(model, [2, 3, 5], 1)
    # the tick being read stepped the one row (a prefill a tick); the unread
    # one stepped both
    assert engine.stats["dropped_row_steps"] == 2
    with pytest.raises(EngineClosed):
        engine.step()


# --------------------------------------------------------------------- #
# the order itself
# --------------------------------------------------------------------- #
def test_dispatch_n_plus_1_precedes_the_read_of_n(model):
    engine = _engine(model)
    rec = _Recorder(engine)
    done = [engine.submit(p, max_new_tokens=n) for p, n in _requests(model, 4)]
    engine.run_until_idle()
    at = {e: i for i, e in enumerate(rec.events)}
    n = len(rec.fed)
    assert sorted(at) == sorted(
        [("dispatch", i) for i in range(1, n + 1)]
        + [("read", i) for i in range(1, n + 1)])
    for i in range(1, n):
        assert at[("dispatch", i)] < at[("dispatch", i + 1)] < at[("read", i)]
        assert at[("read", i)] < at[("read", i + 1)]
    s = engine.stats
    assert (s["decode_steps"], s["overlapped_steps"]) == (n, n - 1)
    # what a dispatch uploaded stays what it was: the host's table mirrors
    # changed under it (growth, releases) while its program was unread, and
    # an upload may alias the memory it was made from
    for given, as_dispatched in rec.uploads:
        for kind, table in given.items():
            np.testing.assert_array_equal(np.asarray(table), as_dispatched[kind])
    # a row that goes on takes its token from the device: the host's is -1
    first = {}
    for rows in rec.fed:
        for index, prompt_len, _, pos, token, trashed in rows:
            if not trashed:
                assert (token == -1) == (pos > prompt_len - 1)
                first.setdefault((index, prompt_len), token)
    assert all(t >= 0 for t in first.values())
    for (p, k), comp in zip(_requests(model, 4), done):
        assert comp.result(timeout=1) == _reference(model, p, k)


def test_a_speculating_engine_reads_n_before_it_dispatches_n_plus_1(model):
    """``ngram_propose`` reads the tokens, so ``speculate_k=2`` retires each
    tick before the next dispatch: depth zero, tokens and counts as ever."""
    engine = _engine(model, speculate_k=2)
    rec = _Recorder(engine)
    reqs = [([5, 9, 5, 9, 5, 9], 8), ([1, 2, 3], 6), ([4, 4, 4, 4], 7)]
    done = [engine.submit(p, max_new_tokens=n) for p, n in reqs]
    outs = []
    while engine.scheduler.has_work():
        outs.append(engine.step())
    n = len(rec.fed)
    assert rec.events == [(kind, i) for i in range(1, n + 1)
                          for kind in ("dispatch", "read")]
    s = engine.stats
    assert (s["overlapped_steps"], s["dropped_row_steps"]) == (0, 0)
    assert engine._inflight is None and s["ticks"] == s["decode_steps"] == n
    assert outs[0]["prefills"] == 1 and outs[0]["decoded"] == 1  # its own tick
    assert s["accepted_tokens"] == s["tokens_out"] == sum(k for _, k in reqs)
    for (p, k), comp in zip(reqs, done):
        assert comp.result(timeout=1) == _reference(model, p, k)


def test_step_and_the_sync_span_describe_the_tick_that_was_retired(model):
    """What ``step()`` returns, and the ``prefills`` of ``sample_sync``, are
    of the tick whose program that call waited for: the benchmark files a
    call's wall time under them."""
    rec = obs.enable()
    engine = _engine(model)
    a = engine.submit([1, 2, 3], max_new_tokens=4)
    outs = [engine.step()]  # enqueues a's prefill and its first step
    b = engine.submit([4, 5, 6, 7], max_new_tokens=2)
    while engine.scheduler.has_work():
        outs.append(engine.step())
    assert outs == [
        {"prefills": 0, "decoded": 0, "completed": []},  # nothing to retire
        {"prefills": 1, "decoded": 1, "completed": []},  # a's prefill tick
        {"prefills": 1, "decoded": 2, "completed": []},  # b's, a beside it
        {"prefills": 0, "decoded": 2, "completed": [b.request_id]},
        {"prefills": 0, "decoded": 1, "completed": [a.request_id]},
    ]
    events = [e for e in rec.drain() if e[1].startswith("rlt.serve.")]
    syncs = [e[5]["prefills"] for e in events if e[1] == "rlt.serve.sample_sync"]
    assert syncs == [o["prefills"] for o in outs[1:]]
    delivered = [e[5]["rows"] for e in events if e[1] == "rlt.serve.deliver"]
    assert delivered == [o["decoded"] for o in outs[1:]]
    assert engine.stats["prefills"] == 2 and engine.stats["completed"] == 2


# --------------------------------------------------------------------- #
# the ends
# --------------------------------------------------------------------- #
def _run_until_idle(engine, done):
    engine.run_until_idle()
    return "length"


def _drain(engine, done):
    engine.start()
    engine.drain(timeout=120)
    assert not engine._thread.is_alive()
    return "length"


def _drain_unthreaded(engine, done):
    engine.step()
    engine.drain()
    return "length"


def _shutdown_without_drain(engine, done):
    engine.step()
    engine.step()
    assert engine._inflight is not None
    engine.shutdown(drain=False)
    return "error"


def _a_program_that_raises(engine, done):
    _Recorder(engine, fail_at=3)
    engine.start()
    for c in done:
        with pytest.raises(RuntimeError, match="boom"):
            c.result(timeout=120)
    engine._thread.join(30)
    assert isinstance(engine.failed, RuntimeError) and not engine.alive
    return "error"


@pytest.mark.parametrize(
    "end", [_run_until_idle, _drain, _drain_unthreaded,
            _shutdown_without_drain, _a_program_that_raises],
    ids=lambda f: f.__name__.strip("_"),
)
def test_every_end_leaves_no_tick_in_flight_and_no_completion_open(model, end):
    engine = _engine(model)
    done = [engine.submit(p, max_new_tokens=n) for p, n in _requests(model, 5)]
    reason = end(engine, done)
    assert engine._inflight is None
    assert all(c.done and c.finish_reason == reason for c in done)
    assert engine.pool.occupancy == 0 and not engine.scheduler.has_work()
    if reason == "length":
        for (p, n), c in zip(_requests(model, 5), done):
            assert c.result(timeout=1) == _reference(model, p, n)


def test_a_raise_at_dispatch_leaves_the_tick_before_it_to_be_read(model):
    """Stepped by hand, a dispatch that raises moves nothing: the tick in
    flight is read by the next call, and the tokens are generate()'s."""
    engine = _engine(model)
    rec = _Recorder(engine, fail_at=3)
    comp = engine.submit([1, 2, 3, 4], max_new_tokens=6)
    engine.step()
    engine.step()
    with pytest.raises(RuntimeError, match="boom"):
        engine.step()
    assert engine._inflight is not None and engine.failed is None
    rec.fail_at = None
    engine.run_until_idle()
    assert comp.result(timeout=1) == _reference(model, [1, 2, 3, 4], 6)
    assert engine._inflight is None


# --------------------------------------------------------------------- #
# the benchmark's readers of the two counters
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("suffix", sorted(ENTRIES))
def test_tick_overlap_share_reads_the_engines_counters(model, suffix):
    manifest = loader.Manifest()
    read = manifest.reader(f"tick_overlap_share.{suffix}")
    engine = _engine(model)
    for p, n in _requests(model, 4):
        engine.submit(p, max_new_tokens=n)
    engine.run_until_idle()
    counters = program.engine_counters(engine)
    steps, over = counters["decode_steps"], counters["overlapped_steps"]
    assert 0 < over < steps
    assert read({"counters": counters}) == pytest.approx(100.0 * over / steps)
    # a parent has no such counter: no reading, never 0
    parent = {k: v for k, v in counters.items() if k != "overlapped_steps"}
    assert read({"counters": parent}) is None
    assert read({"counters": dict(counters, decode_steps=0)}) is None
    assert read({}) is None
    cells, moves = ENTRIES[suffix]
    entry = next(m for m in manifest.raw["per_layer"]
                 if m["name"] == f"tick_overlap_share.{suffix}")
    assert entry == {
        "name": f"tick_overlap_share.{suffix}", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "entry points", "moves": moves,
        "workloads": cells,
    }
    # every cell the entry lists reports it, and no other cell does
    reported = [c for c in manifest.cells
                if any(m.name == entry["name"] for m in manifest.cell(c).per_layer)]
    assert reported == cells
