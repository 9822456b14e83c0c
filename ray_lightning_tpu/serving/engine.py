"""Single-replica continuous-batching inference engine.

Two compiled programs, the first at a few lengths:

- ``_prefill_fn`` — one jitted prefill of one prompt, [1, rung] (at the
  first rung of an engine whose model's prefill rides its decode step, the
  prompt AND the decode rows: "a tick that admits a prompt", below), where
  the rung is the shortest of a short LADDER of lengths that holds the
  prompt (:func:`prefill_rungs`: doubling from 256 up to
  ``max_prompt_len``, the longest prompt admitted and always the last
  rung; an engine whose ``max_prompt_len`` is 256 or less has the one).
  The ladder follows from ``max_prompt_len`` and the block size alone and
  the rung from the prompt's length: there is nothing to set. Prompts are
  right-padded to the rung; the pad positions write garbage (k, v) at
  positions >= the real length, and whole blocks past the prompt go to
  the trash block, but the per-row validity mask of the model's paged
  decode step only ever exposes positions <= the row's current position,
  and decode overwrites each garbage position before advancing past it —
  so padding is free correctness-wise and a handful of shapes is all the
  program ever sees. Causality means the REAL positions' cache entries
  are what an unpadded prefill, or one padded to any other rung, writes.
- ``_decode_fn`` — one jitted paged decode step + sampler over every
  slot ([num_slots] tokens at [num_slots] positions; [num_slots, K] under
  speculation). Free slots ride along with dummy inputs (their outputs
  are ignored and their writes land in the trash block).

One KV layout: requests hold fixed-size BLOCKS from one shared pool
(``paged_kv.PagedKVPool``, a tree of device leaves whose names and block
shapes the model states: K and V per head, or one latent row a position
for latent attention). Prefill writes through a per-request
write-redirect table (shared-prefix blocks land in trash, written
exactly once by the first request), decode reads each row's pages
through the fixed-shape [num_slots, max_blocks] block table (the
model's paged decode step), and block tables GROW on demand as rows
cross block boundaries — a host-side value mutation, never a shape
change, which is the zero-steady-state-recompile contract. Admission is
by block availability (scheduler back-pressure), and common prompt
prefixes are refcount-shared across requests, which is what lifts
resident concurrency past ``num_slots × max_len`` HBM.

The pool is updated IN PLACE: it is donated to both programs, prefill
scatters whole blocks into it and the decode steps carry it through their
layer loop writing one row a slot a layer, so a tick holds one pool and a
program moves the rows it changes, not the pool. The arrays under
``pool.cache`` are therefore dead from each dispatch to the rebind that
follows it; ``_pool_lock`` covers that span, for the one reader on another
thread (``export_shipment``).

After warmup (one prefill compile a rung + one decode compile) the jit
caches are flat: admission, recycling, mixed prompt lengths, EOS — none
of it brings a shape warmup has not resolved. ``compile_stats()`` exposes
the cache sizes so
tests can assert zero steady-state recompiles.

The first sampled token of a request comes from the first DECODE step
after its prefill (re-running the last prompt token at position P-1 —
idempotent cache write, same logits as prefill's last position), which
is what lets prefill skip its logits head and keeps "first token" and
"every other token" the same compiled program.

A tick that admits a prompt. Run as two programs back to back, such a tick
reads every weight twice: once for the prompt's rung of positions, once for
the decode rows. Where the model's serving object provides
``prefill_decode_paged`` (the Llama family does: the prompt's positions ride
the decode rows' matrix products through one pass of the layers, its blocks
written through the write table before the rows read), the engine does not
speculate and its role is ``"both"``, ``_prefill_fn`` at the FIRST rung
(``_fused_rung``) IS that step: under the same name ``serve_prefill``, in
that rung's program's place, taking the prompt row and its write tables and
then ``_decode_fn``'s own arguments, returning what ``_decode_fn`` returns.
A tick whose prompt runs at that rung pads it and builds its write tables in
``rlt.serve.prefill``, prepares the decode rows as ever and dispatches that
ONE program in ``rlt.serve.decode_dispatch``; everything downstream (the
tick in flight, the retire, the counters, the prefill traces) sees a decode
step that happened to carry a prompt. ``stats["fused_prefill_steps"]``
counts such ticks and ``rlt.serve.tick`` says ``fused=1``. A prompt of that
rung that is not its tick's last (``max_prefills_per_tick > 1``) and the
warm-up go through the same program with no row stepped: every slot rides
as the trash block's padding row a free slot always is. The first rung
alone: below it a prefill is bound by the weights' bytes, which is what the
one program saves, most prompts run there, and each fused program costs
set-up the trace of both its halves; the longer rungs keep the prompt's own
program and their ticks the two. So does every other engine (another family,
a speculating engine, a prefill or decode replica): the choice is made once,
from what the code can see, and nothing sets it.

The order of a tick. The next decode step's tokens are the array the last
one returned, its positions are ``pos + 1`` whatever those tokens were, and
the block a position is written to follows from the position alone. So a
decode program is DISPATCHED BEFORE THE ONE BEFORE IT IS READ: one call of
``step()`` schedules, enqueues the admitted prefills, prepares and
dispatches decode step n+1 (its rows take their tokens from step n's output
on the device; a row prefilled or imported since takes the host's), and only
then RETIRES step n: waits for its tokens, delivers them, finishes requests,
releases slots. The host's part of a tick runs under the device's, and the
device goes from one decode program to the next without waiting. What needs
a position advances at dispatch (``Slot.pos``, the block made writable, the
position counters); what needs the token's value happens at retire, in
order. A stop by length is known at dispatch: a row whose step in flight is
its last is not dispatched again, and no row is ever stepped past
``max_new_tokens``. A stop learnt at retire (``eos_id``, an expiry, a
callback that shuts the engine down) finds the row one step further on: that
step's token is DROPPED (never delivered, never counted in ``tokens_out``;
``stats["dropped_row_steps"]``), and its K/V write landed in a block the
request still owned at dispatch, which device order puts before any later
owner's writes (the prefix cache registers only whole prompt blocks before
the first position decode writes, so never a block such a step can reach).
A slot comes free at retire, so the scheduler sees it one tick later. A
speculating engine (``speculate_k > 0``) proposes from the tokens' values,
so its dispatch needs them on the host: the same routine retires each step
before the next is dispatched, as ever. What ``step()`` returns describes
the tick it RETIRED, the one whose program it waited for.

What ``stats`` says of the ticks, over every call and with no sync of its
own (plain sums: a reader subtracts the value before its window):
- ``ticks`` / ``tick_s`` / ``sync_wait_s`` / ``loop_wait_s``: the calls of
  ``step()``, their wall time, the part of it waiting for the sampled tokens,
  and the loop thread's time between calls.
- ``decode_steps`` / ``overlapped_steps``: decode programs dispatched, and
  those dispatched while the one before was unread.
- ``prefills`` / ``fused_prefill_steps``: prompts prefilled, and the ticks
  whose prompt and decode rows went out as one program (a decode step among
  ``decode_steps`` like any other; the prefills at the first rung where a
  tick admits one prompt, 0 for an engine of two programs a tick).
- ``starved_steps`` / ``starved_s``: the device STARVES when the tick in
  flight is complete before the next program is enqueued: nothing is queued
  and it idles until the dispatch lands. A call asks the array in flight
  whether it is complete (no transfer, no wait) at its entry, behind the
  schedule, and just before and at the return of its first dispatch, a
  prefill's or the decode program's; a call that found it so counts one
  step, and the time from the first such instant to the return of that
  dispatch: a lower bound on the dry time. A call with nothing in flight (a
  run's first, a speculating engine's, one after a time without requests)
  counts nothing.
- ``decode_cycles`` / ``decode_cycle_s`` / ``prefill_cycles`` /
  ``prefill_cycle_s``: a CYCLE runs from the end of one retire's sync to the
  end of the next, and goes to the ``prefill_*`` pair where the retired tick
  enqueued prefills ahead of its decode program, else to ``decode_*``; one
  that holds a wait for work is dropped. While the device does not starve a
  cycle is the device's time for that tick's programs, so the sums say what
  share of a window prefill takes, over every tick and with no profiler.

Threading: ``submit`` is callable from any thread; ``start()`` spawns
the loop thread, or call ``step()`` yourself for deterministic
single-threaded driving (tests). ``drain()`` stops admission and
finishes in-flight work; ``shutdown(drain=False)`` fails queued work
immediately.
"""
from __future__ import annotations

import bisect
import itertools
import os
import threading

from ray_lightning_tpu.analysis.sanitizer import rlt_condition, rlt_lock
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_lightning_tpu import observability as _obs
from ray_lightning_tpu.observability import metrics as _metrics
from ray_lightning_tpu.observability import reqtrace as _reqtrace
from ray_lightning_tpu.runtime import compile_cache as _compile_cache
from ray_lightning_tpu.runtime import faults as _faults
from ray_lightning_tpu.serving import migration as _migration
from ray_lightning_tpu.serving.paged_kv import (
    TRASH_BLOCK,
    PagedKVPool,
    Slot,
    STATE,
    stated_windows,
    states_kind,
)
from ray_lightning_tpu.serving.resilience import RequestShed, ShedPolicy
from ray_lightning_tpu.serving.scheduler import (
    ContinuousBatchScheduler,
    Request,
    RequestQueueFull,
)
from ray_lightning_tpu.serving.speculative import ngram_propose

__all__ = [
    "Completion",
    "EngineConfig",
    "EngineClosed",
    "InferenceEngine",
    "RequestQueueFull",
    "RequestShed",
    "prefill_rungs",
]

# TTFT/ITL land in seconds; the default step/IO bounds start at 100 µs
# which is too coarse-grained at the fast end for tiny-model decode
LATENCY_BOUNDS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)

# per-slot-tick accepted-token counts (1 = no speculation win, K = every
# proposal accepted); integer-ish bounds up to the largest sane k
ACCEPTED_BOUNDS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)


# The shortest prefill length worth a program of its own: the chip's ridge
# (a v5e's 197 TFLOP/s over 819 GB/s = 240 operations a byte) rounded up to a
# power of two. With bf16 weights a prefill of one prompt is bound by the
# weights' bytes below about that many tokens, so a shorter shape saves
# nothing.
FIRST_PREFILL_RUNG = 256


def prefill_rungs(max_prompt_len: int, block_size: int) -> Tuple[int, ...]:
    """The padded lengths prefill runs at, ascending: doubling from
    ``FIRST_PREFILL_RUNG``, each rounded up to whole blocks, as far as they
    stay under ``max_prompt_len``, which is always the last rung as it is
    given. 2048 -> (256, 512, 1024, 2048); 1536 -> (256, 512, 1024, 1536);
    256 or less -> the one length. A prompt runs at the first rung that
    holds it (:func:`rung_for`)."""
    rungs = []
    n = FIRST_PREFILL_RUNG
    while n < max_prompt_len:
        whole = -(-n // block_size) * block_size
        if whole < max_prompt_len and whole not in rungs:
            rungs.append(whole)
        n *= 2
    return (*rungs, max_prompt_len)


def rung_for(rungs: Sequence[int], prompt_len: int) -> int:
    """The shortest rung that holds ``prompt_len`` positions."""
    return rungs[bisect.bisect_left(rungs, prompt_len)]


class EngineClosed(RuntimeError):
    """submit() after drain/shutdown."""


@dataclass(frozen=True)
class EngineConfig:
    """Serving knobs (see docs/serving.md for the tuning guide).

    ``max_prompt_len`` is the longest prompt admitted — longer ones are
    rejected at submit — and the last rung of the prefill lengths
    (:func:`prefill_rungs`). ``max_len`` is each slot's
    cache length: ``prompt_len + max_new_tokens <= max_len`` per
    request. Sampling knobs are ENGINE-level (static in the compiled
    sampler); per-request temperatures would be a recompile per value.

    The KV pool is block-paged (``serving/paged_kv.py``): ``block_size``
    defaults to env ``RLT_SERVE_BLOCK_SIZE`` or 16 and must divide
    ``max_len``; ``num_kv_blocks`` sizes the block pool (default: every
    slot at ``max_len``, ``num_slots * max_len / block_size`` + trash);
    ``prefix_cache`` toggles shared-prefix matching. ``kv_layout`` has
    one value, ``"paged"``: the benchmark's data files still pass it.

    Resilience knobs: ``shed_watermark`` is the queue-fill fraction at
    which priority >= 1 requests are shed (priority 0 never sheds;
    see ``serving/resilience.py``). ``head_skip_limit`` /
    ``head_aging_ticks`` bound the scheduler's skip-ahead window behind
    a block-deferred FIFO head (0 = strict FIFO, the default).

    ``speculate_k`` (default env ``RLT_SERVE_SPECULATE_K`` or 0): 0 =
    one token per tick (today's path, byte-identical); k >= 2 = self-
    speculative decode — each tick feeds every slot's pending token plus
    up to k-1 n-gram-proposed continuations through one
    ``decode_step_verify`` call and delivers the greedily-accepted
    prefix as a multi-token burst. Requires greedy sampling
    (temperature 0): greedy acceptance is what keeps the output
    token-identical to the unspeculated engine and to ``generate()``.

    ``role`` (disaggregated serving, see ``serving/migration.py``):
    ``"both"`` (default — the colocated engine, byte-identical to the
    pre-disaggregation behavior), ``"prefill"`` (prefill requests and
    park the result for KV shipment to a decode replica; retains full
    decode capability as the migration fallback), or ``"decode"``
    (additionally accepts shipped KV via ``import_shipment``).
    """

    num_slots: int = 4
    max_prompt_len: int = 64
    max_len: int = 256
    max_queue: int = 256
    max_prefills_per_tick: int = 1
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None  # default per-request eos
    seed: int = 0
    kv_layout: str = "paged"
    block_size: Optional[int] = None  # None -> RLT_SERVE_BLOCK_SIZE or 16
    num_kv_blocks: Optional[int] = None
    prefix_cache: bool = True
    shed_watermark: float = 0.9
    head_skip_limit: int = 0
    head_aging_ticks: int = 16
    speculate_k: Optional[int] = None  # None -> RLT_SERVE_SPECULATE_K or 0
    role: str = "both"  # "both" | "prefill" | "decode" (disaggregation)

    def resolved_block_size(self) -> int:
        if self.block_size is not None:
            return int(self.block_size)
        try:
            return int(os.environ.get("RLT_SERVE_BLOCK_SIZE", "16"))
        except ValueError:
            return 16

    def resolved_speculate_k(self) -> int:
        if self.speculate_k is not None:
            return int(self.speculate_k)
        try:
            return int(os.environ.get("RLT_SERVE_SPECULATE_K", "0"))
        except ValueError:
            return 0

    def validate(self) -> None:
        if self.max_prompt_len < 1:
            raise ValueError("max_prompt_len must be >= 1")
        if self.max_prompt_len >= self.max_len:
            raise ValueError(
                f"max_prompt_len ({self.max_prompt_len}) must be < max_len "
                f"({self.max_len}): a full-length prompt still needs room "
                "for at least one generated token"
            )
        if not 0.0 < self.shed_watermark:
            raise ValueError(
                f"shed_watermark must be > 0, got {self.shed_watermark}"
            )
        if self.kv_layout != "paged":
            raise ValueError(
                f"kv_layout={self.kv_layout!r}: the KV pool is paged (the "
                "slot layout was removed in PR 28)"
            )
        bs = self.resolved_block_size()
        if bs < 1:
            raise ValueError(f"block_size must be >= 1, got {bs}")
        if self.max_len % bs != 0:
            raise ValueError(
                f"max_len ({self.max_len}) must be a multiple of "
                f"block_size ({bs})"
            )
        k = self.resolved_speculate_k()
        if k < 0 or k == 1:
            raise ValueError(
                f"speculate_k must be 0 (off) or >= 2, got {k}: k = 1 "
                "verifies only the pending token, which is the ordinary "
                "decode step with extra overhead"
            )
        if k > 0 and self.temperature > 0.0:
            raise ValueError(
                f"speculate_k={k} requires greedy sampling "
                f"(temperature 0, got {self.temperature}): greedy "
                "verification is what makes the accepted stream "
                "token-identical to the unspeculated engine"
            )
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got "
                f"{self.role!r}"
            )


class Completion:
    """Caller-facing handle: collected tokens + a done event.

    ``tokens`` excludes the prompt. ``finish_reason`` is one of
    ``"eos"`` / ``"length"`` / ``"error"`` / ``"cancelled"``. Streaming:
    pass ``on_token`` at submit — called as ``on_token(request_id,
    token)`` from the engine loop thread for every sampled token.
    """

    __slots__ = (
        "request_id", "tokens", "finish_reason", "error",
        "ttft_s", "tenant", "_done", "submitted_at",
    )

    def __init__(self, request_id: str, tenant: Optional[str] = None):
        self.request_id = request_id
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.ttft_s: Optional[float] = None
        self.tenant = tenant
        self.submitted_at = time.perf_counter()
        self._done = threading.Event()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until finished; returns the generated tokens (no prompt)."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id!r} not finished within {timeout}s"
            )
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    def _finish(self, reason: str, error: Optional[BaseException] = None):
        # idempotent: a completion finished by the step loop must not be
        # re-finished (and its reason clobbered) by a concurrent
        # shutdown(drain=False) racing the same request
        if self._done.is_set():
            return
        self.finish_reason = reason
        self.error = error
        self._done.set()


class _ImportTicket:
    """One cross-thread KV-import request, executed by the engine loop.

    The fleet's migration pump hands the ticket over and waits on
    ``event``; the engine loop thread runs the admit (verify → fault
    point → acquire → install → resume) so every pool/allocator mutation
    stays serialized with prefill/decode — the pump never touches pool
    state directly. ``abandoned`` is set by a pump that gave up waiting
    (admit timeout): the engine skips the ticket instead of admitting a
    request whose migration already moved on."""

    __slots__ = (
        "shipment", "request_id", "max_new_tokens", "eos_id", "on_token",
        "deadline_ms", "priority", "retries", "completion", "error",
        "abandoned", "event",
    )

    def __init__(
        self, shipment, request_id, max_new_tokens, eos_id, on_token,
        deadline_ms, priority, retries,
    ):
        self.shipment = shipment
        self.request_id = request_id
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.on_token = on_token
        self.deadline_ms = deadline_ms
        self.priority = int(priority)
        self.retries = int(retries)
        self.completion: Optional[Completion] = None
        self.error: Optional[BaseException] = None
        self.abandoned = False
        self.event = threading.Event()


@dataclass
class _Tick:
    """What one call of ``step()`` enqueued, until it is retired: the decode
    program's output still on the device (the sampled tokens, the model's
    counters behind them; ``None`` where the tick dispatched no decode
    program), the rows it stepped, each with the admission it was stepped
    for, a speculating tick's proposals by slot, and the prefills enqueued
    ahead of it (how many, and their traces)."""

    sampled: Any
    rows: List[Tuple[Slot, int]]
    proposals: Dict[int, List[int]]
    prefills: int
    prefill_traces: List[tuple]


class InferenceEngine:
    """Continuous batching over one model replica (one process, one set
    of params). See the module docstring for the two-program design."""

    def __init__(
        self,
        params,
        cfg,
        engine_config: Optional[EngineConfig] = None,
        replica_index: Optional[int] = None,
    ):
        import jax

        ecfg = engine_config or EngineConfig()
        ecfg.validate()
        self.cfg = cfg
        self.engine_config = ecfg
        self.params = params
        # the one place the engine learns its model from: prefill, the decode
        # steps and the pool's leaves are the config object's to state
        self._model = cfg.serving()
        self._refuse_unserved(ecfg)
        self.pool = PagedKVPool(
            cfg,
            ecfg.num_slots,
            ecfg.max_len,
            block_size=ecfg.resolved_block_size(),
            num_blocks=ecfg.num_kv_blocks,
            prefix_cache=ecfg.prefix_cache,
        )
        self.scheduler = ContinuousBatchScheduler(
            self.pool,
            max_queue=ecfg.max_queue,
            max_prefills_per_tick=ecfg.max_prefills_per_tick,
            head_skip_limit=ecfg.head_skip_limit,
            head_aging_ticks=ecfg.head_aging_ticks,
        )
        self.scheduler.on_evict = self._on_queue_expired
        # serving fault-injection identity (RLT_FAULT replica<N> specs);
        # None = not a fleet member, serve faults never fire
        self.replica_index = replica_index
        self.shed_policy = ShedPolicy(queue_watermark=ecfg.shed_watermark)
        # multi-tenant QoS: None until configure_tenants installs a
        # registry; every tenant-aware branch below gates on it so the
        # single-tenant path is untouched
        self._tenancy: Optional[Any] = None
        self._tenancy_admission = False
        # optional SLOMonitor whose serving breach couples into shedding
        self.slo_monitor: Optional[Any] = None
        # set by _fail_all: the error that killed the engine loop — the
        # journal pump reads it (via `alive`) to trigger relaunch
        self.failed: Optional[BaseException] = None
        self._ticks = 0
        # the tick whose decode program is dispatched and not yet read
        self._inflight: Optional[_Tick] = None
        # where the next tick cycle starts: the end of the last retire's
        # sync; None where that cycle would hold a time without work
        self._cycle_from: Optional[float] = None
        self._admit_seq = 0
        # request_id -> remaining-token budget armed by a drop-stream fault
        self._drop_stream: Dict[str, int] = {}
        # request_id -> full token history (prompt + generated), the
        # prompt-lookup corpus for the self-speculation proposer; only
        # populated when speculate_k > 0 so k=0 stays allocation-free
        self._history: Dict[str, List[int]] = {}
        self._completions: Dict[str, Completion] = {}
        self._on_token: Dict[str, Callable[[str, int], Any]] = {}
        self._rng = jax.random.key(ecfg.seed)
        self._req_counter = itertools.count()
        self._state_lock = rlt_lock("serving.engine.InferenceEngine._state_lock")
        self._work = rlt_condition(
            "serving.engine.InferenceEngine._work", self._state_lock
        )
        # The pool is DONATED to every program that updates it: the arrays
        # under self.pool.cache are dead from the dispatch until the rebind
        # to the program's output. Both happen under this lock (_update_pool),
        # and so does any read from another thread (export_shipment), so
        # whoever holds it sees live arrays. Never held together with _work.
        self._pool_lock = rlt_lock(
            "serving.engine.InferenceEngine._pool_lock"
        )
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._stop_when_idle = False
        # recent TTFTs for the autoscaler's p95 signal (host-side, tiny)
        self._recent_ttfts: deque = deque(maxlen=128)
        # recent inter-token latencies: the decode pool's autoscaling
        # signal (ITL p99 drives decode capacity; queue depth drives
        # prefill capacity)
        self._recent_itls: deque = deque(maxlen=256)
        # disaggregated serving state (all guarded by self._work; the
        # engine loop thread is the only mutator of pool/allocator state)
        self._role = ecfg.role
        # rid -> {"slot": index, "pinned": chain keys} for parked prefills
        self._exports: Dict[str, Dict[str, Any]] = {}
        self._ready_exports: List[str] = []  # rids awaiting fleet pickup
        self._export_actions: List[tuple] = []  # (rid, "finish"|"cancel")
        self._pending_imports: List[_ImportTicket] = []
        self._import_seq = 0
        # request-scoped tracing: None when telemetry is off, so every
        # per-request/per-token trace site stays a single attribute check
        self._tracer: Optional[_reqtrace.RequestTracer] = (
            _reqtrace.RequestTracer(
                pool=self._role if self._role in ("prefill", "decode") else "serve"
            )
            if _obs.enabled()
            else None
        )
        # goodput ledger for this engine's wall time; a relaunch under the
        # same replica index adopts the predecessor's totals so the
        # published counters stay monotonic (the crash-to-relaunch gap
        # lands in whatever category _fail_all left open: fault_recovery)
        self._goodput = (
            _obs.goodput.new_ledger(
                f"serve{replica_index}" if replica_index is not None else "serve",
                category="idle",
            )
            if _obs.enabled()
            else None
        )
        # throughput/utilization accounting (host side, always on)
        self.stats: Dict[str, float] = {
            "decode_steps": 0,
            # of those, the decode programs dispatched while an earlier one
            # was unread: the host's part of that tick ran under the device's
            "overlapped_steps": 0,
            "prefills": 0,
            # ticks whose prompt and decode rows went out as ONE program (the
            # module docstring: where the model's prefill rides its decode
            # step, prompts of the first rung); 0 for an engine of two
            # programs a tick
            "fused_prefill_steps": 0,
            # the sum of the rungs those prefills ran at: what prefill
            # computed, padding and all, and the prompts' own tokens among
            # them (1 - prefill_tokens / prefill_positions is the padded
            # share)
            "prefill_positions": 0,
            "prefill_tokens": 0,
            "tokens_out": 0,
            "busy_slot_steps": 0,
            # of those, the row steps whose token was dropped: the request
            # had stopped (eos, expiry, shutdown) by the time it was read
            "dropped_row_steps": 0,
            "completed": 0,
            # speculative accounting: accepted_tokens / spec_row_ticks is
            # the mean accepted tokens a speculating row's tick
            "accepted_tokens": 0,
            "spec_row_ticks": 0,
            # loop-time sums, in seconds (sums only: a reader subtracts the
            # value before its window from the value after). tick_s is the
            # wall time of step(), sync_wait_s the part of it spent waiting
            # for the sampled tokens, loop_wait_s the loop thread's time
            # between ticks: tick_s + loop_wait_s is that thread's wall time
            "ticks": 0,
            "tick_s": 0.0,
            "sync_wait_s": 0.0,
            "loop_wait_s": 0.0,
            # the starvation probe: calls whose first dispatch found the
            # tick in flight complete (the device had nothing queued), and a
            # lower bound on how long it had (the module docstring)
            "starved_steps": 0,
            "starved_s": 0.0,
            # tick cycles, sync end to sync end, by whether the retired tick
            # enqueued prefills ahead of its decode program
            "decode_cycles": 0,
            "decode_cycle_s": 0.0,
            "prefill_cycles": 0,
            "prefill_cycle_s": 0.0,
        }
        # what the model's decode step counts (routing, for one with
        # experts), summed over decode ticks: they come back in the array
        # of sampled tokens, so reading them costs no sync of its own
        self.stats.update({name: 0 for name in self._model.counters})
        # a pool with a window kind: the positions a decode tick's rows
        # attend in a full layer (pos + 1 each) and in a window layer (no
        # more than the window), summed over decode ticks
        self._kv_window = max(k.window for k in self.pool.kinds.values())
        if self._kv_window:
            self.stats.update(kv_positions_full=0, kv_positions_window=0)
        # a pool with a state kind: the positions a decode tick's rows hold
        # (pos + 1 each: what a layer that reads every position would read,
        # beside what the model's own counters say its layers chose), and
        # the state's bytes a decode tick reads and writes, every slot's
        self._state_bytes_per_tick = (
            2 * self.pool.num_slots * self.pool.state_bytes_per_slot)
        if self._state_bytes_per_tick:
            self.stats.update(kv_positions_live=0, state_bytes_touched=0)
        self._build_compiled()

    def _refuse_unserved(self, ecfg: EngineConfig) -> None:
        """Settings this model has no code for are refused here, by name,
        not somewhere inside a tick."""
        model = self._model
        if states_kind(model, ecfg.resolved_block_size()):
            if ecfg.resolved_speculate_k() > 0:
                raise ValueError(
                    f"speculate_k={ecfg.resolved_speculate_k()}: the "
                    f"{model.name} keeps leaves of a state kind, and a "
                    "proposal that is not accepted cannot be taken back out "
                    "of a state (speculate_k=0 only)"
                )
            if ecfg.prefix_cache:
                raise ValueError(
                    f"prefix_cache=True: the {model.name} keeps leaves of a "
                    "state kind, and a block shared by prefix says nothing "
                    "of the state behind it (prefix_cache=False only)"
                )
            if ecfg.role != "both":
                raise ValueError(
                    f"role={ecfg.role!r}: KV migration ships K and V blocks, "
                    f"and the {model.name} keeps leaves of a state kind, "
                    "which no shipment carries"
                )
        if ecfg.resolved_speculate_k() > 0 and not model.speculation:
            raise ValueError(
                f"speculate_k={ecfg.resolved_speculate_k()}: the "
                f"{model.name} has no verify step (speculate_k=0 only)"
            )
        windows = stated_windows(model, ecfg.resolved_block_size())
        if windows and ecfg.prefix_cache:
            raise ValueError(
                f"prefix_cache=True: the {model.name} keeps leaves of a "
                f"window kind ({windows[0]} positions), and a block shared "
                "by prefix would be given back by the first request it "
                "falls out of the window of (prefix_cache=False only)"
            )
        if ecfg.role != "both" and tuple(
            model.paged_block_leaves(1)
        ) != _migration.SHIPPED_LEAVES:
            raise ValueError(
                f"role={ecfg.role!r}: KV migration ships K and V blocks, "
                f"and the {model.name} caches "
                f"{sorted(model.paged_block_leaves(1))}"
            )

    # ------------------------------------------------------------------ #
    # compiled programs
    # ------------------------------------------------------------------ #
    def _build_compiled(self) -> None:
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.models.generation import _sample_logits
        from ray_lightning_tpu.ops.paged_attention import (
            fused_sample,
            fused_sample_supported,
            paged_kernel_enabled,
        )
        from ray_lightning_tpu.utils.precision import (
            matmul_precision_scope,
            parse_matmul_precision,
            round_matmul_inputs,
        )

        model = self._model
        ecfg = self.engine_config
        spec_k = self._speculate_k = ecfg.resolved_speculate_k()
        # the SAME matmul-precision helper the train step applies — the
        # decode-parity test pins that train and serve cannot drift
        mp = self._matmul_precision = parse_matmul_precision()

        # the fused Pallas sampler only covers the (greedy | pure
        # temperature) policies where it is bitwise-identical to
        # _sample_logits; anything else keeps the lax sampler, so the
        # kernel knob can never change a token
        use_fused = paged_kernel_enabled() and fused_sample_supported(
            ecfg.temperature, ecfg.top_k, ecfg.top_p
        )

        def sample(logits, key):
            if use_fused:
                return fused_sample(
                    logits, key, ecfg.temperature, ecfg.top_k, ecfg.top_p
                )
            return _sample_logits(
                logits, key, ecfg.temperature, ecfg.top_k, ecfg.top_p
            )

        def _with_precision(fn):
            def wrapped(params, *rest):
                with matmul_precision_scope(mp):
                    params = round_matmul_inputs(mp, params)
                    return fn(params, *rest)

            return wrapped

        # one table covering every position a slot can reach, shared by
        # prefill and decode so rope factors cannot diverge between them
        table = model.rope_table(ecfg.max_len)

        def sampled_of(logits, key, counters=None):
            """The program's first output: the sampled tokens, with the
            model's counters behind them where it has any (one array, one
            read-back)."""
            shape = logits.shape[:-1]
            flat = logits.reshape(-1, logits.shape[-1])
            out = sample(flat, key).astype(jnp.int32).reshape(shape)
            if counters is None:
                return out
            return jnp.concatenate([out, counters.astype(jnp.int32)])

        bs = self.pool.block_size
        self._rungs = prefill_rungs(ecfg.max_prompt_len, bs)

        def prefill_into_paged(params, cache, prompt_row, write_table):
            # the model's batched prefill at the rung the prompt was padded
            # to, its cache rows padded up to whole blocks and cut into
            # them, scattered to the PHYSICAL blocks named by
            # write_table — shared-prefix entries point at the trash
            # block, so a cached prefix is written exactly once (by
            # the request that registered it), never re-written per hit
            # over a state kind the table names [the slot, the prompt's
            # length]: a padded position must not reach the state
            state_at = write_table.get(STATE)
            known = {} if state_at is None else {"length": state_at[1]}
            blocks = model.prefill_blocks(
                params, prompt_row, self._blocks_of(prompt_row.shape[1]),
                bs, table, **known
            )

            # a write table a kind of leaf (a window kind's names the trash
            # block for everything before the window's tail); a state leaf
            # is written whole at its slot, and nowhere if that is no slot
            def written(name, leaf):
                new = blocks[name].astype(leaf.dtype)
                kind = self.pool.leaf_kind[name]
                if kind == STATE:
                    return leaf.at[:, state_at[0]].set(new, mode="drop")
                return leaf.at[:, write_table[kind]].set(new)

            return {name: written(name, leaf) for name, leaf in cache.items()}

        num_slots = self.pool.num_slots
        # what stands in for the output of "the decode program before" where
        # there is none unread: its shape, and no row asks for a token of it
        self._no_previous = jnp.zeros(
            (num_slots + len(model.counters),), jnp.int32
        )

        def decode_paged(params, cache, token, pos, tables, key, prev):
            # a row's token is the host's, or under -1 the one the decode
            # program before this one sampled for it: prev is that program's
            # first output as it stands on the device, unread by the host
            token = jnp.where(token < 0, prev[:num_slots], token)
            logits, cache, counters = model.decode_paged(
                params, cache, token, pos, tables, table
            )
            return sampled_of(logits, key, counters), cache

        def prefill_decode_paged(
            params, cache, prompt_row, write_table, token, pos, tables, key, prev
        ):
            # a tick that admits a prompt, as ONE program: the prompt's rung
            # of positions rides the decode rows' matrix products (the
            # model's own step; every weight is read once), its blocks go
            # where write_table names as prefill_into_paged scatters them,
            # and the output is decode_paged's
            token = jnp.where(token < 0, prev[:num_slots], token)
            logits, cache, counters = model.prefill_decode_paged(
                params, cache, prompt_row, write_table, token, pos, tables,
                table
            )
            return sampled_of(logits, key, counters), cache

        def decode_verify_paged(params, cache, tokens, pos, tables, key):
            # speculative verify: tokens is [num_slots, K] (pending token
            # + K-1 proposals), logits come back [S, K, V] and every
            # position is greedily sampled — the host accept loop keeps
            # the longest matching prefix, so any row that proposed
            # nothing degenerates to the k=0 program's math exactly
            logits, cache = model.decode_verify(
                params, cache, tokens, pos, tables, table
            )
            return sampled_of(logits, key), cache

        def install_blocks(cache, ids, blocks):
            # an imported shipment's blocks, written where the prefill
            # would have written them
            return {
                name: leaf.at[:, ids].set(blocks[name].astype(leaf.dtype))
                for name, leaf in cache.items()
            }

        # not one of the two tracked programs (a shape a block count)
        self._install_fn = jax.jit(install_blocks, donate_argnums=(0,))
        decode_fn = decode_verify_paged if spec_k > 0 else decode_paged
        # Where the model's prefill can ride its decode step, and nothing
        # stands between a prompt and its first step (a speculating engine
        # retires a tick before it dispatches the next; a prefill replica
        # parks the slot), the prefill program of the FIRST rung is that
        # step, in the first rung's program's place under its name. The
        # first rung alone: it is where the weights' bytes bound a prefill
        # (FIRST_PREFILL_RUNG) and where most prompts run, and a fused
        # program costs set-up the trace of both halves (PERF.md, PR 43: four
        # of them took the batch cell's setup_s past its bound), so the
        # longer rungs keep the prompt's own program.
        self._fused_rung: Optional[int] = self._rungs[0] if (
            hasattr(model, "prefill_decode_paged")
            and spec_k == 0 and ecfg.role == "both"
        ) else None

        def prefill_fn(params, cache, prompt_row, write_table, *rows):
            # the prompt alone, or with the decode rows behind it: by what
            # the call hands over, an executable each
            if not rows:
                return prefill_into_paged(params, cache, prompt_row, write_table)
            return prefill_decode_paged(
                params, cache, prompt_row, write_table, *rows)

        # The decode rows' arguments of the fused program where no row is
        # stepped with the prompt (the warm-up; a prompt that is not its
        # tick's last): every slot the padding row a free slot is, token 0 at
        # position 0 of the trash block. What it samples is not read.
        self._no_rows = (
            jnp.zeros((num_slots,), jnp.int32), jnp.zeros((num_slots,), jnp.int32),
            self._on_device({
                kind: np.full_like(t, TRASH_BLOCK)
                for kind, t in self.pool.program_tables().items()
            }),
            jax.random.key(0), self._no_previous,
        ) if self._fused_rung else None
        # One prefill program, specialised by the prompt row's shape: an
        # executable a rung. The pool (argument 1, behind the parameters)
        # is donated to both:
        # the buffer that goes in is the one that comes out, and a program
        # writes only the rows that change (the decode steps carry the pool
        # through their layer loop; prefill's scatter of whole blocks needs
        # the donation alone). Undonated, each would copy the whole pool to
        # change a few rows of it, and a tick would hold it two or three times.
        self._prefill_fn = _compile_cache.jit_program(
            _with_precision(prefill_fn), "serve_prefill",
            donate_argnums=(1,)
        )
        self._decode_fn = _compile_cache.jit_program(
            _with_precision(decode_fn), "serve_decode", donate_argnums=(1,)
        )

    def _blocks_of(self, rung: int) -> int:
        """Whole blocks that hold ``rung`` positions."""
        return (rung - 1) // self.pool.block_size + 1

    def _program_specs(self):
        """(name, fn, dummy_args) for the serving programs, ``serve_prefill``
        once a rung (ascending, so the last is ``max_prompt_len``'s) and then
        ``serve_decode``, with dummy arguments matching the :meth:`step`
        call-site shapes/dtypes exactly — shared by :meth:`warmup` and
        :meth:`cost_summary` so the program they build is the program the
        serving loop dispatches."""
        import jax
        import jax.numpy as jnp

        # the pool by its shapes alone: its arrays are donated every tick
        cache = {
            name: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
            for name, leaf in self.pool.cache.items()
        }
        if self._speculate_k > 0:
            token = jnp.zeros(
                (self.pool.num_slots, self._speculate_k), jnp.int32
            )
        else:
            token = jnp.zeros((self.pool.num_slots,), jnp.int32)
        pos = jnp.zeros((self.pool.num_slots,), jnp.int32)
        key = jax.random.key(0)
        rows = (token, pos, self._on_device(self.pool.program_tables()), key,
                *self._previous_output(None))
        prefills = tuple(
            ("serve_prefill", self._prefill_fn,
             (self.params, cache, jnp.zeros((1, rung), jnp.int32),
              self._on_device(self._trash_table(rung)),
              *(rows if rung == self._fused_rung else ())))
            for rung in self._rungs
        )
        return prefills + (
            ("serve_decode", self._decode_fn, (self.params, cache, *rows)),
        )

    def _previous_output(self, tick: Optional[_Tick]) -> tuple:
        """The decode program's last argument: the output of the decode
        program before it, where ``tick`` is that one and still unread, else
        zeros of its shape (no row then asks for a token of it). A
        speculating engine's program takes none: its tokens are the host's."""
        if self._speculate_k > 0:
            return ()
        return (self._no_previous if tick is None else tick.sampled,)

    @staticmethod
    def _on_device(tables):
        """Block tables by kind of leaf, as the programs take them."""
        import jax.numpy as jnp

        return {kind: jnp.asarray(t) for kind, t in tables.items()}

    def _trash_table(self, rung: int):
        """Prompt write tables of ``rung`` positions that write nothing
        (every entry the trash block)."""
        table = np.full((self._blocks_of(rung),), TRASH_BLOCK, np.int32)
        tables = {kind: table for kind in self.pool.kinds}
        if self.pool.state_leaves:  # no slot: the state is written nowhere
            tables[STATE] = np.array([self.pool.num_slots, rung], np.int32)
        return tables

    def warmup(self) -> Dict[str, int]:
        """Resolve (load from the compile cache, or compile and persist)
        the serving programs, prefill at every rung, so the first real
        request of any length pays dispatch cost only. Replica bring-up
        calls this before reporting alive; a relaunch on a warm cache is
        load-bound, not compile-bound.

        Each rung of prefill is then run once, on zeros, with a write table
        that is all trash block: on the chip a resolved executable's first
        run costs 2-3 ms more than a later one (PERF.md, PR 29), and no
        request should pay that inside a window either. Decode's first run
        is any engine's first tick. With the cache disabled nothing is
        resolved ahead and these runs are what compiles the rungs."""
        for _name, fn, args in self._program_specs():
            if hasattr(fn, "warmup"):
                fn.warmup(*args)
        for rung in self._rungs:
            self._dispatch_prefill(
                np.zeros((1, rung), np.int32), self._trash_table(rung)
            )
        return self.compile_stats()

    def compile_stats(self) -> Dict[str, int]:
        """jit cache sizes — flat after warmup is the zero-steady-state-
        recompile contract the tests assert."""

        def size(fn):
            try:
                return int(fn._cache_size())
            except Exception:
                return -1

        return {
            "prefill_compiles": size(self._prefill_fn),
            "decode_compiles": size(self._decode_fn),
        }

    # ------------------------------------------------------------------ #
    # multi-tenant QoS
    # ------------------------------------------------------------------ #
    def configure_tenants(self, registry: Any, admission: bool = True) -> None:
        """Install a :class:`~.tenancy.TenantRegistry`: the scheduler
        switches to per-tenant DRR queues, the shed policy consults
        tenant classes, and — when ``admission`` is True — submit
        charges each request against its tenant's token-bucket quota.

        A fleet front door passes ``admission=False``: quota is charged
        ONCE at the outermost entry point (the fleet), so retries and
        migrations re-dispatched to member engines are not double-billed.
        """
        self._tenancy = registry
        self._tenancy_admission = bool(admission) and registry is not None
        self.scheduler.configure_tenants(registry)

    # ------------------------------------------------------------------ #
    # submission
    # ------------------------------------------------------------------ #
    def submit(
        self,
        prompt_tokens: Sequence[int],
        max_new_tokens: int = 16,
        request_id: Optional[str] = None,
        eos_id: Any = "__default__",
        on_token: Optional[Callable[[str, int], Any]] = None,
        deadline_ms: Optional[float] = None,
        priority: int = 0,
        retries: int = 0,
        trace_ctx: Optional["_reqtrace.TraceContext"] = None,
        tenant: Optional[str] = None,
    ) -> Completion:
        """Enqueue one request; returns its :class:`Completion` handle.

        ``deadline_ms`` is a TTL from now: once past it the request is
        evicted (queued or decoding) with ``finish_reason="expired"``.
        ``priority`` 0 is the protected class; >= 1 is sheddable (see
        ``EngineConfig.shed_watermark``). ``retries`` is the journal's
        attempt number, threaded into trace records. ``trace_ctx`` is the
        fleet's hop-carrying lineage context (parent attempt, hop index,
        upstream TTFT components); observability-only. ``tenant`` names
        the submitting tenant when a registry is installed
        (:meth:`configure_tenants`): it selects the DRR queue, shed
        class, quota bucket, and per-tenant metric labels.

        Raises :class:`RequestQueueFull` (bounded queue back-pressure),
        :class:`RequestShed` (load-shed verdict on sheddable work),
        :class:`~.tenancy.QuotaExceeded` (tenant over its contracted
        rate), :class:`EngineClosed` after drain/shutdown, and
        ``ValueError`` for prompts that do not fit the compiled shapes.
        """
        tokens = tuple(int(t) for t in prompt_tokens)
        if not tokens:
            raise ValueError("prompt_tokens must be non-empty")
        if len(tokens) > self.engine_config.max_prompt_len:
            raise ValueError(
                f"prompt length {len(tokens)} exceeds max_prompt_len="
                f"{self.engine_config.max_prompt_len} (the longest prompt "
                "admitted and the longest prefill shape compiled; raise it "
                "at engine construction)"
            )
        if eos_id == "__default__":
            eos_id = self.engine_config.eos_id
        tenant_class = None
        if self._tenancy is not None:
            tenant_class = self._tenancy.tenant_class(tenant)
            reg = _obs.registry()
            if reg is not None and tenant is not None:
                reg.counter(
                    _metrics.TENANT_REQUESTS_METRIC,
                    tenant=reg.tenant_label(tenant),
                ).inc()
            if self._tenancy_admission and not self._tenancy.admit(tenant):
                if reg is not None and tenant is not None:
                    reg.counter(
                        _metrics.TENANT_QUOTA_REJECTED_METRIC,
                        tenant=reg.tenant_label(tenant),
                    ).inc()
                from ray_lightning_tpu.serving.tenancy import QuotaExceeded

                raise QuotaExceeded(
                    f"tenant {tenant!r} exceeded its admission quota "
                    "(token bucket empty); retry after the bucket refills"
                )
        if self.shed_policy.should_shed(
            priority=int(priority),
            queue_depth=self.scheduler.queue_depth,
            max_queue=self.engine_config.max_queue,
            slo_breached=self._slo_breached(),
            tenant_class=tenant_class,
        ):
            reg = _obs.registry()
            if reg is not None:
                reg.counter(_metrics.SERVE_SHED_METRIC).inc()
                if self._tenancy is not None and tenant is not None:
                    reg.counter(
                        _metrics.TENANT_SHED_METRIC,
                        tenant=reg.tenant_label(tenant),
                    ).inc()
            raise RequestShed(
                f"request shed (priority={priority}): the engine is past "
                "its queue watermark or burning SLO budget; retry later or "
                "raise the request's priority class"
            )
        rid = request_id or f"req-{next(self._req_counter)}"
        completion = Completion(rid, tenant=tenant)
        req = Request(
            request_id=rid,
            tokens=tokens,
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            on_token=on_token,
            deadline=(
                time.perf_counter() + float(deadline_ms) / 1e3
                if deadline_ms is not None
                else None
            ),
            priority=int(priority),
            retries=int(retries),
            tenant=tenant,
        )
        if self._tracer is not None:
            req.trace = self._tracer.start(
                rid, len(tokens), int(max_new_tokens),
                replica=self.replica_index, retries=int(retries),
                ctx=trace_ctx, tenant=tenant,
            )
        with self._work:
            if self._closed:
                raise EngineClosed(
                    "engine is draining/shut down; no new requests"
                )
            if rid in self._completions:
                raise ValueError(f"duplicate request_id {rid!r}")
            # scheduler.submit validates lengths + bounded queue
            self.scheduler.submit(req)
            self._completions[rid] = completion
            if on_token is not None:
                self._on_token[rid] = on_token
            self._work.notify_all()
        reg = _obs.registry()
        if reg is not None:
            reg.counter("rlt_serve_requests_total").inc()
        return completion

    # ------------------------------------------------------------------ #
    # one iteration
    # ------------------------------------------------------------------ #
    def step(self) -> Dict[str, Any]:
        """Run one scheduler tick: up to N prefills + one batched decode
        dispatched (the last prefill and the decode as ONE program where the
        model's prefill rides its decode step and the prompt runs at the
        first rung: the module docstring, "a tick that admits a prompt"), and
        one tick retired: the one dispatched by the call
        before this one, whose program the device ran meanwhile (a
        speculating engine retires the tick it just dispatched; see the
        module docstring on the order of a tick).

        Returns ``{"prefills": int, "decoded": int, "completed": [ids]}``
        of the tick this call RETIRED, the one whose program it waited for:
        the prefills enqueued ahead of that decode program, the rows it
        stepped, the requests that finished when its tokens were read. An
        engine's first call of a run retires nothing and returns zeros; the
        call after the last dispatch retires the last tick and dispatches
        nothing. Call from a single thread only (the loop thread, or the
        test).

        The tick and its phases are ``rlt.serve.*`` spans on the profiler's
        clock (``observability.phase_span``), and its wall time and its one
        wait for the device are summed into ``stats`` (``ticks``, ``tick_s``,
        ``sync_wait_s``) whether or not anything is being traced. So are,
        with no sync of their own, ``starved_steps`` / ``starved_s`` (the
        calls that found the device with nothing queued at their first
        dispatch: the host kept it waiting) and the tick cycles
        ``decode_cycles`` / ``decode_cycle_s`` / ``prefill_cycles`` /
        ``prefill_cycle_s`` (sync to sync, by whether the retired tick held
        prefills): the module docstring says what starves and what a cycle
        is. The spans say whose tick they belong to: ``prefills=`` is what
        this call enqueued (``rlt.serve.tick``, ``decode_prep``,
        ``decode_dispatch``) or what the retired tick had (``sample_sync``,
        and ``retired_prefills=`` on ``rlt.serve.tick``); ``starved=0|1`` is
        on the call's first dispatch where a tick was in flight;
        ``fused=0|1`` on ``rlt.serve.tick`` says whether this call's prompt
        and rows went out as one program."""
        t0 = time.perf_counter()
        try:
            with _obs.phase_span("rlt.serve.tick", tick=self._ticks + 1) as span:
                return self._run_tick(span)
        finally:
            self.stats["ticks"] += 1
            self.stats["tick_s"] += time.perf_counter() - t0

    def _update_pool(self, run):
        """Dispatch one program that the pool is donated to and bind the pool
        to its output, as one step under ``_pool_lock``: ``run(cache) ->
        (the new cache, the program's other output)``; returns the latter.
        Nothing keeps the tree that went in, which is dead from the dispatch
        on. A program that raises after it consumed its input leaves no
        pool: the engine is then marked failed and no later tick runs."""
        with self._pool_lock:
            try:
                cache, out = run(self.pool.cache)
            except Exception as e:
                gone = any(a.is_deleted() for a in self.pool.cache.values())
                if gone and self.failed is None:
                    self.failed = e
                raise
            self.pool.cache = cache
        return out

    def _dispatch_prefill(self, padded: np.ndarray, where, rows=None):
        """Enqueue prefill of one padded prompt row [1, rung] into the
        physical blocks ``where`` names (one entry a block of the rung; one
        such table a kind of leaf where the pool has several). At the rung
        whose program steps the decode rows too (``_fused_rung``), ``rows``
        are that step's arguments as ``_decode_fn`` takes them behind the
        pool, and its output is returned: the sampled tokens, unread.
        ``rows`` None: no row is stepped (``_no_rows``)."""
        import jax.numpy as jnp

        prompt_row, where = jnp.asarray(padded), self._on_device(where)
        if padded.shape[1] != self._fused_rung:
            return self._update_pool(lambda cache: (
                self._prefill_fn(self.params, cache, prompt_row, where), None,
            ))
        rows = self._no_rows if rows is None else rows
        return self._update_pool(lambda cache: self._prefill_fn(
            self.params, cache, prompt_row, where, *rows
        )[::-1])

    def _dry_since(self, since: Optional[float]) -> Optional[float]:
        """The starvation probe. An engine that overlaps its ticks keeps one
        dispatched tick unread; if that tick's output is complete before the
        next program is enqueued, the device has nothing queued and idles
        until the dispatch lands. Asks the array in flight whether it is
        complete (no transfer, no wait) and returns the first instant it was
        seen so: ``since`` if an earlier probe of this call saw it, else now,
        else None (and None with nothing in flight: an idle engine is not a
        starved device)."""
        if since is not None or self._inflight is None:
            return since
        return time.perf_counter() if self._inflight.sampled.is_ready() else None

    def _first_dispatched(self, span, dry: Optional[float]) -> None:
        """At the return of a call's first dispatch, with a tick in flight:
        the last probe (a dispatch takes a millisecond or more, and a tick
        that completed under it left the device with nothing queued too),
        the span's ``starved=`` and the two counters."""
        dry = self._dry_since(dry)
        span.set_metadata(starved=int(dry is not None))
        if dry is not None:
            self.stats["starved_steps"] += 1
            self.stats["starved_s"] += time.perf_counter() - dry

    def _run_tick(self, tick_span) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        if self.failed is not None:
            raise EngineClosed(
                f"the engine failed ({self.failed!r}) and its KV pool went "
                "with the program that raised: build a new engine"
            ) from self.failed
        self._ticks += 1
        if self._goodput is not None:
            self._goodput.enter("productive_compute")
        # scripted serving faults (RLT_FAULT replica<N> specs): crash
        # raises out of step() -> the loop fails every in-flight request
        # and dies, which is exactly the replica death the journal and
        # breakers must recover from
        _faults.fire_serve_tick_faults(self.replica_index, self._ticks)
        # the starvation probe, at the instants the call passes anyway: here,
        # behind the schedule, and just before and behind its first dispatch
        dry = self._dry_since(None)
        with _obs.phase_span("rlt.serve.schedule"):
            self._process_export_actions()
            self._process_imports()
            self._evict_expired_slots()
            plan = self.scheduler.tick()
        dry = self._dry_since(dry)

        new_exports: List[str] = []
        # (trace, dispatch start, dispatch end) of this tick's prefills:
        # their duration is known at the tick's sync, not at the enqueue
        prefill_traces: List[tuple] = []
        # the tick's last prompt where it runs at the fused rung, padded and
        # with its write tables, until the decode rows it goes out with are
        # prepared (its own slot is one of them: a request has a token to
        # give, and an engine with such a rung parks no slot)
        held: Optional[tuple] = None
        for i, (req, slot) in enumerate(plan.prefills):
            rung = rung_for(self._rungs, req.prompt_len)
            hold = rung == self._fused_rung and i == len(plan.prefills) - 1
            probing = i == 0 and self._inflight is not None and not hold
            if probing:
                dry = self._dry_since(dry)
            with _obs.phase_span(
                "rlt.serve.prefill", prompt_len=req.prompt_len, rung=rung
            ) as span:
                self._admit_seq += 1
                fspec = _faults.serve_request_fault(
                    self.replica_index, self._admit_seq
                )
                if fspec is not None and fspec.kind == "drop-stream":
                    self._drop_stream[req.request_id] = max(
                        1, int(fspec.arg or 1)
                    )
                padded = np.zeros((1, rung), np.int32)
                padded[0, : req.prompt_len] = req.tokens
                tr = req.trace
                t0 = time.perf_counter() if tr is not None else 0.0
                where = self.pool.prompt_write_tables(
                    slot.index, self._blocks_of(rung)
                )
                if hold:
                    held = (padded, where)
                else:
                    self._dispatch_prefill(padded, where)
                if probing:
                    self._first_dispatched(span, dry)
                if tr is not None:
                    prefill_traces.append((tr, t0, time.perf_counter()))
                slot.pos = req.prompt_len - 1
                slot.pending_token = req.tokens[-1]
                if self._speculate_k > 0:
                    self._history[req.request_id] = list(req.tokens)
                if self._role == "prefill":
                    # park the slot for migration: pin its prefix chains NOW
                    # (engine thread — serialized with every other allocator
                    # op) so a sibling release can't drop them to refcount 0
                    # and have them evicted while the shipment is in flight
                    slot.export_pending = True
                    pinned = self.pool.kinds["full"].allocator.pin_request(
                        req.request_id)
                    self._exports[req.request_id] = {
                        "slot": slot.index, "pinned": pinned,
                        "prompt": tuple(req.tokens),
                    }
                    new_exports.append(req.request_id)
                self.stats["prefills"] += 1
                self.stats["prefill_positions"] += rung
                self.stats["prefill_tokens"] += req.prompt_len

        # Who is stepped: every occupied slot but the parked and the spent.
        # An export-pending slot is parked: its KV is in flight to a decode
        # replica, so this engine must not decode it (not even the same-tick
        # first decode of a fresh prefill, or the source would emit a token
        # the receiver then duplicates; a failed migration clears the flag
        # and it resumes in place). The filter runs AFTER the prefill loop so
        # it sees slots parked this tick. A slot is spent when every step of
        # its ``max_new_tokens`` is dispatched: it waits for the retire of
        # the last, and no row is ever stepped past its length (the table
        # has no block there).
        decode_slots = [
            s for s in plan.decode_slots
            if not s.export_pending
            and s.pos - s.prompt_len + 1 < s.max_new_tokens
        ]
        K = self._speculate_k
        tick = _Tick(None, [], {}, len(plan.prefills), prefill_traces)
        if decode_slots:
            rows = len(decode_slots)
            with _obs.phase_span(
                "rlt.serve.decode_prep", rows=rows, prefills=tick.prefills
            ):
                # speculative tick (K > 0): every row carries its pending
                # token plus up to K-1 prompt-lookup proposals; rows with no
                # proposal (or at the end of their budget) ride the same
                # fixed-shape program with padded columns that are sampled
                # and discarded
                token = np.zeros(
                    (self.pool.num_slots, K) if K > 0 else (self.pool.num_slots,),
                    np.int32,
                )
                pos = np.zeros((self.pool.num_slots,), np.int32)
                for slot in decode_slots:
                    if K > 0:
                        # budget: a row may deliver at most `remaining`
                        # tokens this tick, so propose at most remaining-1 —
                        # also what keeps every speculative write inside the
                        # blocks the paged allocator reserved at admission
                        remaining = slot.max_new_tokens - slot.generated
                        props = ngram_propose(
                            self._history.get(slot.request_id, ()),
                            min(K - 1, remaining - 1),
                        )
                        tick.proposals[slot.index] = props
                        token[slot.index, 0] = slot.pending_token
                        for j, p in enumerate(props):
                            token[slot.index, 1 + j] = p
                    else:
                        props = ()
                        # -1: the token is the one the decode program in
                        # flight sampled for this row, still on the device
                        token[slot.index] = (
                            -1 if slot.pending_token is None
                            else slot.pending_token
                        )
                    # on-demand growth: the block holding the deepest
                    # write position (slot.pos, or the last speculative
                    # one) must be physical before the compiled scatter
                    # writes it (a host-side table-value change, never a
                    # shape change)
                    self.pool.ensure_writable(
                        slot, upto_pos=slot.pos + len(props)
                    )
                    pos[slot.index] = slot.pos
                self._rng, sub = jax.random.split(self._rng)
                if self._kv_window:
                    live = pos[[s.index for s in decode_slots]] + 1
                    self.stats["kv_positions_full"] += int(live.sum())
                    self.stats["kv_positions_window"] += int(
                        np.minimum(live, self._kv_window).sum())
                if self._state_bytes_per_tick:
                    self.stats["kv_positions_live"] += int(
                        pos[[s.index for s in decode_slots]].sum()) + rows
                    self.stats["state_bytes_touched"] += (
                        self._state_bytes_per_tick)
                # The tables go up as a snapshot, taken now, behind the
                # growth above: the host's mirrors change again (a release
                # at the retire below, growth at the next dispatch) while
                # the program that reads this upload may still be running,
                # and an upload may alias the host's memory or read it late.
                block_tables = {
                    k: t.copy() for k, t in self.pool.program_tables().items()
                }
                if rows < len(plan.decode_slots):
                    # A slot that is occupied and not stepped rides the
                    # fixed-shape program as a padding row (token 0, pos 0):
                    # with its LIVE block table in place, that padding
                    # write would land in the request's first prompt block
                    # and corrupt the KV the shipment (and any in-place
                    # fallback decode, and any request that shares the
                    # block by prefix) depends on. Point such rows at the
                    # trash block, the same sink free slots use.
                    stepped = {s.index for s in decode_slots}
                    idle = [
                        s.index for s in plan.decode_slots
                        if s.index not in stepped
                    ]
                    for t in block_tables.values():
                        t[idle, :] = TRASH_BLOCK
                inputs = (
                    jnp.asarray(token), jnp.asarray(pos),
                    self._on_device(block_tables), sub,
                    *self._previous_output(self._inflight),
                )
            # else a prefill was the call's first dispatch
            probing = self._inflight is not None and (
                not tick.prefills or (held is not None and tick.prefills == 1))
            if probing:
                dry = self._dry_since(dry)
            with _obs.phase_span(
                "rlt.serve.decode_dispatch", prefills=tick.prefills
            ) as span:
                if held is not None:  # the prompt and the rows: one program
                    tick.sampled = self._dispatch_prefill(*held, rows=inputs)
                    self.stats["fused_prefill_steps"] += 1
                else:
                    tick.sampled = self._update_pool(
                        lambda cache: self._decode_fn(
                            self.params, cache, *inputs
                        )[::-1])
                if probing:
                    self._first_dispatched(span, dry)
            # the step is on its way: what follows from the positions alone
            # moves now, what needs the tokens when they are read
            for slot in decode_slots:
                tick.rows.append((slot, slot.admission))
                slot.pos += 1
                if K == 0:
                    slot.pending_token = None
            self.stats["decode_steps"] += 1
            self.stats["busy_slot_steps"] += rows
            if self._inflight is not None:
                self.stats["overlapped_steps"] += 1

        # One tick is retired a call. Where the next dispatch reads the
        # tokens on the host (a speculating engine proposes from them) it is
        # the tick just dispatched; where it does not, that tick stays in
        # flight and the one before it is retired, which the device finished
        # while the host scheduled, prepared and dispatched this one. A tick
        # without a decode program has nothing to wait for and goes with it.
        retiring, self._inflight = [self._inflight], None
        if tick.sampled is not None and K == 0:
            self._inflight = tick
        else:
            retiring.append(tick)
        report = {"prefills": 0, "decoded": 0, "completed": []}
        for retired in retiring:
            if retired is not None:
                self._retire(retired, report)

        if new_exports:
            # published once the tick is over: the fleet's migration pump
            # reads the block payloads out of self.pool.cache
            # (export_shipment), behind this tick's prefill writes
            with self._work:
                self._ready_exports.extend(new_exports)
                self._work.notify_all()
        tick_span.set_metadata(
            prefills=tick.prefills, retired_prefills=report["prefills"],
            fused=int(held is not None),
        )
        return report

    def _retire(self, tick: _Tick, report: Dict[str, Any]) -> None:
        """Read one dispatched tick's tokens and do everything that needs
        their values, in order: deliver them, finish and release what
        stopped. The tick's one wait for the device is here. A row whose
        slot has changed hands since the dispatch (released at an earlier
        retire, by an expiry or a shutdown, and perhaps admitted anew) had
        stopped before this step: its token is dropped. ``report`` gathers
        what ``step()`` returns."""
        K = self._speculate_k
        report["prefills"] += tick.prefills
        if tick.sampled is None:
            # no decode, so no sync (a prefill-role replica whose slots are
            # all parked): all the host knows is the enqueue
            for tr, t0, t1 in tick.prefill_traces:
                tr.prefilled(t1 - t0, done_at=t1, synced=False)
            if tick.prefills:  # they run inside a later tick's cycle
                self._cycle_from = None
            return
        rows = len(tick.rows)
        report["decoded"] += rows
        completed = report["completed"]
        t_sync = time.perf_counter()
        with _obs.phase_span("rlt.serve.sample_sync", prefills=tick.prefills):
            sampled_host = np.asarray(tick.sampled)  # the per-step sync point
        now = time.perf_counter()
        self.stats["sync_wait_s"] += now - t_sync
        if self._cycle_from is not None:
            kind = "prefill" if tick.prefills else "decode"
            self.stats[kind + "_cycles"] += 1
            self.stats[kind + "_cycle_s"] += now - self._cycle_from
        self._cycle_from = now
        counters = self._model.counters
        if counters:  # behind the tokens, in the same array
            for name, value in zip(
                counters, sampled_host[self.pool.num_slots:]
            ):
                self.stats[name] += int(value)
            sampled_host = sampled_host[: self.pool.num_slots]
        # the first instant the host knows this tick's prefills are done
        for tr, t0, _ in tick.prefill_traces:
            tr.prefilled(now - t0, done_at=now)
        with _obs.phase_span("rlt.serve.deliver", rows=rows):
            reg = _obs.registry()
            for slot, admission in tick.rows:
                rid = slot.request_id
                if rid is None or slot.admission != admission:
                    # the request stopped after this step was dispatched
                    # (eos or expiry learnt since, a re-entrant shutdown
                    # from an on_token callback): nothing to deliver
                    self.stats["dropped_row_steps"] += 1
                    continue
                if K == 0:
                    self._deliver_token(
                        slot, rid, int(sampled_host[slot.index]), now,
                        reg, completed,
                    )
                    continue
                out = sampled_host[slot.index]
                # greedy accept: out[j] is the model's token AFTER
                # consuming proposals[:j]; the first mismatch both ends
                # the accepted prefix AND contributes its correction —
                # so at least one token always lands, same as k=0
                accepted = 1
                for j, p in enumerate(tick.proposals.get(slot.index, [])):
                    if int(out[j]) == int(p):
                        accepted += 1
                    else:
                        break
                before = self.stats["tokens_out"]
                for j in range(accepted):
                    if j:  # the dispatch advanced the one sure position
                        slot.pos += 1
                    if not self._deliver_token(
                        slot, rid, int(out[j]), now, reg, completed
                    ):
                        break
                delivered = int(self.stats["tokens_out"] - before)
                self.stats["spec_row_ticks"] += 1
                self.stats["accepted_tokens"] += delivered
                if delivered > 0 and reg is not None:
                    reg.histogram(
                        "rlt_serve_accepted_tokens",
                        bounds=ACCEPTED_BOUNDS,
                    ).observe(float(delivered), exemplar=rid)

    def _deliver_token(
        self,
        slot,
        rid: str,
        tok: int,
        now: float,
        reg,
        completed: List[str],
    ) -> bool:
        """Deliver ONE sampled token to a slot's request — the shared
        per-token tail of :meth:`step` for both the classic one-token
        tick and a speculative burst (called once per accepted token, in
        order). Returns ``False`` when the slot stopped consuming tokens
        (stream dropped by a scripted fault, request finished on
        EOS/length, or detached re-entrantly by its callback) — which
        truncates the remainder of a burst: tokens past EOS are never
        delivered, never journaled, and the garbage the verify pass wrote
        for them is recycled with the slot."""
        drop_after = self._drop_stream.get(rid)
        if drop_after is not None and slot.generated >= drop_after:
            # scripted drop-stream fault: the request's stream
            # dies here — this token is never delivered, the
            # journal resumes from the tokens the client has
            self._drop_stream.pop(rid, None)
            completed.append(rid)
            self._finish(
                rid, "error",
                _faults.ServeFault(
                    f"scripted serving fault: {rid} stream dropped "
                    f"after {slot.generated} tokens"
                ),
            )
            if slot.trace is not None:
                self._tracer.finish(slot.trace, "error")
            self.pool.release(slot.index)
            return False
        completion = self._completions.get(rid)
        if completion is not None and not completion.done:
            completion.tokens.append(tok)
            if completion.ttft_s is None:
                completion.ttft_s = now - completion.submitted_at
                self._recent_ttfts.append(completion.ttft_s)
                if reg is not None:
                    reg.histogram(
                        "rlt_serve_ttft_seconds",
                        bounds=LATENCY_BOUNDS,
                    ).observe(
                        completion.ttft_s, exemplar=rid
                    )
                    if (
                        self._tenancy is not None
                        and completion.tenant is not None
                    ):
                        reg.histogram(
                            _metrics.TENANT_TTFT_METRIC,
                            bounds=LATENCY_BOUNDS,
                            tenant=reg.tenant_label(completion.tenant),
                        ).observe(completion.ttft_s, exemplar=rid)
                if (
                    self.slo_monitor is not None
                    and self._tenancy is not None
                    and completion.tenant is not None
                ):
                    try:
                        self.slo_monitor.observe_latency(
                            f"tenant_ttft_{completion.tenant}",
                            completion.ttft_s,
                        )
                    except Exception:
                        pass  # unregistered tenant objective: skip
            elif slot.last_token_at is not None:
                itl = now - slot.last_token_at
                self._recent_itls.append(itl)
                if reg is not None:
                    reg.histogram(
                        "rlt_serve_itl_seconds", bounds=LATENCY_BOUNDS
                    ).observe(itl, exemplar=rid)
            cb = self._on_token.get(rid)
            if cb is not None:
                try:
                    cb(rid, tok)
                except Exception:
                    pass  # broken stream consumer must not stall decode
            if slot.request_id != rid:
                # the callback re-entrantly shut down / finished
                # this request; the slot is no longer its tenant
                return False
        if slot.first_token_at is None:
            slot.first_token_at = now
        slot.last_token_at = now
        tr = slot.trace
        if tr is not None:
            tr.token()
        slot.generated += 1
        if self._inflight is None:
            # no later step of this row is dispatched: the next takes this
            # token from the host (else it has it from the device already)
            slot.pending_token = tok
        hist = self._history.get(rid)
        if hist is not None:
            hist.append(tok)
        self.stats["tokens_out"] += 1
        if reg is not None:
            reg.counter("rlt_serve_tokens_total").inc()
        reason = None
        if slot.eos_id is not None and tok == slot.eos_id:
            reason = "eos"
        elif slot.generated >= slot.max_new_tokens:
            reason = "length"
        if reason is not None:
            completed.append(rid)
            self._finish(rid, reason)
            if tr is not None:
                self._tracer.finish(tr, reason)
            self.pool.release(slot.index)
            return False
        return True

    def _finish(
        self,
        request_id: str,
        reason: str,
        error: Optional[BaseException] = None,
    ) -> None:
        completion = self._completions.pop(request_id, None)
        self._on_token.pop(request_id, None)
        self._history.pop(request_id, None)
        if completion is not None:
            completion._finish(reason, error)
        self.stats["completed"] += 1
        reg = _obs.registry()
        if reg is not None:
            reg.counter("rlt_serve_completions_total", reason=reason).inc()
            if (
                self._tenancy is not None
                and completion is not None
                and completion.tenant is not None
            ):
                reg.counter(
                    _metrics.TENANT_COMPLETIONS_METRIC,
                    tenant=reg.tenant_label(completion.tenant),
                    reason=reason,
                ).inc()

    # ------------------------------------------------------------------ #
    # disaggregated serving: KV export (prefill role) / import (decode)
    # ------------------------------------------------------------------ #
    def kv_fingerprint(self) -> str:
        """Engine/layout identity a KV shipment must match to be
        admitted."""
        cache = self.pool.cache
        first = next(iter(cache.values()))
        return _migration.kv_fingerprint(
            self.pool.block_size,
            # one block through every layer: [L, *block]
            first.shape[:1] + first.shape[2:],
            str(first.dtype),
            self.pool.max_len,
            leaves=tuple(cache),
        )

    def drain_ready_exports(self) -> List[str]:
        """Pop the request ids whose prefill finished and whose KV is
        ready to ship (prefill role only; empty otherwise)."""
        with self._work:
            out = self._ready_exports
            self._ready_exports = []
        return out

    def export_shipment(self, request_id: str) -> "_migration.KVShipment":
        """Snapshot a parked prefill's prompt-block KV into a checksummed
        :class:`~.migration.KVShipment`.

        Read-only and callable from the fleet's pump thread: the slot is
        export-parked (the decode filter skips it from its prefill on, so
        no step of it is ever in flight and its blocks are never written)
        and its prefix chains were pinned at arm time. The
        pool's arrays are NOT stable values, though: every tick donates them
        to its programs, and what ``self.pool.cache`` named a moment ago may
        be deleted. So the blocks are gathered under ``_pool_lock``, which a
        tick holds from each dispatch to the rebind: the gather is enqueued
        on live arrays, behind the programs that wrote them, and a later
        donation waits for it. The copy to the host happens outside the
        lock. The shipment carries ALL prompt blocks (including
        source-shared ones): the receiver may not hold the chain."""
        with self._work:
            rec = self._exports.get(request_id)
        if rec is None:
            raise KeyError(f"request {request_id!r} has no parked export")
        slot = self.pool.slots[rec["slot"]]
        if slot.request_id != request_id:
            raise KeyError(
                f"request {request_id!r} no longer owns slot {rec['slot']}"
            )
        alloc = self.pool.kinds["full"].allocs[rec["slot"]]
        bs = self.pool.block_size
        n_prompt_blocks = (slot.prompt_len - 1) // bs + 1
        ids = np.asarray(alloc.blocks[:n_prompt_blocks], np.int32)
        with self._pool_lock:
            if self.failed is not None:
                raise EngineClosed(
                    f"request {request_id!r}: the engine failed and its KV "
                    "pool is gone"
                ) from self.failed
            cache = self.pool.cache
            k, v = cache["k"][:, ids], cache["v"][:, ids]  # [L, n, ...]
        k, v = np.asarray(k), np.asarray(v)
        block_k = [np.ascontiguousarray(k[:, j]) for j in range(n_prompt_blocks)]
        block_v = [np.ascontiguousarray(v[:, j]) for j in range(n_prompt_blocks)]
        prompt = self._export_prompt(request_id, slot)
        # Lineage: the parked slot's trace hands the shipment a hop
        # context (parent rid, accumulated TTFT components, send stamp)
        # so the receiving replica records a linked child hop.
        trace_ctx = (
            slot.trace.export_context() if slot.trace is not None else None
        )
        return _migration.build_shipment(
            request_id=request_id,
            prompt=prompt,
            fingerprint=self.kv_fingerprint(),
            block_size=bs,
            block_k=tuple(block_k),
            block_v=tuple(block_v),
            trace_ctx=trace_ctx,
        )

    def _export_prompt(self, request_id: str, slot) -> tuple:
        """The prompt tokens behind a parked slot. The scheduler's
        Request is gone by prefill time, so the engine keeps the prompt
        in the export record (stored at arm time by :meth:`step`)."""
        with self._work:
            rec = self._exports.get(request_id)
        if rec is None or "prompt" not in rec:
            raise KeyError(
                f"request {request_id!r} has no recorded export prompt"
            )
        return tuple(rec["prompt"])

    def finish_export(self, request_id: str) -> None:
        """Migration landed: release the parked slot and finish the
        source-side completion as ``"migrated"``. Executed by the engine
        loop at the next tick (cross-thread pool mutations are always
        routed through the loop)."""
        with self._work:
            self._export_actions.append((request_id, "finish"))
            self._work.notify_all()

    def cancel_export(self, request_id: str) -> None:
        """Migration gave up: un-park the slot so the request decodes in
        place on this (prefill) replica — the graceful-degradation
        fallback. Executed by the engine loop at the next tick."""
        with self._work:
            self._export_actions.append((request_id, "cancel"))
            self._work.notify_all()

    def _process_export_actions(self) -> None:
        """Engine-loop half of finish_export/cancel_export."""
        if not self._export_actions:
            return
        with self._work:
            actions = self._export_actions
            self._export_actions = []
        for rid, action in actions:
            with self._work:
                rec = self._exports.pop(rid, None)
            if rec is None:
                continue
            slot = self.pool.slots[rec["slot"]]
            if slot.request_id != rid:
                continue  # slot already recycled (expiry / engine death)
            self.pool.kinds["full"].allocator.unpin(rec["pinned"])
            if action == "finish":
                slot.export_pending = False
                self._finish(rid, "migrated")
                if slot.trace is not None:
                    self._tracer.finish(slot.trace, "migrated")
                self.pool.release(slot.index)
            else:  # cancel: resume decoding right here
                slot.export_pending = False

    def import_shipment(
        self,
        shipment: "_migration.KVShipment",
        max_new_tokens: int,
        request_id: Optional[str] = None,
        eos_id: Any = "__default__",
        on_token: Optional[Callable[[str, int], Any]] = None,
        deadline_ms: Optional[float] = None,
        priority: int = 0,
        retries: int = 0,
        timeout: Optional[float] = 30.0,
    ) -> Completion:
        """Admit a prefilled request from a KV shipment (decode role).

        Callable from any thread: the admit itself (verify → fault point
        → worst-case reservation → device install → resume) runs on the
        engine loop thread via a ticket, so pool and allocator state are
        never touched cross-thread. Blocks up to ``timeout`` seconds for
        the verdict; on timeout the ticket is abandoned (the loop skips
        it) and ``TimeoutError`` raises.

        Raises :class:`~.migration.ShipmentMismatch` /
        :class:`~.migration.ShipmentCorrupt` (rejected before any
        payload touches the cache), :class:`~.migration.MigrationRejected`
        (no slot/blocks under the worst-case reservation),
        :class:`EngineClosed`, and whatever a scripted crash-mid-admit
        fault kills the engine with."""
        rid = request_id or f"req-{next(self._req_counter)}"
        if eos_id == "__default__":
            eos_id = self.engine_config.eos_id
        ticket = _ImportTicket(
            shipment, rid, max_new_tokens, eos_id, on_token, deadline_ms,
            priority, retries,
        )
        with self._work:
            if self._closed:
                raise EngineClosed(
                    "engine is draining/shut down; no new shipments"
                )
            self._pending_imports.append(ticket)
            self._work.notify_all()
        if not ticket.event.wait(timeout):
            with self._work:
                ticket.abandoned = True
            if not ticket.event.is_set():
                raise TimeoutError(
                    f"shipment {rid!r} not admitted within {timeout}s"
                )
        if ticket.error is not None:
            raise ticket.error
        assert ticket.completion is not None
        return ticket.completion

    def _process_imports(self) -> None:
        """Engine-loop half of :meth:`import_shipment`. A scripted
        crash-mid-admit fault re-raises out of here so the engine dies
        exactly as a real receiver crash would — after answering the
        waiting pump, so the sender observes the failed attempt instead
        of a timeout."""
        if not self._pending_imports:
            return
        with self._work:
            tickets = self._pending_imports
            self._pending_imports = []
        for ticket in tickets:
            with self._work:
                if ticket.abandoned:
                    continue
            try:
                ticket.completion = self._admit_import(ticket)
            except BaseException as e:
                ticket.error = e
                ticket.event.set()
                if isinstance(e, _faults.ServeFault):
                    raise
                continue
            ticket.event.set()

    def _admit_import(self, ticket: "_ImportTicket") -> Completion:
        import jax.numpy as jnp

        shipment = ticket.shipment
        # gate order is the contract: checksum/fingerprint verification
        # happens BEFORE the fault point and BEFORE any device write — a
        # corrupt shipment is never decoded, not even by a crashing
        # receiver
        _migration.verify_shipment(shipment, self.kv_fingerprint())
        self._import_seq += 1
        _faults.migration_admit_fault(self.replica_index, self._import_seq)
        prompt = tuple(int(t) for t in shipment.prompt)
        rid = ticket.request_id
        if rid in self._completions:
            raise ValueError(f"duplicate request_id {rid!r}")
        deadline = (
            time.perf_counter() + float(ticket.deadline_ms) / 1e3
            if ticket.deadline_ms is not None
            else None
        )
        slot = self.pool.acquire(
            rid, len(prompt), int(ticket.max_new_tokens),
            eos_id=ticket.eos_id, prompt_tokens=prompt,
            deadline=deadline, priority=ticket.priority,
        )
        if slot is None:
            raise _migration.MigrationRejected(
                f"shipment {rid!r}: no slot/blocks under the worst-case "
                "reservation — decode replica at capacity"
            )
        # install the payloads this replica does not already share: the
        # receiver's own prefix-cache hits (alloc.shared leading blocks)
        # hold identical bytes by chain-key construction, everything
        # else gets the shipped blocks. A jitted scatter the pool is donated
        # to, like the two tracked programs (so in place), but not one of
        # them: compile_stats stays flat.
        alloc = self.pool.kinds["full"].allocs[slot.index]
        bs = self.pool.block_size
        n_prompt_blocks = (len(prompt) - 1) // bs + 1
        write = [
            (alloc.blocks[j], j)
            for j in range(alloc.shared, n_prompt_blocks)
        ]
        if write:
            ids = jnp.asarray([b for b, _ in write])
            blocks = {
                "k": np.stack([shipment.block_k[j] for _, j in write], axis=1),
                "v": np.stack([shipment.block_v[j] for _, j in write], axis=1),
            }
            self._update_pool(
                lambda cache: (self._install_fn(cache, ids, blocks), None)
            )
        # resume exactly where the colocated path would be after its own
        # prefill: the next decode step re-runs the last prompt token at
        # pos P-1 (idempotent KV rewrite), so the first emitted token —
        # and every one after — is token-identical to generate()
        slot.pos = len(prompt) - 1
        slot.pending_token = prompt[-1]
        if self._speculate_k > 0:
            self._history[rid] = list(prompt)
        completion = Completion(rid)
        if self._tracer is not None:
            # Seed the receiving hop from the shipment's lineage context:
            # the new trace knows its parent attempt, hop index, and the
            # TTFT seconds spent upstream (the gap since the context's
            # send stamp lands in the "transfer" component).
            slot.trace = self._tracer.start(
                rid, len(prompt), int(ticket.max_new_tokens),
                replica=self.replica_index, retries=ticket.retries,
                ctx=shipment.trace_ctx,
            )
        with self._work:
            self._completions[rid] = completion
            if ticket.on_token is not None:
                self._on_token[rid] = ticket.on_token
        self.stats["prefills"] += 1
        reg = _obs.registry()
        if reg is not None:
            reg.counter("rlt_serve_requests_total").inc()
        return completion

    # ------------------------------------------------------------------ #
    # deadlines + shedding
    # ------------------------------------------------------------------ #
    def _slo_breached(self) -> bool:
        mon = self.slo_monitor
        if mon is None:
            return False
        try:
            return bool(mon.serving_breached())
        except AttributeError:
            return bool(mon.breached())

    def _on_queue_expired(self, req: Request) -> None:
        """Scheduler evicted a queued request past its deadline."""
        self._expire(req.request_id, req.trace)

    def _evict_expired_slots(self) -> None:
        """Evict decoding requests past their deadline: fail the
        completion with ``finish_reason="expired"`` (partial tokens stay
        readable) and recycle the slot's KV capacity immediately."""
        now = time.perf_counter()
        for slot in self.pool.active_slots():
            if slot.deadline is not None and now > slot.deadline:
                if slot.export_pending:
                    # expiring a parked export: drop the record and unpin
                    # its chains so they become evictable again
                    with self._work:
                        rec = self._exports.pop(slot.request_id, None)
                    if rec is not None:
                        self.pool.kinds["full"].allocator.unpin(rec["pinned"])
                self._expire(slot.request_id, slot.trace)
                self.pool.release(slot.index)

    def _expire(self, request_id: str, trace: Optional[Any]) -> None:
        self._finish(request_id, "expired")
        if trace is not None:
            self._tracer.finish(trace, "expired")
        reg = _obs.registry()
        if reg is not None:
            reg.counter(_metrics.SERVE_DEADLINE_EXPIRED_METRIC).inc()

    # ------------------------------------------------------------------ #
    # loop thread + lifecycle
    # ------------------------------------------------------------------ #
    def start(self) -> None:
        """Spawn the serving loop thread (idempotent)."""
        with self._work:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="rlt-serve-engine"
            )
            self._thread.start()

    def _loop(self) -> None:
        led = self._goodput
        while True:
            # everything between two ticks is loop wait, so that tick_s +
            # loop_wait_s is this thread's wall time
            t_wait = time.perf_counter()
            try:
                with self._work:
                    if not self._tick_due():
                        self._cycle_from = None  # no tick cycle holds a wait
                        with _obs.phase_span("rlt.serve.wait_work"):
                            while not self._tick_due():
                                if self._stop_when_idle:
                                    return
                                if led is not None:
                                    led.enter("idle")
                                self._work.wait(timeout=0.05)
            finally:
                self.stats["loop_wait_s"] += time.perf_counter() - t_wait
            try:
                self.step()
            except Exception as e:  # fail every in-flight request loudly
                self._fail_all(e)
                return

    def _tick_due(self) -> bool:
        """Under ``self._work``: the scheduler has work, a dispatched tick
        is unread (its rows may all have stopped since, so no slot says so),
        or migration work needs a tick even when it is idle."""
        return bool(
            self.scheduler.has_work()
            or self._inflight is not None
            or self._pending_imports
            or self._export_actions
        )

    def _fail_all(self, error: BaseException) -> None:
        """Fail every queued and in-flight request with ``error`` and mark
        the engine dead. Where a program raised, the pool is gone too (it
        was donated to that program: ``_update_pool``), so there is nothing
        to resume from: ``failed`` stays set, ``step()`` refuses to run
        again, and the replica is relaunched, not restarted."""
        if self.failed is None:
            self.failed = error
        # the tick in flight is never read: its rows' requests fail with
        # every other below
        tick, self._inflight = self._inflight, None
        if tick is not None:
            self.stats["dropped_row_steps"] += len(tick.rows)
        if self._goodput is not None:
            # the time from here until a successor engine adopts the
            # ledger is unplanned recovery, not idle
            self._goodput.enter("fault_recovery")
        for req in self.scheduler.drain_queue():
            self._finish(req.request_id, "error", error)
            if req.trace is not None:
                self._tracer.finish(req.trace, "error")
        for slot in self.pool.active_slots():
            self._finish(slot.request_id, "error", error)
            if slot.trace is not None:
                self._tracer.finish(slot.trace, "error")
            self.pool.release(slot.index)

    @property
    def alive(self) -> bool:
        """False once the engine loop has died (``_fail_all`` ran) — the
        replica is unusable and must be discarded/relaunched. A never-
        started engine (single-threaded driving) counts as alive."""
        if self.failed is not None:
            return False
        thread = self._thread
        return thread is None or thread.is_alive()

    def handback_queued(self) -> List[Dict[str, Any]]:
        """Preemption/drain-timeout path: stop admission and hand back
        every queued (not yet admitted) request as a resubmittable spec.

        Their completions finish with ``finish_reason="cancelled"`` (no
        error): the journal treats that as "resubmit elsewhere, no
        failure charged", so a drained replica's backlog migrates
        instead of being silently dropped."""
        with self._work:
            self._closed = True
        if self._goodput is not None:
            self._goodput.enter("drain")
        out: List[Dict[str, Any]] = []
        for req in self.scheduler.drain_queue():
            self._finish(req.request_id, "cancelled")
            if req.trace is not None:
                self._tracer.finish(req.trace, "cancelled")
            out.append(
                {
                    "request_id": req.request_id,
                    "prompt": list(req.tokens),
                    "max_new_tokens": req.max_new_tokens,
                    "eos_id": req.eos_id,
                    "priority": req.priority,
                    "deadline": req.deadline,
                    "retries": req.retries,
                    "tenant": req.tenant,
                }
            )
        return out

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        """Single-threaded drive: step until queue and pool are empty and
        the last dispatched tick is retired."""
        for _ in range(max_steps):
            if not (self.scheduler.has_work() or self._inflight is not None):
                return
            self.step()
        raise RuntimeError(f"still busy after {max_steps} steps")

    def drain(self, timeout: Optional[float] = 60.0) -> None:
        """Stop admitting; finish in-flight + queued work; stop the loop."""
        if self._goodput is not None:
            self._goodput.enter("drain")
        with self._work:
            self._closed = True
            self._stop_when_idle = True
            thread = self._thread
            self._work.notify_all()
        if thread is not None:
            thread.join(timeout)
        else:
            self.run_until_idle()

    def shutdown(self, drain: bool = True) -> None:
        """``drain=False`` cancels queued requests and fails in-flight
        ones instead of finishing them."""
        if drain:
            self.drain()
            return
        with self._work:
            self._closed = True
            self._stop_when_idle = True
            thread = self._thread
            self._work.notify_all()
        self._fail_all(EngineClosed("engine shut down without drain"))
        if thread is not None:
            thread.join(5.0)

    # ------------------------------------------------------------------ #
    # views
    # ------------------------------------------------------------------ #
    def load(self) -> Dict[str, float]:
        """Routing + autoscaling signal for the replica front door.

        ``ttft_p95_ms`` is the p95 of the last ~128 first-token
        latencies (0.0 until any request finishes its first token) —
        the latency half of the autoscaler's scale-up condition.
        ``itl_p99_ms`` is the p99 of the last ~256 inter-token
        latencies — the decode-pool scale signal under disaggregation.
        ``role`` threads the pool membership through load beats so the
        router and autoscaler can filter per pool."""
        from ray_lightning_tpu.observability.metrics import percentile

        ttfts = list(self._recent_ttfts)
        p95 = percentile(ttfts, 95.0) * 1000.0 if ttfts else 0.0
        itls = list(self._recent_itls)
        itl_p99 = percentile(itls, 99.0) * 1000.0 if itls else 0.0
        return {
            "queue_depth": self.scheduler.queue_depth,
            "active": self.pool.occupancy,
            "ttft_p95_ms": round(p95, 3),
            "itl_p99_ms": round(itl_p99, 3),
            "role": self._role,
        }

    def drain_request_records(self) -> List[Dict[str, Any]]:
        """Pop finished-request trace records (``requests.jsonl`` lines).

        Empty when telemetry is off. Replica beat loops ship these to the
        driver aggregator; local callers can hand them to
        ``observability.aggregator.write_local_dump``.
        """
        if self._tracer is None:
            return []
        return self._tracer.drain()

    def slot_utilization(self) -> float:
        steps = self.stats["decode_steps"]
        if not steps:
            return 0.0
        return self.stats["busy_slot_steps"] / (steps * self.pool.num_slots)

    def describe(self) -> Dict[str, Any]:
        out = dict(self.stats)
        out.update(self.pool.stats())
        out.update(self.compile_stats())
        out["slot_utilization"] = round(self.slot_utilization(), 4)
        out["block_utilization"] = round(self.pool.block_utilization(), 4)
        out["queue_depth"] = self.scheduler.queue_depth
        return out

    def cost_summary(self) -> Dict[str, Any]:
        """Analytic HLO cost of the two compiled serving programs.

        AOT-lowers prefill (at its last rung, ``max_prompt_len``) and decode
        with dummy arguments matching the
        :meth:`step` call-site shapes/dtypes, publishes the
        ``rlt_step_flops``/``rlt_step_bytes``/collective gauges labeled
        ``program=serve_prefill|serve_decode``, and returns the per-program
        reports with analytic roofline verdicts, the bytes each executable
        updates in place (``alias_bytes``: the donated pool, where the
        program's writes leave it where it is) and ``pool_bytes`` to hold
        that against. With the compile cache on,
        the analysis reuses the cached executable (the one the serving loop
        dispatches), so this is near-free on a warm cache instead of paying
        a second compile."""
        from ray_lightning_tpu import observability as _obs2
        from ray_lightning_tpu.observability import profiler as _profiler

        # a name once: of prefill's rungs the last
        programs = {
            name: (fn, args) for name, fn, args in self._program_specs()
        }
        pool_bytes = sum(int(a.nbytes) for a in self.pool.cache.values())
        out: Dict[str, Any] = {}
        reg = _obs2.registry()
        for name, (fn, args) in programs.items():
            rep = _profiler.analyze_jitted(fn, *args, program=name)
            if rep is None:
                out[name] = None
                continue
            if reg is not None:
                _profiler.publish_cost_report(reg, rep)
            d = rep.to_dict()
            d["roofline"] = _profiler.roofline(rep)
            # beside alias_bytes: a program that updates the pool in place
            # aliases at least this much
            d["pool_bytes"] = pool_bytes
            out[name] = d
        return out
