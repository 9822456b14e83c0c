"""Continuous-batching scheduler: bounded admission queue -> KV slots.

The scheduler is pure host logic (no device work, no jax import) so its
policy is unit-testable without a model. Each engine iteration calls
:meth:`ContinuousBatchScheduler.tick`, which returns a :class:`Plan`:

- ``prefills`` — up to ``max_prefills_per_tick`` queued requests paired
  with the free slots they were just admitted into. Bounding prefills
  per tick is the prefill/decode interleave knob: each prefill is a
  full-prompt forward that stalls every running stream for one
  iteration, so admitting at most N per tick caps the inter-token
  latency hit on in-flight requests while still draining the queue.
- ``decode_slots`` — every occupied slot (including the just-admitted
  ones: their first decode yields their first sampled token, so a
  prefill and the request's first token land in the SAME iteration).

Admission order is FIFO. The queue is bounded — a full queue raises
:class:`RequestQueueFull` at submit time rather than buffering
unboundedly, which is the back-pressure signal a front door needs to
shed load instead of silently growing latency.

Deadline awareness: a request may carry an absolute ``deadline``
(``time.perf_counter`` domain). Each tick sweeps expired requests out of
the queue BEFORE admission — there is no point prefilling work whose
client already gave up — and reports them through ``on_evict`` so the
engine can fail their completions with ``finish_reason="expired"``.

Head-of-line policy: strict FIFO by default (``head_skip_limit=0``) — a
deferred head admits nothing behind it, so long prompts cannot be
starved by a stream of short ones. Setting ``head_skip_limit=N`` allows
up to N later requests to be scanned for admission while the head is
deferred, bounded by ``head_aging_ticks``: once the head has been
deferred that many ticks, skip-ahead is suspended (the tick admits
nothing past it) until the head finally fits — an aging bound that
converts possible starvation into bounded extra latency.

Multi-tenant QoS (:meth:`ContinuousBatchScheduler.configure_tenants`):
installing a :class:`~.tenancy.TenantRegistry` replaces the single FIFO
with one FIFO *per tenant* and admits across them by deficit round-robin
(DRR): a rotation pointer walks the active tenants, each tenant earns
``weight`` credit when its turn arrives and spends one credit per
admission, so sustained throughput converges to the weight ratio while
each tenant's queue stays FIFO internally. The head-skip/aging window
applies PER TENANT QUEUE — a starved tenant's head can only be aged
past by its own tenant's skips, never by another tenant's traffic. With
no registry configured (the default) the original single-queue code
path runs unchanged, byte-identical to the single-tenant scheduler.
"""
from __future__ import annotations

import threading

from ray_lightning_tpu.analysis.sanitizer import rlt_lock
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from ray_lightning_tpu import observability as _obs
from ray_lightning_tpu.observability import metrics as _metrics
from ray_lightning_tpu.serving.paged_kv import PagedKVPool, Slot


class RequestQueueFull(RuntimeError):
    """The admission queue is at capacity — shed load or retry later."""


@dataclass
class Request:
    """One generation request (token ids in, token ids out)."""

    request_id: str
    tokens: Tuple[int, ...]
    max_new_tokens: int
    eos_id: Optional[int] = None
    on_token: Optional[Callable[[str, int], Any]] = None
    submitted_at: float = field(default_factory=time.perf_counter)
    # absolute deadline (perf_counter domain); None = no TTL. Expired
    # requests are swept from the queue each tick and evicted from decode
    # slots by the engine.
    deadline: Optional[float] = None
    # priority class: 0 = highest. The shed policy drops priority >= 1
    # work first when the queue or the SLO budget is melting down.
    priority: int = 0
    # attempt number (0 = first submission) — stamped by the request
    # journal on resubmission so traces/records expose the retry count
    retries: int = 0
    # ticks this request spent as a deferred queue head (aging signal)
    deferred_ticks: int = 0
    # request-scoped trace context (reqtrace.RequestTrace), minted at
    # engine submit; None when telemetry is off or head sampling dropped it
    trace: Optional[Any] = None
    # tenant identity (multi-tenant QoS); None = classless traffic,
    # which rides the default DRR queue when tenancy is configured and
    # is indistinguishable from today's requests when it is not
    tenant: Optional[str] = None

    @property
    def prompt_len(self) -> int:
        return len(self.tokens)


@dataclass
class Plan:
    """What one engine iteration executes."""

    prefills: List[Tuple[Request, Slot]]
    decode_slots: List[Slot]

    @property
    def has_work(self) -> bool:
        return bool(self.prefills or self.decode_slots)


class ContinuousBatchScheduler:
    """FIFO admission from a bounded queue into the slot pool."""

    def __init__(
        self,
        pool: PagedKVPool,
        max_queue: int = 256,
        max_prefills_per_tick: int = 1,
        head_skip_limit: int = 0,
        head_aging_ticks: int = 16,
    ):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_prefills_per_tick < 1:
            raise ValueError(
                "max_prefills_per_tick must be >= 1, got "
                f"{max_prefills_per_tick}"
            )
        if head_skip_limit < 0:
            raise ValueError(
                f"head_skip_limit must be >= 0, got {head_skip_limit}"
            )
        if head_aging_ticks < 1:
            raise ValueError(
                f"head_aging_ticks must be >= 1, got {head_aging_ticks}"
            )
        self.pool = pool
        self.max_queue = int(max_queue)
        self.max_prefills_per_tick = int(max_prefills_per_tick)
        self.head_skip_limit = int(head_skip_limit)
        self.head_aging_ticks = int(head_aging_ticks)
        self._queue: Deque[Request] = deque()
        self._lock = rlt_lock("serving.scheduler.ContinuousBatchScheduler._lock")
        self.queued_total = 0
        self.rejected_total = 0
        self.deferred_total = 0  # ticks the queue head waited for capacity
        self.expired_total = 0  # queued requests swept past their deadline
        self.skipped_total = 0  # admissions that jumped a deferred head
        # engine hook: called (outside the lock) with each queued Request
        # swept past its deadline so its Completion can be failed
        self.on_evict: Optional[Callable[[Request], Any]] = None
        # ---- multi-tenant QoS (None = single-queue path, unchanged) --- #
        self._tenancy: Optional[Any] = None
        self._tqueues: Dict[str, Deque[Request]] = {}
        self._deficit: Dict[str, float] = {}
        self._order: Deque[str] = deque()  # DRR rotation of active tenants
        self._in_order: set = set()
        self.admitted_by_tenant: Dict[str, int] = {}

    # ------------------------------------------------------------------ #
    # multi-tenant QoS
    # ------------------------------------------------------------------ #
    def configure_tenants(self, registry: Any) -> None:
        """Install a :class:`~.tenancy.TenantRegistry` and switch
        admission to per-tenant DRR queues. Requests already queued are
        migrated into their tenants' queues in FIFO order. Passing
        ``None`` is a no-op (the single-queue path stays active)."""
        if registry is None:
            return
        with self._lock:
            self._tenancy = registry
            backlog = list(self._queue)
            self._queue.clear()
            for req in backlog:
                self._tenant_enqueue(req)

    @staticmethod
    def _tenant_key(req: Request) -> str:
        return req.tenant or ""

    def _tenant_enqueue(self, req: Request) -> None:
        """Append to the request's tenant queue (lock held)."""
        key = self._tenant_key(req)
        q = self._tqueues.get(key)
        if q is None:
            q = self._tqueues[key] = deque()
        q.append(req)
        if key not in self._in_order:
            self._order.append(key)
            self._in_order.add(key)

    def _retire_tenant(self, key: str) -> None:
        """Drop a drained tenant from the DRR rotation (lock held).
        Classic DRR: an emptied queue forfeits its residual deficit, so
        an idle tenant cannot bank credit for a later burst."""
        self._deficit.pop(key, None)
        self._in_order.discard(key)
        try:
            self._order.remove(key)
        except ValueError:
            pass

    def tenant_depths(self) -> Dict[str, int]:
        """Queue depth per tenant key ("" = classless traffic); empty
        dict when tenancy is not configured."""
        with self._lock:
            return {k: len(q) for k, q in self._tqueues.items()}

    # ------------------------------------------------------------------ #
    # producer side (any thread)
    # ------------------------------------------------------------------ #
    def submit(self, request: Request) -> None:
        """Enqueue or raise :class:`RequestQueueFull` (bounded queue)."""
        # validate against the pool NOW so an oversized request fails at
        # the submitter, not inside the engine loop where nobody catches it
        if request.prompt_len + request.max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"request {request.request_id!r}: {request.prompt_len} "
                f"prompt + {request.max_new_tokens} new tokens exceed the "
                f"pool's max_len={self.pool.max_len}"
            )
        with self._lock:
            if self._depth_locked() >= self.max_queue:
                self.rejected_total += 1
                raise RequestQueueFull(
                    f"admission queue is full ({self.max_queue} waiting); "
                    "add replicas, raise max_queue, or retry with backoff"
                )
            if self._tenancy is not None:
                self._tenant_enqueue(request)
            else:
                self._queue.append(request)
            self.queued_total += 1
            depth = self._depth_locked()
        self._publish_depth(depth)

    def _depth_locked(self) -> int:
        if self._tenancy is not None:
            return sum(len(q) for q in self._tqueues.values())
        return len(self._queue)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return self._depth_locked()

    # ------------------------------------------------------------------ #
    # engine side (the loop thread)
    # ------------------------------------------------------------------ #
    def tick(self) -> Plan:
        """Admit queued requests into free capacity (bounded per tick)
        and return the iteration plan.

        Admission is peek-then-acquire: the pool may refuse the queue
        head (no free slot, or not enough KV blocks for
        the prompt plus its worst-case growth reservation), in which
        case the head stays queued and, by default, this tick admits
        nothing more. Strict FIFO head-of-line blocking is deliberate:
        skipping ahead to a smaller request would starve long prompts
        under sustained short-request load. ``head_skip_limit`` opens a
        bounded skip-ahead window behind a deferred head, and
        ``head_aging_ticks`` closes it again once the head has waited
        too long (see the module docstring)."""
        prefills: List[Tuple[Request, Slot]] = []
        expired: List[Request] = []
        if self._tenancy is not None:
            return self._tick_drr()
        with self._lock:
            if any(r.deadline is not None for r in self._queue):
                now = time.perf_counter()
                kept: Deque[Request] = deque()
                for req in self._queue:
                    if req.deadline is not None and now > req.deadline:
                        expired.append(req)
                        self.expired_total += 1
                    else:
                        kept.append(req)
                self._queue = kept
            i = 0
            while (
                i < len(self._queue)
                and len(prefills) < self.max_prefills_per_tick
            ):
                req = self._queue[i]
                # aging bound: an over-deferred head closes the
                # skip-ahead window — nothing may jump it until it admits
                if i > 0 and (
                    self._queue[0].deferred_ticks > self.head_aging_ticks
                ):
                    break
                slot = self.pool.acquire(
                    req.request_id,
                    req.prompt_len,
                    req.max_new_tokens,
                    eos_id=req.eos_id,
                    prompt_tokens=req.tokens,
                    deadline=req.deadline,
                    priority=req.priority,
                )
                if slot is None:  # back-pressure: keep the request queued
                    if i == 0:
                        req.deferred_ticks += 1
                        self.deferred_total += 1
                        if req.trace is not None:
                            req.trace.deferred()
                        if self.head_skip_limit == 0:
                            break
                    i += 1
                    if i > self.head_skip_limit:
                        break
                    continue
                del self._queue[i]
                if i > 0:
                    self.skipped_total += 1
                if req.trace is not None:
                    req.trace.admitted(slot.index)
                    slot.trace = req.trace
                prefills.append((req, slot))
                # do not advance i: the next element shifted into place
            depth = len(self._queue)
        self._publish_depth(depth)
        if expired and self.on_evict is not None:
            for req in expired:
                self.on_evict(req)
        return Plan(prefills=prefills, decode_slots=self.pool.active_slots())

    def _tick_drr(self) -> Plan:
        """Tenancy-configured tick: deadline sweep over every tenant
        queue, then deficit-round-robin admission.

        The rotation pointer stays on one tenant until that tenant's
        credit is spent, its queue drains, or its head is blocked by the
        pool — then moves on. Credit (``weight`` per arrival, one unit
        per admission) is what converges sustained admissions to the
        weight ratio; the cap bounds how large a catch-up burst a
        long-blocked tenant can bank. The head-skip/aging window runs
        inside each tenant queue with that queue's own head, so
        cross-tenant traffic can never age past a starved tenant's head
        (the per-tenant aging fix)."""
        prefills: List[Tuple[Request, Slot]] = []
        expired: List[Request] = []
        with self._lock:
            for key, q in self._tqueues.items():
                if not any(r.deadline is not None for r in q):
                    continue
                now = time.perf_counter()
                kept: Deque[Request] = deque()
                for req in q:
                    if req.deadline is not None and now > req.deadline:
                        expired.append(req)
                        self.expired_total += 1
                    else:
                        kept.append(req)
                self._tqueues[key] = kept
            while self._order and len(prefills) < self.max_prefills_per_tick:
                key = self._order[0]
                # dict lookup, not a queue read (rltcheck: .get() on a
                # mapping named *queues trips the blocking-under-lock lint)
                q = self._tqueues[key] if key in self._tqueues else None
                if not q:
                    self._retire_tenant(key)
                    continue
                if self._deficit.get(key, 0.0) < 1.0:
                    weight = float(self._tenancy.weight(key or None))
                    cap = max(weight, 1.0) + float(self.max_prefills_per_tick)
                    self._deficit[key] = min(
                        self._deficit.get(key, 0.0) + weight, cap
                    )
                if self._admit_tenant(key, q, prefills):
                    # pool block: the SHARED server refused this tenant's
                    # head — not the tenant's fault, so the pointer (and
                    # its remaining credit) stays put and the next tick
                    # resumes right here. Rotating here would hand every
                    # fresh tick's pool capacity to whoever sorts first,
                    # collapsing the weight ratio to round-robin.
                    break
                if not q:
                    # drained: residual credit is forfeit (classic DRR —
                    # an idle tenant must not bank credit while absent)
                    self._retire_tenant(key)
                elif self._deficit.get(key, 0.0) < 1.0:
                    self._order.rotate(-1)  # credit spent: next tenant
                # else: tick prefill budget exhausted with credit left —
                # loop condition exits, pointer stays for the next tick
            depth = self._depth_locked()
            tenant_depths = {k: len(q) for k, q in self._tqueues.items()}
        self._publish_depth(depth, tenant_depths)
        if expired and self.on_evict is not None:
            for req in expired:
                self.on_evict(req)
        return Plan(prefills=prefills, decode_slots=self.pool.active_slots())

    def _admit_tenant(
        self,
        key: str,
        q: Deque[Request],
        prefills: List[Tuple[Request, Slot]],
    ) -> bool:
        """Admit from one tenant queue while credit/budget remain (lock
        held). Returns True when the queue head was blocked by the pool
        (deferral charged to THIS tenant's head only)."""
        i = 0
        while (
            i < len(q)
            and len(prefills) < self.max_prefills_per_tick
            and self._deficit.get(key, 0.0) >= 1.0
        ):
            req = q[i]
            # per-tenant aging: an over-deferred head closes this
            # tenant's skip-ahead window; other tenants are unaffected
            if i > 0 and (q[0].deferred_ticks > self.head_aging_ticks):
                return True
            slot = self.pool.acquire(
                req.request_id,
                req.prompt_len,
                req.max_new_tokens,
                eos_id=req.eos_id,
                prompt_tokens=req.tokens,
                deadline=req.deadline,
                priority=req.priority,
            )
            if slot is None:
                if i == 0:
                    req.deferred_ticks += 1
                    self.deferred_total += 1
                    if req.trace is not None:
                        req.trace.deferred()
                    if self.head_skip_limit == 0:
                        return True
                i += 1
                if i > self.head_skip_limit:
                    return True
                continue
            del q[i]
            if i > 0:
                self.skipped_total += 1
            if req.trace is not None:
                req.trace.admitted(slot.index)
                slot.trace = req.trace
            prefills.append((req, slot))
            self._deficit[key] = self._deficit.get(key, 0.0) - 1.0
            self.admitted_by_tenant[key] = (
                self.admitted_by_tenant.get(key, 0) + 1
            )
            # do not advance i: the next element shifted into place
        # scanned off the end with requests still queued: the pool
        # refused everything reachable — a block, same as the head paths
        return len(q) > 0 and i >= len(q)

    def has_work(self) -> bool:
        with self._lock:
            queued = bool(self._queue) or any(self._tqueues.values())
        return queued or self.pool.occupancy > 0

    def drain_queue(self) -> List[Request]:
        """Remove and return every queued (not yet admitted) request —
        shutdown path: their completions are failed, not silently lost."""
        with self._lock:
            out = list(self._queue)
            self._queue.clear()
            for key in list(self._tqueues):
                out.extend(self._tqueues[key])
                self._tqueues[key].clear()
                self._retire_tenant(key)
        self._publish_depth(0)
        return out

    def _publish_depth(
        self, depth: int, tenant_depths: Optional[Dict[str, int]] = None
    ) -> None:
        reg = _obs.registry()
        if reg is not None:
            reg.gauge("rlt_serve_queue_depth").set(depth)
            if tenant_depths:
                for key, tdepth in tenant_depths.items():
                    label = reg.tenant_label(key or "default")
                    reg.gauge(
                        _metrics.TENANT_QUEUE_DEPTH_METRIC, tenant=label
                    ).set(tdepth)
