"""Slot-based KV-cache pool for continuous-batching inference.

One device allocation, many requests: the pool owns a single
``init_kv_cache(cfg, batch=num_slots, max_len)`` cache whose BATCH rows
are slots. A request is admitted into a free slot, decoded in lockstep
with every other occupied slot by one ``decode_step_ragged`` call (each
row at its own position), and recycled on EOS or max-tokens. The cache
tensor itself never reallocates — admission and recycling are pure host
bookkeeping, which is what keeps the steady state at ZERO recompiles:
the device only ever sees the one [L, num_slots, Hkv, C, D] shape.

Slot isolation is structural: ``decode_step_ragged`` scatters each row's
(k, v) into its own batch row and masks attention per row against that
row's own position, so a freed slot's stale keys are never attendable by
its next tenant — prefill overwrites positions [0, P) and the validity
mask hides everything past the row's position anyway.

Occupancy accounting feeds the serving gauges
(``rlt_serve_slot_occupancy``, ``rlt_serve_slot_highwater``) and the
bench sweep's slot-utilization number (busy-slot-steps / decode-steps /
num_slots).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ray_lightning_tpu import observability as _obs


@dataclass
class Slot:
    """Host-side state of one cache row.

    ``pos`` is the position of ``pending_token`` — the token the NEXT
    batched decode step feeds for this row. After a prefill of P prompt
    tokens the cache holds positions [0, P) and ``pos = P - 1`` with
    ``pending_token = prompt[-1]``: the first decode step rewrites that
    last position's (k, v) with identical values and yields the logits
    for position P, i.e. the request's FIRST sampled token. That is what
    lets one jitted decode step serve both "first token after prefill"
    and every later token — there is no separate first-token program.
    """

    index: int
    request_id: Optional[str] = None
    pos: int = -1
    pending_token: int = 0
    prompt_len: int = 0
    generated: int = 0
    max_new_tokens: int = 0
    eos_id: Optional[int] = None
    admitted_at: float = 0.0
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    # absolute deadline (time.perf_counter domain) and priority class of
    # the tenant — the engine evicts expired slots at tick start so a
    # dead-on-arrival stream stops burning decode flops and its KV
    # capacity recycles immediately
    deadline: Optional[float] = None
    priority: int = 0
    # the tenant's RequestTrace (None when telemetry is off or the
    # request was not head-sampled) — the decode loop's only per-token
    # tracing cost is reading this attribute
    trace: Optional[object] = None
    # disaggregated serving: a prefill-role engine parks a freshly
    # prefilled slot here while its KV shipment is in flight — the
    # decode loop skips the slot, and a failed migration clears the flag
    # so the request falls back to decoding in place
    export_pending: bool = False

    @property
    def occupied(self) -> bool:
        return self.request_id is not None

    def reset(self) -> None:
        self.request_id = None
        self.pos = -1
        self.pending_token = 0
        self.prompt_len = 0
        self.generated = 0
        self.max_new_tokens = 0
        self.eos_id = None
        self.admitted_at = 0.0
        self.first_token_at = None
        self.last_token_at = None
        self.deadline = None
        self.priority = 0
        self.trace = None
        self.export_pending = False


class KVSlotPool:
    """num_slots cache rows + free-list + occupancy counters.

    The pool owns the cache arrays (``self.cache``). The engine DONATES
    them to every program that writes them and rebinds ``self.cache`` to
    the program's output (``InferenceEngine._update_pool``): the same
    buffers, updated in place, under new array objects. An array read off
    ``self.cache`` earlier is deleted by the next tick, so keep a host copy
    (``np.array``), not the array. Sliding-window
    configs are refused: their rolling buffers are per-POSITION-modulo
    structures and the serving path sizes every slot to ``max_len``
    (full cache) so that admit/recycle never has to reason about wrap
    soundness per tenant.
    """

    layout = "slot"

    def __init__(self, cfg, num_slots: int, max_len: int):
        from ray_lightning_tpu.models.generation import init_kv_cache

        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if cfg.sliding_window:
            raise ValueError(
                "the serving KV pool requires dense-causal configs: a "
                "rolling sliding-window buffer wraps slots at pos % W, "
                "which is unsound when the same row is recycled across "
                "requests at unrelated depths"
            )
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.cache = init_kv_cache(cfg, self.num_slots, self.max_len)
        self.slots: List[Slot] = [Slot(i) for i in range(self.num_slots)]
        self._free: List[int] = list(range(self.num_slots - 1, -1, -1))
        # lifetime accounting
        self.admitted_total = 0
        self.recycled_total = 0
        self.highwater = 0
        # per-slot tenancy history (slot -> request ids served) — what the
        # recycling e2e asserts on, and `stats()` summarizes
        self.tenancies: Dict[int, List[str]] = {
            i: [] for i in range(self.num_slots)
        }

    # ------------------------------------------------------------------ #
    # admission / recycling
    # ------------------------------------------------------------------ #
    def acquire(
        self,
        request_id: str,
        prompt_len: int,
        max_new_tokens: int,
        eos_id: Optional[int] = None,
        prompt_tokens: Optional[Sequence[int]] = None,
        deadline: Optional[float] = None,
        priority: int = 0,
    ) -> Optional[Slot]:
        """Claim a free slot for a request; ``None`` when the pool is full.

        Length validation is the pool's contract: the final decode for
        this request reads position ``prompt_len - 1 + max_new_tokens - 1``
        which must fit the slot's cache length. ``prompt_tokens`` is
        accepted for interface parity with :class:`~.paged_kv.PagedKVPool`
        (which uses it for prefix matching) and ignored here.
        """
        del prompt_tokens  # slot layout has no prefix sharing
        if prompt_len < 1:
            raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"request {request_id!r} needs {prompt_len} prompt + "
                f"{max_new_tokens} new tokens = "
                f"{prompt_len + max_new_tokens} positions, but pool slots "
                f"hold max_len={self.max_len}"
            )
        if not self._free:
            return None
        slot = self.slots[self._free.pop()]
        slot.request_id = request_id
        slot.prompt_len = int(prompt_len)
        slot.max_new_tokens = int(max_new_tokens)
        slot.eos_id = eos_id
        slot.deadline = deadline
        slot.priority = int(priority)
        slot.generated = 0
        slot.admitted_at = time.perf_counter()
        slot.first_token_at = None
        slot.last_token_at = None
        self.admitted_total += 1
        self.tenancies[slot.index].append(request_id)
        self.highwater = max(self.highwater, self.occupancy)
        self._publish_gauges()
        return slot

    def release(self, index: int) -> Slot:
        """Recycle a slot back to the free list (EOS / max-tokens / error)."""
        slot = self.slots[index]
        if not slot.occupied:
            raise ValueError(f"slot {index} is already free")
        slot.reset()
        self._free.append(index)
        self.recycled_total += 1
        self._publish_gauges()
        return slot

    # ------------------------------------------------------------------ #
    # views
    # ------------------------------------------------------------------ #
    @property
    def occupancy(self) -> int:
        return self.num_slots - len(self._free)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def active_slots(self) -> List[Slot]:
        """Occupied slots in index order (the decode batch)."""
        return [s for s in self.slots if s.occupied]

    def utilization(self) -> float:
        return self.occupancy / self.num_slots

    def stats(self) -> Dict[str, object]:
        return {
            "layout": self.layout,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "occupancy": self.occupancy,
            "highwater": self.highwater,
            "admitted_total": self.admitted_total,
            "recycled_total": self.recycled_total,
            "tenants_per_slot": {
                i: len(v) for i, v in self.tenancies.items()
            },
        }

    def _publish_gauges(self) -> None:
        reg = _obs.registry()
        if reg is not None:
            reg.gauge("rlt_serve_slot_occupancy").set(self.occupancy)
            reg.gauge("rlt_serve_slot_highwater").set(self.highwater)
