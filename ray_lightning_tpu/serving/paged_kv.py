"""Paged KV cache: block allocation + shared-prefix reuse for serving.

A full-``max_len`` cache row a request would cap resident concurrency at
``num_slots × max_len`` HBM regardless of actual lengths. This module
carves ONE device allocation into fixed-size blocks (``block_size`` tokens
each, knob ``RLT_SERVE_BLOCK_SIZE``) and hands requests exactly the blocks
their positions need:

- :class:`BlockAllocator` — pure host logic (no jax, no model): a free
  list of physical blocks, per-request allocations with a worst-case
  growth RESERVATION (so mid-decode growth can never fail), and a
  hash-chained prefix cache with per-block refcounts and LRU eviction
  of refcount-0 chains. Unit-testable without a device.
- :class:`PagedKVPool` — the device-facing pool the engine drives: owns
  the block-shaped cache arrays, a tree of leaves
  ``[layers, num_blocks, *block]`` whose names, layer counts and block
  shapes the MODEL states (``cfg.serving().paged_block_leaves``: K and V
  of ``[Hkv, block_size, D]`` for the Llama family, one latent row
  ``[block_size, W]`` a group of layers for latent attention),
  the host block-table mirror ([num_slots, max_blocks] int32 — a FIXED
  shape, which is what keeps the paged decode at zero steady-state
  recompiles), and the slot bookkeeping (:class:`Slot`: the host-side
  state of one row of the decode batch), delegating block policy to the
  allocator.

Kinds of leaf. A model states of each leaf its KIND (the fourth entry of
``paged_block_leaves``: 0, or a window's width): a FULL kind's layers
attend every position, so a request holds a block for every position it
has; a WINDOW kind's layers attend the last ``window`` positions only, so a
request holds the blocks of those and no others. Each kind has its own
blocks, its own :class:`BlockAllocator` and its own block table a slot
(``PagedKVPool.kinds``); admission is by every kind, each reserving its own
worst case: a window kind's is the most it holds at once, the window and one
block (:func:`window_blocks`), whatever the request's length. A prompt
longer than the window writes only the window's tail into a window kind
(its write table names the trash block before that); as the request grows,
``ensure_writable`` gives the blocks that fell wholly out of the window back
(``BlockAllocator.give_back``: their table entries become the trash block,
and the decode kernel starts its walk behind them) before it grows, so
growth still cannot fail. How many blocks a kind gets follows from the one
setting there is and from what its slots can hold at most; no setting is
new. Refused for a model with a window kind, by name, when the engine or the
pool is built: prefix sharing (a block shared by prefix would be given back
by the first request it falls out of the window of), and in the engine
speculation, KV migration and a mesh. A config with a uniform
``sliding_window`` whose leaves state no kind (the Llama family) is refused
as before. A pool of one kind has the one form too: ``kinds`` is ``{"full":
...}``, and the tables the programs take are ``{"full": table}``;
``block_tables`` and ``prompt_write_table()`` hand out that one table not by
kind, for a pool of one kind only.

A third kind holds STATE: a leaf whose fourth entry is ``"state"`` is one
fixed-size array a slot a layer (``[layers, num_slots, *shape]``: a linear-
attention layer's ``[H, hd, hd]`` float32 state) that does not grow with the
request. It takes no blocks and has no allocator and no table: row ``i`` is
slot ``i``'s. ``acquire`` zeroes the slot's rows, prefill writes them once
(its write tables carry, under ``"state"``, the slot and the prompt's
length), every decode tick updates them in place, and they are given up with
the slot: whoever held the slot before, by whatever end (finished, expired,
failed), the next request starts from zero. ``stats()`` reports
``state.layers``, ``state.leaves``, ``state.bytes_per_slot`` (all leaves
together) and ``state.slots_used``. A model may state several state leaves of
unlike shape and type (a state-space layer's scan state ``[N, C]`` float32 and
its convolution's tail ``[K - 1, C]`` in the model's type). Refused
over a state kind, by name, when the engine is built: prefix sharing (a
shared block says nothing of the state behind it), speculation, KV migration.
A leaf of the full kind may state any block shape: pooled keys, one a 16
positions, are a leaf of 4 rows a block of 64 under the same tables.

Prefix sharing (the system-prompt amortization):

- a prompt's FULL blocks are identified by a rolling hash chain
  (``H_i = sha256(H_{i-1} || tokens[i*bs:(i+1)*bs])``), so a chain hit
  means every preceding block matched too — a shared prefix is always
  a contiguous range of leading blocks at the same absolute positions,
  which is what makes the cached (k, v) (rope-rotated at absolute
  positions) valid for every request that shares it.
- sharing is COPY-ON-WRITE by construction: the serving decode rewrites
  position ``P - 1`` (the idempotent first-token trick) and then writes
  ``P, P+1, ...``, so the block containing ``P - 1`` and everything
  after is always PRIVATE — a matched block that decode would write is
  silently privatized instead of shared (counted in
  ``cow_private_total``). Shared blocks are therefore immutable while
  referenced and no runtime copy kernel is needed: the private
  replacement's contents are re-established by the request's own
  prefill.
- a request's freshly-written full prompt blocks are REGISTERED in the
  chain cache at admission, so the very next request with the same
  system prompt hits them. On release the refcount drops; refcount-0
  chains stay cached (warm) and are evicted leaf-first in LRU order
  only when the allocator needs their blocks back.

Physical block 0 is the TRASH block: prefill writes of shared (already
cached) block slots and the dummy decode writes of free engine slots are
redirected there, so the fixed-shape prefill/decode programs
never need a "skip this write" branch. Trash contents are garbage and
are never attendable (block tables only reference it at masked
positions).
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from ray_lightning_tpu import observability as _obs

__all__ = [
    "BlockAllocation",
    "BlockAllocator",
    "OutOfBlocks",
    "PagedKVPool",
    "Slot",
    "TRASH_BLOCK",
]

# physical block 0: write-redirect target for shared-prefix prefill slots
# and free-slot dummy decode writes; never allocated, never attendable
TRASH_BLOCK = 0
# a leaf's fourth entry where it holds state a slot and no blocks
STATE = "state"


@dataclass
class Slot:
    """Host-side state of one row of the decode batch (an engine slot).

    ``pos`` is the position of ``pending_token`` — the token the NEXT
    batched decode step feeds for this row. After a prefill of P prompt
    tokens the cache holds positions [0, P) and ``pos = P - 1`` with
    ``pending_token = prompt[-1]``: the first decode step rewrites that
    last position's (k, v) with identical values and yields the logits
    for position P, i.e. the request's FIRST sampled token. That is what
    lets one jitted decode step serve both "first token after prefill"
    and every later token — there is no separate first-token program.

    ``pos`` advances when a step is DISPATCHED (the next position follows
    from this one whatever the token turns out to be), ``generated`` when
    its token is read. ``pending_token`` is ``None`` while that token is
    still on the device: the output of a decode program the host has not
    read, which the next program takes from there. ``admission`` says which
    of the pool's admissions the occupant is, so a step dispatched for an
    earlier occupant of the slot is told from the present one's.
    """

    index: int
    request_id: Optional[str] = None
    admission: int = 0
    pos: int = -1
    pending_token: Optional[int] = 0
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
        self.admission = 0
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


class OutOfBlocks(RuntimeError):
    """A block was requested beyond the allocator's capacity guarantee —
    either a ``grow`` past the request's reservation or an internal
    accounting violation. Admission-time shortages are NOT an error:
    :meth:`BlockAllocator.admit` returns ``None`` (back-pressure)."""


@dataclass
class _ChainNode:
    """One cached prefix block: chain key -> physical block + refcount."""

    block: int
    parent: Optional[bytes]  # parent chain key (None for the first block)
    refcount: int = 0  # active requests referencing this block
    children: int = 0  # cached chain nodes extending this one
    last_used: int = 0  # allocator LRU clock
    pinned: int = 0  # in-flight KV shipments referencing this block


@dataclass
class BlockAllocation:
    """Host-side record of one admitted request's blocks.

    ``blocks[:cached]`` are chain-cache-owned (shared or registered by
    this request — released by refcount, never freed directly);
    ``blocks[cached:]`` are plain private blocks returned to the free
    list on release. ``reserved`` counts the growth blocks this request
    is still guaranteed (decremented by :meth:`BlockAllocator.grow`).
    """

    request_id: str
    blocks: List[int]
    shared: int  # leading blocks reused from the prefix cache (hits)
    cached: int  # leading blocks owned by the chain cache (>= shared)
    chain_keys: List[bytes] = field(default_factory=list)
    reserved: int = 0
    # a window kind holds a moving run of the request's logical blocks:
    # ``blocks[i]`` is logical block ``first + i``; ``total`` logical blocks
    # over the request's life, never more than ``peak`` of them at once
    first: int = 0
    total: int = 0
    peak: int = 0


def blocks_for(prompt_len: int, max_new_tokens: int, block_size: int) -> int:
    """Worst-case blocks a request needs: cache positions run
    [0, prompt_len + max_new_tokens - 2] (the final sampled token is
    output, never written)."""
    last_pos = prompt_len + max_new_tokens - 2
    return last_pos // block_size + 1


def window_blocks(window: int, block_size: int) -> int:
    """The most blocks the ``window`` positions ``[pos - window + 1, pos]``
    can touch: ``window / block_size + 1`` where the block divides the
    window (4096 positions in blocks of 16: 257)."""
    return -(-(window - 1) // block_size) + 1


class BlockAllocator:
    """Fixed-size block pool + refcounted prefix-chain cache (pure host).

    Capacity accounting is reservation-based: :meth:`admit` only
    succeeds when the prompt's private blocks AND the request's
    worst-case growth fit in ``free + evictable-cached`` blocks, so
    :meth:`grow` can never fail mid-decode — a request that was admitted
    always finishes. Requests that finish early (EOS) return their
    unused reservation immediately, which is the capacity win over a
    full-length row a request.

    ``window`` > 0 makes it the allocator of a WINDOW kind of leaf, whose
    layers attend the last ``window`` positions only: a request holds the
    blocks of those positions and no others. Admission allocates the
    prompt's tail (the blocks of positions ``[prompt_len - window,
    prompt_len)``) and reserves up to the most it can hold at once
    (:func:`window_blocks`, or its whole life's blocks if that is fewer);
    :meth:`give_back` frees the blocks that fell out of the window and
    turns them into reservation again while the request still has blocks to
    grow into, so growth cannot fail here either. No prefix sharing: a
    block that one request gives back another may still need.
    """

    def __init__(
        self, num_blocks: int, block_size: int, prefix_cache: bool = True,
        window: int = 0,
    ):
        if window and prefix_cache:
            raise ValueError(
                "prefix sharing over a window kind of leaf: a shared block "
                "would be given back by the first request it falls out of "
                "the window of (prefix_cache=False only)"
            )
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (1 data block + the trash "
                f"block), got {num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.prefix_cache_enabled = bool(prefix_cache)
        self.window = int(window)
        # block 0 is TRASH: excluded from the free list forever
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._allocs: Dict[str, BlockAllocation] = {}
        self._chains: Dict[bytes, _ChainNode] = {}
        self._idle_cached = 0  # chain nodes with refcount == 0 (evictable)
        self._pinned_idle = 0  # of those, pinned by an in-flight shipment
        self._reserved_total = 0
        self._clock = 0
        # lifetime counters (stats() + the serving gauges)
        self.admitted_total = 0
        self.released_total = 0
        self.grown_total = 0
        self.prefix_hits_total = 0  # blocks served from the chain cache
        self.prefix_misses_total = 0  # full blocks newly registered
        self.cow_private_total = 0  # matched blocks privatized (decode writes)
        self.evictions_total = 0
        self.deferred_total = 0  # admissions refused for lack of blocks
        self.blocks_highwater = 0  # peak used_blocks over the lifetime
        self.given_back_total = 0  # blocks freed as they left the window

    # ------------------------------------------------------------------ #
    # capacity views
    # ------------------------------------------------------------------ #
    @property
    def capacity(self) -> int:
        """Usable data blocks (excludes the trash block)."""
        return self.num_blocks - 1

    @property
    def used_blocks(self) -> int:
        return self.capacity - len(self._free) - self._idle_cached

    @property
    def cached_blocks(self) -> int:
        """Blocks held by the chain cache with no active reference."""
        return self._idle_cached

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def available(self) -> int:
        """Blocks an admission may claim: free + evictable cached,
        minus everything already promised to active requests. Pinned
        idle chains (an in-flight KV shipment references their bytes)
        are NOT evictable and never counted as claimable supply."""
        evictable = self._idle_cached - self._pinned_idle
        return len(self._free) + evictable - self._reserved_total

    # ------------------------------------------------------------------ #
    # admission / growth / release
    # ------------------------------------------------------------------ #
    def admit(
        self,
        request_id: str,
        prompt_len: int,
        max_new_tokens: int,
        prompt_tokens: Optional[Sequence[int]] = None,
    ) -> Optional[BlockAllocation]:
        """Claim blocks for a request; ``None`` = not enough blocks
        (back-pressure — the caller keeps the request queued).

        Allocates the PROMPT blocks now (positions [0, prompt_len)) and
        reserves the rest of the worst case; pass ``prompt_tokens`` to
        enable prefix matching/registration (without them the request is
        admitted with sharing disabled).
        """
        if prompt_len < 1:
            raise ValueError(f"prompt_len must be >= 1, got {prompt_len}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if request_id in self._allocs:
            raise ValueError(f"request {request_id!r} is already admitted")
        if prompt_tokens is not None and len(prompt_tokens) != prompt_len:
            raise ValueError(
                f"prompt_tokens length {len(prompt_tokens)} != prompt_len "
                f"{prompt_len}"
            )
        bs = self.block_size
        total_needed = blocks_for(prompt_len, max_new_tokens, bs)
        prompt_blocks = (prompt_len - 1) // bs + 1
        # a window kind: the first decode step (at prompt_len - 1) attends
        # positions from prompt_len - window on, so the prompt's earlier
        # blocks are never allocated
        first = max(0, prompt_len - self.window) // bs if self.window else 0
        peak = total_needed - first
        if self.window:
            peak = min(peak, window_blocks(self.window, bs))
        # decode writes positions >= prompt_len - 1, so the block holding
        # that position (and everything after) must be private: sharing is
        # copy-on-write at admission, not at decode time
        writable_from = (prompt_len - 1) // bs
        shareable = min(prompt_len // bs, writable_from)

        keys: List[bytes] = []
        matched: List[_ChainNode] = []
        if self.prefix_cache_enabled and prompt_tokens is not None:
            keys = self._chain_keys(prompt_tokens, shareable)
            for key in keys:
                node = self._chains.get(key)
                if node is None:
                    break
                matched.append(node)
            # a full-prompt match capped by writable_from is the CoW case:
            # the cache HAS the block but decode will write it
            if len(matched) == shareable and shareable < prompt_len // bs:
                extra = self._chain_keys(prompt_tokens, prompt_len // bs)
                if extra[shareable] in self._chains:
                    self.cow_private_total += 1

        shared = len(matched)
        revived = sum(1 for n in matched if n.refcount == 0)
        private_now = prompt_blocks - first - shared
        reserved_new = peak - (prompt_blocks - first)
        if private_now + reserved_new + revived > self.available():
            self.deferred_total += 1
            return None

        # ---- commit (no failures past this point) ---- #
        self._clock += 1
        for node in matched:
            if node.refcount == 0:
                self._idle_cached -= 1
                if node.pinned > 0:
                    self._pinned_idle -= 1
            node.refcount += 1
            node.last_used = self._clock
        self.prefix_hits_total += shared
        blocks = [n.block for n in matched]
        chain_keys = list(keys[:shared])
        cached = shared
        for i in range(first + shared, prompt_blocks):
            block = self._alloc_block()
            blocks.append(block)
            if i < len(keys):  # full block before the write frontier
                parent = keys[i - 1] if i > 0 else None
                self._chains[keys[i]] = _ChainNode(
                    block=block, parent=parent, refcount=1,
                    last_used=self._clock,
                )
                if parent is not None:
                    self._chains[parent].children += 1
                chain_keys.append(keys[i])
                cached += 1
                self.prefix_misses_total += 1
        self._reserved_total += reserved_new
        alloc = BlockAllocation(
            request_id=request_id,
            blocks=blocks,
            shared=shared,
            cached=cached,
            chain_keys=chain_keys,
            reserved=reserved_new,
            first=first,
            total=total_needed,
            peak=peak,
        )
        self._allocs[request_id] = alloc
        self.admitted_total += 1
        self.blocks_highwater = max(self.blocks_highwater, self.used_blocks)
        return alloc

    def grow(self, request_id: str) -> int:
        """Allocate one reserved block for an active request (decode
        crossed a block boundary). Guaranteed to succeed within the
        admission-time reservation; growing past it raises."""
        alloc = self._allocs.get(request_id)
        if alloc is None:
            raise KeyError(f"request {request_id!r} is not admitted")
        if alloc.reserved <= 0:
            raise OutOfBlocks(
                f"request {request_id!r} grew past its reservation "
                f"({len(alloc.blocks)} blocks allocated): the admission "
                "contract sizes blocks to prompt_len + max_new_tokens"
            )
        block = self._alloc_block()
        alloc.reserved -= 1
        self._reserved_total -= 1
        alloc.blocks.append(block)
        self.grown_total += 1
        self.blocks_highwater = max(self.blocks_highwater, self.used_blocks)
        return block

    def give_back(self, request_id: str, first_live_block: int) -> int:
        """Free the blocks of a window kind's request that lie before
        logical block ``first_live_block`` (every position in them is out
        of the window of every query still to come). What the request may
        yet grow into, up to its peak, stays reserved, so a block given
        back is its own next block if it needs one. Returns how many were
        freed."""
        if not self.window:
            raise ValueError(
                "give_back on a full kind of leaf: its layers attend every "
                "position, so no block ever falls out"
            )
        alloc = self._allocs.get(request_id)
        if alloc is None:
            raise KeyError(f"request {request_id!r} is not admitted")
        n = min(first_live_block - alloc.first, len(alloc.blocks))
        if n <= 0:
            return 0
        self._free.extend(alloc.blocks[:n])
        del alloc.blocks[:n]
        alloc.first += n
        ungrown = alloc.total - (alloc.first + len(alloc.blocks))
        reserved = min(alloc.peak - len(alloc.blocks), ungrown)
        self._reserved_total += reserved - alloc.reserved
        alloc.reserved = reserved
        self.given_back_total += n
        return n

    def release(self, request_id: str) -> None:
        """Return a finished request's blocks: refcount-down the cached
        prefix (chains stay warm for future hits), free the private tail,
        return the unused reservation."""
        alloc = self._allocs.pop(request_id, None)
        if alloc is None:
            raise KeyError(f"request {request_id!r} is not admitted")
        self._clock += 1
        for key in alloc.chain_keys:
            node = self._chains[key]
            node.refcount -= 1
            node.last_used = self._clock
            if node.refcount == 0:
                self._idle_cached += 1
                if node.pinned > 0:
                    self._pinned_idle += 1
        self._free.extend(alloc.blocks[alloc.cached:])
        self._reserved_total -= alloc.reserved
        self.released_total += 1

    # ------------------------------------------------------------------ #
    # shipment pinning
    # ------------------------------------------------------------------ #
    def pin_request(self, request_id: str) -> List[bytes]:
        """Pin the cached-chain blocks of an active request for the
        lifetime of an in-flight KV shipment. Returns the pinned chain
        keys — the caller MUST hand them back to :meth:`unpin` when the
        shipment lands or is abandoned.

        This closes the migration eviction race: a shipment's payload
        references chain blocks by content, and if a sibling request
        releases the chain mid-transfer the refcount transiently hits 0
        — without the pin, allocation pressure could LRU-evict and
        rewrite those physical blocks while the shipment (or a retry
        resend reading from the cache) still needs their bytes."""
        alloc = self._allocs.get(request_id)
        if alloc is None:
            raise KeyError(f"request {request_id!r} is not admitted")
        self.pin(alloc.chain_keys)
        return list(alloc.chain_keys)

    def pin(self, chain_keys: Sequence[bytes]) -> None:
        for key in chain_keys:
            node = self._chains.get(key)
            if node is None:
                continue
            node.pinned += 1
            if node.refcount == 0 and node.pinned == 1:
                self._pinned_idle += 1

    def unpin(self, chain_keys: Sequence[bytes]) -> None:
        for key in chain_keys:
            node = self._chains.get(key)
            if node is None:
                continue
            node.pinned = max(0, node.pinned - 1)
            if node.refcount == 0 and node.pinned == 0:
                self._pinned_idle -= 1

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #
    def _chain_keys(
        self, tokens: Sequence[int], n_blocks: int
    ) -> List[bytes]:
        """Rolling hash chain over the first ``n_blocks`` full blocks."""
        bs = self.block_size
        keys: List[bytes] = []
        digest = b""
        for i in range(n_blocks):
            chunk = np.asarray(
                tokens[i * bs:(i + 1) * bs], dtype=np.int64
            ).tobytes()
            digest = hashlib.sha256(digest + chunk).digest()
            keys.append(digest)
        return keys

    def _alloc_block(self) -> int:
        if self._free:
            return self._free.pop()
        evicted = self._evict_lru()
        if evicted is None:
            raise OutOfBlocks(
                "no free or evictable blocks — allocation outside the "
                "admission/reservation contract"
            )
        return evicted

    def _evict_lru(self) -> Optional[int]:
        """Evict the least-recently-used refcount-0 LEAF chain node
        (leaf-first keeps every cached chain reachable from its root).
        Pinned nodes are untouchable: an in-flight KV shipment still
        references their bytes even when no active request does."""
        victim_key = None
        victim = None
        for key, node in self._chains.items():
            if node.refcount == 0 and node.children == 0 and node.pinned == 0:
                if victim is None or node.last_used < victim.last_used:
                    victim_key, victim = key, node
        if victim is None:
            return None
        del self._chains[victim_key]
        if victim.parent is not None:
            self._chains[victim.parent].children -= 1
        self._idle_cached -= 1
        self.evictions_total += 1
        return victim.block

    def stats(self) -> Dict[str, object]:
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "blocks_used": self.used_blocks,
            "blocks_free": self.free_blocks,
            "blocks_cached": self.cached_blocks,
            "blocks_reserved": self._reserved_total,
            "blocks_highwater": self.blocks_highwater,
            "chains_cached": len(self._chains),
            "chains_pinned": sum(
                1 for n in self._chains.values() if n.pinned > 0
            ),
            "admitted_total": self.admitted_total,
            "released_total": self.released_total,
            "grown_total": self.grown_total,
            "prefix_hits_total": self.prefix_hits_total,
            "prefix_misses_total": self.prefix_misses_total,
            "cow_private_total": self.cow_private_total,
            "evictions_total": self.evictions_total,
            "deferred_total": self.deferred_total,
            "given_back_total": self.given_back_total,
        }


def stated_leaves(model, block_size: int) -> Dict[str, tuple]:
    """A model's ``paged_block_leaves`` with every leaf's kind written out:
    leaf -> (layers, block shape, dtype, window; 0 = the full kind,
    ``STATE`` = the state kind, whose "block" is one slot's state). A model
    that states three entries a leaf states the full kind."""
    def kind(rest):
        if not rest:
            return 0
        return STATE if rest[0] == STATE else int(rest[0])

    return {
        name: (layers, tuple(block), dtype, kind(rest))
        for name, (layers, block, dtype, *rest) in
        model.paged_block_leaves(block_size).items()
    }


def stated_windows(model, block_size: int) -> List[int]:
    """The widths of a model's window kinds of leaf."""
    return sorted({w for *_, w in stated_leaves(model, block_size).values()
                   if w != STATE and w})


def states_kind(model, block_size: int) -> bool:
    """Whether a model keeps a leaf of the state kind."""
    return any(w == STATE for *_, w in stated_leaves(model, block_size).values())


@dataclass
class _Kind:
    """One kind of leaf of the pool: the leaves of the layers that attend
    every position (``window`` 0, ``"full"``) or of those that attend the
    last ``window`` only (``"window"``). A kind has its own blocks, its own
    allocator and its own block table a slot; a request is admitted when
    every kind admits it."""

    name: str
    window: int
    leaves: List[str]
    layers: int  # layers of this kind, for the byte arithmetic of readers
    allocator: "BlockAllocator"
    block_tables: np.ndarray  # [num_slots, max_blocks] int32, trash-padded
    allocs: Dict[int, BlockAllocation] = field(default_factory=dict)


class PagedKVPool:
    """Block-paged device KV pool: ``num_slots`` rows of the decode batch
    (free list, occupancy, tenancy history) over one block allocation.

    One device allocation of ``num_blocks`` blocks, a leaf
    ``[layers, num_blocks, *block]`` for each that the model's
    ``paged_block_leaves`` states (what one cached position holds in one
    layer is the model's: K and V per head, or one latent row); each
    engine slot has a row in the FIXED-shape host block table
    [num_slots, max_blocks] (int32, trash-padded) that the model's paged
    decode step reads its pages through. The allocator, the tables and
    the slots know blocks only, never what is in them. The leaves
    (``self.cache``) are DONATED to every engine program that writes them
    and rebound to the program's output (``InferenceEngine._update_pool``):
    the same buffers, updated in place, under new array objects. An array
    read off ``self.cache`` earlier is deleted by the next tick, so keep a
    host copy (``np.array``), not the array.
    Admission is by block availability (the allocator's reservation
    contract), not by free slot alone — the pool can refuse a request
    while slots are free, which is the back-pressure signal the
    scheduler turns into FIFO head-of-line waiting.
    """

    def __init__(
        self,
        cfg,
        num_slots: int,
        max_len: int,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
    ):
        import jax.numpy as jnp

        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if max_len % block_size != 0:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of block_size "
                f"({block_size}): the paged decode's logical length is "
                "max_blocks * block_size and must equal max_len"
            )
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.block_size = int(block_size)
        self.max_blocks = self.max_len // self.block_size
        model = cfg.serving()
        stated = stated_leaves(model, self.block_size)
        # the state kind's leaves: one array a slot a layer, no blocks
        self.state_leaves: List[str] = [
            k for k, v in stated.items() if v[3] == STATE]
        if self.state_leaves and prefix_cache:
            raise ValueError(
                "prefix sharing over a state kind of leaf: a block shared by "
                "prefix says nothing of the state behind it "
                "(prefix_cache=False only)"
            )
        paged = {k: v for k, v in stated.items() if v[3] != STATE}
        widths = sorted({w for *_, w in paged.values() if w})
        if len(widths) > 1:
            raise ValueError(
                f"window kinds of {widths} positions in one model: the pool "
                "keeps one window kind beside the full one"
            )
        if getattr(cfg, "sliding_window", 0) and not widths:
            raise ValueError(
                "the paged KV pool requires dense-causal configs, or a "
                "model that states which of its leaves are of a window "
                "kind (paged_block_leaves): block tables map logical "
                "positions 1:1 to cache slots, which is unsound for "
                "rolling sliding-window buffers"
            )
        # the kinds of leaf, the full one first: each with blocks of its
        # own. ``num_blocks`` is what a kind gets, but never more than every
        # slot at the most it can hold (+ the trash block): max_len for the
        # full kind, the window and one block for a window kind. The split
        # follows from the model's kinds and max_len, not from a setting.
        self.kinds: Dict[str, _Kind] = {}
        for window in sorted({w for *_, w in paged.values()}):
            kind = "window" if window else "full"
            most = self.max_blocks
            if window:
                most = min(most, window_blocks(window, self.block_size))
            worst = self.num_slots * most + 1
            n = worst if num_blocks is None else int(num_blocks)
            if window:
                n = min(n, worst)
            names = [k for k, v in paged.items() if v[3] == window]
            self.kinds[kind] = _Kind(
                name=kind, window=window, leaves=names,
                layers=paged[names[0]][0],
                allocator=BlockAllocator(
                    n, self.block_size, prefix_cache=prefix_cache,
                    window=window),
                # host mirror of the device block tables; trash-padded so
                # free slots and unallocated entries write/gather harmlessly
                block_tables=np.full(
                    (self.num_slots, self.max_blocks), TRASH_BLOCK, np.int32),
            )
        self.leaf_kind = {
            name: kind.name for kind in self.kinds.values()
            for name in kind.leaves
        }
        self.leaf_kind.update(dict.fromkeys(self.state_leaves, STATE))
        self.cache = {
            name: jnp.zeros(
                (layers, self.num_slots if kind == STATE else
                 self.kinds[self.leaf_kind[name]].allocator.num_blocks)
                + block, dtype)
            for name, (layers, block, dtype, kind) in stated.items()
        }
        # the layers that keep state: a layer's leaves (a scan state and a
        # convolution tail) stand beside each other, so the most of any leaf
        self.state_layers = max(
            (stated[k][0] for k in self.state_leaves), default=0)
        self.state_bytes_per_slot = sum(
            int(self.cache[k].nbytes) // self.num_slots
            for k in self.state_leaves)
        self._zero_fn = None
        self.bytes_per_position = int(model.cache_bytes_per_position())
        self.slots: List[Slot] = [Slot(i) for i in range(self.num_slots)]
        self._free: List[int] = list(range(self.num_slots - 1, -1, -1))
        self.admitted_total = 0
        self.recycled_total = 0
        self.highwater = 0
        self.tenancies: Dict[int, List[str]] = {
            i: [] for i in range(self.num_slots)
        }
        self._published_hits = 0.0

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
        """Admit by slot AND block availability; ``None`` when either is
        exhausted (the scheduler keeps the request queued)."""
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
                f"{prompt_len + max_new_tokens} positions, but the pool "
                f"serves max_len={self.max_len}"
            )
        if not self._free:
            return None
        admitted: Dict[str, BlockAllocation] = {}
        for kind in self.kinds.values():
            alloc = kind.allocator.admit(
                request_id, prompt_len, max_new_tokens,
                prompt_tokens=prompt_tokens,
            )
            if alloc is None:  # one kind is short: no kind keeps a block
                for name in admitted:
                    self.kinds[name].allocator.release(request_id)
                self._publish_gauges()
                return None
            admitted[kind.name] = alloc
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
        for kind in self.kinds.values():
            alloc = admitted[kind.name]
            row = kind.block_tables[slot.index]
            row[:] = TRASH_BLOCK
            row[alloc.first: alloc.first + len(alloc.blocks)] = alloc.blocks
            kind.allocs[slot.index] = alloc
        self._zero_state(slot.index)
        self.admitted_total += 1
        slot.admission = self.admitted_total
        self.tenancies[slot.index].append(request_id)
        self.highwater = max(self.highwater, self.occupancy)
        self._publish_gauges()
        return slot

    def _zero_state(self, index: int) -> None:
        """Zero slot ``index``'s rows of every state leaf, in place (the
        leaf is donated to the write and rebound, as ``cache`` is by every
        engine program). Called by the thread that dispatches those programs
        (the scheduler's tick), so it is ordered among them."""
        if not self.state_leaves:
            return
        if self._zero_fn is None:
            import jax

            # one program over every state leaf, whatever their shapes and
            # types: an admission is one dispatch, not one a leaf
            self._zero_fn = jax.jit(
                lambda leaves, i: {
                    k: leaf.at[:, i].set(0) for k, leaf in leaves.items()},
                donate_argnums=(0,))
        self.cache.update(self._zero_fn(
            {k: self.cache[k] for k in self.state_leaves}, np.int32(index)))

    def release(self, index: int) -> Slot:
        slot = self.slots[index]
        if not slot.occupied:
            raise ValueError(f"slot {index} is already free")
        for kind in self.kinds.values():
            kind.allocator.release(slot.request_id)
            kind.block_tables[index, :] = TRASH_BLOCK
            kind.allocs.pop(index, None)
        slot.reset()
        self._free.append(index)
        self.recycled_total += 1
        self._publish_gauges()
        return slot

    # ------------------------------------------------------------------ #
    # block hooks the engine drives
    # ------------------------------------------------------------------ #
    def prompt_write_tables(self, slot_index: int, n_prompt_blocks: int):
        """Write-redirect table for a prefill of ``n_prompt_blocks`` blocks
        (the rung the engine padded this prompt to): entry j is
        the physical block for prompt block j, or TRASH for shared-prefix
        blocks (already written once, immutable while referenced), for
        padding blocks past this prompt's real length and, in a window
        kind, for the blocks before the window's tail, which were never
        allocated. ``{kind: table}``; where the pool holds state, ``"state"``
        names where prefill writes it and what of the rung is real: ``[the
        slot, the prompt's length]``."""
        slot = self.slots[slot_index]
        own = (slot.prompt_len - 1) // self.block_size + 1
        tables = {}
        for kind in self.kinds.values():
            alloc = kind.allocs[slot_index]
            table = np.full((n_prompt_blocks,), TRASH_BLOCK, np.int32)
            lo, hi = alloc.first + alloc.shared, min(own, n_prompt_blocks)
            if hi > lo:
                table[lo:hi] = alloc.blocks[lo - alloc.first: hi - alloc.first]
            tables[kind.name] = table
        if self.state_leaves:
            tables[STATE] = np.array([slot_index, slot.prompt_len], np.int32)
        return tables

    def program_tables(self):
        """The block tables as the decode program takes them: ``{kind:
        [num_slots, max_blocks]}``, the host mirrors themselves."""
        return {kind.name: kind.block_tables for kind in self.kinds.values()}

    # A pool of the full kind alone, as the accepted benchmark's tests read
    # it (tests/bench_harness/test_deepseek_family.py, not a program PR's
    # to edit): the one table and the one write table, not by kind.
    def _only_kind(self) -> _Kind:
        if len(self.kinds) != 1:
            raise ValueError(
                f"a pool of kinds {sorted(self.kinds)} has a block table a "
                "kind: program_tables() / prompt_write_tables()"
            )
        return next(iter(self.kinds.values()))

    @property
    def block_tables(self) -> np.ndarray:
        return self._only_kind().block_tables

    def prompt_write_table(self, slot_index: int, n_prompt_blocks: int):
        name = self._only_kind().name
        return self.prompt_write_tables(slot_index, n_prompt_blocks)[name]

    def ensure_writable(
        self, slot: Slot, upto_pos: Optional[int] = None
    ) -> None:
        """Grow the slot's block table (on demand, from its reservation)
        until the block holding ``upto_pos`` — default ``slot.pos``, the
        position the next decode step writes — is allocated.

        Speculative decode passes ``upto_pos = slot.pos + n_proposals``:
        a verify burst writes candidate (k, v) at every proposed
        position before acceptance is known, so all of them must map to
        physical blocks. The engine clamps proposals to the remaining
        token budget, which keeps ``upto_pos`` within the admission-time
        reservation (``blocks_for``) — growth still cannot fail."""
        pos = slot.pos if upto_pos is None else int(upto_pos)
        needed = pos // self.block_size + 1
        for kind in self.kinds.values():
            alloc = kind.allocs[slot.index]
            row = kind.block_tables[slot.index]
            if kind.window:
                # the step at slot.pos attends [slot.pos - window + 1,
                # slot.pos]: blocks wholly before that go back to the pool
                # first, so the request never holds more than its peak
                live = max(0, slot.pos - kind.window + 1) // self.block_size
                gone = kind.allocator.give_back(slot.request_id, live)
                if gone:
                    row[alloc.first - gone: alloc.first] = TRASH_BLOCK
            while alloc.first + len(alloc.blocks) < needed:
                block = kind.allocator.grow(slot.request_id)
                row[alloc.first + len(alloc.blocks) - 1] = block

    def shared_blocks(self, slot_index: int) -> int:
        """Prefix blocks the slot's request shares (a window kind shares
        none: the engine refuses a prefix cache over it)."""
        return max(kind.allocs[slot_index].shared for kind in self.kinds.values())

    def block_utilization(self) -> float:
        """Of the fullest kind: the one that refuses the next admission."""
        return max(kind.allocator.used_blocks / max(kind.allocator.capacity, 1)
                   for kind in self.kinds.values())

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
        return [s for s in self.slots if s.occupied]

    def utilization(self) -> float:
        return self.occupancy / self.num_slots

    def stats(self) -> Dict[str, object]:
        out = {
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "occupancy": self.occupancy,
            "highwater": self.highwater,
            # what one cached position costs through every layer
            "bytes_per_position": self.bytes_per_position,
            "admitted_total": self.admitted_total,
            "recycled_total": self.recycled_total,
            "tenants_per_slot": {
                i: len(v) for i, v in self.tenancies.items()
            },
        }
        # a kind's layers, window and allocator: the first kind's under the
        # names a pool has always reported, a further kind's behind its name
        for i, kind in enumerate(self.kinds.values()):
            prefix = f"{kind.name}." if i else ""
            out.update({f"{prefix}layers": kind.layers,
                        f"{prefix}window": kind.window})
            out.update({f"{prefix}{k}": v
                        for k, v in kind.allocator.stats().items()})
        if self.state_leaves:
            out.update({"state.layers": self.state_layers,
                        "state.leaves": len(self.state_leaves),
                        "state.bytes_per_slot": self.state_bytes_per_slot,
                        "state.slots_used": self.occupancy})
        return out

    def _publish_gauges(self) -> None:
        reg = _obs.registry()
        if reg is None:
            return
        reg.gauge("rlt_serve_slot_occupancy").set(self.occupancy)
        reg.gauge("rlt_serve_slot_highwater").set(self.highwater)
        alloc = next(iter(self.kinds.values())).allocator
        reg.gauge("rlt_serve_kv_blocks_used").set(alloc.used_blocks)
        reg.gauge("rlt_serve_kv_blocks_free").set(alloc.free_blocks)
        reg.gauge("rlt_serve_kv_blocks_cached").set(alloc.cached_blocks)
        if alloc.prefix_hits_total > self._published_hits:
            reg.counter("rlt_serve_prefix_hits_total").inc(
                alloc.prefix_hits_total - self._published_hits
            )
            self._published_hits = float(alloc.prefix_hits_total)
