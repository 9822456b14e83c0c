"""Continuous-batching inference serving.

Bottom-up:

- :mod:`.paged_kv` — the KV pool, block-paged: fixed-size blocks from
  one device allocation, per-request block tables grown on demand,
  refcounted shared-prefix reuse with LRU eviction, admission by block
  availability; the rows of the decode batch are request slots
  (:class:`~.paged_kv.Slot`), recycled on EOS/max-tokens.
- :mod:`.scheduler` — bounded admission queue + prefill/decode
  interleave policy (pure host logic, peek-then-acquire back-pressure).
- :mod:`.engine` — single-replica loop: one jitted prefill (at a few
  lengths, chosen by the prompt's) + one jitted decode step, streaming
  callbacks, drain/shutdown. Zero steady-state recompiles by
  construction (fixed shapes everywhere, all resolved at warmup).
- :mod:`.replica` — elastic multi-replica front door over the actor
  runtime: least-loaded routing, heartbeat-driven relaunch, and an
  :class:`~.replica.Autoscaler` scaling the fleet on queue depth and
  TTFT p95 with graceful drain on scale-down.
- :mod:`.migration` — disaggregated prefill/decode serving: the
  checksummed, versioned :class:`~.migration.KVShipment` carrying a
  prefilled request's paged KV blocks from the prefill pool to a decode
  replica, plus the retry/timeout :class:`~.migration.MigrationPolicy`
  the fleet's migration pump enforces (bounded attempts, exponential
  backoff, graceful fallback to colocated decode).
- :mod:`.resilience` — the serving-resilience primitives threaded
  through all of the above: a driver-side :class:`~.resilience.
  RequestJournal` that makes requests survive replica deaths (resubmit
  from ``prompt + delivered``), per-replica
  :class:`~.resilience.CircuitBreaker` routing health, the deadline/
  priority-aware :class:`~.resilience.ShedPolicy`, and the SIGTERM
  preemption drain.
- :mod:`.tenancy` — multi-tenant QoS: per-tenant contracts
  (:class:`~.tenancy.TenantSpec`), token-bucket admission quotas, and
  the :class:`~.tenancy.TenantRegistry` that switches the scheduler to
  deficit-round-robin per-tenant queues and the shed policy to tenant
  classes. Nothing changes until a registry is installed.
"""
from ray_lightning_tpu.serving.engine import (  # noqa: F401
    Completion,
    EngineClosed,
    EngineConfig,
    InferenceEngine,
)
from ray_lightning_tpu.serving.migration import (  # noqa: F401
    KVShipment,
    MigrationPolicy,
    MigrationRejected,
    MigrationStats,
    ShipmentCorrupt,
    ShipmentError,
    ShipmentMismatch,
    build_shipment,
    kv_fingerprint,
    verify_shipment,
)
from ray_lightning_tpu.serving.paged_kv import (  # noqa: F401
    BlockAllocation,
    BlockAllocator,
    OutOfBlocks,
    PagedKVPool,
    Slot,
)
from ray_lightning_tpu.serving.replica import (  # noqa: F401
    Autoscaler,
    CapacityBlocked,
    LocalReplicaFleet,
    ReplicaGroup,
    ServeFuture,
    ServeReplicaActor,
    autoscale_decision,
    needs_relaunch,
    pick_least_loaded,
)
from ray_lightning_tpu.serving.resilience import (  # noqa: F401
    CircuitBreaker,
    JournalEntry,
    RequestJournal,
    RequestShed,
    ShedPolicy,
    install_sigterm_drain,
)
from ray_lightning_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatchScheduler,
    Plan,
    Request,
    RequestQueueFull,
)
from ray_lightning_tpu.serving.tenancy import (  # noqa: F401
    QuotaExceeded,
    TenantRegistry,
    TenantSpec,
    TokenBucket,
    parse_tenant_specs,
)

__all__ = [
    "Autoscaler",
    "CapacityBlocked",
    "BlockAllocation",
    "BlockAllocator",
    "CircuitBreaker",
    "Completion",
    "ContinuousBatchScheduler",
    "EngineClosed",
    "EngineConfig",
    "InferenceEngine",
    "JournalEntry",
    "KVShipment",
    "LocalReplicaFleet",
    "MigrationPolicy",
    "MigrationRejected",
    "MigrationStats",
    "OutOfBlocks",
    "PagedKVPool",
    "Plan",
    "QuotaExceeded",
    "ReplicaGroup",
    "Request",
    "RequestJournal",
    "RequestQueueFull",
    "RequestShed",
    "ServeFuture",
    "ServeReplicaActor",
    "ShedPolicy",
    "ShipmentCorrupt",
    "ShipmentError",
    "ShipmentMismatch",
    "Slot",
    "TenantRegistry",
    "TenantSpec",
    "TokenBucket",
    "autoscale_decision",
    "build_shipment",
    "install_sigterm_drain",
    "kv_fingerprint",
    "needs_relaunch",
    "parse_tenant_specs",
    "pick_least_loaded",
    "verify_shipment",
]
