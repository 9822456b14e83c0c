"""Workload generation + trace replay: the million-user scenario harness.

Every serving policy in this repo (DRR fairness, shed ordering, quotas,
autoscaling, chip arbitration, chaos recovery) is ultimately a claim
about behaviour under realistic load — many tenants, diurnal cycles,
bursts, flash crowds, heavy-tail prompts. This package builds that load
side as a first-class subsystem:

- :mod:`.traces` — seeded arrival-trace generators (diurnal / bursty /
  flash-crowd, heavy-tail prompt lengths) and the JSONL recorded-trace
  format. Pure host logic, no jax import, fully deterministic per seed.
- :mod:`.replay` — the :class:`~.replay.ReplayDriver` that plays a trace
  against a live fleet (virtual-time accelerated, chaos faults welcome)
  and emits a verdict artifact: goodput decomposition summing to wall
  time, per-tenant SLO attainment, quota conformance, and a bounded
  cross-tenant wait ratio (the zero-starvation check).

Entry point: ``python -m ray_lightning_tpu.cli replay``.
"""
from ray_lightning_tpu.workloads.replay import (  # noqa: F401
    ReplayDriver,
    run_replay,
)
from ray_lightning_tpu.workloads.traces import (  # noqa: F401
    ArrivalEvent,
    bursty_trace,
    diurnal_trace,
    flash_crowd_trace,
    heavy_tail_prompt_len,
    read_trace,
    write_trace,
)

__all__ = [
    "ArrivalEvent",
    "ReplayDriver",
    "bursty_trace",
    "diurnal_trace",
    "flash_crowd_trace",
    "heavy_tail_prompt_len",
    "read_trace",
    "run_replay",
    "write_trace",
]
