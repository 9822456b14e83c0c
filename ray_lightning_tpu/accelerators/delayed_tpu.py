"""Delayed-accelerator support: the driver stays off the TPU so that a
CPU-only machine (or a driver sharing a host with its workers) can launch
TPU training.

Role parity: the reference's ``_GPUAccelerator`` registered as ``"_gpu"``,
whose whole purpose is letting a GPU-less driver construct a Trainer that
trains on GPUs remotely (reference:
ray_lightning/accelerators/delayed_gpu_accelerator.py:30-60). On TPU the
problem is sharper — libtpu/the PJRT plugin claims the chip EXCLUSIVELY per
process, so a driver that so much as initializes the backend starves its own
workers. The mechanism here is therefore config-level: pin the driver's
platform to CPU before any device use and leave chip acquisition to worker
actors (which read their own ``JAX_PLATFORMS`` at boot).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Type

import jax


def ensure_driver_off_accelerator() -> bool:
    """Pin this process to CPU if no backend is initialized yet.

    Returns True when the pin took effect (or already CPU); False when a
    non-CPU backend was already live (too late to delay — caller should
    warn). Safe to call multiple times.
    """
    # the same probe jax.distributed.initialize() uses; it creates nothing
    if jax._src.xla_bridge.backends_are_initialized():  # noqa: SLF001
        return jax.default_backend() == "cpu"
    jax.config.update("jax_platforms", "cpu")
    return True


class Accelerator:
    """Minimal accelerator protocol (PTL-parity surface)."""

    name = "base"

    @staticmethod
    def is_available() -> bool:
        raise NotImplementedError

    @staticmethod
    def parallel_devices() -> List:
        return list(jax.devices())


class DelayedTPUAccelerator(Accelerator):
    """Reports available even with no local TPU: the devices live in the
    worker actors, not the driver (reference: delayed_gpu_accelerator.py's
    ``is_available() -> True`` trick, :47-50)."""

    name = "_tpu"

    @staticmethod
    def is_available() -> bool:
        return True

    @staticmethod
    def parallel_devices() -> List:
        # tolerate an empty/CPU-only driver (reference :38-45)
        try:
            return [d for d in jax.devices() if d.platform == "tpu"]
        except Exception:
            return []

    @staticmethod
    def setup_driver() -> bool:
        return ensure_driver_off_accelerator()


class CPUAccelerator(Accelerator):
    name = "cpu"

    @staticmethod
    def is_available() -> bool:
        return True


ACCELERATOR_REGISTRY: Dict[str, Type[Accelerator]] = {
    "_tpu": DelayedTPUAccelerator,
    "tpu": DelayedTPUAccelerator,
    "cpu": CPUAccelerator,
    "auto": CPUAccelerator,
}
