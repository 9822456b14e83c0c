"""Seed management across driver and workers.

The reference propagates ``PL_GLOBAL_SEED`` to every actor and calls
``reset_seed()`` inside each worker before process-group setup
(reference: ray_lightning/launchers/ray_launcher.py:159-175,
ray_ddp.py:167). Here the seed also feeds the root ``jax.random.key``.
"""
from __future__ import annotations

import os
import random
import sys
from typing import Optional

import numpy as np

GLOBAL_SEED_ENV = "RLT_GLOBAL_SEED"


def seed_everything(seed: Optional[int] = None) -> int:
    if seed is None:
        env = os.environ.get(GLOBAL_SEED_ENV)
        seed = int(env) if env is not None else random.SystemRandom().randint(0, 2**31 - 1)
    seed = int(seed)
    os.environ[GLOBAL_SEED_ENV] = str(seed)
    random.seed(seed)
    np.random.seed(seed % (2**32))
    # torch is seeded where the process has loaded it; a run that never
    # imported it has no torch generator to seed and does not load it here
    torch = sys.modules.get("torch")
    if torch is not None:
        try:
            torch.manual_seed(seed)
        except Exception:
            pass
    return seed


def reset_seed() -> Optional[int]:
    """Re-apply the propagated global seed inside a worker process."""
    env = os.environ.get(GLOBAL_SEED_ENV)
    if env is None:
        return None
    return seed_everything(int(env))
