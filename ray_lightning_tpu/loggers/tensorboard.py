"""TensorBoard logger, available when a tensorboard writer is importable.

The writer is imported when a ``TensorBoardLogger`` is constructed (or
``TENSORBOARD_AVAILABLE`` is asked for), never when this module is:
``torch.utils.tensorboard`` loads torch and tensorflow, 20-30 s on a TPU
host that every process importing the package would pay. Without the
writer, construction raises the informative error of the Unavailable
pattern — the same optional-dependency fallback the reference uses for Tune
(reference: ray_lightning/tune.py:13-27, util.py:42-46).
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import numpy as np

from ray_lightning_tpu.loggers.base import Logger
from ray_lightning_tpu.utils.common import optional_import

_WRITER = "torch.utils.tensorboard"


def __getattr__(name: str):
    if name == "TENSORBOARD_AVAILABLE":
        return optional_import(_WRITER) is not None
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class TensorBoardLogger(Logger):
    def __init__(self, save_dir: str, name: str = "default", version: Optional[str] = None):
        tensorboard = optional_import(_WRITER)
        if tensorboard is None:
            raise RuntimeError(
                "Cannot instantiate: tensorboard is not installed; use CSVLogger")
        self._save_dir = save_dir
        self._name = name
        self._version = str(version) if version is not None else "version_0"
        self._dir = os.path.join(save_dir, name, self._version)
        self._writer = tensorboard.SummaryWriter(self._dir)

    @property
    def name(self) -> str:
        return self._name

    @property
    def version(self) -> str:
        return self._version

    @property
    def log_dir(self) -> str:
        return self._dir

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        self._writer.add_text("hparams", str(params))

    def log_metrics(self, metrics: Dict[str, float], step: Optional[int] = None) -> None:
        for k, v in metrics.items():
            self._writer.add_scalar(k, float(np.asarray(v)), global_step=step)

    def save(self) -> None:
        self._writer.flush()

    def finalize(self, status: str) -> None:
        self._writer.flush()
        self._writer.close()
