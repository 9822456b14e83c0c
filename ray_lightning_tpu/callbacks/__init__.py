from ray_lightning_tpu.callbacks.base import Callback
from ray_lightning_tpu.callbacks.checkpoint import ModelCheckpoint
from ray_lightning_tpu.callbacks.early_stopping import EarlyStopping
from ray_lightning_tpu.callbacks.throughput import ThroughputMonitor
from ray_lightning_tpu.callbacks.profiler import ProfilerCallback
from ray_lightning_tpu.callbacks.orbax_checkpoint import OrbaxModelCheckpoint

__all__ = [
    "Callback",
    "ModelCheckpoint",
    "EarlyStopping",
    "ThroughputMonitor",
    "ProfilerCallback",
    "OrbaxModelCheckpoint",
    "ORBAX_AVAILABLE",
]


def __getattr__(name: str):
    # worked out when asked for: the answer costs the import of orbax
    if name == "ORBAX_AVAILABLE":
        from ray_lightning_tpu.callbacks import orbax_checkpoint

        return orbax_checkpoint.ORBAX_AVAILABLE
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
