"""ray_lightning_tpu: a TPU-native distributed training framework with the
capabilities of ray-project/ray_lightning, built on JAX/XLA/pallas.

Public surface parity (reference: ray_lightning/__init__.py:1-5 exports the
three strategies) plus the Trainer/LightningModule layer the reference gets
from PyTorch Lightning and the actor runtime it gets from Ray — both of
which this package provides natively.
"""
from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.core.datamodule import LightningDataModule
from ray_lightning_tpu.core.data import (
    DataLoader,
    Dataset,
    TensorDataset,
    DictDataset,
    RandomDataset,
    TokenFileDataset,
    DistributedSampler,
)
from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.strategies.base import Strategy, XLAStrategy, SingleDeviceStrategy
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.sharding import ShardingPolicy
from ray_lightning_tpu.callbacks import (
    Callback,
    ModelCheckpoint,
    EarlyStopping,
    ThroughputMonitor,
    ProfilerCallback,
    OrbaxModelCheckpoint,
)
from ray_lightning_tpu.cli import LightningCLI
from ray_lightning_tpu.utils.seed import seed_everything
from ray_lightning_tpu.strategies.ray_strategies import (
    RayStrategy,
    RayTPUStrategy,
    HorovodRayStrategy,
    RayShardedStrategy,
)
__version__ = "0.1.0"

__all__ = [
    "LightningModule",
    "LightningDataModule",
    "DataLoader",
    "Dataset",
    "TensorDataset",
    "DictDataset",
    "RandomDataset",
    "TokenFileDataset",
    "DistributedSampler",
    "Trainer",
    "Strategy",
    "XLAStrategy",
    "SingleDeviceStrategy",
    "MeshSpec",
    "build_mesh",
    "ShardingPolicy",
    "Callback",
    "ModelCheckpoint",
    "EarlyStopping",
    "ThroughputMonitor",
    "ProfilerCallback",
    "OrbaxModelCheckpoint",
    "LightningCLI",
    "seed_everything",
    "RayStrategy",
    "RayTPUStrategy",
    "HorovodRayStrategy",
    "RayShardedStrategy",
    "interop",
]


def __getattr__(name: str):
    # PEP 562: the torch bridge imports torch, so it loads when somebody
    # asks for it (``rlt.interop``, ``from ray_lightning_tpu import
    # interop``), not with the package
    if name == "interop":
        import importlib

        return importlib.import_module("ray_lightning_tpu.interop")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
