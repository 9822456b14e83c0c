"""The Ray-actor strategy family: RayStrategy / RayTPUStrategy,
RayShardedStrategy, HorovodRayStrategy.

API parity with the reference's three public strategies
(reference: ray_lightning/__init__.py:1-5; ray_ddp.py:23-333;
ray_ddp_sharded.py:12-13; ray_horovod.py:32-183), redesigned per SURVEY §7:
all three are ONE engine — Ray-placed worker actors, a JAX collective group,
and a GSPMD :class:`ShardingPolicy` — under three names:

- ``RayStrategy`` (= ``RayTPUStrategy``): data parallel. Params replicated,
  batch sharded; XLA emits the gradient all-reduce over ICI (the role NCCL
  allreduce plays in the reference's DDP).
- ``RayShardedStrategy``: ZeRO. Same mesh, but optimizer state (stage>=1)
  and parameters (stage 3) shard over the data axis — the FairScale
  OSS/sharded-grad equivalent, expressed as shardings instead of wrapper
  modules.
- ``HorovodRayStrategy``: ring-allreduce parity name. On TPU the ring IS the
  ICI torus; XLA's all-reduce is already a ring/tree hybrid over it, so this
  is the same compiled program as RayStrategy.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Tuple

from ray_lightning_tpu.parallel.mesh import MeshSpec
from ray_lightning_tpu.parallel.sharding import ShardingPolicy
from ray_lightning_tpu.strategies.base import XLAStrategy
from ray_lightning_tpu.utils.common import rank_zero_warn


class RayStrategy(XLAStrategy):
    """Distributed data-parallel training over Ray-style worker actors.

    Constructor parity (reference: ray_ddp.py:69-116): ``num_workers``,
    ``num_cpus_per_worker``, ``use_gpu`` (alias for "workers own the
    accelerator"), ``init_hook``, ``resources_per_worker``. TPU-specific:
    ``platform`` ("cpu" to run workers on the virtual CPU backend — the test
    path — or None to inherit the image's TPU platform) and
    ``devices_per_worker`` (forced host device count for CPU workers).
    """

    strategy_name = "ddp_ray"

    def __init__(
        self,
        num_workers: int = 1,
        num_cpus_per_worker: int = 1,
        use_gpu: bool = False,
        use_tpu: Optional[bool] = None,
        init_hook: Optional[Callable] = None,
        resources_per_worker: Optional[Dict[str, float]] = None,
        platform: Optional[str] = None,
        devices_per_worker: Optional[int] = None,
        chips_per_host: Optional[int] = None,
        mesh_spec: Optional[MeshSpec] = None,
        sharding_policy: Optional[ShardingPolicy] = None,
        dcn_grad_compression: Optional[str] = None,
        debug_collectives: bool = False,
        max_failures: int = 0,
        elastic: Optional[bool] = None,
        min_workers: Optional[int] = None,
        heartbeat_interval: Optional[float] = None,
        hang_timeout: Optional[float] = None,
        telemetry: Optional[bool] = None,
        prefetch_depth: Optional[int] = None,
        loader_num_workers: Optional[int] = None,
        xla_cache_dir: Optional[str] = None,
        partition_rules: Optional[Any] = None,
        zero_quantized_allgather: Optional[bool] = None,
        zero_gather_group_size: int = 8,
        **kwargs: Any,
    ):
        super().__init__(
            mesh_spec,
            sharding_policy,
            dcn_grad_compression=dcn_grad_compression,
            heartbeat_interval=heartbeat_interval,
            hang_timeout=hang_timeout,
            telemetry=telemetry,
            prefetch_depth=prefetch_depth,
            loader_num_workers=loader_num_workers,
            xla_cache_dir=xla_cache_dir,
            partition_rules=partition_rules,
            zero_quantized_allgather=zero_quantized_allgather,
            zero_gather_group_size=zero_gather_group_size,
        )
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        self.num_workers = int(num_workers)
        self.num_cpus_per_worker = num_cpus_per_worker
        self.use_gpu = use_gpu  # accepted for drop-in parity; TPU path ignores
        self.use_tpu = use_tpu if use_tpu is not None else not use_gpu
        self.init_hook = init_hook
        self.resources_per_worker = dict(resources_per_worker or {})
        self.platform = platform
        self.devices_per_worker = devices_per_worker
        self.chips_per_host = chips_per_host
        self.debug_collectives = debug_collectives
        self.max_failures = int(max_failures)
        self._elastic = elastic
        self._min_workers = min_workers
        if kwargs:
            rank_zero_warn("ignoring unsupported strategy kwargs: %s", sorted(kwargs))
        self._launcher = None
        # (global_rank, world, local_rank, node_rank)
        self._worker_ctx: Optional[Tuple[int, int, int, int]] = None

    # ------------------------------------------------------------------ #
    # pickling: the launcher (driver-side actor handles) and mesh never ship
    # ------------------------------------------------------------------ #
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_launcher"] = None
        state["_mesh"] = None
        state["_trainer"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    # ------------------------------------------------------------------ #
    @property
    def launcher(self):
        """Driver-side: lazily construct; worker-side: None (stages run
        inline — the equivalent of the reference's set_remote flag,
        ray_ddp.py:128-134)."""
        if self._is_remote:
            return None
        if self._launcher is None:
            from ray_lightning_tpu.launchers.ray_launcher import RayLauncher

            self._launcher = RayLauncher(self)
        return self._launcher

    @launcher.setter
    def launcher(self, value):
        self._launcher = value

    def _set_worker_context(
        self,
        global_rank: int,
        num_workers: int,
        local_rank: int = 0,
        node_rank: Optional[int] = None,
    ) -> None:
        self._worker_ctx = (
            global_rank,
            num_workers,
            local_rank,
            node_rank if node_rank is not None else global_rank,
        )
        os.environ["RLT_GLOBAL_RANK"] = str(global_rank)
        os.environ["RLT_LOCAL_RANK"] = str(local_rank)

    def worker_env(self) -> Dict[str, Optional[str]]:
        """Env for worker actor interpreters (decided before spawn: the
        child reads it when its own jax comes up; see runtime.api)."""
        env: Dict[str, Optional[str]] = {}
        if self.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            flags = os.environ.get("XLA_FLAGS", "")
            n = self.devices_per_worker or 1
            flags = " ".join(
                f for f in flags.split() if "xla_force_host_platform_device_count" not in f
            )
            env["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={n}".strip()
        elif self.platform:
            env["JAX_PLATFORMS"] = self.platform
        # else: inherit (workers grab the TPU; driver should stay off it)
        # the telemetry verdict must be explicit in the child: a ctor-only
        # telemetry=True would otherwise be invisible to the worker's boot
        # phase (spans start before the strategy payload is unpickled)
        env["RLT_TELEMETRY"] = "1" if self.telemetry else "0"
        # Every worker (and any relaunch/scale-up replacement) resolves the
        # cache dir the way the driver does — JAX_COMPILATION_CACHE_DIR,
        # then RLT_XLA_CACHE_DIR, then <checkout>/.xla_cache, all inherited
        # — so only a ctor-level xla_cache_dir= has to be carried over.
        if self._xla_cache_dir is not None:
            env["RLT_XLA_CACHE_DIR"] = self.xla_cache_dir or "0"
        return env

    # ------------------------------------------------------------------ #
    # topology
    # ------------------------------------------------------------------ #
    @property
    def world_size(self) -> int:
        if self._worker_ctx is not None:
            return self._worker_ctx[1]
        return self.num_workers

    @property
    def global_rank(self) -> int:
        if self._worker_ctx is not None:
            return self._worker_ctx[0]
        return 0

    @property
    def local_rank(self) -> int:
        """Host-local rank from the launcher's node-IP mapping (reference:
        ray_launcher.py:130-157); 0 in the common one-actor-per-host layout."""
        if self._worker_ctx is not None:
            return self._worker_ctx[2]
        return 0

    @property
    def node_rank(self) -> int:
        if self._worker_ctx is not None:
            return self._worker_ctx[3]
        return self.global_rank

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    # ------------------------------------------------------------------ #
    # elastic membership knobs (ctor > env > default)
    # ------------------------------------------------------------------ #
    @property
    def elastic(self) -> bool:
        """Shrink/grow the worker group on failure instead of relaunching
        the whole group (ctor ``elastic=`` > ``RLT_ELASTIC`` > False)."""
        if self._elastic is not None:
            return bool(self._elastic)
        return os.environ.get("RLT_ELASTIC", "0") == "1"

    @property
    def min_workers(self) -> int:
        """Smallest world size elastic training may shrink to before giving
        up and falling back to the max_failures relaunch path (ctor
        ``min_workers=`` > ``RLT_MIN_WORKERS`` > 1)."""
        if self._min_workers is not None:
            return max(1, int(self._min_workers))
        try:
            return max(1, int(os.environ.get("RLT_MIN_WORKERS", "1")))
        except ValueError:
            return 1

    def teardown(self) -> None:
        super().teardown()
        if self._launcher is not None:
            self._launcher.teardown_workers()
            self._launcher = None


# North-star spelling (BASELINE.json): explicit TPU name.
RayTPUStrategy = RayStrategy


class RayShardedStrategy(RayStrategy):
    """ZeRO sharded data-parallel (reference: ray_ddp_sharded.py:12-13 via
    FairScale). ``zero_stage``: 1/2 shard optimizer state, 3 also shards
    parameters (FSDP). All stages are just sharding annotations; XLA compiles
    the reduce-scatter/all-gather pattern over ICI."""

    strategy_name = "ddp_sharded_ray"

    def __init__(self, *args, zero_stage: int = 2, **kwargs):
        kwargs.setdefault(
            "sharding_policy", ShardingPolicy(zero_stage=zero_stage, data_axes=("dp",))
        )
        super().__init__(*args, **kwargs)
        self.zero_stage = zero_stage


class HorovodRayStrategy(RayStrategy):
    """Ring-allreduce parity name (reference: ray_horovod.py:32-183). On TPU
    the physical ring is the ICI torus and XLA's compiled all-reduce already
    uses it optimally, so this shares RayStrategy's engine; it exists so
    reference users can switch without renaming."""

    strategy_name = "horovod_ray"

    def __init__(self, num_workers: int = 1, num_cpus_per_worker: int = 1, use_gpu: bool = False, **kwargs):
        super().__init__(
            num_workers=num_workers,
            num_cpus_per_worker=num_cpus_per_worker,
            use_gpu=use_gpu,
            **kwargs,
        )

    @property
    def num_slots(self) -> int:  # hvd.size() parity
        return self.world_size
