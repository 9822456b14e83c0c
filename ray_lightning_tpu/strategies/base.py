"""Strategy layer: how a Trainer's compiled step maps onto devices.

Role parity with the reference's strategy classes (reference:
ray_lightning/ray_ddp.py:23-333) but TPU-native: a Strategy owns a
``jax.sharding.Mesh`` plus a :class:`ShardingPolicy`, and the "distributed
training protocol" is nothing more than the shardings it hands the Trainer —
XLA's GSPMD partitioner compiles the matching collectives (gradient
all-reduce for replicated params, reduce-scatter/all-gather for ZeRO) over
ICI/DCN. There is no backend string, no process group object, no bucketing:
the reference's ``init_process_group`` (ray_ddp.py:192-196) corresponds to
``jax.distributed.initialize`` done by the launcher, and its DDP gradient
hooks correspond to compiler-inserted collectives.

``XLAStrategy`` is the in-process strategy over local devices; the Ray-actor
strategies (launch + multi-host) derive from it and add a launcher.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.partition_rules import (
    ShardingReport,
    apply_partition_rules,
    optstate_shardings_from_params,
    parse_partition_rules,
)
from ray_lightning_tpu.parallel.sharding import (
    ShardingPolicy,
    batch_sharding,
    fsdp_leaf_sharding,
    replicated_sharding,
    shard_divisor,
    warn_silently_replicated,
)


class Strategy:
    """Base strategy: single process, devices visible to this process."""

    strategy_name = "base"

    def __init__(
        self,
        mesh_spec: Optional[MeshSpec] = None,
        sharding_policy: Optional[ShardingPolicy] = None,
        dcn_grad_compression: Optional[str] = None,
        heartbeat_interval: Optional[float] = None,
        hang_timeout: Optional[float] = None,
        telemetry: Optional[bool] = None,
        prefetch_depth: Optional[int] = None,
        loader_num_workers: Optional[int] = None,
        xla_cache_dir: Optional[str] = None,
        partition_rules: Optional[Any] = None,
        zero_quantized_allgather: Optional[bool] = None,
        zero_gather_group_size: int = 8,
        pipeline_stages: Optional[int] = None,
        pipeline_microbatches: Optional[int] = None,
    ):
        self.mesh_spec = mesh_spec or MeshSpec.data_parallel()
        self.sharding_policy = sharding_policy or ShardingPolicy.ddp()
        self._dcn_grad_compression = dcn_grad_compression
        self._heartbeat_interval = heartbeat_interval
        self._hang_timeout = hang_timeout
        self._telemetry = telemetry
        self._prefetch_depth = prefetch_depth
        self._loader_num_workers = loader_num_workers
        self._xla_cache_dir = xla_cache_dir
        self._partition_rules = partition_rules
        self._zero_quantized_allgather = zero_quantized_allgather
        self.zero_gather_group_size = int(zero_gather_group_size)
        self._pipeline_stages = pipeline_stages
        self._pipeline_microbatches = pipeline_microbatches
        self._sharding_report: Optional[ShardingReport] = None
        self._mesh: Optional[Mesh] = None
        self._trainer = None
        self._module = None
        self.launcher = None
        self._is_remote = False  # True inside a worker actor

    @property
    def dcn_grad_compression(self) -> str:
        """Gradient compression mode for the cross-slice (DCN) hop:
        ``"none"`` (default, XLA's implicit full-precision all-reduce) or
        ``"int8"`` (block-scaled int8 reduce-scatter/all-gather with error
        feedback — see ``parallel/compression.py``). The constructor
        argument wins; otherwise the ``RLT_DCN_COMPRESSION`` env var."""
        mode = self._dcn_grad_compression
        if mode is None:
            mode = os.environ.get("RLT_DCN_COMPRESSION") or "none"
        mode = str(mode).lower()
        if mode not in ("none", "int8"):
            raise ValueError(
                f"dcn_grad_compression (RLT_DCN_COMPRESSION) must be 'none' "
                f"or 'int8', got {mode!r}"
            )
        return mode

    @property
    def partition_rules(self):
        """Ordered regex -> PartitionSpec rules claiming param (and, by
        inheritance, optimizer-state) tensors by tree path. Constructor
        argument wins (a wire string or a sequence of
        :class:`~ray_lightning_tpu.parallel.partition_rules.PartitionRule`);
        otherwise the ``RLT_PARTITION_RULES`` env var
        (``"regex=spec;regex=spec"``). ``None`` = inference only."""
        rules = self._partition_rules
        if rules is None:
            rules = os.environ.get("RLT_PARTITION_RULES") or None
        return parse_partition_rules(rules)

    @property
    def zero_quantized_allgather(self) -> bool:
        """Quantize the explicit-ZeRO param all-gather (int8 block-scaled
        payload + error feedback, EQuARX-style). Constructor argument wins;
        otherwise ``RLT_ZERO_QUANTIZED_ALLGATHER``. Requires
        ``zero_stage >= 3`` (enforced when the step is built)."""
        value = self._zero_quantized_allgather
        if value is None:
            raw = os.environ.get("RLT_ZERO_QUANTIZED_ALLGATHER", "")
            if raw == "":
                return False
            if raw.lower() in ("1", "true", "yes", "on"):
                return True
            if raw.lower() in ("0", "false", "no", "off"):
                return False
            raise ValueError(
                f"RLT_ZERO_QUANTIZED_ALLGATHER must be a boolean flag, got "
                f"{raw!r}"
            )
        return bool(value)

    @property
    def pipeline_stages(self) -> int:
        """Number of 1F1B pipeline stages the trainer's step runs over the
        mesh's ``"pp"`` axis (``parallel/pipeline_1f1b.py``). ``0`` (the
        default) disables pipelining. A non-zero value requires the module
        to implement ``pipeline_stage``/``pipeline_last`` and the mesh to
        carry a ``pp`` axis of exactly this size. Constructor argument
        wins; otherwise ``RLT_PP_STAGES``."""
        value = self._pipeline_stages
        if value is None:
            value = os.environ.get("RLT_PP_STAGES")
        if value in (None, ""):
            return 0
        value = int(value)
        if value < 0:
            raise ValueError(
                f"pipeline_stages (RLT_PP_STAGES) must be >= 0, got {value}"
            )
        return value

    @property
    def pipeline_microbatches(self) -> int:
        """Microbatches per step under 1F1B pipelining; the global batch
        must divide evenly into them. More microbatches shrink the pipeline
        bubble (steady state needs M >= stages). Constructor argument wins;
        otherwise ``RLT_PP_MICROBATCHES``; defaults to ``pipeline_stages``."""
        value = self._pipeline_microbatches
        if value is None:
            value = os.environ.get("RLT_PP_MICROBATCHES")
        if value in (None, ""):
            return self.pipeline_stages
        value = int(value)
        if value <= 0:
            raise ValueError(
                f"pipeline_microbatches (RLT_PP_MICROBATCHES) must be > 0, "
                f"got {value}"
            )
        return value

    @property
    def heartbeat_interval(self) -> float:
        """Seconds between worker liveness ticks (see runtime/supervisor.py).
        Constructor argument wins; otherwise the ``RLT_HEARTBEAT_INTERVAL``
        env var; default 1.0s."""
        value = self._heartbeat_interval
        if value is None:
            value = os.environ.get("RLT_HEARTBEAT_INTERVAL")
        if value in (None, ""):
            return 1.0
        value = float(value)
        if value <= 0:
            raise ValueError(
                f"heartbeat_interval (RLT_HEARTBEAT_INTERVAL) must be > 0, "
                f"got {value}"
            )
        return value

    @property
    def hang_timeout(self) -> Optional[float]:
        """Seconds of worker heartbeat silence before the driver declares a
        hang, kills the group and (with ``max_failures``) relaunches from
        the newest checkpoint. ``None``/``0`` disables supervision (the
        default). Constructor argument wins; otherwise ``RLT_HANG_TIMEOUT``."""
        value = self._hang_timeout
        if value is None:
            value = os.environ.get("RLT_HANG_TIMEOUT")
        if value in (None, ""):
            return None
        value = float(value)
        if value < 0:
            raise ValueError(
                f"hang_timeout (RLT_HANG_TIMEOUT) must be >= 0, got {value}"
            )
        return value or None

    @property
    def prefetch_depth(self) -> int:
        """Device-side input lookahead: how many batches beyond the one
        being trained have their host->device transfers dispatched (see
        ``core/prefetch.DevicePrefetcher``). Costs that many extra resident
        batches on device; ``0`` is the fully synchronous path. Constructor
        argument wins; otherwise ``RLT_PREFETCH_DEPTH``; default 2."""
        value = self._prefetch_depth
        if value is None:
            value = os.environ.get("RLT_PREFETCH_DEPTH")
        if value in (None, ""):
            return 2
        value = int(value)
        if value < 0:
            raise ValueError(
                f"prefetch_depth (RLT_PREFETCH_DEPTH) must be >= 0, got {value}"
            )
        return value

    @property
    def loader_num_workers(self) -> Optional[int]:
        """Background threads assembling host batches for the train loop
        (see ``core/prefetch.AsyncLoader``). ``None`` (default) defers to
        the dataloader's own ``num_workers`` hint (else one feeder thread);
        ``0`` keeps host loading synchronous on the training thread.
        Constructor argument wins; otherwise ``RLT_LOADER_WORKERS``."""
        value = self._loader_num_workers
        if value is None:
            value = os.environ.get("RLT_LOADER_WORKERS")
        if value in (None, ""):
            return None
        value = int(value)
        if value < 0:
            raise ValueError(
                f"loader_num_workers (RLT_LOADER_WORKERS) must be >= 0, "
                f"got {value}"
            )
        return value

    @property
    def xla_cache_dir(self) -> Optional[str]:
        """Directory of the persistent XLA compile/executable cache shared
        by the driver and every worker it spawns (see
        ``runtime/compile_cache.py``). ``JAX_COMPILATION_CACHE_DIR`` wins
        when set; then the constructor argument; then the
        ``RLT_XLA_CACHE_DIR`` env var; otherwise ``<checkout>/.xla_cache``.
        ``"0"``/``"off"`` disables (returns None)."""
        from ray_lightning_tpu.runtime.compile_cache import resolve_cache_dir

        return resolve_cache_dir(self._xla_cache_dir)

    @property
    def telemetry(self) -> bool:
        """Whether the distributed flight recorder is on (spans + metrics
        shipped to the driver aggregator over the heartbeat channel; see
        ``observability/``). Off by default — instrumented paths reduce to
        a single attribute check. Constructor argument wins; otherwise the
        ``RLT_TELEMETRY`` env var (``1``/``true``/``yes``/``on``)."""
        if self._telemetry is not None:
            return bool(self._telemetry)
        from ray_lightning_tpu.observability import env_enabled

        return env_enabled()

    # ------------------------------------------------------------------ #
    # wiring
    # ------------------------------------------------------------------ #
    def connect(self, trainer, module) -> None:
        self._trainer = trainer
        self._module = module

    def set_remote(self, remote: bool) -> None:
        """Mark that we now run inside a worker (reference: ray_ddp.py:128-134)."""
        self._is_remote = remote

    # ------------------------------------------------------------------ #
    # environment
    # ------------------------------------------------------------------ #
    def setup_environment(self) -> None:
        if self._mesh is None:
            self._mesh = build_mesh(self.mesh_spec, self._devices())

    def _devices(self):
        return jax.devices()

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self.setup_environment()
        return self._mesh

    def teardown(self) -> None:
        self._mesh = None

    # ------------------------------------------------------------------ #
    # topology
    # ------------------------------------------------------------------ #
    @property
    def world_size(self) -> int:
        """Number of participating *processes* (hosts), not chips."""
        return 1

    @property
    def global_rank(self) -> int:
        return 0

    @property
    def local_rank(self) -> int:
        return 0

    @property
    def node_rank(self) -> int:
        return 0

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    @property
    def num_chips(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def distributed_sampler_kwargs(self) -> Optional[Dict[str, int]]:
        """Rank sharding for the *host-side* dataloader.

        One shard per process; the per-process batch is further split across
        the local mesh data axes on device. (The reference shards per GPU
        worker, ray_ddp.py:315-324; per-host is the TPU-native grain.)
        """
        if self.world_size <= 1:
            return None
        return {"num_replicas": self.world_size, "rank": self.global_rank}

    # ------------------------------------------------------------------ #
    # shardings
    # ------------------------------------------------------------------ #
    @property
    def batch_sharding(self) -> NamedSharding:
        return batch_sharding(self.mesh, self.sharding_policy.data_axes)

    @property
    def replicated(self) -> NamedSharding:
        return replicated_sharding(self.mesh)

    def param_shardings(self, params: Any) -> Any:
        # a module may own its sharding layout (e.g. the llama family's
        # megatron tp + fsdp rules); otherwise partition rules first, then
        # the generic largest-divisible-axis inference for unmatched leaves
        module_fn = getattr(self._module, "param_shardings", None)
        if callable(module_fn):
            sh = module_fn(self.mesh)
            if sh is not None:
                self._optstate_rule = None  # propagate from params via XLA
                self._sharding_report = None
                return sh
        policy = self.sharding_policy
        mesh = self.mesh
        rules = self.partition_rules or ()
        report = ShardingReport()
        axes = policy.effective_shard_axes

        if policy.zero_stage >= 3:
            def fallback(path, leaf):
                return fsdp_leaf_sharding(
                    mesh, leaf, axes, policy.min_shard_size
                )
        else:
            repl = replicated_sharding(mesh)

            def fallback(path, leaf):
                return repl, "replicated"

        sh = apply_partition_rules(mesh, params, rules, fallback, report)
        _, divisor = shard_divisor(mesh, axes)
        warn_silently_replicated(
            [e.path for e in report.silently_replicated()], divisor
        )
        resolutions: Dict[str, Any] = {}
        flat_sh, _ = jax.tree_util.tree_flatten(sh)
        for entry, leaf_sh in zip(report.entries, flat_sh):
            resolutions[entry.path] = (entry.shape, leaf_sh)
        self._sharding_report = report

        if policy.zero_stage >= 1:
            def opt_fallback(path, leaf):
                return fsdp_leaf_sharding(
                    mesh, leaf, axes, policy.min_shard_size
                )
        else:
            repl0 = replicated_sharding(mesh)

            def opt_fallback(path, leaf):
                return repl0, "replicated"

        def optstate_rule(opt_state: Any) -> Any:
            return optstate_shardings_from_params(
                mesh, opt_state, resolutions, opt_fallback, report
            )

        self._optstate_rule = optstate_rule
        return sh

    def optstate_shardings(self, opt_state: Any) -> Optional[Any]:
        """None means: let XLA propagate optimizer-state shardings from the
        (already-sharded) params through ``tx.init``."""
        if not hasattr(self, "_optstate_rule"):
            raise RuntimeError("call param_shardings first")
        if self._optstate_rule is None:
            return None
        return self._optstate_rule(opt_state)

    def describe_shardings(self) -> str:
        """Human-readable report of what claimed every tensor (rule /
        inference / inheritance), including leaves that stayed replicated
        because no axis divides the shard count. Populated by
        ``param_shardings``/``optstate_shardings`` during setup. Under
        composed configs (explicit ZeRO and/or 1F1B pipelining) this is
        extended with the pipeline-stage placement and the per-leaf ZeRO
        shard fraction — a mis-written rule silently replicating a hot
        tensor shows up here as fraction 1.0 before the run burns chips."""
        if self._sharding_report is not None:
            base = self._sharding_report.describe()
        else:
            base = (
                "no sharding report: params not resolved yet, or the module "
                "owns its sharding layout (module.param_shardings)"
            )
        extra = self._describe_composed()
        return base + ("\n" + extra if extra else "")

    def _describe_composed(self) -> str:
        trainer = self._trainer
        if trainer is None:
            return ""
        lines = []
        pp_cfg = getattr(trainer, "_pp_cfg", None)
        if pp_cfg:
            lines.append(
                f"pipeline: {pp_cfg['stages']} stages x "
                f"{pp_cfg['microbatches']} microbatches over axis "
                f"{pp_cfg['axis']!r} (stage params lead with "
                f"{pp_cfg['axis']!r}; last-stage params replicated across "
                "stages)"
            )
        ctx = getattr(trainer, "_zero_ctx", None)
        if ctx is not None:
            n_dev = self.num_chips
            lines.append(
                f"ZeRO shard fractions over {n_dev} devices (fraction of "
                "each tensor + its optimizer state one device holds; 1.0 = "
                "fully replicated):"
            )
            for i, path in enumerate(ctx.leaf_paths):
                frac = ctx.shard_fraction(i)
                kind = (
                    "zero+model" if ctx.is_big(i) and frac < 1.0 / ctx.n
                    else "zero" if ctx.is_big(i)
                    else "model" if frac < 1.0
                    else "replicated"
                )
                lines.append(f"  {path}: {frac:.4g} [{kind}]")
        if not lines:
            return ""
        return "composed parallelism:\n" + "\n".join(
            "  " + l for l in lines
        )

    def place_params(self, params: Any) -> Any:
        """Host pytree -> device arrays with the policy's shardings."""
        shardings = self.param_shardings(params)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), params, shardings
        )


    # ------------------------------------------------------------------ #
    # data movement
    # ------------------------------------------------------------------ #
    def shard_batch(self, batch: Any) -> Any:
        """Host numpy batch -> device arrays sharded over the data axes.

        In multi-process mode each process holds its slice of the global
        batch; ``make_array_from_process_local_data`` assembles the global
        sharded array without any host gather.
        """
        sharding = self.batch_sharding
        multiproc = jax.process_count() > 1
        n_shards = 1
        for entry in sharding.spec:
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if a is not None:
                    n_shards *= self.mesh.shape[a]

        # each process only needs its local slice divisible by its
        # addressable shards; the sampler already split the global batch
        local_shards = max(1, n_shards // jax.process_count()) if multiproc else n_shards

        def put(x):
            x = np.asarray(x)
            if x.ndim and local_shards > 1 and x.shape[0] % local_shards:
                raise ValueError(
                    f"per-process batch size {x.shape[0]} is not divisible by "
                    f"the {local_shards} local data-parallel shards of mesh "
                    f"{dict(self.mesh.shape)}; pick batch_size as a multiple "
                    f"of {local_shards}"
                )
            if multiproc:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, batch)

    def global_batch_size(self, local_batch_size: int) -> int:
        return local_batch_size * self.world_size

    # ------------------------------------------------------------------ #
    # host-side sync helpers (used outside jit, e.g. metric reduce)
    # ------------------------------------------------------------------ #
    def barrier(self) -> None:
        pass

    def broadcast_host(self, obj: Any, src: int = 0) -> Any:
        return obj


class XLAStrategy(Strategy):
    """In-process strategy over all (or a subset of) local devices.

    The default when no strategy is passed: data-parallel over every local
    chip of one host. With 8 forced CPU devices this is also the test-time
    stand-in for an 8-chip slice.
    """

    strategy_name = "xla"

    def __init__(
        self,
        mesh_spec: Optional[MeshSpec] = None,
        sharding_policy: Optional[ShardingPolicy] = None,
        devices: Optional[int] = None,
        dcn_grad_compression: Optional[str] = None,
        heartbeat_interval: Optional[float] = None,
        hang_timeout: Optional[float] = None,
        telemetry: Optional[bool] = None,
        prefetch_depth: Optional[int] = None,
        loader_num_workers: Optional[int] = None,
        xla_cache_dir: Optional[str] = None,
        partition_rules: Optional[Any] = None,
        zero_quantized_allgather: Optional[bool] = None,
        zero_gather_group_size: int = 8,
        pipeline_stages: Optional[int] = None,
        pipeline_microbatches: Optional[int] = None,
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
            pipeline_stages=pipeline_stages,
            pipeline_microbatches=pipeline_microbatches,
        )
        self._num_devices = devices

    def _devices(self):
        devs = jax.devices()
        if self._num_devices is not None:
            devs = devs[: self._num_devices]
        return devs


class SingleDeviceStrategy(XLAStrategy):
    strategy_name = "single_device"

    def __init__(self):
        super().__init__(MeshSpec(axes={"dp": 1}), ShardingPolicy.ddp(), devices=1)
