"""The Trainer: PTL-parity fit/validate/test/predict driving a compiled step.

Architecture (TPU-first, not a port):
- The entire optimization step — forward, backward, optimizer update, metric
  computation — is ONE ``jax.jit``-compiled function, traced once per
  (shape, dtype) signature and executed every step on device. There is no
  eager per-batch Python in the hot loop beyond host->device batch transfer
  and callback dispatch.
- Distribution is delegated to the Strategy's shardings; XLA GSPMD inserts
  the collectives. ``params``/``opt_state`` are donated each step so the
  update is in-place in HBM.
- When the Strategy has a launcher (Ray-actor strategies), ``fit`` ships the
  whole (trainer, module) to workers and recovers rank-0 results — the
  reference's launch flow (reference: ray_lightning/launchers/
  ray_launcher.py:48-69,252-310) with byte-stream weights instead of
  ``torch.save``.

Hot-loop hygiene: per-step logged values stay as device arrays; host
synchronization happens only at logger flush points and epoch boundaries.
"""
from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization as flax_serialization

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.callbacks.base import Callback
from ray_lightning_tpu.callbacks.checkpoint import ModelCheckpoint
from ray_lightning_tpu.core.data import DataLoader, DistributedSampler, ensure_loader
from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.loggers.base import Logger
from ray_lightning_tpu.loggers.csv_logger import CSVLogger
from ray_lightning_tpu.runtime import compile_cache as _compile_cache
from ray_lightning_tpu.strategies.base import Strategy, XLAStrategy
from ray_lightning_tpu.utils import fsio
from ray_lightning_tpu.utils.precision import (
    cast_floats,
    matmul_precision_scope,
    parse_matmul_precision,
    parse_precision,
    round_matmul_inputs,
)
from ray_lightning_tpu.utils.seed import seed_everything
from ray_lightning_tpu.utils.serialization import to_state_stream, load_state_stream

__version__ = "0.1.0"


@dataclass
class TrainerState:
    fn: Optional[str] = None  # fit | validate | test | predict
    status: str = "initializing"  # running | finished | interrupted

    def as_dict(self) -> Dict[str, str]:
        return {"fn": self.fn or "", "status": self.status}


def _spanned_pulls(batches):
    """``batches``, with every pull of the next item inside an
    ``rlt.train.input_wait`` span: the time the loop waits for its input."""
    it = iter(batches)
    while True:
        with obs.phase_span("rlt.train.input_wait"):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


@dataclass
class _EpochAggregator:
    """Accumulates per-batch on_epoch metrics as device scalars; reduces at
    epoch end (single host sync)."""

    sums: Dict[str, list] = field(default_factory=dict)
    weights: Dict[str, list] = field(default_factory=dict)

    def update(self, logs: Dict[str, Any], batch_size: int) -> None:
        for name, value in logs.items():
            self.sums.setdefault(name, []).append(value)
            self.weights.setdefault(name, []).append(batch_size)

    def reduce(self, meta_lookup) -> Dict[str, np.ndarray]:
        out = {}
        for name, values in self.sums.items():
            vals = np.asarray(jax.device_get(values), dtype=np.float64)
            meta = meta_lookup(name)
            reduce_kind = meta.reduce if meta else "mean"
            if reduce_kind == "mean":
                w = np.asarray(self.weights[name], dtype=np.float64)
                out[name] = np.asarray(np.sum(vals * w) / max(np.sum(w), 1e-12))
            elif reduce_kind == "sum":
                out[name] = np.asarray(np.sum(vals))
            elif reduce_kind == "max":
                out[name] = np.asarray(np.max(vals))
            elif reduce_kind == "min":
                out[name] = np.asarray(np.min(vals))
            else:
                out[name] = np.asarray(vals[-1])
        return out


class Trainer:
    def __init__(
        self,
        max_epochs: Optional[int] = None,
        min_epochs: int = 0,
        max_steps: int = -1,
        callbacks: Optional[List[Callback]] = None,
        logger: Any = True,
        strategy: Optional[Strategy] = None,
        accelerator: str = "auto",
        devices: Any = "auto",
        enable_checkpointing: bool = True,
        default_root_dir: Optional[str] = None,
        log_every_n_steps: int = 50,
        check_val_every_n_epoch: int = 1,
        val_check_interval: Optional[Union[int, float]] = None,
        num_sanity_val_steps: int = 0,
        limit_train_batches: Optional[Union[int, float]] = None,
        limit_val_batches: Optional[Union[int, float]] = None,
        limit_test_batches: Optional[Union[int, float]] = None,
        limit_predict_batches: Optional[Union[int, float]] = None,
        gradient_clip_val: Optional[float] = None,
        accumulate_grad_batches: int = 1,
        precision: Optional[Union[str, int]] = None,
        seed: Optional[int] = None,
        enable_progress_bar: bool = False,
        fast_dev_run: bool = False,
        use_distributed_sampler: bool = True,
    ):
        self.max_epochs = max_epochs if max_epochs is not None else 1000
        self.min_epochs = min_epochs
        self.max_steps = max_steps
        self.log_every_n_steps = log_every_n_steps
        self.check_val_every_n_epoch = check_val_every_n_epoch
        # PTL semantics: ints are batch/step counts, floats are fractions of
        # the epoch (reference inherits this from PTL 1.6 Trainer args)
        for _name in (
            "val_check_interval",
            "limit_train_batches",
            "limit_val_batches",
            "limit_test_batches",
            "limit_predict_batches",
        ):
            _v = locals()[_name]
            if _v is not None and not isinstance(_v, int):
                if not isinstance(_v, float):
                    raise TypeError(f"{_name} must be int, float, or None, got {_v!r}")
                if not 0.0 <= _v <= 1.0:
                    raise ValueError(
                        f"{_name}={_v}: float values are epoch fractions and "
                        "must be in [0.0, 1.0]; pass an int for a batch count"
                    )
        self.val_check_interval = val_check_interval
        self.num_sanity_val_steps = num_sanity_val_steps
        self.limit_train_batches = limit_train_batches
        self.limit_val_batches = limit_val_batches
        self.limit_test_batches = limit_test_batches
        self.limit_predict_batches = limit_predict_batches
        self.gradient_clip_val = gradient_clip_val
        self.accumulate_grad_batches = accumulate_grad_batches
        self.precision = precision
        # PTL parity: precision is a real dtype policy, not a stored string
        # (None = module-owned dtypes; see utils/precision.py)
        self.precision_policy = parse_precision(precision)
        self.seed = seed
        self.enable_progress_bar = enable_progress_bar
        self.fast_dev_run = fast_dev_run
        self.use_distributed_sampler = use_distributed_sampler
        self.enable_checkpointing = enable_checkpointing and not fast_dev_run
        # safe-boundary hooks: callables fired at the points where chip
        # membership may change without losing work — every per-step
        # health tick (boundary="step") and every epoch end
        # (boundary="epoch_end"). The ChipArbiter's training handle
        # registers here to learn when a shrink/grow is safe to apply.
        self._safe_boundary_hooks: List[Callable[[int, str], None]] = []
        if fast_dev_run:
            self.max_epochs = 1
            self.limit_train_batches = 1
            self.limit_val_batches = 1
            self.limit_test_batches = 1

        self.default_root_dir = os.path.abspath(default_root_dir or os.getcwd())

        self.strategy: Strategy = strategy or XLAStrategy()
        self.accelerator = accelerator
        if accelerator in ("_tpu", "tpu") and hasattr(self.strategy, "num_workers"):
            # delayed accelerator: only launcher strategies train in REMOTE
            # workers — for those, keep the driver off the chip (reference
            # _GPUAccelerator role; accelerators/delayed_tpu.py). In-process
            # strategies must keep their accelerator.
            from ray_lightning_tpu.accelerators import DelayedTPUAccelerator
            from ray_lightning_tpu.utils.common import rank_zero_warn

            if not DelayedTPUAccelerator.setup_driver():
                rank_zero_warn(
                    "accelerator='_tpu' requested but a non-CPU backend is "
                    "already initialized in the driver; workers may fail to "
                    "acquire the TPU"
                )

        self.callbacks: List[Callback] = list(callbacks or [])
        if self.enable_checkpointing and not any(
            isinstance(c, ModelCheckpoint) for c in self.callbacks
        ):
            self.callbacks.append(ModelCheckpoint())
        # checkpoint-writing callbacks dispatch LAST (PTL semantics): the
        # state they snapshot must reflect every other callback having
        # already processed the hook (stable within each group, so the
        # save/restore state-key enumeration is unchanged between runs)
        self.callbacks.sort(key=lambda c: c.saves_checkpoints)

        if logger is True:
            self.logger: Optional[Logger] = CSVLogger(
                os.path.join(self.default_root_dir, "lightning_logs")
            )
        elif logger is False or logger is None:
            self.logger = None
        else:
            self.logger = logger

        # runtime state
        self.state = TrainerState()
        self.current_epoch = 0
        self.global_step = 0
        self.should_stop = False
        self.sanity_checking = False
        self.num_val_batches = 0
        self.val_enabled = False
        self._val_ran_this_epoch = False
        # False while inside an epoch's batch loop: checkpoints written then
        # (val_check_interval saves) record epoch_complete=False so a resume
        # re-runs the partial epoch instead of skipping its remainder
        self._epoch_ended = True
        self.callback_metrics: Dict[str, np.ndarray] = {}
        self.logged_metrics: Dict[str, Any] = {}
        self._module: Optional[LightningModule] = None
        self._params = None
        self._opt_state = None
        self._tx = None
        self._alt_txs = None  # alternating optimizers (GAN-style), or None
        self._alt_labels = None
        # compressed DCN collectives context (parallel/compression.py), set
        # by _setup_dcn_compression when the strategy enables it; None means
        # the standard GSPMD implicit-all-reduce train step
        self._dcn_ctx = None
        # explicit-ZeRO context (parallel/zero.py), set by _setup_zero for
        # RayShardedStrategy(zero_stage>=2) when the model/optimizer shape
        # qualifies; None means sharding stays GSPMD placement only
        self._zero_ctx = None
        self._zero_tx = None  # clip-stripped wrap of the configured tx
        # why the explicit ZeRO path was declined (slug mirrored in
        # rlt_zero_fallback_total{reason}); None = engaged or never tried
        self._zero_fallback_reason = None
        # 1F1B pipeline config (strategy pipeline_stages/RLT_PP_STAGES),
        # set by _setup_pipeline; None means no pipelining
        self._pp_cfg = None
        self._configured_tx = None  # pre-_wrap_tx optax transformation
        self._train_program = "train_step"  # compile-cache/profiler key
        self._matmul_precision = "default"  # resolved in _build_train_step
        self._rng_root = None
        self._datamodule = None
        # flight recorder handle: None when telemetry is off, so every
        # instrumented hot path reduces to one attribute check (`if rec`)
        self._obs = None
        self._goodput = None
        self._profiler = None
        self._first_step_dispatched = False
        self._restored_ckpt: Optional[Dict[str, Any]] = None
        # set by the launcher on a max_failures relaunch: newest checkpoint
        # the crashed worker group wrote ("orbax:<dir>" for the sharded path)
        self._relaunch_ckpt_path: Optional[str] = None

    # ------------------------------------------------------------------ #
    # public properties
    # ------------------------------------------------------------------ #
    @property
    def world_size(self) -> int:
        return self.strategy.world_size

    @property
    def global_rank(self) -> int:
        return self.strategy.global_rank

    @property
    def local_rank(self) -> int:
        return self.strategy.local_rank

    @property
    def is_global_zero(self) -> bool:
        return self.strategy.is_global_zero

    @property
    def is_global_zero_writer(self) -> bool:
        """Who writes checkpoints: global rank 0 (driver or worker-0)."""
        return self.strategy.is_global_zero

    @property
    def lightning_module(self) -> Optional[LightningModule]:
        return self._module

    @property
    def model(self) -> Optional[LightningModule]:
        return self._module

    @property
    def checkpoint_callback(self) -> Optional[ModelCheckpoint]:
        for cb in self.callbacks:
            if isinstance(cb, ModelCheckpoint):
                return cb
        return None

    @property
    def checkpoint_callbacks(self) -> List[ModelCheckpoint]:
        return [cb for cb in self.callbacks if isinstance(cb, ModelCheckpoint)]

    @property
    def early_stopping_callback(self):
        from ray_lightning_tpu.callbacks.early_stopping import EarlyStopping

        for cb in self.callbacks:
            if isinstance(cb, EarlyStopping):
                return cb
        return None

    @property
    def params(self):
        return self._params

    # ------------------------------------------------------------------ #
    # callback dispatch
    # ------------------------------------------------------------------ #
    def _hook(self, name: str, *args) -> None:
        module_hook = getattr(self._module, name, None)
        if callable(module_hook):
            module_hook(*args)
        for cb in self.callbacks:
            getattr(cb, name)(self, self._module, *args)

    def _cb(self, name: str, *args) -> None:
        for cb in self.callbacks:
            getattr(cb, name)(self, self._module, *args)

    # ------------------------------------------------------------------ #
    # entry points
    # ------------------------------------------------------------------ #
    def fit(
        self,
        model: LightningModule,
        train_dataloaders=None,
        val_dataloaders=None,
        datamodule=None,
        ckpt_path: Optional[str] = None,
    ) -> None:
        self.state.fn = "fit"
        self._launch(
            self._fit_impl, model, train_dataloaders, val_dataloaders, datamodule, ckpt_path
        )

    def validate(
        self, model=None, dataloaders=None, datamodule=None, ckpt_path=None, verbose=True
    ):
        self.state.fn = "validate"
        return self._launch(self._eval_impl, model, dataloaders, datamodule, ckpt_path, "val")

    def test(
        self, model=None, dataloaders=None, datamodule=None, ckpt_path=None, verbose=True
    ):
        self.state.fn = "test"
        return self._launch(self._eval_impl, model, dataloaders, datamodule, ckpt_path, "test")

    def predict(self, model=None, dataloaders=None, datamodule=None, ckpt_path=None):
        self.state.fn = "predict"
        return self._launch(self._predict_impl, model, dataloaders, datamodule, ckpt_path)

    def _launch(self, fn, model, *args):
        model = model or self._module
        if model is None:
            raise ValueError("no model provided")
        self._module = model
        model.trainer = self
        self.strategy.connect(self, model)
        launcher = self.strategy.launcher
        self.state.status = "running"
        try:
            if launcher is not None:
                result = launcher.launch(fn, model, *args, trainer=self)
            else:
                result = fn(model, *args)
            self.state.status = "finished"
            return result
        except BaseException as e:
            self.state.status = "interrupted"
            self._cb("on_exception", e)
            raise

    # ------------------------------------------------------------------ #
    # dataloader resolution
    # ------------------------------------------------------------------ #
    def _resolve_loader(self, explicit, datamodule, module_hook_name: str):
        if explicit is not None:
            return ensure_loader(explicit)
        if datamodule is not None:
            hook = getattr(datamodule, module_hook_name, None)
            if hook is not None:
                loader = hook()
                if loader is not None:
                    return ensure_loader(loader)
        hook = getattr(self._module, module_hook_name, None)
        if hook is not None:
            loader = hook()
            if loader is not None:
                return ensure_loader(loader)
        return None

    def _maybe_shard_loader(self, loader, shuffle: bool):
        """Inject the rank-sharding sampler (reference: ray_ddp.py:315-324)."""
        kwargs = self.strategy.distributed_sampler_kwargs
        if (
            kwargs is None
            or not self.use_distributed_sampler
            or not isinstance(loader, DataLoader)
            or loader.sampler is not None
        ):
            return loader
        sampler = DistributedSampler(
            len(loader.dataset),
            shuffle=shuffle,
            seed=int(os.environ.get("RLT_GLOBAL_SEED", "0")),
            drop_last=loader.drop_last,
            **kwargs,
        )
        return loader.with_sampler(sampler)

    # ------------------------------------------------------------------ #
    # optimizer normalization
    # ------------------------------------------------------------------ #
    @staticmethod
    def _broadcast_labels(labels, params):
        """Expand a label *prefix* tree (e.g. {"gen": 0, "disc": 1} over a
        nested param pytree) to the params' full structure; callables are
        applied to params first. Exact-structure labels pass through."""
        if callable(labels):
            labels = labels(params)
        prefix_def = jax.tree_util.tree_structure(labels)
        subtrees = prefix_def.flatten_up_to(params)
        flat = jax.tree_util.tree_leaves(labels)
        full = [
            jax.tree_util.tree_map(lambda _, l=l: l, st)
            for l, st in zip(flat, subtrees)
        ]
        return jax.tree_util.tree_unflatten(prefix_def, full)

    def _wrap_tx(self, tx, skip_clip: bool = False) -> optax.GradientTransformation:
        """Trainer-level knobs applied around any optimizer. ``skip_clip``
        is for the explicit-ZeRO step: inside its shard_map the optimizer
        sees shard-LOCAL gradients, so ``clip_by_global_norm`` would clip
        by the wrong (per-shard) norm — the step computes the true global
        norm itself with a psum and pre-scales the gradients."""
        if self.gradient_clip_val and not skip_clip:
            tx = optax.chain(optax.clip_by_global_norm(self.gradient_clip_val), tx)
        if self.accumulate_grad_batches > 1:
            tx = optax.MultiSteps(tx, every_k_schedule=self.accumulate_grad_batches)
        return tx

    def _normalize_tx(self, configured) -> Optional[optax.GradientTransformation]:
        self._alt_txs = None
        self._alt_labels = None
        if isinstance(configured, dict) and "optimizers" in configured:
            opts = configured["optimizers"]
            labels = configured.get("param_labels")
            if labels is None:
                raise ValueError(
                    "configure_optimizers returned {'optimizers': ...} "
                    "without 'param_labels' (a pytree of labels — a prefix "
                    "over the params is fine — or a callable params -> labels)"
                )
            if isinstance(opts, (list, tuple)):
                # ALTERNATING optimizers (PTL optimizer_idx / GAN-style):
                # one compiled program runs len(opts) sequential sub-steps;
                # sub-step i takes value_and_grad of
                # training_step(..., optimizer_idx=i) and updates only the
                # leaves labeled i (set_to_zero for the rest, so XLA DCEs
                # the unused gradient branches). param_labels maps each
                # leaf to an optimizer index.
                def wrapped(i, tx):
                    def lab(params, i=i):
                        full = self._broadcast_labels(labels, params)
                        return jax.tree_util.tree_map(
                            lambda l: "active" if int(l) == i else "frozen", full
                        )

                    return optax.multi_transform(
                        {"active": self._wrap_tx(tx), "frozen": optax.set_to_zero()},
                        lab,
                    )

                self._alt_txs = [wrapped(i, tx) for i, tx in enumerate(opts)]
                self._alt_labels = labels
                return None
            # several optimizers over DISJOINT parameter groups (the common
            # "different lr/opt for head vs body"): optax.multi_transform
            # routes each labeled leaf to its transformation inside ONE
            # compiled step.
            configured = optax.multi_transform(
                opts, lambda p: self._broadcast_labels(labels, p)
            )
        elif isinstance(configured, dict):
            configured = configured.get("optimizer", configured)
        # optax transforms are NamedTuples; only unwrap plain containers
        if isinstance(configured, (list, tuple)) and not hasattr(configured, "update"):
            if len(configured) != 1:
                raise ValueError(
                    "a bare list of optimizers is ambiguous: for PTL-style "
                    "ALTERNATING optimizers (optimizer_idx) return "
                    "{'optimizers': [tx0, tx1], 'param_labels': <leaf -> "
                    "optimizer index>}; for per-parameter-group optimizers "
                    "over one loss return {'optimizers': {label: tx}, "
                    "'param_labels': ...} (optax.multi_transform)"
                )
            configured = configured[0]
        if not hasattr(configured, "update"):
            raise TypeError(
                "configure_optimizers must return an optax.GradientTransformation"
            )
        # kept un-wrapped so the explicit-ZeRO step can re-wrap with
        # skip_clip=True (it owns global-norm clipping)
        self._configured_tx = configured
        return self._wrap_tx(configured)

    # ------------------------------------------------------------------ #
    # compressed DCN collectives (parallel/compression.py)
    # ------------------------------------------------------------------ #
    def _setup_dcn_compression(self):
        """Resolve the strategy's ``dcn_grad_compression`` knob into a
        context dict for the compressed train step, or None for the
        standard implicit-all-reduce path.

        Compression replaces XLA's implicit gradient all-reduce with an
        explicit ``shard_map`` collective, so it only composes with
        configurations where the gradient reduction is the ONLY cross-
        device traffic in the step: replicated params/optimizer over pure
        data-parallel axes. Anything else raises (or warns and falls back
        where a silent no-op is the correct semantics).
        """
        mode = getattr(self.strategy, "dcn_grad_compression", "none")
        if mode == "none":
            return None
        from ray_lightning_tpu.parallel.compression import DEFAULT_BLOCK_SIZE
        from ray_lightning_tpu.parallel.mesh import split_dcn_axes
        from ray_lightning_tpu.utils.common import rank_zero_warn

        if self._alt_txs is not None:
            rank_zero_warn(
                "dcn_grad_compression=%r is not supported with alternating "
                "optimizers; gradients stay uncompressed",
                mode,
            )
            return None
        mesh = self.strategy.mesh
        policy = self.strategy.sharding_policy
        ici_axes, dcn_axes = split_dcn_axes(
            self.strategy.mesh_spec, mesh, policy.data_axes
        )
        if not dcn_axes:
            rank_zero_warn(
                "dcn_grad_compression=%r but no data axis rides DCN "
                "(MeshSpec.dcn_axes is empty or the dcn axes have size 1); "
                "gradients stay uncompressed",
                mode,
            )
            return None
        if len(dcn_axes) > 1:
            raise ValueError(
                f"dcn_grad_compression supports one DCN data axis, got "
                f"{dcn_axes}; fold the cross-slice axes into a single one"
            )
        if policy.zero_stage != 0:
            raise ValueError(
                f"dcn_grad_compression requires replicated params and "
                f"optimizer state (zero_stage=0), got zero_stage="
                f"{policy.zero_stage}: under ZeRO the update itself is "
                "sharded and the quantized reduce-scatter is not implemented"
            )
        non_data = [
            a
            for a in mesh.axis_names
            if a not in policy.data_axes and mesh.shape[a] > 1
        ]
        if non_data:
            raise ValueError(
                f"dcn_grad_compression supports pure data-parallel meshes; "
                f"model axes {non_data} have size > 1"
            )
        module_fn = getattr(self._module, "param_shardings", None)
        if callable(module_fn) and module_fn(mesh) is not None:
            raise ValueError(
                "dcn_grad_compression requires replicated params, but the "
                "module owns a sharded layout (param_shardings)"
            )
        try:
            block_size = int(
                os.environ.get("RLT_DCN_BLOCK_SIZE", DEFAULT_BLOCK_SIZE)
            )
        except ValueError:
            raise ValueError(
                f"RLT_DCN_BLOCK_SIZE={os.environ['RLT_DCN_BLOCK_SIZE']!r} "
                "is not an int"
            )
        dcn_axis = dcn_axes[0]
        batch_axes = tuple(
            a
            for a in policy.data_axes
            if a in mesh.axis_names and mesh.shape[a] > 1
        )
        return {
            "mesh": mesh,
            "dcn_axis": dcn_axis,
            "dcn_size": int(mesh.shape[dcn_axis]),
            "ici_axes": ici_axes,
            "batch_axes": batch_axes,
            "block_size": block_size,
        }

    # ------------------------------------------------------------------ #
    # explicit ZeRO update sharding (parallel/zero.py, 2004.13336)
    # ------------------------------------------------------------------ #
    def _setup_zero(self):
        """Decide whether the EXPLICIT ZeRO update path runs (reduce-scatter
        grads -> 1/N optimizer update per rank -> grouped param all-gather
        inside a shard_map), returning its ZeroContext, or None for the
        implicit GSPMD-placement path.

        The explicit step assumes ELEMENTWISE optimizer transforms
        (adam/sgd/rmsprop/adamw/...): per-tensor-norm optimizers
        (lamb/lars/adafactor) compute tensor statistics that are wrong on
        a 1/N shard and must stay on the GSPMD path.

        Composes with MODEL-axis parallelism: partition_rules (and the
        pipeline's stage axis) claim model axes per leaf and the ZeRO
        machinery runs per model shard; only rules that claim the DATA
        axis itself force the GSPMD fallback. Every declined path is
        observable: ``rlt_zero_fallback_total{reason}`` increments and
        ``self._zero_fallback_reason`` carries the slug for
        :meth:`describe_parallelism`.
        """
        policy = self.strategy.sharding_policy
        quantized = bool(getattr(self.strategy, "zero_quantized_allgather", False))
        self._zero_fallback_reason = None
        if policy.zero_stage < 2:
            if quantized:
                raise ValueError(
                    "zero_quantized_allgather (RLT_ZERO_QUANTIZED_ALLGATHER) "
                    "requires a ZeRO strategy with zero_stage >= 3, got "
                    f"zero_stage={policy.zero_stage}"
                )
            return None
        from ray_lightning_tpu.parallel.zero import (
            PAD_UNIT,
            ZeroContext,
            ZeroLayoutError,
        )
        from ray_lightning_tpu.utils.common import rank_zero_warn

        def fallback(reason, slug):
            self._zero_fallback_reason = slug
            reg = obs.registry()
            if reg is not None:
                reg.counter("rlt_zero_fallback_total", reason=slug).inc()
            if quantized:
                raise ValueError(
                    "zero_quantized_allgather needs the explicit ZeRO update "
                    f"step, but {reason}"
                )
            rank_zero_warn(
                "explicit ZeRO update path disabled (%s); zero_stage=%d "
                "falls back to GSPMD sharding propagation",
                reason,
                policy.zero_stage,
            )
            return None

        if self._alt_txs is not None:
            return fallback(
                "alternating optimizers are configured",
                "alternating_optimizers",
            )
        if self._dcn_ctx is not None:
            return fallback(
                "dcn_grad_compression is active", "dcn_compression"
            )
        mesh = self.strategy.mesh
        module_fn = getattr(self._module, "param_shardings", None)
        if callable(module_fn) and module_fn(mesh) is not None:
            return fallback(
                "the module owns its sharding layout", "module_shardings"
            )
        data_axes = [
            a
            for a in policy.data_axes
            if a in mesh.axis_names and mesh.shape[a] > 1
        ]
        if len(data_axes) > 1:
            return fallback(
                f"needs a single data axis, got {data_axes}",
                "multiple_data_axes",
            )
        axis = data_axes[0] if data_axes else policy.data_axes[0]
        if axis not in mesh.axis_names:
            return fallback(
                f"data axis {axis!r} missing from the mesh",
                "missing_data_axis",
            )
        try:
            param_specs, claims = self._model_axis_specs()
        except ValueError as err:
            return fallback(str(err), "bad_model_specs")
        if claims:
            return fallback(
                f"partition_rules claim the data axis ({claims}); rules "
                "may only claim model axes under the explicit ZeRO step",
                "rules_claim_data_axis",
            )
        n = int(mesh.shape[axis])
        if PAD_UNIT % n:
            return fallback(
                f"world size {n} does not divide the padding unit "
                f"{PAD_UNIT} (padded shapes would depend on the world size "
                "and break elastic state handoff)",
                "pad_unit",
            )
        try:
            ctx = ZeroContext(
                mesh,
                axis,
                self._param_shape_tree,
                stage=policy.zero_stage,
                min_shard_size=policy.min_shard_size,
                quantized=quantized,
                gather_group_size=getattr(
                    self.strategy, "zero_gather_group_size", 8
                ),
                param_specs=param_specs,
            )
        except ZeroLayoutError as err:
            return fallback(str(err), "layout_ambiguous")
        if not ctx.big_leaves:
            return fallback(
                f"no float param leaf reaches min_shard_size="
                f"{policy.min_shard_size}",
                "no_big_leaves",
            )
        self._zero_tx = self._wrap_tx(self._configured_tx, skip_clip=True)
        self._publish_zero_telemetry(ctx)
        return ctx

    def _model_axis_specs(self):
        """Per-leaf MODEL-axis PartitionSpecs for the composed train step:
        the pipeline's stage axis first (``stages/`` leaves lead with the
        pp axis), then the strategy's regex partition rules. Returns
        ``(spec_tree_or_None, claims)`` where ``claims`` is a non-empty
        description when a rule claims a DATA axis (the caller must fall
        back to GSPMD placement — the explicit ZeRO step owns that axis)."""
        from jax.sharding import PartitionSpec as P

        from ray_lightning_tpu.parallel.partition_rules import (
            resolve_rule,
            spec_axes,
        )
        from ray_lightning_tpu.parallel.sharding import path_str

        rules = self.strategy.partition_rules or ()
        pp_cfg = self._pp_cfg
        if not rules and pp_cfg is None:
            return None, ""
        data_axes = set(self.strategy.sharding_policy.data_axes)
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self._param_shape_tree
        )
        specs, claims = [], []
        for key_path, _leaf in flat:
            path = path_str(key_path)
            is_stage = pp_cfg is not None and (
                path == "stages" or path.startswith("stages/")
            )
            rule = resolve_rule(rules, path)
            if rule is not None:
                spec = rule.partition_spec()
                hit = sorted(set(spec_axes(spec)) & data_axes)
                if hit:
                    claims.append(
                        f"{rule.pattern!r} places {path} on {hit}"
                    )
                elif is_stage and (not len(spec) or spec[0] != pp_cfg["axis"]):
                    raise ValueError(
                        f"pipeline stage param {path!r} matched rule "
                        f"{rule.pattern!r} with spec {spec}, which does not "
                        f"lead with the stage axis {pp_cfg['axis']!r}"
                    )
            elif is_stage:
                spec = P(pp_cfg["axis"])
            else:
                spec = P()
            specs.append(spec)
        return (
            jax.tree_util.tree_unflatten(treedef, specs),
            "; ".join(claims),
        )

    def _publish_zero_telemetry(self, ctx) -> None:
        """Wire-cost gauges for the ZeRO param all-gather: what the
        configured gather costs per step vs what an fp32 gather would —
        the quantization win as numbers, next to the profiler's
        rlt_collective_bytes_total for the same program."""
        reg = obs.registry()
        if reg is None:
            return
        reg.gauge(
            "rlt_zero_allgather_bytes", program="zero_train_step"
        ).set(float(ctx.gather_wire_bytes()))
        reg.gauge(
            "rlt_zero_allgather_fp32_bytes", program="zero_train_step"
        ).set(float(ctx.gather_fp32_bytes()))
        reg.gauge("rlt_zero_sharded_params").set(float(len(ctx.big_leaves)))

    def _build_zero_train_step(self):
        """The explicit ZeRO train step: grads reduce-scattered over the
        data axis, optimizer update on this rank's 1/N shard (fp32 masters
        at stage 3, re-sliced params at stage 2), updated params
        all-gathered per layer group — optionally as an int8 block-scaled
        payload with error feedback carried in the ZeroState.

        Under composed model-axis parallelism (partition rules), params
        enter the shard_map with their MODEL-axis specs: the module's
        ``training_step`` sees its tp-local weight shards and must perform
        its cross-shard math with the f/g operators from
        ``parallel.pipeline_1f1b`` (``identity_fwd_psum_bwd`` /
        ``psum_fwd_identity_bwd``) so replicated-leaf gradients come out
        identical across the model axes — gradient reduction then crosses
        only the data axis (scatter_grads)."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from ray_lightning_tpu.parallel.zero import ZeroState

        module = self._module
        policy = self.precision_policy
        compute_dtype = policy.compute_dtype
        ctx = self._zero_ctx
        tx = self._zero_tx
        axis = ctx.axis
        clip = self.gradient_clip_val
        mp = self._matmul_precision
        state_specs = ctx.state_specs(self._opt_state)

        def _mean(v):
            return (
                jax.lax.pmean(v, axis)
                if ctx.n > 1
                and jnp.issubdtype(jnp.result_type(v), jnp.inexact)
                else v
            )

        def train_step(params, zstate, batch, rng_root, step):
            with matmul_precision_scope(mp):
                rng = jax.random.fold_in(rng_root, step)
                batch = cast_floats(batch, compute_dtype)
                batch = round_matmul_inputs(mp, batch)

                def loss_fn(p):
                    if policy.cast_params_in_compute:
                        p = cast_floats(p, compute_dtype)
                    p = round_matmul_inputs(mp, p)
                    module._capture_begin("train", rng)
                    out = module.training_step(p, batch, step)
                    logs = module._capture_end()
                    if isinstance(out, dict):
                        loss = out["loss"]
                        mutated = out.get("mutated_params")
                    else:
                        loss, mutated = out, None
                    return loss, (logs, mutated)

                (loss, (logs, mutated)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                # batch-mean big grads land as this rank's [chunk] slice
                mixed_g = ctx.scatter_grads(grads)
                if clip:
                    gnorm = ctx.global_grad_norm(mixed_g)
                    scale = jnp.minimum(
                        1.0, clip / jnp.maximum(gnorm, 1e-12)
                    )
                    mixed_g = jax.tree_util.tree_map(
                        lambda g: g * scale.astype(g.dtype)
                        if jnp.issubdtype(g.dtype, jnp.floating)
                        else g,
                        mixed_g,
                    )
                cur = ctx.current_mixed(params, zstate.masters)
                updates, new_inner = tx.update(mixed_g, zstate.inner, cur)
                new_mixed = optax.apply_updates(cur, updates)
                new_params, new_masters, new_ef = ctx.gather_params(
                    params, new_mixed, zstate.gather_ef
                )
                if mutated is not None and isinstance(new_params, dict):
                    # forward-mutated collections (e.g. batch_stats) are
                    # device-varying here — average them like DDP buffers
                    mutated = jax.tree_util.tree_map(_mean, mutated)
                    new_params = {
                        k: (
                            mutated[k]
                            if (k != "params" and k in mutated)
                            else v
                        )
                        for k, v in new_params.items()
                    }
                logs = {k: _mean(v) for k, v in logs.items()}
                logs.setdefault("loss", _mean(loss))
                return (
                    new_params,
                    ZeroState(new_inner, new_masters, tuple(new_ef)),
                    logs,
                )

        # params carry their model-axis specs (all-P() without rules): the
        # body sees model-local shards, the data axis stays ZeRO's own
        pspec = ctx.param_spec_tree
        mapped = shard_map(
            train_step,
            mesh=ctx.mesh,
            in_specs=(pspec, state_specs, P(axis), P(), P()),
            out_specs=(pspec, state_specs, P()),
            check_rep=False,
        )
        # distinct program name: its cost report (and the profiler's
        # collective attribution) must not collide with "train_step"
        return _compile_cache.jit_program(
            mapped, "zero_train_step", donate_argnums=(0, 1)
        )

    # ------------------------------------------------------------------ #
    # 1F1B pipeline parallelism (parallel/pipeline_1f1b.py)
    # ------------------------------------------------------------------ #
    def _setup_pipeline(self):
        """Validate and assemble the 1F1B pipeline config from the
        strategy's ``pipeline_stages``/``pipeline_microbatches`` knobs
        (env ``RLT_PP_STAGES``/``RLT_PP_MICROBATCHES``), or None when
        pipelining is off. Pipelining is an explicit opt-in, so a config
        that cannot run raises instead of silently falling back."""
        stages = int(getattr(self.strategy, "pipeline_stages", 0) or 0)
        if not stages:
            return None
        from ray_lightning_tpu.core.module import LightningModule

        module = self._module
        cls = type(module)
        if (
            cls.pipeline_stage is LightningModule.pipeline_stage
            or cls.pipeline_last is LightningModule.pipeline_last
        ):
            raise ValueError(
                "pipeline_stages > 0 requires the module to override both "
                "pipeline_stage(stage_params, x) and "
                "pipeline_last(last_params, y, targets)"
            )
        if self._alt_txs is not None:
            raise ValueError(
                "pipeline_stages cannot compose with alternating optimizers"
            )
        if self._dcn_ctx is not None:
            raise ValueError(
                "pipeline_stages cannot compose with dcn_grad_compression"
            )
        mesh = self.strategy.mesh
        axis = "pp"
        if axis not in mesh.axis_names or int(mesh.shape[axis]) != stages:
            raise ValueError(
                f"pipeline_stages={stages} needs a mesh {axis!r} axis of "
                f"exactly that size; the mesh has {dict(mesh.shape)} "
                "(build it with MeshSpec.pipeline or MeshSpec.composed)"
            )
        microbatches = int(
            getattr(self.strategy, "pipeline_microbatches", 0) or stages
        )
        policy = self.strategy.sharding_policy
        data_axes = [
            a
            for a in policy.data_axes
            if a in mesh.axis_names and mesh.shape[a] > 1
        ]
        if len(data_axes) > 1:
            raise ValueError(
                f"the pipelined step supports at most one data axis, got "
                f"{data_axes}"
            )
        tmpl = self._param_shape_tree
        if not (isinstance(tmpl, dict) and {"stages", "last"} <= set(tmpl)):
            raise ValueError(
                "a pipelined module's init_params must return "
                '{"stages": <per-stage leaves>, "last": <head params>}'
            )
        for leaf in jax.tree_util.tree_leaves(tmpl["stages"]):
            shape = tuple(getattr(leaf, "shape", ()))
            if not shape or shape[0] != stages:
                raise ValueError(
                    f'every "stages" leaf must lead with the stage count '
                    f"{stages}; got shape {shape}"
                )
        return {
            "stages": stages,
            "microbatches": microbatches,
            "axis": axis,
            "data_axis": data_axes[0] if data_axes else None,
            "param_specs": None,  # attached after _model_axis_specs
        }

    def _attach_pipeline_specs(self):
        """Resolve the pipeline's per-leaf placement from the rules engine
        (run after ``_setup_zero`` so the composed claim check happened).
        Rules claiming a DATA axis are a hard error here: the pipelined
        step's explicit shard_map owns the batch axis."""
        specs, claims = self._model_axis_specs()
        if claims:
            raise ValueError(
                f"partition_rules claim a data axis under pipelining "
                f"({claims}); stage placement may only use model axes"
            )
        self._pp_cfg["param_specs"] = specs

    def _build_pipeline_train_step(self):
        """1F1B pipelined train step. The forward/backward is the manual
        1F1B schedule of ``parallel/pipeline_1f1b.py`` (its own shard_map
        over the pp [+ tp + data] axes; per-stage/tp placement from the
        rules engine; gradients leave it mean-reduced over the data axis
        and replicated there). The update is either the plain optax step
        on the GSPMD-placed leaves ("pipeline_train_step") or — composed
        with explicit ZeRO — a second shard_map that reduce-scatters the
        dp-replicated grads, updates each rank's local shard, and re-runs
        the grouped (optionally int8-quantized, error-fed-back) param
        all-gather ("pipeline_zero_train_step")."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from ray_lightning_tpu.parallel.pipeline_1f1b import pipeline_1f1b_loss
        from ray_lightning_tpu.parallel.zero import ZeroState

        module = self._module
        cfg = self._pp_cfg
        mesh = self.strategy.mesh
        policy = self.precision_policy
        compute_dtype = policy.compute_dtype
        mp = self._matmul_precision
        ctx = self._zero_ctx
        clip = self.gradient_clip_val
        program = self._train_program
        data_spec = P(cfg["data_axis"]) if cfg["data_axis"] else P()
        stage_specs = (
            cfg["param_specs"]["stages"] if cfg["param_specs"] else None
        )
        microbatches = cfg["microbatches"]
        axis = cfg["axis"]
        stage_fn = module.pipeline_stage
        last_fn = module.pipeline_last

        if ctx is not None:
            tx = self._zero_tx
            state_specs = ctx.state_specs(self._opt_state)
            pspec = ctx.param_spec_tree

            def update_body(params, zstate, grads):
                # grads arrive dp-replicated and already batch-mean-reduced
                # (the 1F1B schedule psums over data axes not in a leaf's
                # spec): psum_scatter/n of n identical copies is exactly
                # this rank's slice, so the one scatter path serves both
                # the in-body-grad and the pipeline-grad steps
                mixed_g = ctx.scatter_grads(grads)
                if clip:
                    gnorm = ctx.global_grad_norm(mixed_g)
                    scale = jnp.minimum(
                        1.0, clip / jnp.maximum(gnorm, 1e-12)
                    )
                    mixed_g = jax.tree_util.tree_map(
                        lambda g: g * scale.astype(g.dtype)
                        if jnp.issubdtype(g.dtype, jnp.floating)
                        else g,
                        mixed_g,
                    )
                cur = ctx.current_mixed(params, zstate.masters)
                updates, new_inner = tx.update(mixed_g, zstate.inner, cur)
                new_mixed = optax.apply_updates(cur, updates)
                new_params, new_masters, new_ef = ctx.gather_params(
                    params, new_mixed, zstate.gather_ef
                )
                return new_params, ZeroState(
                    new_inner, new_masters, tuple(new_ef)
                )

            mapped_update = shard_map(
                update_body,
                mesh=mesh,
                in_specs=(pspec, state_specs, pspec),
                out_specs=(pspec, state_specs),
                check_rep=False,
            )
        else:
            tx = self._tx
            mapped_update = None

        def train_step(params, opt_state, batch, rng_root, step):
            with matmul_precision_scope(mp):
                if not (isinstance(batch, (tuple, list)) and len(batch) == 2):
                    raise ValueError(
                        "pipeline_stages > 0 expects batches of "
                        "(inputs, targets)"
                    )
                x, targets = batch
                x = cast_floats(x, compute_dtype)
                x = round_matmul_inputs(mp, x)

                def loss_fn(p):
                    if policy.cast_params_in_compute:
                        p = cast_floats(p, compute_dtype)
                    p = round_matmul_inputs(mp, p)
                    return pipeline_1f1b_loss(
                        stage_fn,
                        last_fn,
                        p["stages"],
                        p["last"],
                        x,
                        targets,
                        mesh,
                        axis=axis,
                        num_microbatches=microbatches,
                        data_spec=data_spec,
                        param_spec=stage_specs,
                    )

                loss, grads = jax.value_and_grad(loss_fn)(params)
                if mapped_update is not None:
                    new_params, new_opt_state = mapped_update(
                        params, opt_state, grads
                    )
                else:
                    updates, new_opt_state = tx.update(
                        grads, opt_state, params
                    )
                    new_params = optax.apply_updates(params, updates)
                return new_params, new_opt_state, {"loss": loss}

        return _compile_cache.jit_program(
            train_step, program, donate_argnums=(0, 1)
        )

    def _stack_ef_residual(self, opt_state):
        """The error-feedback residual is device-varying over the dcn axis
        (each rank's quantization error is its own), but the jit boundary
        carries GLOBAL arrays — so the residual lives globally stacked as
        ``[n_dcn, *leaf]`` sharded over the dcn axis, and the shard_map'd
        step squeezes/restores the local singleton. Replaces the chain's
        freshly-initialized (unstacked) EF state with stacked zeros."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        ctx = self._dcn_ctx
        mesh, n = ctx["mesh"], ctx["dcn_size"]
        ef, rest = opt_state[0], tuple(opt_state[1:])
        shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(ctx["dcn_axis"])), ef
        )
        # jit + out_shardings materializes the global zeros correctly in
        # multi-process meshes (a host-side device_put could not address
        # other processes' shards)
        stacked = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda r: jnp.zeros((n,) + r.shape, r.dtype), ef
            ),
            out_shardings=shardings,
        )()
        return (stacked,) + rest

    def _build_compressed_train_step(self):
        """The single-optimizer train step with the dp-axis gradient
        reduction as an EXPLICIT shard_map collective: full-precision pmean
        over the in-slice (ICI) axes, block-scaled int8 payload over the
        cross-slice (DCN) hop, error feedback carried in the optimizer
        chain's leading ``ErrorFeedbackState``. Same math as
        ``_build_train_step`` otherwise."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        module = self._module
        tx = self._tx
        policy = self.precision_policy
        compute_dtype = policy.compute_dtype
        ctx = self._dcn_ctx
        mesh = ctx["mesh"]
        batch_axes = ctx["batch_axes"]
        batch_entry = batch_axes[0] if len(batch_axes) == 1 else batch_axes
        reduce_axes = tuple(ctx["ici_axes"]) + (ctx["dcn_axis"],)
        ef_spec = jax.tree_util.tree_map(
            lambda _: P(ctx["dcn_axis"]), self._opt_state[0]
        )
        opt_spec = (ef_spec,) + tuple(
            jax.tree_util.tree_map(lambda _: P(), s)
            for s in self._opt_state[1:]
        )

        def _mean(v):
            return (
                jax.lax.pmean(v, reduce_axes)
                if jnp.issubdtype(jnp.result_type(v), jnp.inexact)
                else v
            )

        def train_step(params, opt_state, batch, rng_root, step):
            rng = jax.random.fold_in(rng_root, step)
            batch = cast_floats(batch, compute_dtype)

            def loss_fn(p):
                if policy.cast_params_in_compute:
                    p = cast_floats(p, compute_dtype)
                module._capture_begin("train", rng)
                out = module.training_step(p, batch, step)
                logs = module._capture_end()
                if isinstance(out, dict):
                    loss = out["loss"]
                    mutated = out.get("mutated_params")
                else:
                    loss, mutated = out, None
                return loss, (logs, mutated)

            (loss, (logs, mutated)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            # the leading EF transform reduces the gradient across the mesh
            # (two_phase_dcn_reduce); drop the residual's local singleton
            # before the update, restore it for the carried-out state
            ef_local = jax.tree_util.tree_map(lambda x: x[0], opt_state[0])
            updates, new_state = tx.update(
                grads, (ef_local,) + tuple(opt_state[1:]), params
            )
            new_ef = jax.tree_util.tree_map(lambda x: x[None], new_state[0])
            new_opt_state = (new_ef,) + tuple(new_state[1:])
            new_params = optax.apply_updates(params, updates)
            if mutated is not None and isinstance(new_params, dict):
                # forward-mutated collections (e.g. batch_stats) are
                # device-varying here — average them like DDP buffers
                mutated = jax.tree_util.tree_map(_mean, mutated)
                new_params = {
                    k: (mutated[k] if (k != "params" and k in mutated) else v)
                    for k, v in new_params.items()
                }
            logs = {k: _mean(v) for k, v in logs.items()}
            logs.setdefault("loss", _mean(loss))
            return new_params, new_opt_state, logs

        mapped = shard_map(
            train_step,
            mesh=mesh,
            in_specs=(P(), opt_spec, P(batch_entry), P(), P()),
            out_specs=(P(), opt_spec, P()),
            check_rep=False,
        )
        # first dispatch resolves through the shared executable cache:
        # an elastic resize back to a seen topology, or a relaunch on a
        # warm cache dir, skips XLA entirely (runtime/compile_cache.py)
        return _compile_cache.jit_program(
            mapped, "train_step", donate_argnums=(0, 1)
        )

    # ------------------------------------------------------------------ #
    # compiled steps
    # ------------------------------------------------------------------ #
    def _build_train_step(self):
        # resolved at build time so RLT_MATMUL_PRECISION set after the
        # Trainer ctor (or per elastic relaunch) still applies
        self._matmul_precision = parse_matmul_precision()
        self._train_program = "train_step"
        if self._alt_txs is not None:
            return self._build_alternating_train_step()
        if self._dcn_ctx is not None:
            return self._build_compressed_train_step()
        if self._pp_cfg is not None:
            self._train_program = (
                "pipeline_zero_train_step"
                if self._zero_ctx is not None
                else "pipeline_train_step"
            )
            return self._build_pipeline_train_step()
        if self._zero_ctx is not None:
            self._train_program = "zero_train_step"
            return self._build_zero_train_step()
        module = self._module
        tx = self._tx
        policy = self.precision_policy
        compute_dtype = policy.compute_dtype
        mp = self._matmul_precision

        def _step_body(params, opt_state, batch, rng_root, step):
            rng = jax.random.fold_in(rng_root, step)
            batch = cast_floats(batch, compute_dtype)
            batch = round_matmul_inputs(mp, batch)

            def loss_fn(p):
                if policy.cast_params_in_compute:
                    # mixed precision: forward/backward on a bf16 view of
                    # the fp32 masters (grads flow back to the masters)
                    p = cast_floats(p, compute_dtype)
                p = round_matmul_inputs(mp, p)
                module._capture_begin("train", rng)
                out = module.training_step(p, batch, step)
                logs = module._capture_end()
                if isinstance(out, dict):
                    loss = out["loss"]
                    mutated = out.get("mutated_params")
                else:
                    loss, mutated = out, None
                return loss, (logs, mutated)

            (loss, (logs, mutated)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if mutated is not None and isinstance(new_params, dict):
                # non-differentiable collections (e.g. flax batch_stats)
                # take their forward-pass-mutated values, not the
                # optimizer's no-op update
                new_params = {
                    k: (mutated[k] if (k != "params" and k in mutated) else v)
                    for k, v in new_params.items()
                }
            logs = dict(logs)
            logs.setdefault("loss", loss)
            return new_params, new_opt_state, logs

        def train_step(params, opt_state, batch, rng_root, step):
            # the precision scope is active while the body TRACES, which is
            # when jax.default_matmul_precision takes effect under jit
            with matmul_precision_scope(mp):
                return _step_body(params, opt_state, batch, rng_root, step)

        return _compile_cache.jit_program(
            train_step, "train_step", donate_argnums=(0, 1)
        )

    def _build_alternating_train_step(self):
        """PTL multiple-optimizer semantics, compiled: training_step is
        traced once per optimizer_idx and the sub-steps run sequentially
        inside ONE XLA program (the PTL 1.6 loop called training_step per
        optimizer per batch eagerly; here the alternation is unrolled at
        trace time, so there is no per-step recompilation or dispatch)."""
        import inspect

        module = self._module
        txs = self._alt_txs
        policy = self.precision_policy
        compute_dtype = policy.compute_dtype
        sig = inspect.signature(module.training_step)
        if "optimizer_idx" not in sig.parameters and not any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in sig.parameters.values()
        ):
            raise TypeError(
                f"configure_optimizers returned {len(txs)} alternating "
                "optimizers, so training_step must accept an "
                "`optimizer_idx` argument (PTL multiple-optimizer contract)"
            )

        def train_step(params, opt_states, batch, rng_root, step):
            rng = jax.random.fold_in(rng_root, step)
            batch = cast_floats(batch, compute_dtype)
            logs_all: Dict[str, Any] = {}
            new_states = []
            for i, tx in enumerate(txs):

                def loss_fn(p, i=i):
                    if policy.cast_params_in_compute:
                        p = cast_floats(p, compute_dtype)
                    module._capture_begin("train", jax.random.fold_in(rng, i))
                    out = module.training_step(p, batch, step, optimizer_idx=i)
                    logs = module._capture_end()
                    if isinstance(out, dict):
                        loss, mutated = out["loss"], out.get("mutated_params")
                    else:
                        loss, mutated = out, None
                    return loss, (logs, mutated)

                (loss_i, (logs_i, mutated)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                updates, st = tx.update(grads, opt_states[i], params)
                params = optax.apply_updates(params, updates)
                if mutated is not None and isinstance(params, dict):
                    # same contract as the single-optimizer step: forward-
                    # mutated non-differentiable collections win
                    params = {
                        k: (mutated[k] if (k != "params" and k in mutated) else v)
                        for k, v in params.items()
                    }
                new_states.append(st)
                logs_all.update(logs_i)
                logs_all[f"loss_opt{i}"] = loss_i
            # 'loss' = total over sub-steps (no single sub-loss is "the"
            # loss; monitor loss_opt{i} or module-logged names for one)
            logs_all.setdefault(
                "loss",
                sum(logs_all[f"loss_opt{i}"] for i in range(len(txs))),
            )
            return params, tuple(new_states), logs_all

        return _compile_cache.jit_program(
            train_step, "train_step", donate_argnums=(0, 1)
        )

    def _build_eval_step(self, phase: str):
        module = self._module
        step_fn = {
            "val": module.validation_step,
            "test": module.test_step,
        }[phase]

        policy = self.precision_policy
        compute_dtype = policy.compute_dtype

        def eval_step(params, batch, step):
            batch = cast_floats(batch, compute_dtype)
            if policy.cast_params_in_compute:
                params = cast_floats(params, compute_dtype)
            module._capture_begin(phase)
            out = step_fn(params, batch, step)
            logs = module._capture_end()
            if isinstance(out, dict):
                for k, v in out.items():
                    logs.setdefault(k, jnp.asarray(v))
            return logs

        return _compile_cache.jit_program(eval_step, f"{phase}_step")

    # ------------------------------------------------------------------ #
    # fit implementation (runs on driver, or inside a worker actor)
    # ------------------------------------------------------------------ #
    def _fit_impl(self, model, train_dataloaders, val_dataloaders, datamodule, ckpt_path):
        if getattr(self.strategy, "telemetry", False):
            obs.enable()
        self._obs = obs.get_recorder()
        # goodput wall-time ledger: every second of this fit classified
        # into a category; published on each heartbeat (collect_beat_payload)
        self._goodput = (
            obs.goodput.new_ledger("train") if self._obs is not None else None
        )
        self._first_step_dispatched = False
        self._step_log_buffer = []
        self._input_prefetcher = None
        self._input_stats = {"starved_s": 0.0, "batches": 0}
        # fleet profiler: armed by telemetry (driver command file) or by
        # RLT_PROFILE_AT_STEP; fully absent otherwise so the hot loop keeps
        # its single-attribute-check fast path
        self._profiler = None
        if self._obs is not None or os.environ.get(
            obs.profiler.PROFILE_AT_STEP_ENV
        ):
            from ray_lightning_tpu.observability.aggregator import telemetry_dir

            try:
                self._profiler = obs.profiler.FleetProfiler(
                    telemetry_dir(self.default_root_dir),
                    rank=getattr(self.strategy, "global_rank", 0) or 0,
                    recorder=self._obs,
                )
            except Exception:
                self._profiler = None
        _setup_wall, _setup_t0 = time.time(), time.perf_counter()
        seed = seed_everything(self.seed)
        self._seed_used = seed
        self._datamodule = datamodule
        elastic_agent = getattr(self, "_elastic_agent", None)
        if elastic_agent is not None and elastic_agent.is_joiner:
            # warm spare: block until a grow command admits us, join that
            # rendezvous, and pick up our logical rank — all before the
            # backend is built, so setup_environment sees the joined world
            self._elastic_join(elastic_agent)
        self.strategy.setup_environment()
        if hasattr(model, "mesh"):
            model.mesh = self.strategy.mesh
        model.precision_policy = self.precision_policy

        if datamodule is not None:
            datamodule.prepare_data()
            datamodule.setup("fit")
        model.prepare_data()
        model.setup("fit")
        self._cb("setup", "fit")

        train_loader = self._resolve_loader(train_dataloaders, datamodule, "train_dataloader")
        val_loader = self._resolve_loader(val_dataloaders, datamodule, "val_dataloader")
        if train_loader is None:
            raise ValueError("fit requires a train dataloader")
        train_loader = self._maybe_shard_loader(train_loader, shuffle=True)
        val_loader = self._maybe_shard_loader(val_loader, shuffle=False)

        # --- parameters & optimizer, placed with the policy's shardings ---
        self._rng_root = jax.random.key(seed)
        host_params = model._params if model._params is not None else model.init_params(
            self._rng_root
        )
        host_params = cast_floats(host_params, self.precision_policy.param_dtype)
        # elastic resizes rebuild the placed templates from these shapes
        # (the live arrays may be poisoned by a failed donated step)
        self._param_shape_tree = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), host_params
        )
        if elastic_agent is not None and elastic_agent.pending_handoff_cmd is not None:
            # adopt-from-handoff joiner: survivors mid-resize are placing
            # ZEROS onto their rebuilt templates right now, and multihost
            # device_put cross-checks values across processes — run the
            # identical placement program here; the handoff below supplies
            # the real values
            host_params = jax.tree_util.tree_map(
                lambda a: np.zeros(a.shape, a.dtype), host_params
            )
        self._tx = self._normalize_tx(model.configure_optimizers())
        self._dcn_ctx = self._setup_dcn_compression()
        # pipeline first (zero's composed layout needs the stage axis),
        # then the explicit-ZeRO decision — both need the optimizer/dcn
        # verdicts above and must precede placement: the composed step owns
        # its params' placement (model-axis specs; data-axis shards live in
        # the ZeroState, not in GSPMD placement)
        self._pp_cfg = self._setup_pipeline()
        self._zero_ctx = self._setup_zero()
        if self._pp_cfg is not None:
            self._attach_pipeline_specs()
        self._params = self._place_params(host_params)
        if self._dcn_ctx is not None:
            from ray_lightning_tpu.parallel.compression import (
                two_phase_dcn_reduce,
                with_error_feedback,
            )

            # the EF wrapper runs FIRST in the chain: it performs the
            # two-phase reduction itself (inside the shard_map'd step), so
            # every transform after it sees the fully reduced gradient
            compressor = two_phase_dcn_reduce(
                self._dcn_ctx["ici_axes"],
                self._dcn_ctx["dcn_axis"],
                self._dcn_ctx["dcn_size"],
                block_size=self._dcn_ctx["block_size"],
            )
            self._tx = optax.chain(with_error_feedback(compressor), self._tx)
        if self._alt_txs is not None:
            # every label must name a real optimizer and every optimizer
            # must own at least one leaf — an out-of-range label would
            # silently freeze its group (set_to_zero in every sub-step)
            full_labels = self._broadcast_labels(self._alt_labels, host_params)
            try:
                seen = {int(l) for l in jax.tree_util.tree_leaves(full_labels)}
            except (TypeError, ValueError):
                raise ValueError(
                    "with a LIST of alternating optimizers, param_labels "
                    "must map each leaf to an optimizer INDEX (int); for "
                    "string-labeled parameter groups over one loss use the "
                    "dict form {'optimizers': {label: tx}, ...}"
                )
            k = len(self._alt_txs)
            if not seen <= set(range(k)) or len(seen) < k:
                raise ValueError(
                    f"param_labels must cover exactly the optimizer indices "
                    f"0..{k - 1}; got labels {sorted(seen)}"
                )
            # alternating: one state per optimizer, advanced sequentially
            init_fn = lambda p: tuple(tx.init(p) for tx in self._alt_txs)
        elif self._zero_ctx is not None:
            # reads self._zero_ctx at CALL time: an elastic resize swaps in
            # the new-world context and this very closure re-initializes
            init_fn = lambda p: self._zero_ctx.init_state(self._zero_tx, p)
        else:
            init_fn = self._tx.init
        self._opt_init_fn = init_fn  # elastic resizes re-init from this
        opt_shapes = jax.eval_shape(init_fn, self._params)
        opt_shardings = self._opt_shardings_for(opt_shapes)
        if opt_shardings is None:
            # moments inherit the param shardings through XLA propagation
            self._opt_state = jax.jit(init_fn)(self._params)
        else:
            self._opt_state = jax.jit(init_fn, out_shardings=opt_shardings)(
                self._params
            )
        if self._dcn_ctx is not None:
            self._opt_state = self._stack_ef_residual(self._opt_state)
            self._publish_dcn_telemetry(host_params)

        relaunch_ckpt = getattr(self, "_relaunch_ckpt_path", None)
        if relaunch_ckpt is not None:
            # crash relaunch: the newest mid-run state beats whatever
            # ckpt_path the original fit() call carried
            ckpt_path = relaunch_ckpt
        if ckpt_path is not None:
            self._restore_spec(ckpt_path)
        if elastic_agent is not None and elastic_agent.pending_handoff_cmd is not None:
            # re-admitted worker: survivors wrote a live-state snapshot for
            # our membership epoch — it beats any checkpoint restore above
            self._load_elastic_handoff(elastic_agent)

        train_step = self._build_train_step()
        val_step = self._build_eval_step("val") if val_loader is not None else None
        if self._obs is not None:
            # one span covering data resolution + param/opt init + restore
            self._obs.add_span(
                "fit/setup", _setup_wall, time.perf_counter() - _setup_t0,
                step=self.global_step,
            )

        if self.logger is not None and self.is_global_zero:
            self.logger.log_hyperparams(dict(model.hparams))

        self._hook("on_fit_start")
        self._hook("on_train_start")

        # sanity validation
        if val_loader is not None and self.num_sanity_val_steps > 0:
            self.sanity_checking = True
            self._cb("on_sanity_check_start")
            self._run_eval_epoch(val_loader, val_step, limit=self.num_sanity_val_steps, record=False)
            self._cb("on_sanity_check_end")
            self.sanity_checking = False

        try:
            while self.current_epoch < self.max_epochs and not self.should_stop:
                try:
                    self._run_train_epoch(train_loader, train_step, val_loader, val_step)
                except Exception as err:
                    cmd = self._elastic_resize_for(err)
                    if cmd is None:
                        raise
                    train_step, val_step = self._apply_resize(
                        cmd, train_loader, val_loader, err=err
                    )
                    # same semantics as a mid-epoch checkpoint resume: the
                    # epoch re-runs from its start — some batches retrain,
                    # none are skipped
                    continue
                self.current_epoch += 1
                self._fire_safe_boundary("epoch_end")
                if 0 <= self.max_steps <= self.global_step:
                    self.should_stop = True
                if self.should_stop and self.current_epoch < self.min_epochs:
                    self.should_stop = False
                if elastic_agent is not None:
                    # epoch boundary: admit a pending grow (or any resize
                    # that raced the end of the epoch). This runs even on
                    # the FINAL boundary so a joiner blocked in its admission
                    # barrier is released and exits cleanly with the group.
                    cmd = elastic_agent.poll_epoch_end()
                    if cmd is not None:
                        train_step, val_step = self._apply_resize(
                            cmd, train_loader, val_loader
                        )
        finally:
            # an epoch aborted by an exception skips its own drain/fold;
            # settle both before the logger closes. The drain reads device
            # arrays — a collective failure can leave them unreadable, and
            # that must not mask the original error
            if self._goodput is not None:
                self._goodput.enter("drain")
            try:
                self._drain_step_logs()
            except Exception:
                self._step_log_buffer = []
            if self._input_prefetcher is not None:
                self._input_stats["starved_s"] += self._input_prefetcher.starved_s
                self._input_stats["batches"] += self._input_prefetcher.batches
                self._input_prefetcher = None
            if self._profiler is not None:
                # a window cut short by should_stop/exception still stops
                # the device trace and ships its records
                self._profiler.close()
                self._profiler = None
            self._hook("on_train_end")
            self._hook("on_fit_end")
            if self.logger is not None:
                self.logger.finalize(self.state.status)
            self._cb("teardown", "fit")
            model.teardown("fit")
            if datamodule is not None:
                datamodule.teardown("fit")

        model._params = self._params
        in_process = (
            getattr(self.strategy, "launcher", None) is None
            and not getattr(self.strategy, "_is_remote", False)
        )
        # worker processes leave pending profile records for the final
        # heartbeat flush; in-process runs must drain them here or lose them
        profile_records = obs.profiler.drain_pending() if in_process else None
        if in_process and (self._obs is not None or profile_records):
            # in-process strategies have no driver aggregator: dump this
            # process's ring + registry directly so single-host runs still
            # produce trace.json/metrics.json under the root dir
            from ray_lightning_tpu.observability import metrics as _obs_metrics
            from ray_lightning_tpu.observability.aggregator import (
                telemetry_dir,
                write_local_dump,
            )

            write_local_dump(
                telemetry_dir(self.default_root_dir),
                self._obs,
                _obs_metrics.get_registry() if self._obs is not None else None,
                profile=profile_records,
            )
        return None

    def _publish_dcn_telemetry(self, host_params) -> None:
        """Record the DCN compression contract (payload bytes before/after
        the int8 block encoding) as gauges + a trace event. Telemetry-off
        cost: one attribute check."""
        if self._obs is None:
            return
        try:
            from ray_lightning_tpu.parallel.compression import (
                compression_summary,
            )

            summary = compression_summary(
                host_params, block_size=self._dcn_ctx["block_size"]
            )
        except Exception:  # telemetry must never break fit
            return
        reg = obs.metrics.get_registry()
        reg.gauge("rlt_dcn_payload_bytes", kind="uncompressed").set(
            summary["uncompressed_bytes"]
        )
        reg.gauge("rlt_dcn_payload_bytes", kind="compressed").set(
            summary["compressed_bytes"]
        )
        reg.gauge("rlt_dcn_compression_ratio").set(summary["ratio"])
        obs.event("dcn_compression", step=self.global_step, **summary)

    def _prefetch_shard(self, loader, limit):
        """Yield ``(idx, host_batch, device_batch)`` through the async input
        pipeline: host batch assembly runs on background threads
        (``AsyncLoader``) and up to ``strategy.prefetch_depth`` batches have
        their host->device transfers dispatched ahead of the step being
        trained (``DevicePrefetcher``) — jax transfers are async, so input
        copies overlap step compute at the cost of ``depth`` extra resident
        batches. ``strategy.loader_num_workers=0`` (``RLT_LOADER_WORKERS=0``)
        keeps host loading synchronous on this thread; both layers preserve
        the inline loop's error step (a bad batch never swallows the good
        batches sharded before it)."""
        from ray_lightning_tpu.core.prefetch import AsyncLoader, DevicePrefetcher

        num_workers = self.strategy.loader_num_workers
        if not isinstance(loader, AsyncLoader) and num_workers != 0:
            loader = AsyncLoader(loader, num_workers=num_workers)
        self._input_prefetcher = DevicePrefetcher(
            self.strategy.shard_batch,
            depth=self.strategy.prefetch_depth,
            recorder=self._obs,
        )
        return self._input_prefetcher.iterate(loader, limit)

    def register_safe_boundary_hook(
        self, hook: Callable[[int, str], None]
    ) -> None:
        """Register ``hook(global_step, boundary)`` to fire at every safe
        resize boundary: each training health tick (``boundary="step"``)
        and each epoch end (``boundary="epoch_end"``). Hooks must be
        cheap and must not raise — exceptions are logged and swallowed so
        an arbiter bug can never kill the step loop."""
        self._safe_boundary_hooks.append(hook)

    def _fire_safe_boundary(self, boundary: str) -> None:
        if not self._safe_boundary_hooks:
            return
        from ray_lightning_tpu.utils.common import rank_zero_warn

        for hook in self._safe_boundary_hooks:
            try:
                hook(self.global_step, boundary)
            except Exception:
                rank_zero_warn(
                    f"safe-boundary hook {hook!r} raised at "
                    f"{boundary} (step {self.global_step}); ignoring"
                )

    def _health_tick(self, train: bool) -> None:
        """Per-batch liveness tick: fire any scripted fault for this rank at
        this global step (train batches only — a validation batch must not
        re-fire a step fault), then publish a heartbeat for the driver-side
        hang supervisor. Both are cheap no-ops when unconfigured."""
        from ray_lightning_tpu import session as _session
        from ray_lightning_tpu.runtime import faults as _faults

        if train:
            _faults.fire_step_faults(self.global_step)
            self._fire_safe_boundary("step")
        _session.emit_heartbeat(self.global_step)
        agent = getattr(self, "_elastic_agent", None)
        if train and agent is not None:
            # O(1) ledger poll (one stat): an immediate-apply resize aborts
            # the epoch via the loop's MembershipChanged handler
            cmd = agent.poll_now()
            if cmd is not None:
                from ray_lightning_tpu.runtime.elastic import MembershipChanged

                raise MembershipChanged(cmd)

    # ------------------------------------------------------------------ #
    # elastic membership (shrink/grow without a full relaunch)
    # ------------------------------------------------------------------ #
    def _elastic_join(self, agent) -> None:
        """Warm-spare admission: wait for the grow command naming our boot
        id, join its rendezvous, and adopt our logical rank."""
        from ray_lightning_tpu.runtime import elastic as _elastic

        with obs.span("elastic/join", boot_id=agent.boot_id):
            while True:
                cmd = agent.wait_for_join()
                try:
                    cmd = agent.connect(cmd)
                    break
                except _elastic.MembershipChanged:
                    # admission superseded before we connected; wait for the
                    # next command that names us
                    continue
            rank = cmd.rank_of(agent.boot_id)
            self.strategy._set_worker_context(
                rank, cmd.world, local_rank=0, node_rank=rank
            )

    def _load_elastic_handoff(self, agent) -> None:
        """Joiner side of the admission handoff: adopt the survivors' live
        params/opt-state/progress snapshot, then ack the membership epoch."""
        from ray_lightning_tpu.runtime import elastic as _elastic

        cmd = agent.pending_handoff_cmd
        agent.pending_handoff_cmd = None
        with obs.span("elastic/handoff_load", epoch=cmd.epoch):
            payload = _elastic.read_handoff(cmd.handoff, timeout=agent.join_timeout)
            self._apply_handoff_payload(payload)
        agent.ack(cmd)

    def _elastic_resize_for(self, err: BaseException):
        """Map an exception escaping the epoch loop to a resize command, or
        None when it is not an elastic event. A collective failure (a peer
        died mid-step) waits for the driver's shrink verdict."""
        agent = getattr(self, "_elastic_agent", None)
        if agent is None:
            return None
        from ray_lightning_tpu.runtime import elastic as _elastic

        if isinstance(err, _elastic.MembershipChanged):
            return err.cmd
        if _elastic.is_collective_failure(err):
            return agent.wait_for_resize()
        return None

    def _place_params(self, host_params):
        """Host params -> device arrays. Under the explicit ZeRO step (or
        a pipelined step) the composed model-axis specs place the params —
        sharded over model axes, REPLICATED over the data axis (the 1/N
        data shards live in the ZeroState, not in GSPMD placement);
        otherwise the strategy's policy decides."""
        from jax.sharding import NamedSharding

        specs = None
        if self._zero_ctx is not None:
            specs = self._zero_ctx.param_spec_tree
        elif self._pp_cfg is not None:
            specs = self._pp_cfg["param_specs"]
        if specs is not None:
            mesh = self.strategy.mesh
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                host_params,
                specs,
            )
        return self.strategy.place_params(host_params)

    def _opt_shardings_for(self, opt_shapes):
        """Optimizer-state shardings for the engaged program: the explicit
        ZeRO mirror rule, XLA propagation from the composed-placed params
        (pipeline without ZeRO), or the strategy's rules/policy."""
        if self._zero_ctx is not None:
            return self._zero_ctx.state_shardings(opt_shapes)
        if self._pp_cfg is not None:
            return None  # moments inherit the placed-param shardings
        return self.strategy.optstate_shardings(opt_shapes)

    def describe_parallelism(self) -> str:
        """One-stop summary of the engaged training program and every
        composed-parallelism decision: which step runs, why the explicit
        ZeRO path fell back (if it did — mirrored in
        ``rlt_zero_fallback_total{reason}``), the pipeline config, the
        ZeRO layout, and the per-leaf placement report."""
        lines = [f"train program: {self._train_program}"]
        if self._zero_fallback_reason:
            lines.append(
                "explicit ZeRO fallback: "
                f"{self._zero_fallback_reason} "
                "(rlt_zero_fallback_total{reason=...})"
            )
        if self._pp_cfg is not None:
            cfg = self._pp_cfg
            lines.append(
                f"pipeline: {cfg['stages']} stages x "
                f"{cfg['microbatches']} microbatches over {cfg['axis']!r}"
                + (
                    f", data axis {cfg['data_axis']!r}"
                    if cfg["data_axis"]
                    else ", no data axis"
                )
            )
        if self._zero_ctx is not None:
            lines.append(self._zero_ctx.describe())
        lines.append(self.strategy.describe_shardings())
        return "\n".join(lines)

    def _host_opt_state(self):
        """Optimizer state as host-readable arrays. Explicit-ZeRO state is
        sharded across processes, so a multi-process run gathers it to
        replicated through a tiny jitted identity first (device_get cannot
        read other processes' shards)."""
        if self._zero_ctx is not None and jax.process_count() > 1:
            repl = self.strategy.replicated
            shardings = jax.tree_util.tree_map(
                lambda _: repl, self._opt_state
            )
            gathered = jax.jit(lambda s: s, out_shardings=shardings)(
                self._opt_state
            )
            return jax.device_get(gathered)
        return jax.device_get(self._opt_state)

    def _salvage_live_state(self):
        """Host copies of (params, opt_state) if still readable. A failed
        train step poisons its donated inputs — those read back as deleted
        arrays — so salvage degrades to None and the caller falls back to
        the handoff/checkpoint tiers."""
        try:
            for leaf in jax.tree_util.tree_leaves((self._params, self._opt_state)):
                if hasattr(leaf, "is_deleted") and leaf.is_deleted():
                    return None
            return (jax.device_get(self._params), self._host_opt_state())
        except Exception:
            return None

    def _place_host_state(self, salvage) -> None:
        """Re-place host (params, opt_state) onto the CURRENT templates —
        ``self._params``/``self._opt_state`` must already be freshly
        initialized at the new world size (mirrors ``_restore_checkpoint``)."""
        host_params, host_opt = salvage
        host_params = cast_floats(host_params, self.precision_policy.param_dtype)
        self._params = self._place_params(host_params)
        if host_opt is not None and self._opt_state is not None:
            self._opt_state = jax.tree_util.tree_map(
                lambda tmpl, h: jax.device_put(h, tmpl.sharding)
                if hasattr(tmpl, "sharding")
                else h,
                self._opt_state,
                host_opt,
            )

    def _apply_handoff_payload(self, payload: Dict[str, Any]) -> None:
        self._place_host_state((payload["params"], payload.get("opt_state")))
        meta = payload.get("meta") or {}
        if "epoch" in meta:
            self.current_epoch = int(meta["epoch"])
        if "global_step" in meta:
            self.global_step = int(meta["global_step"])
        aux = payload.get("aux")
        if aux is not None:
            self._restore_aux_state({**aux, **aux.get("user", {})})

    def _apply_resize(self, cmd, train_loader, val_loader, err=None):
        """Transition this worker to membership epoch ``cmd.epoch``: settle
        host buffers, contribute/salvage live state, reconnect at the new
        world size, rebuild mesh + placed templates + compiled steps, and
        restore state through the best available tier (live handoff >
        pinned checkpoint). Returns the rebuilt (train_step, val_step)."""
        from ray_lightning_tpu import session as _session
        from ray_lightning_tpu.runtime import elastic as _elastic

        agent = self._elastic_agent
        _t_wall, _t0 = time.time(), time.perf_counter()
        if self._goodput is not None:
            # planned resizes are elastic transitions; an exception-driven
            # one is unplanned fault recovery
            self._goodput.enter(
                "fault_recovery" if err is not None else "elastic_transition"
            )
        my_rank = cmd.rank_of(agent.boot_id)
        if my_rank is None:  # evicted while transitioning: not our group
            raise _elastic.MembershipChanged(cmd)
        new_world = cmd.world

        # -- settle host-side buffers while the old backend still exists --
        try:
            self._drain_step_logs()
        except Exception:
            self._step_log_buffer = []
        if self._input_prefetcher is not None:
            try:
                self._input_stats["starved_s"] += self._input_prefetcher.starved_s
                self._input_stats["batches"] += self._input_prefetcher.batches
            except Exception:
                pass
            self._input_prefetcher = None

        # -- contribute live state BEFORE disconnecting ---------------------
        writer = (
            cmd.handoff_writer is not None and agent.boot_id == cmd.handoff_writer
        )
        salvage = None
        if writer or (cmd.kind == "shrink" and new_world == 1):
            salvage = self._salvage_live_state()
        if writer:
            if salvage is not None:
                _elastic.write_handoff(
                    cmd.handoff,
                    {
                        "params": salvage[0],
                        "opt_state": salvage[1],
                        "meta": {
                            "epoch": int(self.current_epoch),
                            "global_step": int(self.global_step),
                        },
                        "aux": self.collect_aux_state(),
                    },
                )
            else:
                # live state was poisoned by the failed step: tell readers
                # to fall back to the checkpoint tier instead of waiting
                _elastic.write_handoff_failed(cmd.handoff)
        _session.emit_heartbeat(self.global_step, force=True)

        # -- rendezvous at the new membership epoch ------------------------
        with obs.span("elastic/reconnect", epoch=cmd.epoch, world=new_world):
            cmd = agent.reconnect(cmd)
            my_rank = cmd.rank_of(agent.boot_id)
            new_world = cmd.world
        strategy = self.strategy
        strategy._set_worker_context(
            my_rank, new_world, local_rank=0, node_rank=my_rank
        )
        strategy._mesh = None
        strategy.setup_environment()
        if hasattr(self._module, "mesh"):
            self._module.mesh = strategy.mesh
        # the old root key lived on the torn-down backend; recreate it
        # bitwise-identically from the run seed
        self._rng_root = jax.random.key(self._seed_used)

        # -- rebuild placed templates exactly as _fit_impl does ------------
        if self._pp_cfg is not None:
            # revalidate the pipeline against the rebuilt mesh (the pp/tp
            # axes must survive the resize — only the data axis is elastic)
            self._pp_cfg = self._setup_pipeline()
        if self._zero_ctx is not None:
            # re-chunk the ZeRO layout for the new world size; PAD_UNIT is
            # world-independent, so the padded GLOBAL shapes — and with
            # them the handoff/checkpoint state trees — are unchanged
            new_ctx = self._setup_zero()
            if new_ctx is None:
                raise RuntimeError(
                    f"elastic {cmd.kind} to world {new_world}: the explicit "
                    "ZeRO layout cannot be rebuilt at this size "
                    f"({self._zero_fallback_reason}) and its optimizer "
                    "state does not transfer to the GSPMD path"
                )
            self._zero_ctx = new_ctx
        if self._pp_cfg is not None:
            self._attach_pipeline_specs()
        host_zeros = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), self._param_shape_tree
        )
        self._params = self._place_params(host_zeros)
        opt_shapes = jax.eval_shape(self._opt_init_fn, self._params)
        opt_shardings = self._opt_shardings_for(opt_shapes)
        if opt_shardings is None:
            self._opt_state = jax.jit(self._opt_init_fn)(self._params)
        else:
            self._opt_state = jax.jit(
                self._opt_init_fn, out_shardings=opt_shardings
            )(self._params)
        if self._dcn_ctx is not None:
            self._opt_state = self._stack_ef_residual(self._opt_state)

        # -- state tiers: live handoff > own salvage > pinned checkpoint ---
        restored = False
        if cmd.handoff:
            if writer and salvage is not None:
                self._place_host_state(salvage)
                restored = True
            elif not writer:
                payload = _elastic.read_handoff(
                    cmd.handoff, timeout=agent.failure_wait, allow_failed=True
                )
                if payload is not None:
                    self._apply_handoff_payload(payload)
                    restored = True
        elif salvage is not None:
            self._place_host_state(salvage)
            restored = True
        if not restored and cmd.restore:
            self._restore_spec(cmd.restore)
            restored = True
        if not restored:
            raise RuntimeError(
                f"elastic {cmd.kind} (membership epoch {cmd.epoch}): no live "
                "state survived and no checkpoint is available to restore from"
            ) from err

        # -- rebuild compiled steps + reassign data shards -----------------
        self._first_step_dispatched = False
        self._resize_sampler(train_loader, my_rank, new_world)
        self._resize_sampler(val_loader, my_rank, new_world)
        train_step = self._build_train_step()
        val_step = self._build_eval_step("val") if val_loader is not None else None
        self._cb("on_membership_resize")
        agent.ack(cmd)
        if self._obs is not None:
            self._obs.add_span(
                "elastic/resize",
                _t_wall,
                time.perf_counter() - _t0,
                step=self.global_step,
            )
        _session.emit_heartbeat(self.global_step, force=True)
        return train_step, val_step

    def _resize_sampler(self, loader, rank: int, world: int) -> None:
        """Reassign a loader's DistributedSampler to the new replica set."""
        sampler = getattr(loader, "sampler", None) if loader is not None else None
        if not isinstance(sampler, DistributedSampler):
            return
        sampler.num_replicas = world
        sampler.rank = rank
        if sampler.drop_last:
            sampler.num_samples = sampler.data_len // world
        else:
            sampler.num_samples = -(-sampler.data_len // world)  # ceil div

    def _run_train_epoch(self, train_loader, train_step, val_loader, val_step):
        model = self._module
        if hasattr(train_loader, "set_epoch"):
            train_loader.set_epoch(self.current_epoch)
        self.val_enabled = val_loader is not None
        self._val_ran_this_epoch = False
        self.num_val_batches = (
            self._loader_len(val_loader, self.limit_val_batches, "limit_val_batches")
            if val_loader
            else 0
        )
        # before the hooks: a save_checkpoint() from on_train_epoch_start
        # must already record this epoch as partial, not the previous one's
        # completed state
        self._epoch_ended = False
        self._hook("on_train_epoch_start")
        aggregator = _EpochAggregator()
        t_epoch = time.perf_counter()
        n_batches = 0
        limit_train = self._resolve_limit(
            self.limit_train_batches, train_loader, "limit_train_batches"
        )
        # float val_check_interval = validate every fraction of this epoch's
        # train batches (PTL); int = every N global steps. Like PTL, the
        # fractional path still honors check_val_every_n_epoch.
        val_every_n_batches = None
        if (
            isinstance(self.val_check_interval, float)
            and val_loader is not None
            and (self.current_epoch + 1) % self.check_val_every_n_epoch == 0
        ):
            n_train = self._loader_len(
                train_loader, limit_train, "limit_train_batches"
            )
            if not n_train:
                raise ValueError(
                    f"val_check_interval={self.val_check_interval}: a float "
                    "fraction requires a sized train dataloader"
                )
            val_every_n_batches = int(n_train * self.val_check_interval)
            if val_every_n_batches == 0:
                raise ValueError(
                    f"val_check_interval={self.val_check_interval} of a "
                    f"{n_train}-batch epoch resolves to every 0 batches; "
                    f"use a fraction >= {1.0 / n_train:.4g} or an int step "
                    "interval"
                )

        # hoisted handles: the telemetry-off hot loop pays exactly one
        # `rec is not None` check per batch (plus one for the profiler,
        # which is only non-None when telemetry or a profile env is armed)
        rec = self._obs
        prof = self._profiler
        led = self._goodput
        step_hist = (
            obs.metrics.get_registry().histogram("rlt_step_time_seconds")
            if rec is not None
            else None
        )
        # the time between loop iterations is the prefetch generator
        # pulling the next batch: input wait until the body reclassifies
        if led is not None:
            led.enter("input_wait")
        # rlt.train.*: the step's phases on the profiler's clock
        for batch_idx, batch, device_batch in _spanned_pulls(
            self._prefetch_shard(train_loader, limit_train)
        ):
            if led is not None:
                led.enter(
                    "productive_compute"
                    if self._first_step_dispatched
                    else "compile"
                )
            if rec is not None or prof is not None:
                _it_wall, _it_t0 = time.time(), time.perf_counter()
                if prof is not None:
                    prof.before_step(self.global_step, device_batch)
            self._health_tick(train=True)
            with obs.phase_span("rlt.train.callbacks", hook="batch_start"):
                self._cb("on_train_batch_start", batch, batch_idx)
            with obs.phase_span("rlt.train.step", step=self.global_step):
                self._params, self._opt_state, logs = train_step(
                    self._params,
                    self._opt_state,
                    device_batch,
                    self._rng_root,
                    np.int32(self.global_step),
                )
                batch_size = self._batch_size_of(batch)
                self._record_train_logs(logs, aggregator, batch_size)
            with obs.phase_span("rlt.train.callbacks", hook="batch_end"):
                self._cb("on_train_batch_end", logs, batch, batch_idx)
                # the module's own hook behind the callbacks': one of them
                # may have read the step's outputs, and it can tell
                self._module.on_train_batch_end(logs, batch, batch_idx)
            self.global_step += 1
            n_batches += 1
            if rec is not None or prof is not None:
                _dt = time.perf_counter() - _it_t0
                _first = not self._first_step_dispatched
                self._first_step_dispatched = True
                if rec is not None:
                    if _first:
                        # the first dispatch blocks on jit trace + XLA
                        # compile; keep it out of the step-time histogram
                        rec.add_span(
                            "compile", _it_wall, _dt, step=self.global_step - 1
                        )
                    else:
                        # host-side step interval: equals device step time
                        # once the dispatch pipeline backpressures
                        rec.add_span("step", _it_wall, _dt, step=self.global_step - 1)
                        step_hist.observe(_dt)
                if prof is not None:
                    if _first:
                        # one-time AOT cost analysis of the compiled step
                        prof.analyze(
                            self._train_program,
                            train_step,
                            (
                                self._params,
                                self._opt_state,
                                device_batch,
                                self._rng_root,
                                np.int32(self.global_step),
                            ),
                        )
                    else:
                        prof.after_step(
                            self.global_step - 1,
                            _dt,
                            sync=logs,
                            starved_s=(
                                self._input_prefetcher.starved_s
                                if self._input_prefetcher is not None
                                else 0.0
                            ),
                        )

            if val_loader is not None and (
                (
                    val_every_n_batches is not None
                    and (batch_idx + 1) % val_every_n_batches == 0
                )
                or (
                    isinstance(self.val_check_interval, int)
                    and self.val_check_interval
                    and self.global_step % self.val_check_interval == 0
                )
            ):
                self._run_validation(val_loader, val_step)

            if led is not None:
                led.enter("input_wait")
            if 0 <= self.max_steps <= self.global_step:
                self.should_stop = True
                break
        else:
            # the loop ran to its natural end; only a max_steps break leaves
            # the epoch marked partial so epoch-end saves resume correctly
            self._epoch_ended = True

        # off the critical path now: flush deferred step metrics, then fold
        # the epoch's input-pipeline stats into the run totals (the
        # prefetcher itself is dropped — it holds the recorder and a bound
        # shard_fn, neither of which should ride a trainer pickle)
        if led is not None:
            led.enter("idle")
        self._drain_step_logs()
        if self._input_prefetcher is not None:
            self._input_stats["starved_s"] += self._input_prefetcher.starved_s
            self._input_stats["batches"] += self._input_prefetcher.batches
            self._input_prefetcher = None

        # epoch-level train metrics
        epoch_metrics = aggregator.reduce(self._module._log_meta.get)
        epoch_out: Dict[str, np.ndarray] = {}
        for name, value in epoch_metrics.items():
            meta = model._log_meta.get(name)
            if meta is None or not meta.on_epoch:
                continue
            out_name = f"{name}_epoch" if (meta.on_step and meta.on_epoch) else name
            self.callback_metrics[out_name] = value
            self.logged_metrics[out_name] = value
            epoch_out[out_name] = value
        if self.logger is not None and self.is_global_zero and epoch_out:
            self.logger.log_metrics(epoch_out, step=self.global_step)

        if (
            val_loader is not None
            and not self.val_check_interval
            and (self.current_epoch + 1) % self.check_val_every_n_epoch == 0
        ):
            self._run_validation(val_loader, val_step)

        self._hook("on_train_epoch_end")

        if self.enable_progress_bar and self.is_global_zero:
            dt = time.perf_counter() - t_epoch
            # one batched readback at epoch end (callbacks may have stored
            # device arrays); non-scalar entries are skipped, not crashed on
            head = dict(list(self.callback_metrics.items())[:6])
            shown = {}
            for k, v in jax.device_get(head).items():
                v = np.asarray(v)
                if v.size == 1:
                    shown[k] = f"{float(v):.4f}"
            print(
                f"[epoch {self.current_epoch}] {n_batches} steps in {dt:.1f}s {shown}",
                flush=True,
            )

    def _record_train_logs(self, logs, aggregator: _EpochAggregator, batch_size: int):
        model = self._module
        epoch_logs = {}
        for name, value in logs.items():
            meta = model._log_meta.get(name)
            if meta is None:
                # implicit "loss" emitted by the step wrapper
                self.logged_metrics[name] = value
                epoch_logs[name] = value
                continue
            if meta.on_step:
                out = f"{name}_step" if (meta.on_step and meta.on_epoch) else name
                self.logged_metrics[out] = value
            if meta.on_epoch:
                epoch_logs[name] = value
        aggregator.update(epoch_logs, batch_size)
        if (
            self.logger is not None
            and self.is_global_zero
            and self.log_every_n_steps
            and self.global_step % self.log_every_n_steps == 0
        ):
            # deferred: hold the (fresh, non-donated) device scalars and
            # resolve them in one device_get at the next drain point —
            # epoch end, validation, or fit teardown — so the hot loop
            # never blocks on a host readback
            step_metrics = {
                k: v
                for k, v in self.logged_metrics.items()
                if not isinstance(v, np.ndarray)
            }
            if step_metrics:
                self._step_log_buffer.append((self.global_step, step_metrics))

    def _drain_step_logs(self) -> None:
        """Resolve and emit the step metrics deferred by
        ``_record_train_logs``: one batched ``jax.device_get`` for the whole
        buffer, off the critical path. Non-scalar values are dropped (the
        logger row format is scalar-only)."""
        if not self._step_log_buffer:
            return
        buffered, self._step_log_buffer = self._step_log_buffer, []
        if self.logger is None or not self.is_global_zero:
            return
        resolved = jax.device_get([metrics for _, metrics in buffered])
        for (step, _), metrics in zip(buffered, resolved):
            row = {}
            for name, value in metrics.items():
                value = np.asarray(value)
                if value.size == 1:
                    row[name] = float(value)
            if row:
                self.logger.log_metrics(row, step=step)

    def _run_validation(self, val_loader, val_step):
        # validation is a logger flush point: deferred step rows land
        # before the val rows so the CSV stays step-ordered
        self._drain_step_logs()
        led = self._goodput
        ctx = (
            led.phase("productive_compute")
            if led is not None
            else contextlib.nullcontext()
        )
        with ctx, obs.span("validate", step=self.global_step):
            self._hook("on_validation_epoch_start")
            self._cb("on_validation_start")
            metrics = self._run_eval_epoch(
                val_loader, val_step, limit=self.limit_val_batches, record=True
            )
            self._val_ran_this_epoch = True
            self._hook("on_validation_epoch_end")
            self._cb("on_validation_end")
        return metrics

    def _run_eval_epoch(self, loader, eval_step, limit=None, record=True, phase="val"):
        if hasattr(loader, "set_epoch"):
            loader.set_epoch(self.current_epoch)
        aggregator = _EpochAggregator()
        limit = self._resolve_limit(limit, loader, f"limit_{phase}_batches")
        for batch_idx, batch in enumerate(loader):
            if limit is not None and batch_idx >= limit:
                break
            self._health_tick(train=False)
            device_batch = self.strategy.shard_batch(batch)
            logs = eval_step(self._params, device_batch, np.int32(batch_idx))
            aggregator.update(logs, self._batch_size_of(batch))
            hook = "on_test_batch_end" if phase == "test" else "on_validation_batch_end"
            self._cb(hook, logs, batch, batch_idx)
        metrics = aggregator.reduce(self._module._log_meta.get)
        if record:
            for name, value in metrics.items():
                self.callback_metrics[name] = value
                self.logged_metrics[name] = value
            if self.logger is not None and self.is_global_zero and metrics:
                self.logger.log_metrics(metrics, step=self.global_step)
        return metrics

    @staticmethod
    def _batch_size_of(batch) -> int:
        leaves = jax.tree_util.tree_leaves(batch)
        for leaf in leaves:
            if hasattr(leaf, "shape") and len(leaf.shape) > 0:
                return int(leaf.shape[0])
        return 1

    @staticmethod
    def _resolve_limit(limit, loader, name: str):
        """PTL semantics: int = batch count, float = fraction of len(loader)."""
        if limit is None or isinstance(limit, int):
            return limit
        try:
            n = len(loader)
        except TypeError:
            raise ValueError(
                f"{name}={limit}: a float fraction requires a sized dataloader"
            )
        resolved = int(n * limit)
        if resolved == 0 and limit > 0.0:
            raise ValueError(
                f"{name}={limit} of a {n}-batch dataloader resolves to 0 "
                "batches; use a larger fraction or an int batch count"
            )
        return resolved

    def _loader_len(self, loader, limit, name: str = "limit") -> int:
        try:
            n = len(loader)
        except TypeError:
            n = 0
        limit = self._resolve_limit(limit, loader, name) if n else limit
        if isinstance(limit, int):
            n = min(n, limit)
        return n

    # ------------------------------------------------------------------ #
    # validate / test / predict implementations
    # ------------------------------------------------------------------ #
    def _eval_impl(self, model, dataloaders, datamodule, ckpt_path, phase: str):
        seed_everything(self.seed)
        self.strategy.setup_environment()
        if hasattr(model, "mesh"):
            model.mesh = self.strategy.mesh
        if datamodule is not None:
            datamodule.prepare_data()
            datamodule.setup(phase if phase != "val" else "validate")
        model.prepare_data()
        model.setup(phase)
        hook_name = {"val": "val_dataloader", "test": "test_dataloader"}[phase]
        loader = self._resolve_loader(dataloaders, datamodule, hook_name)
        if loader is None:
            raise ValueError(f"{phase} requires a dataloader")
        loader = self._maybe_shard_loader(loader, shuffle=False)

        if ckpt_path is not None:
            with open(ckpt_path, "rb") as f:
                ckpt = load_state_stream(f.read())
            model._params = ckpt["state_dict"]
        if model._params is None:
            raise ValueError(f"{phase} requires trained params (fit first or pass ckpt_path)")
        model.precision_policy = self.precision_policy
        self._params = self.strategy.place_params(
            cast_floats(model._params, self.precision_policy.param_dtype)
        )

        eval_step = self._build_eval_step(phase)
        limit = self.limit_val_batches if phase == "val" else self.limit_test_batches
        if phase == "test":
            self._cb("on_test_start")
        metrics = self._run_eval_epoch(eval_step=eval_step, loader=loader, limit=limit, phase=phase)
        for name, value in metrics.items():
            self.callback_metrics[name] = value
        if phase == "test":
            self._cb("on_test_epoch_end")
            self._cb("on_test_end")
        if self.logger is not None:
            self.logger.save()
        return [dict(metrics)]

    def _predict_impl(self, model, dataloaders, datamodule, ckpt_path):
        seed_everything(self.seed)
        self.strategy.setup_environment()
        if hasattr(model, "mesh"):
            model.mesh = self.strategy.mesh
        if datamodule is not None:
            datamodule.prepare_data()
            datamodule.setup("predict")
        model.prepare_data()
        model.setup("predict")
        loader = self._resolve_loader(dataloaders, datamodule, "predict_dataloader")
        if loader is None:
            raise ValueError("predict requires a dataloader")
        if ckpt_path is not None:
            with open(ckpt_path, "rb") as f:
                ckpt = load_state_stream(f.read())
            model._params = ckpt["state_dict"]
        if model._params is None:
            raise ValueError("predict requires trained params")
        model.precision_policy = self.precision_policy
        self._params = self.strategy.place_params(
            cast_floats(model._params, self.precision_policy.param_dtype)
        )
        module = model

        policy = self.precision_policy

        @jax.jit
        def predict_step(params, batch, step):
            batch = cast_floats(batch, policy.compute_dtype)
            if policy.cast_params_in_compute:
                params = cast_floats(params, policy.compute_dtype)
            module._capture_begin("predict")
            out = module.predict_step(params, batch, step)
            module._capture_end()
            return out

        self._cb("on_predict_start")
        outputs = []
        limit_predict = self._resolve_limit(
            self.limit_predict_batches, loader, "limit_predict_batches"
        )
        for batch_idx, batch in enumerate(loader):
            if limit_predict is not None and batch_idx >= limit_predict:
                break
            device_batch = self.strategy.shard_batch(batch)
            out = predict_step(self._params, device_batch, np.int32(batch_idx))
            outputs.append(jax.device_get(out))
        self._cb("on_predict_end")
        return outputs

    # ------------------------------------------------------------------ #
    # checkpointing
    # ------------------------------------------------------------------ #
    def dump_checkpoint(self, weights_only: bool = False) -> Dict[str, Any]:
        model = self._module
        params_host = jax.device_get(self._params if self._params is not None else model._params)
        ckpt: Dict[str, Any] = {
            "epoch": self.current_epoch,
            "epoch_complete": bool(self._epoch_ended),
            "global_step": self.global_step,
            "rlt_version": __version__,
            "state_dict": flax_serialization.to_state_dict(params_host),
            "hyper_parameters": dict(model.hparams),
        }
        if not weights_only:
            if self._opt_state is not None:
                ckpt["optimizer_state"] = flax_serialization.to_state_dict(
                    self._host_opt_state()
                )
            from ray_lightning_tpu.callbacks.base import collect_callback_states

            ckpt["callbacks"] = collect_callback_states(self.callbacks)
            ckpt["callback_metrics"] = {
                k: np.asarray(v) for k, v in self.callback_metrics.items()
            }
        model.on_save_checkpoint(ckpt)
        return ckpt

    def save_checkpoint(self, filepath: str, weights_only: bool = False) -> None:
        led = self._goodput
        ctx = (
            led.phase("checkpoint") if led is not None
            else contextlib.nullcontext()
        )
        with ctx, obs.span("checkpoint/save", step=self.global_step, path=filepath):
            ckpt = self.dump_checkpoint(weights_only)
            filepath = os.path.abspath(filepath)
            os.makedirs(os.path.dirname(filepath), exist_ok=True)
            # write-then-rename: a process killed mid-save (the exact moment
            # the crash-relaunch path later scans this directory) must never
            # leave a truncated .ckpt that the relaunch would pick as "newest"
            fsio.atomic_write_bytes(filepath, to_state_stream(ckpt))
        reg = obs.registry()
        if reg is not None:
            reg.counter("rlt_checkpoint_saves_total").inc()

    def collect_aux_state(self) -> Dict[str, Any]:
        """Non-array resume state shared by BOTH checkpoint formats:
        callback states (EarlyStopping patience, ModelCheckpoint best-k),
        callback metrics, and the module's ``on_save_checkpoint`` extras.
        The orbax callback serializes this alongside the sharded arrays."""
        from ray_lightning_tpu.callbacks.base import collect_callback_states

        user: Dict[str, Any] = {
            "epoch": self.current_epoch,
            "global_step": self.global_step,
        }
        self._module.on_save_checkpoint(user)
        return {
            "callbacks": collect_callback_states(self.callbacks),
            "callback_metrics": {
                k: np.asarray(v) for k, v in self.callback_metrics.items()
            },
            "user": user,
        }

    def _restore_aux_state(self, ckpt: Dict[str, Any]) -> None:
        """Apply the shared resume protocol: callback states, callback
        metrics, and the module's ``on_load_checkpoint``. ``ckpt`` is the
        full dict for the .ckpt format, or the reassembled aux dict for
        orbax — both carry the same keys."""
        from ray_lightning_tpu.callbacks.base import restore_callback_states

        restore_callback_states(self.callbacks, ckpt.get("callbacks", {}))
        for k, v in ckpt.get("callback_metrics", {}).items():
            self.callback_metrics[k] = np.asarray(v)
        self._module.on_load_checkpoint(ckpt)

    def _restore_spec(self, ckpt_path: str) -> None:
        """Dispatch a restore spec: ``orbax@<step>:<dir>`` (exact step),
        ``orbax:<dir>`` (latest committed), or a plain ``.ckpt`` path."""
        with obs.span("checkpoint/restore", path=ckpt_path):
            if ckpt_path.startswith("orbax@"):
                step_s, dirpath = ckpt_path[len("orbax@") :].split(":", 1)
                self._restore_orbax(dirpath, step=int(step_s))
            elif ckpt_path.startswith("orbax:"):
                self._restore_orbax(ckpt_path[len("orbax:") :])
            else:
                self._restore_checkpoint(ckpt_path)

    def _restore_orbax(self, dirpath: str, step: Optional[int] = None) -> None:
        """Resume from an orbax step (default: latest) onto the CURRENT
        shardings (``self._params``/``self._opt_state`` are the freshly-
        initialized templates at this point in ``_fit_impl``; orbax
        reshards on read)."""
        from ray_lightning_tpu.callbacks.orbax_checkpoint import (
            OrbaxModelCheckpoint,
        )

        restored = OrbaxModelCheckpoint.restore(
            dirpath, self._params, self._opt_state, step=step
        )
        self._params = restored["params"]
        if "opt_state" in restored:
            self._opt_state = restored["opt_state"]
        self.global_step = restored["step"]
        meta = restored.get("meta")
        if meta is not None:
            epoch = int(np.asarray(meta["epoch"]))
            complete = bool(np.asarray(meta.get("epoch_complete", True)))
            self.current_epoch = epoch + 1 if complete else epoch
            aux = meta.get("aux")
            if aux is not None:
                aux = load_state_stream(np.asarray(aux).tobytes())
                # user extras merge top-level so on_load_checkpoint sees
                # the same dict shape on_save_checkpoint wrote into
                self._restore_aux_state({**aux, **aux.get("user", {})})

    def _restore_checkpoint(self, ckpt_path: str) -> None:
        with open(ckpt_path, "rb") as f:
            ckpt = load_state_stream(f.read())
        # params: restore into the existing (possibly sharded) structure;
        # re-apply the precision policy — the checkpoint may carry different
        # dtypes than this run requests (e.g. fp32 ckpt, bf16-true resume)
        host_params = flax_serialization.from_state_dict(
            jax.device_get(self._params), ckpt["state_dict"]
        )
        host_params = cast_floats(host_params, self.precision_policy.param_dtype)
        self._params = self._place_params(host_params)
        if "optimizer_state" in ckpt and self._opt_state is not None:
            host_opt = flax_serialization.from_state_dict(
                self._host_opt_state(), ckpt["optimizer_state"]
            )
            # the freshly-initialized opt_state is the sharding template —
            # restore each leaf with the sharding it already has (works for
            # both policy-driven and module-owned layouts)
            self._opt_state = jax.tree_util.tree_map(
                lambda tmpl, h: jax.device_put(h, tmpl.sharding)
                if hasattr(tmpl, "sharding")
                else h,
                self._opt_state,
                host_opt,
            )
        # a mid-epoch save (epoch_complete False) resumes by re-running its
        # epoch from the start — some batches retrain, none are skipped;
        # checkpoints from older versions lack the flag and keep epoch + 1
        base = int(ckpt.get("epoch", 0))
        self.current_epoch = base + 1 if ckpt.get("epoch_complete", True) else base
        self.global_step = int(ckpt.get("global_step", 0))
        self._restore_aux_state(ckpt)
