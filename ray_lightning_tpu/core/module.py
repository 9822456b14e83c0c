"""JAX-native LightningModule.

API parity target: the ``pl.LightningModule`` surface the reference's models
use (reference: ray_lightning/tests/utils.py:28-210 ``BoringModel`` /
``LightningMNISTClassifier`` / ``XORModel``) — ``training_step`` /
``validation_step`` / ``test_step`` / ``predict_step`` /
``configure_optimizers`` / ``self.log`` — re-designed for JAX's functional
model: steps are **pure functions of (params, batch)** that the Trainer traces
once under ``jax.jit`` and executes on the TPU every step.

Key design point — ``self.log`` under tracing: PTL's ``self.log`` is an eager
side effect. Under XLA there are no per-step host side effects, so ``log``
captures the *traced* value into a buffer that the Trainer returns as part of
the compiled step's outputs. Metric aggregation (on_step / on_epoch / forked
``_step``/``_epoch`` names, reference behavior tested in
ray_lightning/tests/test_ddp.py:326-352) happens on host from those outputs.
Because data-parallel loss/metrics are computed over the globally sharded
batch inside jit, XLA's GSPMD partitioner inserts the cross-device reductions
— ``sync_dist=True`` is the default semantics for free.
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_lightning_tpu.utils.serialization import load_state_stream


@dataclass
class LogMeta:
    on_step: bool
    on_epoch: bool
    prog_bar: bool = False
    reduce: str = "mean"  # mean | sum | max | min


@dataclass
class _StepContext:
    """Per-trace context: phase, rng, and the captured log buffer."""

    phase: str  # "train" | "val" | "test" | "predict"
    rng: Optional[jax.Array] = None
    logs: Dict[str, jax.Array] = field(default_factory=dict)


class HParams(dict):
    """Dict with attribute access, like PTL's AttributeDict hparams."""

    def __getattr__(self, item):
        try:
            return self[item]
        except KeyError as e:
            raise AttributeError(item) from e

    def __setattr__(self, key, value):
        self[key] = value


class LightningModule:
    """Base class for user models.

    Subclasses define the network (typically a ``flax.linen.Module`` held as
    an attribute), ``init_params``, the ``*_step`` pure functions and
    ``configure_optimizers`` (returning an optax transformation).
    """

    def __init__(self):
        self._trainer = None
        self._step_ctx: Optional[_StepContext] = None
        self._log_meta: Dict[str, LogMeta] = {}
        self._params = None  # populated after fit / load_from_checkpoint
        self.hparams: HParams = getattr(self, "hparams", HParams())

    # ------------------------------------------------------------------ #
    # wiring
    # ------------------------------------------------------------------ #
    @property
    def trainer(self):
        return self._trainer

    @trainer.setter
    def trainer(self, trainer):
        self._trainer = trainer

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = value

    @property
    def global_rank(self) -> int:
        return self._trainer.global_rank if self._trainer is not None else 0

    @property
    def current_epoch(self) -> int:
        return self._trainer.current_epoch if self._trainer is not None else 0

    @property
    def global_step(self) -> int:
        return self._trainer.global_step if self._trainer is not None else 0

    @property
    def step_rng(self) -> jax.Array:
        """Per-step PRNG key, valid inside a ``*_step`` while being traced.

        Use for dropout etc.: ``self.model.apply(params, x, rngs={"dropout":
        self.step_rng}, deterministic=False)``.
        """
        if self._step_ctx is None or self._step_ctx.rng is None:
            raise RuntimeError("step_rng is only available inside a *_step call")
        return self._step_ctx.rng

    @property
    def training(self) -> bool:
        return self._step_ctx is not None and self._step_ctx.phase == "train"

    # ------------------------------------------------------------------ #
    # hyperparameters
    # ------------------------------------------------------------------ #
    def save_hyperparameters(self, *args, ignore=()):
        """Record the calling ``__init__``'s arguments into ``self.hparams``.

        Checkpoints embed these so ``load_from_checkpoint`` can rebuild the
        module (PTL parity).
        """
        frame = inspect.currentframe().f_back
        arg_info = inspect.getargvalues(frame)
        if args:
            captured = {}
            for a in args:
                if isinstance(a, dict):
                    captured.update(a)
                elif isinstance(a, str):
                    captured[a] = arg_info.locals.get(a)
        else:
            captured = {
                k: v
                for k, v in arg_info.locals.items()
                if k not in ("self", "__class__") and not k.startswith("_")
                and k not in ignore
            }
        for k, v in captured.items():
            self.hparams[k] = v

    # ------------------------------------------------------------------ #
    # params / model
    # ------------------------------------------------------------------ #
    def init_params(self, rng: jax.Array):
        """Initialize and return the parameter pytree.

        Default implementation initializes ``self.model`` (a flax module)
        against ``self.example_input_array`` when both are present.
        """
        model = getattr(self, "model", None)
        example = getattr(self, "example_input_array", None)
        if model is not None and example is not None:
            if isinstance(example, (tuple, list)):
                return model.init(rng, *example)
            return model.init(rng, example)
        raise NotImplementedError(
            "Override init_params(rng), or set both `self.model` (a flax "
            "module) and `self.example_input_array`."
        )

    def forward(self, params, *args, **kwargs):
        model = getattr(self, "model", None)
        if model is None:
            raise NotImplementedError("Override forward() or set self.model")
        return model.apply(params, *args, **kwargs)

    def __call__(self, params, *args, **kwargs):
        return self.forward(params, *args, **kwargs)

    # ------------------------------------------------------------------ #
    # steps (user overrides; traced under jit by the Trainer)
    # ------------------------------------------------------------------ #
    def training_step(self, params, batch, batch_idx):
        raise NotImplementedError

    def validation_step(self, params, batch, batch_idx):
        return None

    def test_step(self, params, batch, batch_idx):
        # Default to the validation logic, like PTL's common pattern.
        return self.validation_step(params, batch, batch_idx)

    def predict_step(self, params, batch, batch_idx):
        return self.forward(params, batch)

    # ------------------------------------------------------------------ #
    # 1F1B pipeline contract (strategies with pipeline_stages > 0)
    # ------------------------------------------------------------------ #
    def pipeline_stage(self, stage_params, x):
        """One pipeline stage's forward: ``x -> activations``. Under a
        pipelined strategy ``init_params`` must return
        ``{"stages": <leaves with leading dim == pipeline_stages>,
        "last": <head params>}`` and the batch must be ``(x, targets)``;
        ``stage_params`` is one stage's slice of the ``"stages"`` subtree
        (leading dim stripped). Tensor-parallel math inside a stage must
        use the f/g operators from ``parallel.pipeline_1f1b``
        (``identity_fwd_psum_bwd`` / ``psum_fwd_identity_bwd``) — a plain
        ``psum`` double-counts cotangents under the manual pipeline VJP."""
        raise NotImplementedError(
            "pipeline_stages > 0 requires the module to implement "
            "pipeline_stage(stage_params, x)"
        )

    def pipeline_last(self, last_params, y, targets):
        """Loss head on the final stage's output: ``(y, targets) -> scalar
        per-microbatch loss`` (mean-reduced over microbatches by the 1F1B
        schedule). ``last_params`` is the ``"last"`` subtree, replicated
        across pipeline stages."""
        raise NotImplementedError(
            "pipeline_stages > 0 requires the module to implement "
            "pipeline_last(last_params, y, targets)"
        )

    def configure_optimizers(self):
        raise NotImplementedError

    # ------------------------------------------------------------------ #
    # throughput advertisement (consumed by ThroughputMonitor so MFU /
    # tokens-per-sec appear without hand-fed arithmetic)
    # ------------------------------------------------------------------ #
    def flops_per_sample(self) -> Optional[float]:
        """Training FLOPs for ONE sample (fwd+bwd), or None if unknown."""
        return None

    def tokens_per_sample(self) -> Optional[int]:
        """Tokens per sample for LM-style throughput, or None."""
        return None

    # ------------------------------------------------------------------ #
    # logging
    # ------------------------------------------------------------------ #
    def log(
        self,
        name: str,
        value,
        on_step: Optional[bool] = None,
        on_epoch: Optional[bool] = None,
        prog_bar: bool = False,
        reduce: str = "mean",
        sync_dist: bool = True,  # accepted for parity; sync is inherent
        **_: Any,
    ) -> None:
        ctx = self._step_ctx
        if ctx is None:
            return  # logging outside a step is a no-op, like PTL warns
        phase = ctx.phase
        if on_step is None:
            on_step = phase == "train"
        if on_epoch is None:
            on_epoch = phase != "train"
        self._log_meta[name] = LogMeta(
            on_step=on_step, on_epoch=on_epoch, prog_bar=prog_bar, reduce=reduce
        )
        ctx.logs[name] = jnp.asarray(value)

    def log_dict(self, metrics: Dict[str, Any], **kwargs) -> None:
        for k, v in metrics.items():
            self.log(k, v, **kwargs)

    # internal: trainer drives these around each traced step
    def _capture_begin(self, phase: str, rng: Optional[jax.Array] = None) -> None:
        self._step_ctx = _StepContext(phase=phase, rng=rng)

    def _capture_end(self) -> Dict[str, jax.Array]:
        logs = self._step_ctx.logs if self._step_ctx else {}
        self._step_ctx = None
        return logs

    # ------------------------------------------------------------------ #
    # hooks (subset of the PTL hook surface used by the reference's tests,
    # reference: ray_lightning/tests/utils.py:28-96)
    # ------------------------------------------------------------------ #
    def prepare_data(self) -> None: ...

    def setup(self, stage: str) -> None: ...

    def teardown(self, stage: str) -> None: ...

    def on_fit_start(self) -> None: ...

    def on_fit_end(self) -> None: ...

    def on_train_start(self) -> None: ...

    def on_train_end(self) -> None: ...

    def on_train_epoch_start(self) -> None: ...

    def on_train_batch_end(self, outputs, batch, batch_idx) -> None: ...

    def on_train_epoch_end(self) -> None: ...

    def on_validation_epoch_start(self) -> None: ...

    def on_validation_epoch_end(self) -> None: ...

    def on_save_checkpoint(self, checkpoint: Dict[str, Any]) -> None: ...

    def on_load_checkpoint(self, checkpoint: Dict[str, Any]) -> None: ...

    # optional dataloader hooks (PTL parity)
    def train_dataloader(self):
        return None

    def val_dataloader(self):
        return None

    def test_dataloader(self):
        return None

    def predict_dataloader(self):
        return None

    # ------------------------------------------------------------------ #
    # checkpoint IO
    # ------------------------------------------------------------------ #
    @classmethod
    def load_from_checkpoint(cls, path: str, **override_hparams):
        """Rebuild the module from a checkpoint file and attach its params."""
        with open(path, "rb") as f:
            ckpt = load_state_stream(f.read())
        hparams = dict(ckpt.get("hyper_parameters", {}))
        hparams.update(override_hparams)
        try:
            module = cls(**hparams) if hparams else cls()
        except TypeError:
            # ctor takes a single config dict (reference MNISTClassifier style)
            module = cls(hparams)
        module._params = ckpt["state_dict"]
        return module
