"""Async sharded checkpointing via orbax — the TPU-native upgrade of the
reference's byte-stream checkpoints (SURVEY §5 checkpoint/resume: "orbax-style
async checkpointing of sharded arrays" is the designed-for equivalent).

Unlike the msgpack stream path (which gathers to host), orbax writes each
shard from the process that owns it and overlaps I/O with the next training
steps (async). Restoring with a different mesh/worker count reshards
transparently — the ZeRO "checkpoint downsizing" capability the reference
tests via FairScale (reference: tests/test_ddp_sharded.py:118-137).
"""
from __future__ import annotations

import itertools
import os
from typing import Any, Dict, Optional

import jax
import numpy as np

from ray_lightning_tpu import observability as obs
from ray_lightning_tpu.callbacks.base import Callback
from ray_lightning_tpu.utils.common import optional_import


def _ocp():
    """``orbax.checkpoint``, imported at the first call and not with this
    module: 12 s on a TPU host, ``google.cloud.logging`` most of it."""
    ocp = optional_import("orbax.checkpoint")
    if ocp is None:
        raise RuntimeError("orbax-checkpoint is not installed")
    return ocp


def __getattr__(name: str):
    if name == "ORBAX_AVAILABLE":
        return optional_import("orbax.checkpoint") is not None
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class OrbaxModelCheckpoint(Callback):
    """Periodic async checkpoints of (params, opt_state, step) with
    retention, via ocp.CheckpointManager."""

    saves_checkpoints = True

    def __init__(
        self,
        dirpath: Optional[str] = None,
        every_n_epochs: int = 1,
        every_n_steps: Optional[int] = None,
        max_to_keep: int = 3,
        async_save: bool = True,
    ):
        _ocp()  # a missing library is refused here, at construction
        self.dirpath = dirpath
        self.every_n_epochs = max(1, every_n_epochs)
        if every_n_steps is None:
            raw = os.environ.get("RLT_CKPT_EVERY_N_STEPS")
            if raw:
                every_n_steps = int(raw)
        # streaming saves: also checkpoint every N optimizer steps so a
        # crash/shrink mid-epoch loses at most N steps, not a whole epoch
        self.every_n_steps = max(1, int(every_n_steps)) if every_n_steps else None
        self.max_to_keep = max_to_keep
        self.async_save = async_save
        self._manager: Optional["ocp.CheckpointManager"] = None

    @staticmethod
    def default_dirpath(trainer) -> str:
        """Single source of truth for the dirpath default — the launcher's
        crash-relaunch scanner resolves through this too, so the two can
        never drift onto different directories."""
        return os.path.join(trainer.default_root_dir, "orbax_ckpt")

    def setup(self, trainer, module, stage: str) -> None:
        if self.dirpath is None:
            self.dirpath = self.default_dirpath(trainer)
        self._manager = self._build_manager()

    def _build_manager(self) -> "ocp.CheckpointManager":
        # create=False skips CheckpointManager.__init__'s cross-process
        # directory barrier: processes reach manager construction at
        # different times in an elastic group (a joiner builds its manager
        # in setup while survivors are mid-resize), so any collective here
        # deadlocks. Directory creation is just a local mkdir instead —
        # every worker shares one filesystem in the paths that reach this.
        os.makedirs(os.path.abspath(self.dirpath), exist_ok=True)
        self._realign_barrier_counters()
        ocp = _ocp()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=self.max_to_keep,
            enable_async_checkpointing=self.async_save,
            create=False,
        )
        return ocp.CheckpointManager(
            os.path.abspath(self.dirpath), options=options
        )

    def on_train_epoch_end(self, trainer, module) -> None:
        if trainer.sanity_checking or self._manager is None:
            return
        if trainer.current_epoch % self.every_n_epochs != 0:
            return
        self._save(trainer, trainer.global_step, bool(trainer._epoch_ended))

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx) -> None:
        if trainer.sanity_checking or self._manager is None:
            return
        if self.every_n_steps is None:
            return
        # this hook fires BEFORE the trainer bumps global_step, so the step
        # the just-applied update produced is global_step + 1
        step = trainer.global_step + 1
        if step % self.every_n_steps != 0:
            return
        latest = self._manager.latest_step()
        if latest is not None and step <= latest:
            # a resume re-runs its epoch from the start; those steps are
            # already committed on disk
            return
        # wait-on-previous: at most one async commit in flight, so a fast
        # cadence degrades to synchronous instead of queueing unboundedly
        self._manager.wait_until_finished()
        self._save(trainer, step, epoch_complete=False)

    def _save(self, trainer, step: int, epoch_complete: bool) -> None:
        ocp = _ocp()
        items = {"params": ocp.args.StandardSave(trainer._params)}
        if trainer._opt_state is not None:
            items["opt_state"] = ocp.args.StandardSave(trainer._opt_state)
        # metadata lets a crash-relaunch run the FULL resume protocol, not
        # just the weights: epoch loop position plus the trainer's shared
        # aux state (callback states, callback metrics, module extras) —
        # carried as one msgpack stream inside a uint8 array (orbax items
        # must be array pytrees; the stream already round-trips numpy)
        from ray_lightning_tpu.utils.serialization import to_state_stream

        aux = to_state_stream(trainer.collect_aux_state())
        items["meta"] = ocp.args.StandardSave(
            {
                "epoch": np.asarray(trainer.current_epoch),
                "epoch_complete": np.asarray(epoch_complete),
                "aux": np.frombuffer(aux, dtype=np.uint8).copy(),
            }
        )
        # the span covers only the (usually short) async dispatch; the
        # actual shard writes overlap with subsequent training steps
        with obs.span(
            "checkpoint/orbax_save", step=step, dir=self.dirpath
        ):
            self._manager.save(step, args=ocp.args.Composite(**items))
        reg = obs.registry()
        if reg is not None:
            reg.counter("rlt_checkpoint_saves_total", format="orbax").inc()

    def on_membership_resize(self, trainer, module) -> None:
        """Elastic resize: the old manager's async machinery holds commit
        barriers spanning the OLD process group — closing it (or waiting on
        it) could block against peers that are already dead. Abandon it
        without closing and open a fresh manager over the same directory;
        partially-written steps are uncommitted and invisible to
        latest_step()."""
        if self._manager is None:
            return
        self._manager = None
        self._manager = self._build_manager()

    @staticmethod
    def _realign_barrier_counters() -> None:
        """Orbax embeds process-LOCAL monotonic counters in its multihost
        barrier names (``multihost/counters.py``): two processes only
        rendezvous if they have performed the same number of saves since
        interpreter start. In an elastic group that is false by design — a
        joiner starts at zero while survivors have been saving all along —
        so the counters are re-zeroed on every member at manager (re)build,
        which is a membership-synchronous point on all of them."""
        try:
            from orbax.checkpoint.multihost import counters as _counters
        except ImportError:  # pragma: no cover - layout varies across versions
            return
        for name in vars(_counters):
            if name.startswith("_") and name.endswith("_counter"):
                setattr(_counters, name, itertools.count())

    def on_fit_end(self, trainer, module) -> None:
        if self._manager is not None:
            self._manager.wait_until_finished()

    def teardown(self, trainer, module, stage: str) -> None:
        if self._manager is not None:
            self._manager.close()
            self._manager = None

    # ------------------------------------------------------------------ #
    def latest_step(self) -> Optional[int]:
        return self._manager.latest_step() if self._manager else None

    @staticmethod
    def restore(
        dirpath: str,
        params_template: Any,
        opt_state_template: Any = None,
        step: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Restore onto the templates' shardings — templates may use a
        DIFFERENT mesh than the save ran on; orbax reshards on read.

        The result always carries ``step``; ``opt_state`` and ``meta``
        (epoch, for crash-relaunch resume) appear when present on disk —
        checkpoints from older versions lack ``meta``, weights-only saves
        lack ``opt_state``.
        """
        dirpath = os.path.abspath(dirpath)
        manager = _ocp().CheckpointManager(dirpath)
        with obs.span("checkpoint/orbax_restore", dir=dirpath):
            return OrbaxModelCheckpoint._restore_with(
                manager, dirpath, params_template, opt_state_template, step
            )

    @staticmethod
    def _restore_with(manager, dirpath, params_template, opt_state_template, step):
        ocp = _ocp()
        try:
            step = step if step is not None else manager.latest_step()
            if step is None:
                raise FileNotFoundError(f"no orbax checkpoints under {dirpath}")
            to_abstract = lambda tree: jax.tree_util.tree_map(
                ocp.utils.to_shape_dtype_struct, tree
            )
            items = {"params": ocp.args.StandardRestore(to_abstract(params_template))}
            step_dir = os.path.join(dirpath, str(step))
            if opt_state_template is not None and os.path.isdir(
                os.path.join(step_dir, "opt_state")
            ):
                items["opt_state"] = ocp.args.StandardRestore(
                    to_abstract(opt_state_template)
                )
            if os.path.isdir(os.path.join(step_dir, "meta")):
                items["meta"] = ocp.args.StandardRestore()
            restored = manager.restore(step, args=ocp.args.Composite(**items))
            out = dict(restored.items())
            out["step"] = int(step)
            return out
        finally:
            manager.close()
