"""Step-time / throughput / MFU monitor.

First-class upgrade of the reference's example-only ``CUDACallback`` (epoch
seconds + peak CUDA memory, reference:
ray_lightning/examples/ray_ddp_sharded_example.py:16-45): measures per-step
wall time, samples/sec, optional tokens/sec/chip and model-FLOPs-utilization
against the chip's peak matmul throughput.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import jax
import numpy as np

from ray_lightning_tpu import observability as _obs
from ray_lightning_tpu.callbacks.base import Callback
from ray_lightning_tpu.utils.common import rank_zero_warn

# Peak bf16 matmul TFLOP/s per chip, keyed by a substring of the
# ``device_kind`` JAX reports (vendor documentation; the v5e reports
# "TPU v5 lite"). A chip that is not here is an error, not a default.
_PEAK_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5 lite": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
}

PEAK_TFLOPS_ENV = "RLT_PEAK_TFLOPS"

SAMPLES_PER_SEC_METRIC = "rlt_samples_per_sec"
TRAIN_MFU_METRIC = "rlt_train_mfu"
TOKENS_PER_CHIP_METRIC = "rlt_tokens_per_sec_per_chip"


def detect_peak_tflops() -> Optional[float]:
    """Peak bf16 TFLOP/s per chip, or None on the CPU: a utilization is a
    device metric and is "not measured" there (callers leave it out).
    ``RLT_PEAK_TFLOPS`` overrides detection (the only correct source for
    chips this table doesn't know); an accelerator missing from the table
    raises instead of reporting MFU against another chip's peak."""
    override = os.environ.get(PEAK_TFLOPS_ENV)
    if override:
        try:
            value = float(override)
            if value > 0:
                return value
            rank_zero_warn(
                "%s must be > 0, got %r; ignoring", PEAK_TFLOPS_ENV, override
            )
        except ValueError:
            rank_zero_warn(
                "%s is not a number: %r; ignoring", PEAK_TFLOPS_ENV, override
            )
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = getattr(dev, "device_kind", "").lower()
    for key, tflops in _PEAK_TFLOPS.items():
        if key in kind:
            return tflops
    raise ValueError(
        f"unknown accelerator {kind!r}: no peak TFLOP/s on record for MFU; "
        f"set {PEAK_TFLOPS_ENV} to the chip's real peak"
    )


class ThroughputMonitor(Callback):
    def __init__(
        self,
        flops_per_sample: Optional[float] = None,
        tokens_per_sample: Optional[int] = None,
        window: int = 20,
        log_every_n_steps: int = 0,
        sync_every: int = 4,
    ):
        self.flops_per_sample = flops_per_sample
        self.tokens_per_sample = tokens_per_sample
        self.window = window
        self.log_every_n_steps = log_every_n_steps
        # JAX dispatch is async: a per-step timestamp records enqueue time,
        # which is wildly optimistic until the pipeline backpressures. But a
        # per-step device sync would serialize host and device for the whole
        # run. Compromise: block on the outputs once every `sync_every`
        # steps and record the interval's MEAN step time — honest numbers,
        # 1/sync_every of the stall.
        self.sync_every = max(1, sync_every)
        self._times: list = []  # per-interval mean step times
        self._last_sync_t: Optional[float] = None
        self._steps_since_sync = 0
        self._batch_size: Optional[int] = None

    def setup(self, trainer, module, stage: str) -> None:
        # adopt the module's advertised throughput numbers when the user
        # didn't hand-feed them (llama advertises flops/tokens per sample)
        def advertised(name):
            value = getattr(module, name, None)
            # a module may expose these as methods (the LightningModule
            # hooks) or plain numeric attributes
            return value() if callable(value) else value

        if self.flops_per_sample is None:
            flops = advertised("flops_per_sample")
            if flops:
                self.flops_per_sample = float(flops)
        if self.tokens_per_sample is None:
            tokens = advertised("tokens_per_sample")
            if tokens:
                self.tokens_per_sample = int(tokens)

    @staticmethod
    def _infer_batch_size(batch) -> int:
        leaves = jax.tree_util.tree_leaves(batch)
        return int(leaves[0].shape[0]) if leaves else 0

    def on_train_batch_start(self, trainer, module, batch, batch_idx) -> None:
        self._batch_size = self._infer_batch_size(batch)

    def _record_interval(self, now: float) -> None:
        if self._last_sync_t is not None and self._steps_since_sync:
            self._times.append(
                (now - self._last_sync_t) / self._steps_since_sync
            )
            if len(self._times) > self.window:
                self._times.pop(0)
        self._last_sync_t = now
        self._steps_since_sync = 0

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx) -> None:
        self._steps_since_sync += 1
        if self._steps_since_sync < self.sync_every:
            return
        leaves = jax.tree_util.tree_leaves(outputs)
        if leaves:
            jax.block_until_ready(leaves)
        self._record_interval(time.perf_counter())
        self._publish_telemetry(trainer)
        if (
            self.log_every_n_steps
            and trainer.global_step % self.log_every_n_steps == 0
            and trainer.logger is not None
        ):
            trainer.logger.log_metrics(self.summary(trainer), step=trainer.global_step)

    def _publish_telemetry(self, trainer) -> None:
        """Push the rolling throughput numbers into the flight recorder's
        registry so the driver aggregator can report cluster samples/sec
        and MFU. Runs only at sync points; one None check when disabled."""
        reg = _obs.registry()
        if reg is None:
            return
        summary = self.summary(trainer)
        for name, key in (
            (SAMPLES_PER_SEC_METRIC, "samples_per_sec"),
            (TRAIN_MFU_METRIC, "train_mfu"),
            (TOKENS_PER_CHIP_METRIC, "tokens_per_sec_per_chip"),
        ):
            if key in summary:
                reg.gauge(name).set(summary[key])

    def summary(self, trainer) -> dict:
        if not self._times or not self._batch_size:
            return {}
        # the first interval absorbs compilation only when training started
        # there; _record_interval never measures from t=0, so all retained
        # intervals are steady-state
        step_time = float(np.mean(self._times))
        n_chips = max(1, trainer.world_size * jax.local_device_count())
        global_batch = self._batch_size * max(1, trainer.world_size)
        out = {
            "step_time_s": step_time,
            "samples_per_sec": global_batch / step_time,
        }
        if self.tokens_per_sample:
            out["tokens_per_sec_per_chip"] = (
                global_batch * self.tokens_per_sample / step_time / n_chips
            )
        peak_tflops = detect_peak_tflops() if self.flops_per_sample else None
        if peak_tflops:
            achieved = global_batch * self.flops_per_sample / step_time / n_chips
            out["train_mfu"] = achieved / (peak_tflops * 1e12)
        return out

    def on_train_end(self, trainer, module) -> None:
        summary = self.summary(trainer)
        for k, v in summary.items():
            trainer.callback_metrics[k] = np.asarray(v)
        self._publish_telemetry(trainer)
