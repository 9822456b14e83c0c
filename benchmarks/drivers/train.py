"""Drives a training cell: ``Trainer.fit`` on the job the traffic file states.

Built as ``chip_smoke.py::_make_probe`` and ``_fit`` are: a ``Callback`` ends
every step by reading the synced loss, and keeps the host clock. Set-up is
everything up to the window's first instant: import, weights made on the
device from the seed, compile or cache load, and the job's first steps, which
are the same compiled step on the same state that the window then drives
(the plain reference follows those steps after the fit has ended and its
state is freed). The window opens at the end of step ``steps_before_window``
and closes at the end of the first step that ends after ``--seconds``: the
rate is all the tokens of those steps over all of that time.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import lm_data, program, trace_reduce
from benchmarks.correct import Check

TRACE_STEPS = 3  # steps under the profiler in a --trace 1 run
MIN_STEP_S = 0.2  # sizes the row buffer: no step of a cell is shorter


def run(cell, seed: int, seconds: float, trace: bool, ctx) -> Dict[str, Any]:
    sizes, job, settings, family = cell.config, cell.traffic, cell.settings, cell.family
    opt = job["optimizer"]
    seq, rows_per_chip = job["seq_len"], job["rows_per_chip"]
    warm = int(job["steps_before_window"])
    checked = int(job["checked_steps"])
    batch = rows_per_chip * cell.chips
    max_steps = warm + int(seconds / MIN_STEP_S) + 2
    rows = lm_data.rows(seed, batch * max_steps, seq, sizes["vocab_size"])

    ctx.mark("rows_made")
    cfg = family.program.model_config(sizes, max_seq=seq, **settings.get("model", {}))
    ctx.mark("program_imported")
    module = family.program.make_module(cfg, sizes, seed, opt)
    tracer = trace_reduce.Tracer(os.path.join(ctx.scratch, "trace")) if trace else None

    class Probe(program.callback_base()):
        def __init__(self) -> None:
            self.losses: List[float] = []
            self.ends: List[float] = []
            self.first_grad: Dict[str, float] = {}
            self.change: Dict[str, float] = {}
            self.t_open = self.t_close = 0.0
            self.trace_at = -1

        def on_train_start(self, trainer, module) -> None:
            ctx.mark("train_start")

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            with trace_reduce.span("bench.callback"):
                # reading the loss waits for the step: the interval is the
                # device's time plus the host's share, not the enqueue
                self.losses.append(float(np.asarray(outputs["loss"])))
                now = time.perf_counter()
                self.ends.append(now)
                k = len(self.ends)
                if k <= warm:
                    ctx.mark(f"step{k}_end")
                if k == 1:
                    self.first_grad = program.first_gradient_norms(trainer, opt["b1"])
                if k == checked - 1:
                    self.change = program.change_norms(trainer, family.weights, sizes, seed)
                if k == warm:
                    ctx.window_opens()
                    self.t_open = time.perf_counter()
                    self.ends[-1] = self.t_open
                elif k > warm:
                    if tracer is not None:
                        if k == warm + 2:
                            tracer.start()
                            self.trace_at = k
                        elif k == self.trace_at + TRACE_STEPS:
                            tracer.stop()
                    if now - self.t_open >= seconds and not self.t_close:
                        if tracer is not None and tracer.path is None and self.trace_at > 0:
                            tracer.stop()
                        self.t_close = now
                        ctx.window_closes()
                        # should_stop is read between epochs only; the batch
                        # loop leaves when max_steps is reached
                        trainer.should_stop = True
                        trainer.max_steps = trainer.global_step + 1

    probe = Probe()
    trainer = program.make_trainer(cell.chips, [probe], ctx.scratch, seed, max_steps)
    trainer.fit(module, train_dataloaders=program.make_loader(rows, batch))
    if not probe.t_close:
        raise RuntimeError(
            f"the job ran out of rows after {len(probe.ends)} steps before "
            f"{seconds}s had passed: a step shorter than {MIN_STEP_S}s")

    steps = sum(1 for t in probe.ends[warm:] if t <= probe.t_close)
    elapsed = probe.t_close - probe.t_open
    tokens_per_step = batch * seq
    rate = steps * tokens_per_step / elapsed
    peak_bytes = ctx.memory_peak_bytes()
    program.release_trainer(trainer, module)
    del trainer, module
    gc.collect()

    t_ref = time.perf_counter()
    first = {"losses": probe.losses, "first_grad": probe.first_grad, "change": probe.change}
    want = reference_numbers(cell, seed, rows, batch)
    check = hold_to_reference(cell, first, want)
    check.note("reference_s", time.perf_counter() - t_ref)
    check.note("setup_marks_s", ctx.marks)
    check.require("loss_fell", probe.losses[-1] < probe.losses[0] and
                  bool(np.all(np.isfinite(probe.losses))),
                  f"{probe.losses[0]:.4f} -> {probe.losses[-1]:.4f}")

    facts: Dict[str, Any] = {
        "train_tokens_per_s": rate,
        "flops_per_token": family.counts.train_flops_per_token(sizes, seq),
        "chips": cell.chips,
        "trace_path": tracer.path if tracer is not None else None,
        "first_steps": dict(first, rows=rows[: checked * batch], batch=batch, reference=want),
    }
    return {
        "attempted": steps, "failed": 0, "check": check,
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": probe.t_open - ctx.t0},
        "facts": facts, "memory_peak_bytes": peak_bytes,
    }


def reference_numbers(cell, seed: int, rows: np.ndarray, batch: int, quant=None):
    """The plain reference follows the job's first steps (all but the last
    with their updates, the last for its loss): the losses, the first
    gradient's norm per leaf, the norm per leaf of the parameters' change
    before the last step."""
    checked = int(cell.traffic["checked_steps"])
    ref = cell.family.reference.TrainReference(
        cell.config, seed, cell.traffic["optimizer"], quant=quant)
    losses, grad = [], {}
    for k in range(checked - 1):
        loss, norms = ref.step(rows[k * batch:(k + 1) * batch])
        losses.append(loss)
        if k == 0:
            grad = norms
    change = ref.change_norms()
    losses.append(ref.loss(rows[(checked - 1) * batch: checked * batch]))
    del ref
    gc.collect()
    return {"losses": losses, "first_grad": grad, "change": change}


def hold_to_reference(cell, got: Dict[str, Any], want: Dict[str, Any]) -> Check:
    """Every number compared, each against its own limit."""
    limits = cell.settings["correct"]["limits"]
    check = Check()
    for k, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        check.hold(f"loss_gap_step{k + 1}", abs(a - b) / abs(b), limits["loss_gap"],
                   f"{a:.6f} vs {b:.6f}")
    gap, where = worst_leaf_gap(got["first_grad"], want["first_grad"])
    check.hold("grad_norm_gap", gap, limits["grad_norm_gap"], where)
    gap, where = worst_leaf_gap(got["change"], want["change"])
    check.hold("change_norm_gap", gap, limits["change_norm_gap"], where)
    return check


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]):
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    if set(got) != set(want):
        raise RuntimeError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    floor = float(np.median(list(want.values())))
    worst, where = 0.0, ""
    for name, ref in want.items():
        gap = abs(got[name] - ref) / max(ref, floor, 1e-30)
        if gap >= worst:
            worst, where = gap, f"{name}: {got[name]:.6g} vs {ref:.6g}"
    return worst, where
