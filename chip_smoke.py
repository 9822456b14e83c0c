#!/usr/bin/env python3
"""Quickest proof that the train and serve main paths start on the chip.

    python chip_smoke.py            # one chip: kernels, train, serve, actor
    python chip_smoke.py --chips 4  # four chips: 4 worker actors vs dp=4

The parent imports no JAX: a chip belongs to one process, so every phase
that needs a fresh owner of the chip is a child of this script, run one
after another. Each child prints one JSON line per phase; the parent's last
line is ``{"ok": true, "device": {...}}`` with the device as JAX reported it
in the children. No chip, a refused kernel or a failed phase is a non-zero
exit and no result line.

Shapes: ``LlamaConfig.small()`` at full width and depth (dim 2048, 16
layers, 16/8 heads, ffn 5632, vocab 32000, seq 2048), random weights and
synthetic tokens from ``--seed``. The phase functions take the config as an
argument so ``tests/test_chip_smoke.py`` can drive them at tiny size on the
CPU; what only a chip can show (platform, Mosaic custom calls) is asserted
in :func:`_child`, not in them.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))

# one child process per entry, in this order; a child runs its phases in turn
ONE_CHIP = ("train", "serve", "serve_spec", "actor")
FOUR_CHIP = ("workers4", "dp4")
CHILD_TIMEOUT_S = 900

# greedy tokens of two bf16 paths may differ only where the reference's top
# logits tie: lm_head's output has bf16 resolution, so a tie is exact (gap
# 0.0, all the chip has shown) or the paths round the top logit one ulp
# apart. bf16 keeps 8 significant bits: ulp(x) = 2**(floor(log2|x|) - 7)
BF16_TIE_ULPS = 2
# loss curves of two data-parallel layouts: the CPU DDP tests' tolerance
# (tests/test_zero.py), and 2 bf16 ulps where bf16 gradients are summed in
# another order
LOSS_RTOL = 1e-4
LOSS_RTOL_BF16 = 2.0 ** -7


class NoChip(RuntimeError):
    pass


# --------------------------------------------------------------------- #
# shared helpers (children only: they import jax)
# --------------------------------------------------------------------- #
def _peak_bytes() -> Optional[int]:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class _CacheWatch:
    """Where this process keeps compiled programs, and what it found there:
    JAX's persistent cache (hits counted from jax.monitoring events) and the
    repo's serialized-executable layer above it."""

    def __init__(self) -> None:
        import jax

        from ray_lightning_tpu.runtime import compile_cache

        self.jax_hits = 0
        self.jax_misses = 0
        self.dir = compile_cache.configure_jax_persistent_cache()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.jax_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.jax_misses += 1

    def facts(self) -> Dict[str, Any]:
        from ray_lightning_tpu.runtime import compile_cache

        stats = compile_cache.get_cache().stats
        return {
            "cache_dir": self.dir,
            "jax_cache_hits": self.jax_hits,
            "jax_cache_misses": self.jax_misses,
            "rltx_disk_hits": stats["disk_hits"],
            "rltx_misses": stats["misses"],
        }


def _make_probe(root: str):
    """A Callback that records, in whichever process runs the fit loop, the
    synced loss and wall time of every step and what that process sees of
    the devices; each rank leaves ``worker_<rank>.json`` under ``root``."""
    import jax
    import numpy as np

    from ray_lightning_tpu.callbacks.base import Callback
    from ray_lightning_tpu.utils.fsio import atomic_writer

    class StepProbe(Callback):
        def __init__(self) -> None:
            self.losses: List[float] = []
            self.step_s: List[float] = []
            self.facts: Dict[str, Any] = {}
            self.first_batch: Any = None  # as the step received it
            self._t = 0.0

        def on_train_start(self, trainer, module) -> None:
            self._t = time.perf_counter()

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            # float() waits for the step: the interval is device time plus
            # the host's share, not the enqueue
            self.losses.append(float(np.asarray(outputs["loss"])))
            now = time.perf_counter()
            self.step_s.append(now - self._t)
            self._t = now
            if batch_idx == 0:
                leaf = jax.tree_util.tree_leaves(trainer.params)[0]
                self.first_batch = trainer.strategy.shard_batch(batch)
                placed = jax.tree_util.tree_leaves(self.first_batch)[0]
                self.facts = {
                    "rank": trainer.global_rank,
                    "platform": jax.devices()[0].platform,
                    "local_devices": jax.local_device_count(),
                    "global_devices": jax.device_count(),
                    "local_device_ids": [d.id for d in jax.local_devices()],
                    "param_device_ids": sorted(
                        d.id for d in leaf.sharding.device_set
                    ),
                    "batch_device_ids": sorted(
                        d.id for d in placed.sharding.device_set
                    ),
                }

        def on_train_end(self, trainer, module) -> None:
            record = dict(
                self.facts, losses=self.losses, step_s=self.step_s,
                peak_bytes=_peak_bytes(),
            )
            path = os.path.join(root, f"worker_{trainer.global_rank}.json")
            with atomic_writer(path, "w") as f:
                json.dump(record, f)

    return StepProbe()


def _custom_calls(lowered) -> int:
    """Mosaic kernels in a lowered program: 0 means the reference branch or
    interpret mode was taken."""
    return lowered.as_text().count("tpu_custom_call")


def _read_workers(root: str, n: int) -> List[Dict[str, Any]]:
    out = []
    for rank in range(n):
        with open(os.path.join(root, f"worker_{rank}.json")) as f:
            out.append(json.load(f))
    return out


def _check_losses(losses: Sequence[float]) -> None:
    import numpy as np

    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def _fit(cfg, strategy, *, batch: int, steps: int, seed: int, root: str,
         replicas: int = 1, capture_step: bool = False):
    """One ``Trainer.fit`` of ``LlamaModule`` on synthetic tokens, through the
    entry points a user calls; ``batch`` is one loader's, of which
    ``replicas`` processes each run one. Returns (trainer, module, compiled
    step)."""
    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.models.llama import LlamaModule, SyntheticLMDataModule

    os.makedirs(root, exist_ok=True)
    module = LlamaModule(cfg, warmup_steps=2, total_steps=max(steps, 4))
    data = SyntheticLMDataModule(
        cfg, batch_size=batch, n_train=batch * replicas * steps, n_val=batch,
        seed=seed,
    )
    trainer = rlt.Trainer(
        strategy=strategy,
        max_epochs=1,
        max_steps=steps,
        check_val_every_n_epoch=2,  # one epoch: no validation program
        callbacks=[_make_probe(root)],
        enable_checkpointing=False,
        logger=False,
        default_root_dir=root,
        seed=seed,
    )
    built: Dict[str, Any] = {}
    if capture_step:
        # the same hook tests/test_parallel3d.py uses to reach the step
        orig = trainer._build_train_step
        trainer._build_train_step = lambda: built.setdefault("step", orig())
    trainer.fit(module, datamodule=data)
    return trainer, module, built.get("step")


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def phase_kernels(seq: int = 512, head_dim: int = 128,
                  vocab: int = 32000) -> Dict[str, Any]:
    """The flash kernels against the einsum reference on this backend:
    forward with GQA, both backward kernels, and the sliding-window band
    (128-blocks, window not block-aligned, so skipped, partial and full
    blocks all occur). bf16 inputs; the reference runs in float32 at the
    highest matmul precision. Then the sampler kernel of every decode
    program against ``jnp.argmax`` / ``jax.random.categorical``, bitwise."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.attention import attention, reference_attention
    from ray_lightning_tpu.ops.paged_attention import fused_sample

    def inputs(seed, b, hq, hkv):
        kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
        return (
            jax.random.normal(kq, (b, hq, seq, head_dim), jnp.bfloat16),
            jax.random.normal(kk, (b, hkv, seq, head_dim), jnp.bfloat16),
            jax.random.normal(kv, (b, hkv, seq, head_dim), jnp.bfloat16),
        )

    def ref(window):
        def fn(q, k, v):
            with jax.default_matmul_precision("highest"):
                return reference_attention(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True, window=window,
                )
        return fn

    def flash(window, blocks):
        def fn(q, k, v):
            return attention(
                q, k, v, causal=True, impl="flash", window=window,
                block_q=blocks, block_k=blocks,
            ).astype(jnp.float32)
        return fn

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    facts: Dict[str, Any] = {}
    for name, window, blocks, seed in (
        ("dense", None, None, 0), ("window", 96, 128, 2),
    ):
        q, k, v = inputs(seed, 2, 4, 2)
        out = jax.jit(flash(window, blocks))(q, k, v)
        want = ref(window)(q, k, v)
        err = float(jnp.max(jnp.abs(out - want)))
        if not err < 3e-2:  # bf16 inputs and output: ~1e-2 rounding floor
            raise AssertionError(f"flash forward ({name}) off by {err}")
        facts[f"fwd_err_{name}"] = err
        got = jax.jit(jax.grad(loss(flash(window, blocks)), argnums=(0, 1, 2)))(
            q, k, v
        )
        exp = jax.grad(loss(ref(window)), argnums=(0, 1, 2))(q, k, v)
        rel = max(
            float(
                jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                / (jnp.max(jnp.abs(b)) + 1e-6)
            )
            for a, b in zip(got, exp)
        )
        if not rel < 4e-2:
            raise AssertionError(f"flash backward ({name}) off by {rel}")
        facts[f"bwd_rel_err_{name}"] = rel

    # logits with bf16 resolution, as lm_head leaves them: ties are common,
    # so the first-max tie-break is part of what is compared
    key = jax.random.key(3)
    logits32 = jax.random.normal(key, (8, vocab), jnp.bfloat16).astype(jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        logits = logits32.astype(dtype)
        name = jnp.dtype(dtype).name
        greedy = jax.jit(lambda x, k: fused_sample(x, k, 0.0))(logits, key)
        if not bool(jnp.all(greedy == jnp.argmax(logits, axis=-1))):
            raise AssertionError(f"sampler kernel ({name}) is not jnp.argmax")
        drawn = jax.jit(lambda x, k: fused_sample(x, k, 0.8))(logits, key)
        same = bool(jnp.all(drawn == jax.random.categorical(key, logits / 0.8)))
        # bf16: XLA may keep the noise and the sum in excess precision where
        # the kernel rounds them to bf16, so equality there is a fact of
        # the backend, reported and not required
        if not same and dtype == jnp.float32:
            raise AssertionError("sampler kernel (float32) is not categorical")
        facts[f"sampler_temperature_equal_{name}"] = same
    return facts


def phase_train(cfg, *, batch: int, steps: int, seed: int, root: str,
                strategy=None) -> Dict[str, Any]:
    """In-process ``XLAStrategy`` fit; loss finite and falling, the step
    compiled exactly once."""
    import numpy as np

    import ray_lightning_tpu as rlt

    trainer, module, step = _fit(
        cfg, strategy or rlt.XLAStrategy(), batch=batch, steps=steps,
        seed=seed, root=root, capture_step=True,
    )
    me = _read_workers(root, 1)[0]
    _check_losses(me["losses"])
    if step._cache_size() != 1:
        raise AssertionError(
            f"train step resolved {step._cache_size()} executables, want 1"
        )
    # the step lowered again on the state the fit left: its Pallas kernels
    # show as tpu_custom_call whether the executable was compiled here or
    # loaded from the cache
    lowered = step.lower(
        trainer._params, trainer._opt_state,
        next(cb.first_batch for cb in trainer.callbacks if hasattr(cb, "first_batch")),
        trainer._rng_root, np.int32(trainer.global_step),
    )
    return {
        "params_millions": round(cfg.num_params() / 1e6, 1),
        "batch": batch,
        "seq": cfg.max_seq,
        "losses": me["losses"],
        "compile_s": me["step_s"][0],
        "step_s": me["step_s"][1:],
        "step_compilations": step._cache_size(),
        "custom_calls": _custom_calls(lowered),
        "param_device_ids": me["param_device_ids"],
        "batch_device_ids": me["batch_device_ids"],
        "peak_bytes": me["peak_bytes"],
    }


def _prompt(length: int, vocab: int, seed: int) -> List[int]:
    """A short motif repeated, so prompt-lookup speculation finds matches."""
    import numpy as np

    motif = np.random.default_rng(seed).integers(1, vocab, size=4)
    return [int(motif[i % 4]) for i in range(length)]


def phase_serve(cfg, *, speculate_k: int,
                prompt_lens: Sequence[int], max_new: int, seed: int,
                **engine_kwargs) -> Dict[str, Any]:
    """``InferenceEngine`` with the defaults of this backend, a few requests
    of mixed prompt lengths through ``submit()``; tokens equal to
    ``generate(temperature=0.0)`` on the same weights (in bf16, where they
    part, greedy under the teacher-forced reference up to a tie:
    :func:`greedy_under_reference`), one prefill compilation a rung of the
    engine's prefill lengths and one decode compilation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.generation import generate
    from ray_lightning_tpu.models.llama import init_params
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    params = init_params(jax.random.key(seed), cfg)
    prompts = [
        _prompt(n, cfg.vocab_size, seed + i) for i, n in enumerate(prompt_lens)
    ]
    t0 = time.perf_counter()
    wants = [
        np.asarray(
            generate(params, jnp.asarray([p], jnp.int32), cfg, max_new,
                     temperature=0.0)
        )[0, len(p):].tolist()
        for p in prompts
    ]
    reference_s = time.perf_counter() - t0

    engine = InferenceEngine(
        params, cfg,
        EngineConfig(speculate_k=speculate_k, **engine_kwargs),
    )
    t0 = time.perf_counter()
    warm = engine.warmup()  # prefill resolved at each of its lengths, and decode
    compile_s = time.perf_counter() - t0
    engine.start()
    try:
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
        gots = [h.result(timeout=600) for h in handles]
        serve_s = time.perf_counter() - t0
    finally:
        engine.shutdown()
    compiles = engine.compile_stats()
    if compiles != warm:
        raise AssertionError(f"serving programs recompiled: {warm} -> {compiles}")
    if any(len(g) != max_new for g in gots):
        raise AssertionError(f"short completions: {[len(g) for g in gots]}")

    facts: Dict[str, Any] = {
        "speculate_k": speculate_k,
        "prompt_lens": list(prompt_lens),
        "max_new": max_new,
        "tokens_equal_generate": gots == wants,
        "compile_s": compile_s,
        "serve_s": serve_s,
        "reference_s": reference_s,
        "compile_stats": compiles,
        "custom_calls": {
            name: _custom_calls(fn.lower(*args))
            for name, fn, args in engine._program_specs()
        },
        "decode_steps": engine.stats["decode_steps"],
        "accepted_tokens": engine.stats["accepted_tokens"],
        "peak_bytes": _peak_bytes(),
    }
    if gots != wants:
        # The engine's prefill is padded to max_prompt_len and its decode
        # kernels sum in another order than generate()'s, so in bf16 a tie
        # may fall the other way and the streams part there.
        if cfg.dtype != jnp.bfloat16:
            raise AssertionError(f"tokens differ from generate(): {gots} vs {wants}")
        facts["first_divergence"] = [
            next((i for i, (a, b) in enumerate(zip(g, w)) if a != b), None)
            for g, w in zip(gots, wants)
        ]
        facts["tie_tolerance_ulps"] = BF16_TIE_ULPS
        facts.update(greedy_under_reference(params, cfg, prompts, gots))
    return facts


def greedy_under_reference(params, cfg, prompts, streams) -> Dict[str, Any]:
    """Teacher-force the reference forward over each prompt + stream: every
    stream token must be an argmax of the reference's logits at its position
    up to ``BF16_TIE_ULPS`` bf16 ulps of the top logit. A runner-up that
    does not tie with the top fails. Returns the worst gap seen."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.llama import forward

    width = max(len(p) + len(g) for p, g in zip(prompts, streams))
    fwd = jax.jit(lambda p, t: forward(p, t, cfg)[0])
    worst = worst_ulps = 0.0
    for r, (p, g) in enumerate(zip(prompts, streams)):
        row = np.zeros((1, width), np.int32)
        row[0, : len(p) + len(g)] = list(p) + list(g)
        logits = np.asarray(fwd(params, jnp.asarray(row)), np.float32)[0]
        for i, tok in enumerate(g):
            at = logits[len(p) - 1 + i]
            top = float(at.max())
            gap = top - float(at[tok])
            ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 2.0 ** -126))) - 7)
            if gap > BF16_TIE_ULPS * ulp:
                raise AssertionError(
                    f"request {r} token {i} ({tok}) is not greedy under the "
                    f"reference: logit {float(at[tok])} vs top {top} "
                    f"({gap / ulp:.1f} bf16 ulps > {BF16_TIE_ULPS})"
                )
            worst, worst_ulps = max(worst, gap), max(worst_ulps, gap / ulp)
    return {"worst_logit_gap": worst, "worst_logit_gap_ulps": worst_ulps}


def phase_workers(cfg, *, num_workers: int, batch: int, steps: int, seed: int,
                  root: str, **strategy_kwargs) -> Dict[str, Any]:
    """``RayStrategy`` fit, one actor process per chip; ``batch`` is each
    worker's. Every worker must see one local device and ``num_workers``
    global ones, parameters and batch must span all of them, and rank-0
    weights must come back to this (driver) process."""
    import jax
    import numpy as np
    from jax._src import xla_bridge

    import ray_lightning_tpu as rlt

    driver_clean = not xla_bridge.backends_are_initialized()
    _, module, _ = _fit(
        cfg,
        rlt.RayStrategy(num_workers=num_workers, use_tpu=True, **strategy_kwargs),
        batch=batch, steps=steps, seed=seed, root=root, replicas=num_workers,
    )
    workers = _read_workers(root, num_workers)
    for w in workers:
        if (w["local_devices"], w["global_devices"]) != (1, num_workers):
            raise AssertionError(f"worker {w['rank']} sees {w}")
        if len(w["param_device_ids"]) != num_workers:
            raise AssertionError(f"params not on every chip: {w}")
        if len(w["batch_device_ids"]) != num_workers:
            raise AssertionError(f"batch not on every chip: {w}")
    owned = sorted(i for w in workers for i in w["local_device_ids"])
    if len(set(owned)) != num_workers:
        raise AssertionError(f"workers share a device: {owned}")
    _check_losses(workers[0]["losses"])
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(module.params)]
    if not leaves or not all(np.all(np.isfinite(x.astype(np.float32))) for x in leaves):
        raise AssertionError("rank-0 weights did not come back finite")
    return {
        "num_workers": num_workers,
        "global_batch": batch * num_workers,
        "worker_platforms": [w["platform"] for w in workers],
        "local_devices": [w["local_devices"] for w in workers],
        "global_devices": [w["global_devices"] for w in workers],
        "owned_device_ids": owned,
        "param_device_ids": workers[0]["param_device_ids"],
        "batch_device_ids": workers[0]["batch_device_ids"],
        "losses": workers[0]["losses"],
        "compile_s": workers[0]["step_s"][0],
        "step_s": workers[0]["step_s"][1:],
        "peak_bytes": [w["peak_bytes"] for w in workers],
        "weights_returned": len(leaves),
        # a driver that had a backend before the fit cannot tell (tests);
        # one that had none must still have none: the chip is the workers'
        "driver_backend_free": (
            not xla_bridge.backends_are_initialized() if driver_clean else None
        ),
    }


def phase_dp(cfg, *, dp: int, batch: int, steps: int, seed: int, root: str,
             compare_with: Optional[Sequence[float]] = None) -> Dict[str, Any]:
    """The in-process twin of :func:`phase_workers`: one process, a
    ``dp``-wide mesh, the same seed and global batch. With ``compare_with``
    the two loss curves must agree."""
    import jax.numpy as jnp
    import numpy as np

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.parallel.mesh import MeshSpec

    facts = phase_train(
        cfg, batch=batch, steps=steps, seed=seed, root=root,
        strategy=rlt.XLAStrategy(
            devices=dp, mesh_spec=MeshSpec(axes={"dp": dp})
        ),
    )
    for key in ("param_device_ids", "batch_device_ids"):
        if len(facts[key]) != dp:
            raise AssertionError(f"{key} do not span {dp} devices: {facts[key]}")
    if compare_with is not None:
        mine, theirs = np.asarray(facts["losses"]), np.asarray(compare_with)
        rel = float(np.max(np.abs(mine - theirs) / np.abs(theirs)))
        facts["loss_max_rel_diff"] = rel
        # said on this line, not loosened silently: which tolerance held
        if rel <= LOSS_RTOL:
            facts["loss_rtol"] = LOSS_RTOL
        elif cfg.dtype == jnp.bfloat16 and rel <= LOSS_RTOL_BF16:
            facts["loss_rtol"] = LOSS_RTOL_BF16
        else:
            raise AssertionError(
                f"loss curves differ by {rel}: {mine.tolist()} vs "
                f"{theirs.tolist()}"
            )
    return facts


# --------------------------------------------------------------------- #
# child: one fresh owner of the chip
# --------------------------------------------------------------------- #
def _emit(phase: str, t0: float, facts: Dict[str, Any]) -> None:
    line = {"phase": phase, "ok": True,
            "seconds": round(time.perf_counter() - t0, 2)}
    line.update(facts)
    print(json.dumps(line), flush=True)


def _require_tpu(count: Optional[int] = None) -> Dict[str, Any]:
    """The device as JAX reports it; no TPU (or too few) is NoChip."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu":
        raise NoChip(
            f"no chip found: JAX reports platform {device['platform']!r}"
        )
    if count is not None and device["count"] != count:
        raise NoChip(f"need {count} chips, JAX reports {device['count']}")
    return device


def _require_host_chips(count: int) -> None:
    """For children whose workers own the chip: ask the filesystem, not
    JAX — a driver that initialised a backend would hold the chip."""
    from ray_lightning_tpu import runtime as rt

    found = rt.local_tpu_chips()
    if found < count:
        raise NoChip(f"no chip found: this host offers {found} TPU chip(s), "
                     f"need {count}")


def _need_kernels(phase: str, custom_calls: int) -> None:
    if custom_calls <= 0:
        raise AssertionError(
            f"{phase}: no tpu_custom_call in the compiled program — the "
            "reference branch or interpret mode was taken"
        )


def _child(args: argparse.Namespace) -> int:
    os.chdir(HERE)
    from ray_lightning_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.small()
    phase, seed = args.phase, args.seed
    root = tempfile.mkdtemp(prefix=f"chip_smoke_{phase}_")
    t0 = time.perf_counter()

    if phase == "train":
        device = _require_tpu()
        cache = _CacheWatch()
        _emit("kernels", t0, dict(phase_kernels(), device=device))
        t0 = time.perf_counter()
        facts = phase_train(cfg, batch=8, steps=8, seed=seed, root=root)
        _need_kernels(phase, facts["custom_calls"])
        _emit(phase, t0, dict(facts, **cache.facts(), device=device))

    elif phase in ("serve", "serve_spec"):
        # the one-token decode program, then the verify program at k = 4
        device = _require_tpu()
        cache = _CacheWatch()
        spec = phase == "serve_spec"
        facts = phase_serve(
            cfg,
            speculate_k=4 if spec else 0,
            # two rungs of prefill (256, 512), a prompt on each
            prompt_lens=(7, 40, 300, 40), max_new=12, seed=seed,
            num_slots=8, max_prompt_len=512, max_len=2048,
        )
        _need_kernels(phase + " prefill", facts["custom_calls"]["serve_prefill"])
        _need_kernels(phase + " decode", facts["custom_calls"]["serve_decode"])
        facts.update(cache.facts())
        if spec and not facts["jax_cache_hits"] + facts["rltx_disk_hits"]:
            raise AssertionError(
                f"no compile-cache hit in {facts['cache_dir']}: the serve "
                "child compiled the same generate() programs before this one"
            )
        _emit(phase, t0, dict(facts, device=device))

    elif phase == "actor":
        _require_host_chips(1)
        facts = phase_workers(
            LlamaConfig.tiny(), num_workers=1, batch=8, steps=2, seed=seed,
            root=root,
        )
        _check_worker_phase(facts)
        _emit(phase, t0, facts)

    elif phase == "workers4":
        _require_host_chips(4)
        facts = phase_workers(
            cfg, num_workers=4, batch=8, steps=4, seed=seed, root=root,
        )
        _check_worker_phase(facts)
        _emit(phase, t0, facts)

    elif phase == "dp4":
        device = _require_tpu(4)
        cache = _CacheWatch()
        facts = phase_dp(
            cfg, dp=4, batch=32, steps=4, seed=seed, root=root,
            compare_with=json.loads(args.compare_with),
        )
        _need_kernels(phase, facts["custom_calls"])
        _emit(phase, t0, dict(facts, **cache.facts(), device=device))

    else:
        raise ValueError(f"unknown phase {phase!r}")
    return 0


def _check_worker_phase(facts: Dict[str, Any]) -> None:
    if set(facts["worker_platforms"]) != {"tpu"}:
        raise NoChip(f"no chip found: workers ran on {facts['worker_platforms']}")
    if facts["driver_backend_free"] is not True:
        raise AssertionError("the driver initialised a JAX backend of its own")


# --------------------------------------------------------------------- #
# parent: no JAX here
# --------------------------------------------------------------------- #
def _run_child(phase: str, args: argparse.Namespace,
               extra: Sequence[str] = ()) -> List[Dict[str, Any]]:
    """Run one phase child to its end; its output passes through. Returns
    its JSON phase lines; raises on a non-zero exit or a timeout."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed), *extra]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in (env.get("PYTHONPATH"),) if p]
    )
    proc = subprocess.Popen(
        cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        start_new_session=True,  # so a timeout can stop its actors too
    )
    lines: List[Dict[str, Any]] = []
    # a thread, so the read loop below stays a plain blocking read
    alarm = threading.Timer(CHILD_TIMEOUT_S, _kill_group, (proc,))
    alarm.daemon = True
    alarm.start()
    try:
        for raw in proc.stdout:
            sys.stdout.write(raw)
            sys.stdout.flush()
            if raw.startswith("{"):
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "phase" in rec:
                    lines.append(rec)
        rc = proc.wait()
    finally:
        timed_out = not alarm.is_alive()  # it ran: the child was killed
        alarm.cancel()
        _kill_group(proc)  # whatever the child left running
    if timed_out:
        raise RuntimeError(f"phase {phase}: no end after {CHILD_TIMEOUT_S}s")
    if rc != 0:
        raise RuntimeError(f"phase {phase}: exit code {rc}")
    if not lines:
        raise RuntimeError(f"phase {phase}: exit code 0 but no phase line")
    return lines


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _parent(args: argparse.Namespace) -> int:
    device = None
    try:
        if args.chips == 4:
            ray = _run_child("workers4", args)[-1]
            dp = _run_child(
                "dp4", args, ["--compare-with", json.dumps(ray["losses"])]
            )[-1]
            device = dp["device"]
        else:
            for phase in ONE_CHIP:
                for rec in _run_child(phase, args):
                    device = device or rec.get("device")
    except RuntimeError as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    if not device or device.get("platform") != "tpu":
        print(f"chip_smoke: FAILED: phases ran on {device}, not on a chip",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-worker fit and its dp=4 twin")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=ONE_CHIP + FOUR_CHIP,
                    help=argparse.SUPPRESS)
    ap.add_argument("--compare-with", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not args.phase:
        return _parent(args)
    try:
        return _child(args)
    except NoChip as err:
        print(f"chip_smoke: {err}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
