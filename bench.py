"""Benchmark: flagship decoder-LM training throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

Metric is the north-star from BASELINE.json — LightningModule tokens/sec/chip
on a full training step (fwd + bwd + adamw, bf16, remat, flash attention).
The reference publishes no numbers (BASELINE.md), so vs_baseline is measured
MFU relative to the 40% MFU target BASELINE.md sets for the stretch config.

It measures on a chip or not at all: the bench child exits non-zero when JAX
reports no TPU, and so does this script when the child, or any phase inside
it, fails. There is no CPU run of the headline metric and no stored number
served in place of a live one.

  orchestrator (this process, never imports jax: a chip belongs to one
  process, and the child needs it)
    └─ bench child (--_child): the measurement, RLT_BENCH_TIMEOUT (1800 s)

The bench child does ALL on-chip work in ONE process: flash block-size
autotune (an attention fwd+bwd microbench retraced per config — block
sizes are static args), a matmul-ceiling measurement the kernel is
compared against, then the training measurement. RLT_BENCH_AUTOTUNE=0
disables the in-child sweeps; explicit RLT_FLASH_BLOCK_Q/K pins win
outright. The child also sweeps remat_policy ("nothing" vs "dots" — the
HBM-vs-FLOPs trade) on a short train-step window and keeps the winner;
RLT_BENCH_REMAT_SWEEP=0 disables just that sweep.

Detail sweeps (detail.input_pipeline, detail.serving, ... — one child each,
see the ``_attach_*`` functions) still pin the virtual CPU backend and
report counts and CPU timings labeled as such; ROADMAP S1 replaces them
with cells that run on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time


def _env_timeout(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _measure_matmul_ceiling(jnp, jax) -> float:
    """Achieved bf16 matmul TFLOPs on a big square — the practical MXU
    ceiling the flash kernel is judged against."""
    n = 4096
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    f(a, b).block_until_ready()
    t0 = time.perf_counter()
    reps = 10
    out = a
    for _ in range(reps):
        out = f(out, b)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    return 2.0 * n * n * n * reps / dt / 1e12


def _should_autotune(environ) -> bool:
    """Autotune gate: RLT_BENCH_AUTOTUNE=0 disables, and explicit
    RLT_FLASH_BLOCK_Q/K pins win outright (no sweep)."""
    return (
        environ.get("RLT_BENCH_AUTOTUNE", "1") != "0"
        and "RLT_FLASH_BLOCK_Q" not in environ
        and "RLT_FLASH_BLOCK_K" not in environ
    )


# (block_q, block_k) the autotune times; tests/test_tpu_compile.py compiles
# each for the chip at small's shapes
FLASH_BLOCK_CANDIDATES = ((512, 512), (512, 256), (256, 512), (256, 256))


def _autotune_flash(jax, jnp, cfg, batch, seq):
    """Time attention fwd+bwd per (block_q, block_k) in THIS process (each
    config is a retrace — block sizes are static args). Returns a note dict
    {picked: "BQxBK", fwd_bwd_ms_by_block, fwd_tflops} or None when no
    candidate divides the sequence. Far cheaper than recompiling the full
    train step per config, and no helper process: the chip has one owner.
    A candidate the compiler or the chip refuses fails the child: every
    candidate is a block shape the kernels claim to support."""
    from ray_lightning_tpu.ops.attention import attention

    # shapes must mirror the training step's kernel exactly — including
    # GQA (n_kv_heads), or the sweep tunes a kernel the model never runs
    B, H, HKV, D = batch, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(kq, (B, H, seq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, HKV, seq, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, HKV, seq, D), jnp.bfloat16)

    def attn_loss(q, k, v, bq, bk):
        out = attention(q, k, v, causal=True, impl="flash",
                        block_q=bq, block_k=bk)
        return jnp.sum(out.astype(jnp.float32))

    grad_fn = jax.jit(
        jax.grad(attn_loss, argnums=(0, 1, 2)), static_argnums=(3, 4)
    )
    tried = {}
    best = None
    for bq, bk in FLASH_BLOCK_CANDIDATES:
        if seq % bq or seq % bk:
            continue
        out = grad_fn(q, k, v, bq, bk)
        jax.block_until_ready(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(3):
            out = grad_fn(q, k, v, bq, bk)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 3
        tried[f"{bq}x{bk}"] = round(dt * 1e3, 3)
        if best is None or dt < best[2]:
            best = (bq, bk, dt)
    if best is None:
        return None
    # kernel-vs-ceiling: fwd-only achieved TFLOPs with the winning blocks.
    # causal flash fwd ~ 2*B*H*S^2*D flops (two matmuls, half masked off)
    fwd = jax.jit(
        lambda q, k, v: attention(q, k, v, causal=True, impl="flash",
                                  block_q=best[0], block_k=best[1]),
    )
    fwd(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        o = fwd(q, k, v)
    o.block_until_ready()
    fwd_dt = (time.perf_counter() - t0) / 5
    fwd_tflops = 2.0 * B * H * seq * seq * D / fwd_dt / 1e12
    return {
        "picked": f"{best[0]}x{best[1]}",
        "fwd_bwd_ms_by_block": tried,
        "fwd_tflops": round(fwd_tflops, 2),
    }


def _child(args: argparse.Namespace) -> int:
    """Child: run the measurement and print one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dataclasses import replace

    from ray_lightning_tpu.callbacks.throughput import detect_peak_tflops
    from ray_lightning_tpu.models.llama import (
        LlamaConfig,
        init_params,
        lm_loss,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench: no chip found: JAX reports platform {dev.platform!r}",
            file=sys.stderr,
        )
        return 3
    preset = args.preset
    cfg = getattr(LlamaConfig, preset)()
    # small: ~5.3 GB bf16 params+adam, so batch 8 x seq 2048 fills a v5e's
    # 16 GB HBM without flirting with OOM
    batch = args.batch or {"small": 8}.get(preset, 16)
    seq = cfg.max_seq

    autotune_note = None
    matmul_ceiling = None
    if _should_autotune(os.environ):
        matmul_ceiling = round(_measure_matmul_ceiling(jnp, jax), 2)
        autotune_note = _autotune_flash(jax, jnp, cfg, batch, seq)
        if autotune_note:
            bq, bk = (int(x) for x in autotune_note["picked"].split("x"))
            cfg = replace(cfg, flash_block_q=bq, flash_block_k=bk)
            autotune_note["fwd_vs_matmul_ceiling"] = round(
                autotune_note["fwd_tflops"] / max(matmul_ceiling, 1e-9), 3
            )

    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)

    def make_step(step_cfg):
        def train_step(params, opt_state, tokens):
            (loss, _), grads = jax.value_and_grad(
                lambda p: lm_loss(p, tokens, step_cfg), has_aux=True
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(train_step, donate_argnums=(0, 1))

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        jnp.int32,
    )

    # remat policy is the other big MFU lever (HBM-vs-FLOPs): time one
    # short window per policy and keep the winner. Gated independently of
    # the flash sweep (RLT_FLASH_BLOCK pins must not silently disable
    # this one). A policy that does not fit the chip's memory is a result
    # of the sweep; any other failure fails the bench.
    remat_note = None
    step = None
    if (
        cfg.remat
        and os.environ.get("RLT_BENCH_AUTOTUNE", "1") != "0"
        and os.environ.get("RLT_BENCH_REMAT_SWEEP", "1") != "0"
    ):
        timed = {}
        steps_by_policy = {}
        for policy in ("nothing", "dots"):
            p = s = None
            try:
                pcfg = replace(cfg, remat_policy=policy)
                pstep = make_step(pcfg)
                p = init_params(jax.random.key(0), pcfg)
                s = tx.init(p)
                p, s, _ = pstep(p, s, tokens)  # compile + warm
                jax.block_until_ready(jax.tree_util.tree_leaves(p)[0])
                t0 = time.perf_counter()
                for _ in range(3):
                    p, s, loss_ = pstep(p, s, tokens)
                float(loss_)
                timed[policy] = round((time.perf_counter() - t0) / 3 * 1e3, 2)
                steps_by_policy[policy] = pstep
            except jax.errors.JaxRuntimeError as exc:
                if "RESOURCE_EXHAUSTED" not in str(exc):
                    raise
                timed[policy] = "does not fit"
            finally:
                # sweep leftovers must not double the params+opt HBM peak
                # under the real measurement
                del p, s
        ok = {k: v for k, v in timed.items() if isinstance(v, float)}
        picked = min(ok, key=ok.get)
        cfg = replace(cfg, remat_policy=picked)
        step = steps_by_policy[picked]  # reuse the compiled winner
        remat_note = {"picked": picked, "step_ms_by_policy": timed}

    params = init_params(jax.random.key(0), cfg)
    opt_state = tx.init(params)
    if step is None:
        step = make_step(cfg)

    # the first warmup step pays the XLA compile (unless the remat sweep
    # already compiled the winner) — reported as detail.compile_ms so a
    # compile-time regression is visible next to the steady-state number
    compile_ms = None
    for i in range(args.warmup):
        if i == 0:
            t_compile = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        if i == 0:
            float(loss)
            compile_ms = round((time.perf_counter() - t_compile) * 1e3, 2)
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    final_loss = float(loss)  # forces completion of the whole chain
    elapsed = time.perf_counter() - t0

    # per-step distribution for the observability report: a handful of
    # fully-synced steps (float(loss) blocks) so p50/p90 are honest device
    # times, not async-dispatch enqueue times. Kept small — the throughput
    # number above stays the pipelined measurement.
    from ray_lightning_tpu.observability.aggregator import step_time_stats

    step_times = []
    for _ in range(min(args.steps, 8)):
        ts = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        float(loss)
        step_times.append(time.perf_counter() - ts)
    step_dist = step_time_stats({0: step_times})

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * args.steps / elapsed
    flops_per_token = cfg.flops_per_token()
    achieved_tflops = tokens_per_sec * flops_per_token / 1e12
    peak = detect_peak_tflops()
    mfu = achieved_tflops / peak
    # vs_baseline is MFU against the 40% BASELINE.md target
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": {
            "preset": preset,
            "params_millions": round(cfg.num_params() / 1e6, 1),
            "batch": batch,
            "seq": seq,
            "steps": args.steps,
            "step_time_ms": round(elapsed / args.steps * 1e3, 2),
            "achieved_tflops_per_chip": round(achieved_tflops, 2),
            "mfu": round(mfu, 4),
            "peak_tflops_assumed": peak,
            "final_loss": round(final_loss, 4),
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", "?"),
            "compile_ms": compile_ms,
            **step_dist,
        },
    }
    from ray_lightning_tpu.observability import metrics as _obs_metrics

    devmem = _obs_metrics.device_memory_stats()
    if devmem:
        result["detail"]["hbm_peak_bytes"] = max(
            d.get("peak_bytes", 0) for d in devmem
        )
    if matmul_ceiling is not None:
        result["detail"]["matmul_ceiling_tflops_measured"] = matmul_ceiling
    if autotune_note:
        result["detail"]["flash_autotune"] = autotune_note
    if remat_note:
        result["detail"]["remat_sweep"] = remat_note
    # analytic cost accounting of the compiled step: FLOPs/bytes from XLA
    # itself (vs the hand-derived flops_per_token above), collective byte
    # volumes, and a roofline verdict. A second compile of the step;
    # RLT_BENCH_COST=0 skips it.
    if os.environ.get("RLT_BENCH_COST", "1") != "0":
        from ray_lightning_tpu.observability import profiler as _profiler

        rep = _profiler.analyze_jitted(
            step, params, opt_state, tokens, program="bench_train_step"
        )
        if rep is not None:
            cost = rep.to_dict()
            cost["roofline"] = _profiler.roofline(
                rep,
                step_time_s=elapsed / args.steps,
                peak_tflops=peak,
            )
            cost["mfu"] = cost["roofline"].get("mfu")
            result["detail"]["cost_analysis"] = cost
    print(json.dumps(result))
    return 0


def _dcn_sweep(args: argparse.Namespace) -> int:
    """Child: the compressed-DCN-collectives sweep (--_dcn_sweep).

    Measures tokens/s of a tiny-LM train step with the standard implicit
    full-precision all-reduce vs the explicit shard_map int8 two-phase
    reduction (parallel/compression.py) on a {dp: N} mesh whose dp axis is
    DECLARED as DCN. Single host, forced-CPU virtual devices: the
    collectives and quantization math are real, the slow cross-slice link
    is not — so the payload-bytes reduction (the quantity DCN actually
    cares about) is reported analytically alongside the measured step
    times, and the whole result is labeled with its platform.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax
    import jax.numpy as jnp
    from dataclasses import replace
    from jax.experimental.shard_map import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params, lm_loss
    from ray_lightning_tpu.parallel.compression import (
        DEFAULT_BLOCK_SIZE,
        payload_bytes,
        two_phase_dcn_reduce,
        with_error_feedback,
    )
    from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh

    n = len(jax.devices())
    if n < 2:
        print(json.dumps({"error": f"dcn sweep needs >= 2 devices, have {n}"}))
        return 0
    mesh = build_mesh(MeshSpec(axes={"dp": n}, dcn_axes=("dp",)))
    cfg = replace(LlamaConfig.tiny(), remat=False)
    seq = cfg.max_seq
    batch = n  # one sequence per emulated slice
    reps = max(1, int(_env_timeout("RLT_BENCH_DCN_STEPS", 5)))
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    params = jax.device_put(
        init_params(jax.random.key(0), cfg), NamedSharding(mesh, P())
    )
    tokens = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
            jnp.int32,
        ),
        NamedSharding(mesh, P("dp")),
    )

    def time_mode(step, state):
        p, s, loss = step(params, state, tokens)  # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(reps):
            p, s, loss = step(p, s, tokens)
        final = float(loss)
        dt = time.perf_counter() - t0
        return batch * seq * reps / dt, final

    # off: GSPMD's implicit full-precision all-reduce over dp
    def plain_step(p, s, toks):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss(q, toks, cfg), has_aux=True
        )(p)
        upd, s = tx.update(grads, s, p)
        return optax.apply_updates(p, upd), s, loss

    off_tps, off_loss = time_mode(jax.jit(plain_step), tx.init(params))

    # on: the trainer's compressed step shape — explicit shard_map
    # collective, int8 wire payload, error feedback stacked over dp
    ctx = optax.chain(
        with_error_feedback(
            two_phase_dcn_reduce((), "dp", n, block_size=DEFAULT_BLOCK_SIZE)
        ),
        tx,
    )
    state0 = ctx.init(params)
    ef0 = jax.tree_util.tree_map(
        lambda r: jax.device_put(
            jnp.zeros((n,) + r.shape, r.dtype), NamedSharding(mesh, P("dp"))
        ),
        state0[0],
    )
    state0 = (ef0,) + tuple(state0[1:])
    ef_spec = jax.tree_util.tree_map(lambda _: P("dp"), state0[0])
    st_spec = (ef_spec,) + tuple(
        jax.tree_util.tree_map(lambda _: P(), s) for s in state0[1:]
    )

    def comp_body(p, s, toks):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss(q, toks, cfg), has_aux=True
        )(p)
        ef_local = jax.tree_util.tree_map(lambda x: x[0], s[0])
        upd, new = ctx.update(grads, (ef_local,) + tuple(s[1:]), p)
        new_ef = jax.tree_util.tree_map(lambda x: x[None], new[0])
        return (
            optax.apply_updates(p, upd),
            (new_ef,) + tuple(new[1:]),
            jax.lax.pmean(loss, "dp"),
        )

    comp_step = jax.jit(
        shard_map(
            comp_body,
            mesh=mesh,
            in_specs=(P(), st_spec, P("dp")),
            out_specs=(P(), st_spec, P()),
            check_rep=False,
        )
    )
    on_tps, on_loss = time_mode(comp_step, state0)

    unc_bytes, comp_bytes = payload_bytes(params, DEFAULT_BLOCK_SIZE)
    # ring all-reduce (or reduce-scatter + all-gather) moves 2(n-1)/n of
    # the payload per device per step; the ratio is payload-independent
    wire = 2.0 * (n - 1) / n
    print(
        json.dumps(
            {
                "platform": "cpu",
                "emulated": True,
                "devices": n,
                "dcn_axis": "dp",
                "block_size": DEFAULT_BLOCK_SIZE,
                "preset": "tiny",
                "steps": reps,
                "tokens_per_sec": {
                    "none": round(off_tps, 1),
                    "int8": round(on_tps, 1),
                },
                "final_loss": {
                    "none": round(off_loss, 4),
                    "int8": round(on_loss, 4),
                },
                "dcn_bytes_per_device_per_step": {
                    "none": round(unc_bytes * wire),
                    "int8": round(comp_bytes * wire),
                },
                "payload_reduction": round(unc_bytes / comp_bytes, 2),
            }
        )
    )
    return 0


def _attach_dcn_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.dcn_compression (the compressed-collectives sweep) to a
    fresh measurement. The sweep child is pinned to the virtual CPU backend
    with 4 forced host devices — it never acquires the chip, so it cannot
    orphan device-side work (the one-process rule in the module docstring
    is about chip acquisition). RLT_BENCH_DCN_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_DCN_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f
        for f in sweep_env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    sweep_env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()
    ok, sweep, serr = _run(
        [sys.executable, here, "--_dcn_sweep"],
        _env_timeout("RLT_BENCH_DCN_TIMEOUT", 600.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "tokens_per_sec" in sweep:
        detail["dcn_compression"] = sweep
    else:
        detail["dcn_compression"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _input_microbench(
    delay_ms: float = 0.0,
    num_workers: int = 0,
    prefetch_depth: int = 0,
    steps: int = 24,
    batch: int = 8,
    dim: int = 256,
) -> dict:
    """Time a small jitted step fed through the input pipeline.

    ``delay_ms`` is injected into collate to emulate a slow host loader
    (decode/IO); ``num_workers=0, prefetch_depth=0`` is the synchronous
    baseline, anything else routes through AsyncLoader + DevicePrefetcher.
    Importable so tests can run the comparison in-process. Returns
    {"steps", "steps_per_sec", "input_starved_ms"}.
    """
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.core.data import DataLoader, RandomDataset, default_collate
    from ray_lightning_tpu.core.prefetch import AsyncLoader, DevicePrefetcher

    delay_s = max(0.0, float(delay_ms)) / 1e3

    def collate(items):
        if delay_s:
            time.sleep(delay_s)
        return default_collate(items)

    @jax.jit
    def step(w, x):
        for _ in range(8):
            x = jnp.tanh(x @ w)
        return w + 1e-4 * jnp.mean(x) * jnp.eye(w.shape[0], dtype=w.dtype), x

    dataset = RandomDataset(dim, steps * batch)
    loader = DataLoader(
        dataset, batch_size=batch, collate_fn=collate, drop_last=True
    )
    w = jnp.eye(dim, dtype=jnp.float32)
    w, out = step(w, jnp.asarray(dataset.data[:batch]))  # compile outside timing
    jax.block_until_ready(out)

    src = (
        AsyncLoader(loader, num_workers=num_workers, prefetch_factor=2)
        if num_workers > 0
        else loader
    )
    pf = DevicePrefetcher(jax.device_put, depth=prefetch_depth)
    n = 0
    t0 = time.perf_counter()
    for _idx, _host, device_batch in pf.iterate(src):
        w, out = step(w, device_batch)
        n += 1
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return {
        "steps": n,
        "steps_per_sec": round(n / max(dt, 1e-9), 2),
        "input_starved_ms": round(pf.starved_s * 1e3, 2),
    }


def _input_sweep(args: argparse.Namespace) -> int:
    """Child: the async-input-pipeline sweep (--_input_sweep).

    Runs the microbench twice — synchronous loading vs AsyncLoader(2
    workers) + DevicePrefetcher(depth 2) — with RLT_BENCH_SLOW_LOADER ms
    of emulated host-loading latency per batch (default 10), and reports
    the speedup plus the starvation counter both ways. CPU-pinned: this
    measures pipeline overlap, not chip FLOPs.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    delay_ms = _env_timeout("RLT_BENCH_SLOW_LOADER", 10.0)
    sync = _input_microbench(delay_ms, num_workers=0, prefetch_depth=0)
    pipelined = _input_microbench(delay_ms, num_workers=2, prefetch_depth=2)
    print(
        json.dumps(
            {
                "platform": "cpu",
                "slow_loader_ms": round(delay_ms, 2),
                "num_workers": 2,
                "prefetch_depth": 2,
                "steps_per_sec": {
                    "sync": sync["steps_per_sec"],
                    "async": pipelined["steps_per_sec"],
                },
                "speedup": round(
                    pipelined["steps_per_sec"] / max(sync["steps_per_sec"], 1e-9), 2
                ),
                "input_starved_ms": {
                    "sync": sync["input_starved_ms"],
                    "async": pipelined["input_starved_ms"],
                },
            }
        )
    )
    return 0


def _attach_input_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.input_pipeline (sync vs async input feeding) and the
    headline detail.input_starved_ms to a fresh measurement. Like the DCN
    sweep the child is CPU-pinned — it never acquires the chip.
    RLT_BENCH_INPUT_SWEEP=0 disables; RLT_BENCH_SLOW_LOADER sets the
    emulated per-batch host latency in ms."""
    if os.environ.get("RLT_BENCH_INPUT_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_input_sweep"],
        _env_timeout("RLT_BENCH_INPUT_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "steps_per_sec" in sweep:
        detail["input_pipeline"] = sweep
        detail["input_starved_ms"] = sweep["input_starved_ms"]["async"]
    else:
        detail["input_pipeline"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _serve_microbench(
    engine,
    rate_rps: float,
    num_requests: int,
    max_new_tokens: int,
    vocab: int,
    seed: int = 0,
) -> dict:
    """Offer ``num_requests`` at ``rate_rps`` to a RUNNING engine and
    report throughput/latency/utilization for that load level.

    Arrival is a fixed 1/rate interarrival (deterministic, so runs are
    comparable); TTFT comes from the engine's own per-completion clock.
    Importable so tests can drive the ramp in-process.
    """
    import numpy as np

    from ray_lightning_tpu.observability.metrics import percentile

    rng = np.random.default_rng(seed)
    interarrival = 1.0 / max(rate_rps, 1e-9)
    decode0 = engine.stats["decode_steps"]
    busy0 = engine.stats["busy_slot_steps"]
    hits0 = engine.pool.kinds["full"].allocator.prefix_hits_total
    misses0 = engine.pool.kinds["full"].allocator.prefix_misses_total
    completions = []
    t0 = time.perf_counter()
    for i in range(num_requests):
        target = t0 + i * interarrival
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        plen = int(rng.integers(3, engine.engine_config.max_prompt_len + 1))
        prompt = [int(t) for t in rng.integers(1, vocab, size=plen)]
        completions.append(
            engine.submit(prompt, max_new_tokens=max_new_tokens)
        )
    for c in completions:
        c.result(timeout=120)
    wall = time.perf_counter() - t0
    ttfts = [c.ttft_s for c in completions if c.ttft_s is not None]
    tokens = sum(len(c.tokens) for c in completions)
    decode_steps = engine.stats["decode_steps"] - decode0
    busy = engine.stats["busy_slot_steps"] - busy0
    num_slots = engine.pool.num_slots
    out = {
        "offered_rps": rate_rps,
        "requests": num_requests,
        "tokens_per_sec": round(tokens / max(wall, 1e-9), 2),
        "ttft_p50_ms": round(percentile(ttfts, 50) * 1e3, 2) if ttfts else None,
        "ttft_p95_ms": round(percentile(ttfts, 95) * 1e3, 2) if ttfts else None,
        "slot_utilization": round(
            busy / max(decode_steps * num_slots, 1), 4
        ),
    }
    alloc = engine.pool.kinds["full"].allocator
    hits = alloc.prefix_hits_total - hits0
    misses = alloc.prefix_misses_total - misses0
    # peak (not instantaneous: the level has drained by now)
    out["block_utilization"] = round(
        alloc.blocks_highwater / max(alloc.capacity, 1), 4
    )
    out["prefix_hit_rate"] = round(hits / max(hits + misses, 1), 4)
    return out


def _serve_chaos_bench(params, cfg) -> dict:
    """RLT_BENCH_SERVE_CHAOS=1: goodput under a sustained replica-kill
    loop. A 2-replica LocalReplicaFleet serves the request batch while
    RLT_BENCH_SERVE_FAULT (default ``replica0:crash@every:8``) keeps
    killing replica 0; the journal retries the orphaned requests on the
    survivor. Reports retries, sheds, relaunches, and completed tokens/s
    under fault ("goodput") — the serving-resilience regression number.
    """
    import numpy as np

    import ray_lightning_tpu.runtime.faults as _faults
    from ray_lightning_tpu.serving.replica import LocalReplicaFleet

    num_requests = int(os.environ.get("RLT_BENCH_SERVE_REQUESTS", "12"))
    prev_fault = os.environ.get("RLT_FAULT")
    os.environ["RLT_FAULT"] = os.environ.get(
        "RLT_BENCH_SERVE_FAULT", "replica0:crash@every:8"
    )
    _faults._serve_cache = None
    # max_prompt_len must fit the RESUME prefill (prompt + tokens already
    # delivered), not just the original prompt: <= 7 prompt + 8 new - 1
    fleet = LocalReplicaFleet(
        lambda: (params, cfg),
        engine_kwargs=dict(num_slots=4, max_prompt_len=16, max_len=32),
        initial_replicas=2,
        max_retries=8,
        breaker_threshold=2,
        breaker_cooldown_s=0.2,
    )
    try:
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        entries = []
        rejected = 0
        for _ in range(num_requests):
            plen = int(rng.integers(3, 8))
            prompt = [
                int(t) for t in rng.integers(1, cfg.vocab_size, size=plen)
            ]
            try:
                entries.append(fleet.submit(prompt, max_new_tokens=8))
            except Exception:
                rejected += 1
        tokens = 0
        completed = 0
        for e in entries:
            try:
                tokens += len(e.result(timeout=120))
                completed += 1
            except Exception:
                pass
        wall = time.perf_counter() - t0
        stats = fleet.stats()
    finally:
        fleet.shutdown()
        if prev_fault is None:
            os.environ.pop("RLT_FAULT", None)
        else:
            os.environ["RLT_FAULT"] = prev_fault
        _faults._serve_cache = None
    return {
        "retries": stats["retries"],
        "shed": stats["shed"] + rejected,
        "relaunches": stats["relaunches"],
        "completed_under_kill": completed,
        "goodput_under_kill": round(tokens / max(wall, 1e-9), 2),
    }


def _serve_sweep(args: argparse.Namespace) -> int:
    """Child: the continuous-batching serving sweep (--_serve_sweep).

    Stands up a tiny float32 engine (4 slots) and ramps offered load
    across RLT_BENCH_SERVE_RATES (default "4,16,64" req/s), reporting
    tokens/s, TTFT p50/p95 and slot utilization at each level. CPU-pinned
    like the other sweeps — this measures the batching/scheduling path,
    not chip FLOPs. RLT_BENCH_SERVE_CHAOS=1 appends the replica-kill-loop
    goodput numbers (see :func:`_serve_chaos_bench`).
    """
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    rates = [
        float(r)
        for r in os.environ.get("RLT_BENCH_SERVE_RATES", "4,64,512").split(",")
        if r.strip()
    ]
    num_requests = int(os.environ.get("RLT_BENCH_SERVE_REQUESTS", "12"))
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = InferenceEngine(
        params,
        cfg,
        EngineConfig(
            num_slots=4, max_prompt_len=8, max_len=32, block_size=8,
        ),
    )
    engine.start()
    try:
        # warmup: compile both programs off the clock (but on this timer —
        # reported as compile_ms next to the steady-state levels)
        t_compile = time.perf_counter()
        engine.submit([1, 2, 3], max_new_tokens=2).result(timeout=120)
        compile_ms = round((time.perf_counter() - t_compile) * 1e3, 2)
        levels = [
            _serve_microbench(
                engine, rate, num_requests,
                max_new_tokens=8, vocab=cfg.vocab_size, seed=i,
            )
            for i, rate in enumerate(rates)
        ]
        compiles = engine.compile_stats()
    finally:
        engine.shutdown(drain=False)
    payload = {
        "platform": "cpu",
        "num_slots": 4,
        "levels": levels,
        "peak_tokens_per_sec": max(
            lvl["tokens_per_sec"] for lvl in levels
        ),
        "compile_stats": compiles,
        "compile_ms": compile_ms,
    }
    if os.environ.get("RLT_BENCH_SERVE_CHAOS", "0") == "1":
        payload.update(_serve_chaos_bench(params, cfg))
    print(json.dumps(payload))
    return 0


def _attach_serve_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.serving (the continuous-batching offered-load ramp)
    to a fresh measurement. CPU-pinned like the DCN/input sweeps — the
    child never acquires the chip. RLT_BENCH_SERVE_SWEEP=0 disables;
    RLT_BENCH_SERVE_RATES / RLT_BENCH_SERVE_REQUESTS shape the ramp.
    RLT_BENCH_SERVE_CHAOS=1 adds
    detail.serving.retries / .shed / .goodput_under_kill from a
    replica-kill-loop run (see _serve_chaos_bench)."""
    if os.environ.get("RLT_BENCH_SERVE_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_serve_sweep"],
        _env_timeout("RLT_BENCH_SERVE_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "levels" in sweep:
        detail["serving"] = sweep
    else:
        detail["serving"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _replay_sweep(args: argparse.Namespace) -> int:
    """Child: the multi-tenant trace-replay sweep (--_replay_sweep).

    Plays the diurnal and flash-crowd presets (seeded, virtual-time
    accelerated) through a tenant-aware 2-replica fleet and reports the
    verdict's headline numbers per preset: goodput fraction, per-tenant
    SLO attainment, and the cross-tenant p95/mean wait ratio — the
    standing fairness regression surface (docs/serving.md). CPU-pinned
    like the other sweeps: this measures scheduling policy, not FLOPs.
    RLT_BENCH_REPLAY_DURATION / RLT_BENCH_REPLAY_SPEED shape the run.
    """
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import (
        LocalReplicaFleet,
        TenantRegistry,
        TenantSpec,
    )
    from ray_lightning_tpu.workloads import diurnal_trace, flash_crowd_trace
    from ray_lightning_tpu.workloads.replay import ReplayDriver

    duration = float(os.environ.get("RLT_BENCH_REPLAY_DURATION", "8"))
    speed = float(os.environ.get("RLT_BENCH_REPLAY_SPEED", "8"))
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mix = {"gold": 4.0, "free": 1.0}
    presets = {
        "diurnal": diurnal_trace(
            duration, 4.0, tenants=mix, seed=0, heavy_tail=True,
            prompt_len=(2, 8), max_new_tokens=4,
        ),
        "flash_crowd": flash_crowd_trace(
            duration, 3.0, crowd_tenant="free", crowd_at_s=duration / 3,
            tenants={"gold": 1.0}, seed=0, heavy_tail=True,
            prompt_len=(2, 8), max_new_tokens=4,
        ),
    }
    payload = {"platform": "cpu", "duration_s": duration, "speed": speed}
    for name, events in presets.items():
        registry = TenantRegistry([
            TenantSpec("gold", tenant_class="guaranteed", weight=4.0),
            TenantSpec("free", tenant_class="best_effort", weight=1.0),
        ])
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=dict(
                num_slots=4, max_prompt_len=8, max_len=32, max_queue=512,
            ),
            initial_replicas=2,
            tenants=registry,
        )
        try:
            verdict = ReplayDriver(
                fleet, events, tenants=registry, speed=speed, seed=0,
                vocab=int(cfg.vocab_size), max_prompt_len=8,
                trace_meta={"generator": name},
            ).run()
        finally:
            fleet.shutdown()
        payload[name] = {
            "events": len(events),
            "passed": verdict["passed"],
            "goodput_fraction": verdict["goodput"]["fraction"],
            "max_wait_ratio": verdict["starvation"]["max_wait_ratio"],
            "slo_attainment": {
                t: row.get("slo_attainment")
                for t, row in verdict["tenants"].items()
            },
        }
    print(json.dumps(payload))
    return 0


def _attach_replay_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.replay (the multi-tenant trace-replay fairness
    sweep) to a fresh measurement. RLT_BENCH_REPLAY_SWEEP=0 disables;
    RLT_BENCH_REPLAY_TIMEOUT bounds the child (default 300 s);
    RLT_BENCH_REPLAY_DURATION / RLT_BENCH_REPLAY_SPEED shape the
    presets."""
    if os.environ.get("RLT_BENCH_REPLAY_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_replay_sweep"],
        _env_timeout("RLT_BENCH_REPLAY_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "flash_crowd" in sweep:
        detail["replay"] = sweep
    else:
        detail["replay"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _compile_sweep(args: argparse.Namespace) -> int:
    """Child: the compile-time microbenchmark (--_compile_sweep).

    Measures cold vs warm build time of the three real programs — the
    llama train step and the engine's serve_prefill/serve_decode pair —
    through the persistent executable cache (runtime/compile_cache.py),
    against a fresh cache dir so "cold" is honest. Three passes per
    program: cold (XLA compile + persist), warm (in-memory hit — the
    second-engine / rebuilt-step path), disk (memory cleared, load the
    serialized executable — the relaunched-process path). All compiles
    happen before any executable load, so the CPU load-taint hazard
    (tests/conftest.py) cannot fire. Reported as detail.compile_cache;
    the long-standing pjit-microbenchmark TODO (SNIPPETS.md [1-2])."""
    import dataclasses
    import tempfile as _tempfile

    sweep_dir = _tempfile.mkdtemp(prefix="rlt-compile-sweep-")
    os.environ["RLT_XLA_CACHE_DIR"] = sweep_dir
    os.environ["RLT_COMPILE_CACHE"] = "1"
    os.environ["RLT_COMPILE_CACHE_EXEC"] = "1"  # dedicated child: loads OK

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params, lm_loss
    from ray_lightning_tpu.runtime import compile_cache as _cc
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )

    def train_step(p, s, toks):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss(q, toks, cfg), has_aux=True
        )(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=2, max_prompt_len=8, max_len=32)
    )
    programs = [
        (
            "train_step",
            _cc.wrap(jax.jit(train_step, donate_argnums=(0, 1)), "train_step"),
            (params, opt_state, tokens),
        ),
    ] + [(name, fn, a) for name, fn, a in engine._program_specs()]

    cache = _cc.get_cache()

    def resolve_ms(fn, a):
        t0 = time.perf_counter()
        fn.cached_compiled(*a)
        return (time.perf_counter() - t0) * 1e3

    report = {name: {} for name, _, _ in programs}
    for phase in ("cold_ms", "warm_ms", "disk_ms"):
        if phase != "cold_ms":
            # model a FRESH build (new engine / rebuilt step): drop the
            # wrapper handles so warm pays the real lower+hash+lookup...
            for _, fn, _a in programs:
                fn._compiled.clear()
        if phase == "disk_ms":
            # ...and a FRESH PROCESS: drop the memory layer so the resolve
            # deserializes the persisted executable (the relaunch path)
            cache.clear_memory()
        for name, fn, a in programs:
            report[name][phase] = round(resolve_ms(fn, a), 2)
    for name in report:
        cold = max(report[name]["cold_ms"], 1e-9)
        report[name]["warm_over_cold"] = round(report[name]["warm_ms"] / cold, 4)
        report[name]["disk_over_cold"] = round(report[name]["disk_ms"] / cold, 4)
    st = cache.stats
    total = st["hits"] + st["misses"]
    print(json.dumps({
        "platform": "cpu",
        "programs": report,
        "hits": st["hits"],
        "misses": st["misses"],
        "disk_hits": st["disk_hits"],
        "hit_rate": round(st["hits"] / total, 4) if total else 0.0,
        "warm_over_cold": max(p["warm_over_cold"] for p in report.values()),
        "compile_ms_total": round(st["compile_ms_total"], 2),
    }))
    return 0


def _attach_compile_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.compile_cache (cold vs warm build ms per program, hit
    rate) to a fresh measurement. CPU-pinned like the other sweeps; with
    detail.compile_ms this is the tracked compile-time regression surface.
    RLT_BENCH_COMPILE_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_COMPILE_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_compile_sweep"],
        _env_timeout("RLT_BENCH_COMPILE_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "programs" in sweep:
        detail["compile_cache"] = sweep
    else:
        detail["compile_cache"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _arbitration_sweep(args: argparse.Namespace) -> int:
    """Child: the chip-arbitration sweep (--_arbitration_sweep).

    Stands up both workloads on one tiny llama — a LocalReplicaFleet at
    device capacity plus a real jitted train step over a simulated chip
    ledger — and drives a ChipArbiter through one forced borrow/return
    cycle, timing the two latencies an operator plans around:

    - borrow_to_first_token_ms: forced-borrow tick start -> a request
      served by the GROWN fleet delivers its first token (shrink + warm
      replica boot + prefill; PR 11's executable cache is what keeps the
      boot load-bound);
    - return_to_first_step_ms: forced-return tick start -> the first
      training step completes on the regrown mesh (drain + regrow +
      step).

    Reported as detail.arbitration."""
    import dataclasses
    import tempfile as _tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params, lm_loss
    from ray_lightning_tpu.runtime.arbiter import ChipArbiter, FleetServeHandle
    from ray_lightning_tpu.serving.replica import LocalReplicaFleet

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )

    @jax.jit
    def train_step(p, s, toks):
        (loss, _), grads = jax.value_and_grad(
            lambda q: lm_loss(q, toks, cfg), has_aux=True
        )(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    class _Train:
        """Simulated chip ledger over a real train step: shrink frees a
        chip immediately (no mesh on CPU), grow runs one real step so
        return-to-first-step pays the honest compute."""

        def __init__(self, devs):
            self.devs = list(devs)
            self.params, self.opt_state = params, opt_state

        def devices(self):
            return list(self.devs)

        def shrink(self, count):
            freed, self.devs = self.devs[-count:], self.devs[:-count]
            return freed

        def grow(self, devices):
            self.devs.extend(devices)
            self.params, self.opt_state, _ = train_step(
                self.params, self.opt_state, tokens
            )
            jax.block_until_ready(self.params)

    fleet = LocalReplicaFleet(
        builder=lambda: (params, cfg),
        engine_kwargs=dict(num_slots=2, max_prompt_len=8, max_len=32),
        initial_replicas=1,
        capacity=1,
    )
    train = _Train(["chip0", "chip1"])
    # warm the step executable so return-to-first-step measures the
    # regrow + step, not the first-trace XLA compile
    train.grow([])
    serve = FleetServeHandle(fleet)
    arb = ChipArbiter(
        _tempfile.mkdtemp(prefix="rlt-arb-sweep-"),
        train,
        serve,
        devices={"chip0": "train", "chip1": "train"},
        min_train_devices=1,
        cooldown_s=0.0,
    )

    arb.request_transfer("borrow")
    t0 = time.perf_counter()
    if arb.tick() != "borrowed":
        print(json.dumps({"error": "forced borrow did not complete"}))
        return 1
    entry = fleet.submit([1, 2, 3], max_new_tokens=4)
    deadline = time.perf_counter() + 60.0
    while not entry.tokens and time.perf_counter() < deadline:
        time.sleep(0.001)
    borrow_ms = (time.perf_counter() - t0) * 1e3

    arb.request_transfer("return")
    t1 = time.perf_counter()
    if arb.tick() != "returned":
        print(json.dumps({"error": "forced return did not complete"}))
        return 1
    return_ms = (time.perf_counter() - t1) * 1e3
    entry.result(timeout=60.0)
    fleet.shutdown()
    print(json.dumps({
        "platform": "cpu",
        "borrow_to_first_token_ms": round(borrow_ms, 2),
        "return_to_first_step_ms": round(return_ms, 2),
        "transfers_completed": arb.transfers_completed,
        "state": arb.state,
    }))
    return 0


def _attach_arbitration_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.arbitration (borrow-to-first-token and
    return-to-first-step ms through one forced borrow/return cycle).
    CPU-pinned like the other sweeps. RLT_BENCH_ARBITRATION_SWEEP=0
    disables."""
    if os.environ.get("RLT_BENCH_ARBITRATION_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_arbitration_sweep"],
        _env_timeout("RLT_BENCH_ARBITRATION_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "borrow_to_first_token_ms" in sweep:
        detail["arbitration"] = sweep
    else:
        detail["arbitration"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _goodput_sweep(args) -> int:
    """Child: the goodput ledger sweep (--_goodput_sweep).

    Runs a tiny in-process CPU fit with telemetry enabled and reports the
    wall-time goodput breakdown the observability layer folded into
    ``summary.json`` — so every bench round carries a goodput fraction
    alongside the throughput number, and a regression that shifts wall
    time from productive_compute into input_wait/idle is visible even
    when tokens/s barely moves. Reported as detail.goodput."""
    import tempfile as _tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.observability.aggregator import _read_summary

    class _GoodputModel(rlt.LightningModule):
        def __init__(self):
            super().__init__()
            self.model = nn.Dense(2)
            self.example_input_array = jnp.zeros((1, 32), jnp.float32)

        def training_step(self, params, batch, batch_idx):
            return jnp.mean(self.model.apply(params, batch) ** 2)

        def configure_optimizers(self):
            return optax.sgd(0.1)

        def train_dataloader(self):
            return rlt.DataLoader(
                rlt.RandomDataset(32, 64), batch_size=8, drop_last=True
            )

    root = _tempfile.mkdtemp(prefix="rlt-goodput-sweep-")
    os.environ.pop("RLT_TELEMETRY_DIR", None)  # keep the dump under root
    trainer = rlt.Trainer(
        default_root_dir=root,
        max_epochs=1,
        limit_train_batches=6,
        strategy=rlt.XLAStrategy(devices=1, telemetry=True),
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    trainer.fit(_GoodputModel())
    summary = _read_summary(os.path.join(root, "telemetry"))
    gp = (summary or {}).get("goodput")
    if not gp:
        print(json.dumps({"error": "fit produced no goodput summary"}))
        return 1
    print(json.dumps({
        "platform": "cpu",
        "fraction": gp.get("fraction"),
        "total_s": gp.get("total_s"),
        "by_category": gp.get("by_category", {}),
    }))
    return 0


def _attach_goodput_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.goodput (wall-time category breakdown + fraction
    from a tiny telemetry-enabled CPU fit). RLT_BENCH_GOODPUT_SWEEP=0
    disables."""
    if os.environ.get("RLT_BENCH_GOODPUT_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_goodput_sweep"],
        _env_timeout("RLT_BENCH_GOODPUT_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "fraction" in sweep:
        detail["goodput"] = sweep
    else:
        detail["goodput"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _zero_sweep(args) -> int:
    """Child: the ZeRO sharding sweep (--_zero_sweep).

    Trains the same tiny MLP under four configurations — replicated DDP,
    explicit ZeRO-2, explicit ZeRO-3, and ZeRO-3 with the int8
    block-scaled parameter all-gather — on 4 virtual CPU devices and
    reports, per config: median post-warmup step time, analytic
    collective bytes per step (from the profiler's HLO cost report of
    the compiled program), and live state bytes (sum of addressable
    shard sizes of params + optimizer state, so replicated state counts
    once per device and sharded state once total). For the quantized
    config it also reports the all-gather wire bytes next to the fp32
    equivalent so the compression delta is visible in every bench round.
    Reported as detail.zero."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=4".strip()
    )
    os.environ.pop("RLT_TELEMETRY_DIR", None)  # keep dumps under tmp roots

    import jax

    jax.config.update("jax_platforms", "cpu")
    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as _np
    import optax

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.parallel.sharding import ShardingPolicy

    class _Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.tanh(nn.Dense(512)(x))
            return nn.Dense(16)(h)

    class _ZeroModel(rlt.LightningModule):
        def __init__(self):
            super().__init__()
            self.net = _Net()

        def init_params(self, rng):
            return self.net.init(rng, jnp.zeros((1, 64)))

        def training_step(self, params, batch, batch_idx):
            x, y = batch
            loss = jnp.mean((self.net.apply(params, x) - y) ** 2)
            self.log("loss", loss)
            return loss

        def configure_optimizers(self):
            return optax.adam(1e-2)

    def _loader():
        rng = _np.random.RandomState(0)
        x = rng.randn(128, 64).astype(_np.float32)
        y = rng.randn(128, 16).astype(_np.float32)
        return rlt.DataLoader(
            list(zip(x, y)),
            batch_size=32,
            collate_fn=lambda items: (
                _np.stack([i[0] for i in items]),
                _np.stack([i[1] for i in items]),
            ),
        )

    class _StepTimer(rlt.Callback):
        """Per-step wall times (blocking on params so async dispatch does
        not fold device time into a later interval) plus the profiler's
        cost reports, grabbed inside the loop — the trainer closes and
        drops the profiler before on_train_end fires."""

        def __init__(self):
            self.marks = []
            self.reports = {}

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            jax.block_until_ready(trainer._params)
            self.marks.append(time.perf_counter())
            prof = getattr(trainer, "_profiler", None)
            if prof is not None and prof._reports:
                self.reports = dict(prof._reports)

    def _live_bytes(tree) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += int(sum(s.data.nbytes for s in shards))
            elif hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
        return total

    configs = [
        ("ddp", 0, False),
        ("zero2", 2, False),
        ("zero3", 3, False),
        ("zero3_int8_gather", 3, True),
    ]
    out = {"platform": "cpu", "devices": 4, "configs": {}}
    for name, stage, quant in configs:
        policy = ShardingPolicy(
            zero_stage=stage, data_axes=("dp",), min_shard_size=1024
        )
        timer = _StepTimer()
        root = tempfile.mkdtemp(prefix=f"rlt-zero-sweep-{name}-")
        trainer = rlt.Trainer(
            default_root_dir=root,
            max_steps=8,
            max_epochs=10,
            strategy=rlt.XLAStrategy(
                devices=4,
                sharding_policy=policy,
                telemetry=True,
                zero_quantized_allgather=quant,
            ),
            callbacks=[timer],
            enable_progress_bar=False,
            enable_checkpointing=False,
            logger=False,
        )
        trainer.fit(_ZeroModel(), _loader())
        deltas = sorted(
            b - a for a, b in zip(timer.marks[1:-1], timer.marks[2:])
        )
        entry = {
            "program": trainer._train_program,
            "step_ms": (
                round(deltas[len(deltas) // 2] * 1e3, 3) if deltas else None
            ),
            "state_bytes": _live_bytes((trainer._params, trainer._opt_state)),
        }
        rep = timer.reports.get(trainer._train_program)
        if rep is not None:
            entry["collective_bytes"] = rep.collective_bytes
        ctx = getattr(trainer, "_zero_ctx", None)
        if ctx is not None:
            entry["allgather_wire_bytes"] = ctx.gather_wire_bytes()
            entry["allgather_fp32_bytes"] = ctx.gather_fp32_bytes()
        out["configs"][name] = entry
    q8 = out["configs"].get("zero3_int8_gather", {})
    if q8.get("allgather_fp32_bytes"):
        out["quantized_allgather_savings"] = round(
            1.0 - q8["allgather_wire_bytes"] / q8["allgather_fp32_bytes"], 4
        )
    print(json.dumps(out))
    return 0


def _attach_zero_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.zero (DDP vs explicit ZeRO-2/3 vs int8-gather step
    time, collective bytes, live state bytes). RLT_BENCH_ZERO_SWEEP=0
    disables."""
    if os.environ.get("RLT_BENCH_ZERO_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_zero_sweep"],
        _env_timeout("RLT_BENCH_ZERO_TIMEOUT", 600.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "configs" in sweep:
        detail["zero"] = sweep
    else:
        detail["zero"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _parallelism_sweep(args) -> int:
    """Child: the composed-parallelism matrix (--_parallelism_sweep).

    Trains under four compositions on 4 virtual CPU devices — ddp,
    zero3 (data-axis state sharding), zero3+tp (ZeRO x tensor-parallel
    partition rules with the int8 all-gather), and zero3+tp+pp (the full
    3D stack: megatron f/g math inside 1F1B pipeline stages) — and
    reports per config: the engaged program, median post-warmup step
    time, live state bytes (addressable shards: sharded state counts
    once, replicated once per device), analytic collective bytes per
    step (rlt_collective_bytes_total source), jit cache size after the
    run (the zero-recompile invariant), and the roofline verdict for the
    measured step. Reported as detail.parallelism."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f
    )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count=4".strip()
    )
    os.environ.pop("RLT_TELEMETRY_DIR", None)  # keep dumps under tmp roots

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as _np
    import optax

    import ray_lightning_tpu as rlt
    from ray_lightning_tpu.observability import profiler as _prof
    from ray_lightning_tpu.parallel.mesh import MeshSpec
    from ray_lightning_tpu.parallel.pipeline_1f1b import (
        identity_fwd_psum_bwd,
        psum_fwd_identity_bwd,
    )
    from ray_lightning_tpu.parallel.sharding import ShardingPolicy

    class _TpMLP(rlt.LightningModule):
        """Explicit-params MLP; megatron column->row math when tp is on."""

        def __init__(self, tp=False):
            super().__init__()
            self.tp = tp

        def init_params(self, rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": 0.2 * jax.random.normal(k1, (64, 512), jnp.float32),
                "b1": jnp.zeros((512,), jnp.float32),
                "w2": 0.2 * jax.random.normal(k2, (512, 16), jnp.float32),
                "b2": jnp.zeros((16,), jnp.float32),
            }

        def training_step(self, params, batch, batch_idx):
            x, y = batch
            if self.tp:
                hin = identity_fwd_psum_bwd(x, "tp")
                h = jnp.tanh(hin @ params["w1"] + params["b1"])
                out = (
                    psum_fwd_identity_bwd(h @ params["w2"], "tp")
                    + params["b2"]
                )
            else:
                h = jnp.tanh(x @ params["w1"] + params["b1"])
                out = h @ params["w2"] + params["b2"]
            loss = jnp.mean((out - y) ** 2)
            self.log("loss", loss)
            return loss

        def configure_optimizers(self):
            return optax.adam(1e-2)

    class _PipeTpModel(rlt.LightningModule):
        """2 pipeline stages, each a megatron column->row pair over tp."""

        def init_params(self, rng):
            k1, k2, k3 = jax.random.split(rng, 3)
            return {
                "stages": {
                    "wa": 0.2 * jax.random.normal(k1, (2, 32, 64), jnp.float32),
                    "wb": 0.2 * jax.random.normal(k2, (2, 64, 32), jnp.float32),
                },
                "last": {
                    "head": 0.2 * jax.random.normal(k3, (32, 8), jnp.float32)
                },
            }

        def pipeline_stage(self, sp, x):
            hin = identity_fwd_psum_bwd(x, "tp")
            h = jnp.tanh(hin @ sp["wa"])
            return psum_fwd_identity_bwd(h @ sp["wb"], "tp")

        def pipeline_last(self, lp, y, targets):
            return jnp.mean((y @ lp["head"] - targets) ** 2)

        def configure_optimizers(self):
            return optax.adam(1e-2)

    def _loader(d_in, d_out):
        rng = _np.random.RandomState(0)
        x = rng.randn(128, d_in).astype(_np.float32)
        y = rng.randn(128, d_out).astype(_np.float32)
        return rlt.DataLoader(
            list(zip(x, y)),
            batch_size=32,
            collate_fn=lambda items: (
                _np.stack([i[0] for i in items]),
                _np.stack([i[1] for i in items]),
            ),
        )

    class _StepTimer(rlt.Callback):
        """Per-step wall times plus the profiler's cost reports, grabbed
        inside the loop — the trainer drops the profiler before
        on_train_end fires."""

        def __init__(self):
            self.marks = []
            self.reports = {}

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            jax.block_until_ready(trainer._params)
            self.marks.append(time.perf_counter())
            prof = getattr(trainer, "_profiler", None)
            if prof is not None and prof._reports:
                self.reports = dict(prof._reports)

    def _live_bytes(tree) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += int(sum(s.data.nbytes for s in shards))
            elif hasattr(leaf, "nbytes"):
                total += int(leaf.nbytes)
        return total

    TP_RULES = "^w1$=None,tp;^b1$=tp;^w2$=tp,None"
    PP_TP_RULES = "stages/wa=pp,None,tp;stages/wb=pp,tp,None"
    configs = [
        # name, model factory, loader dims, strategy kwargs
        ("ddp", lambda: _TpMLP(tp=False), (64, 16), dict(
            sharding_policy=ShardingPolicy.ddp(),
        )),
        ("zero3", lambda: _TpMLP(tp=False), (64, 16), dict(
            sharding_policy=ShardingPolicy(
                zero_stage=3, data_axes=("dp",), min_shard_size=1024
            ),
        )),
        ("zero3_tp", lambda: _TpMLP(tp=True), (64, 16), dict(
            mesh_spec=MeshSpec(axes={"dp": -1, "tp": 2}),
            sharding_policy=ShardingPolicy(
                zero_stage=3, data_axes=("dp",), min_shard_size=1024
            ),
            partition_rules=TP_RULES,
            zero_quantized_allgather=True,
        )),
        ("zero3_tp_pp", lambda: _PipeTpModel(), (32, 8), dict(
            mesh_spec=MeshSpec.composed(dp=1, tp=2, pp=2),
            sharding_policy=ShardingPolicy(
                zero_stage=3, data_axes=("dp",), min_shard_size=1024
            ),
            partition_rules=PP_TP_RULES,
            pipeline_stages=2,
            pipeline_microbatches=4,
        )),
    ]
    out = {"platform": "cpu", "devices": 4, "configs": {}}
    for name, model_fn, dims, strat_kw in configs:
        timer = _StepTimer()
        root = tempfile.mkdtemp(prefix=f"rlt-par-sweep-{name}-")
        trainer = rlt.Trainer(
            default_root_dir=root,
            max_steps=8,
            max_epochs=10,
            strategy=rlt.XLAStrategy(devices=4, telemetry=True, **strat_kw),
            enable_progress_bar=False,
            enable_checkpointing=False,
            logger=False,
            callbacks=[timer],
            seed=0,
        )
        built = {}
        orig = trainer._build_train_step
        trainer._build_train_step = lambda _o=orig, _b=built: _b.setdefault(
            "step", _o()
        )
        trainer.fit(model_fn(), _loader(*dims))
        deltas = sorted(
            b - a for a, b in zip(timer.marks[1:-1], timer.marks[2:])
        )
        step_s = deltas[len(deltas) // 2] if deltas else None
        state_bytes = _live_bytes((trainer._params, trainer._opt_state))
        entry = {
            "program": trainer._train_program,
            "step_ms": round(step_s * 1e3, 3) if step_s else None,
            "state_bytes": state_bytes,
            "state_bytes_per_device": state_bytes // 4,
        }
        try:
            entry["jit_cache_entries"] = int(built["step"]._cache_size())
        except Exception:
            pass
        rep = timer.reports.get(trainer._train_program)
        if rep is not None:
            entry["collective_bytes"] = rep.collective_bytes
            roof = _prof.roofline(rep, step_time_s=step_s)
            entry["roofline_verdict"] = roof.get("verdict")
            entry["measured_bound"] = roof.get("measured_bound")
            entry["mfu"] = roof.get("mfu")
        ctx = getattr(trainer, "_zero_ctx", None)
        if ctx is not None:
            entry["allgather_wire_bytes"] = ctx.gather_wire_bytes()
            entry["allgather_fp32_bytes"] = ctx.gather_fp32_bytes()
        out["configs"][name] = entry
    cfg = out["configs"]
    tp, z3 = cfg.get("zero3_tp", {}), cfg.get("zero3", {})
    if tp.get("state_bytes_per_device") and z3.get("state_bytes_per_device"):
        # the tentpole's acceptance: model-axis sharding must shrink
        # per-device state strictly below data-axis-only ZeRO
        out["tp_state_below_zero3"] = bool(
            tp["state_bytes_per_device"] < z3["state_bytes_per_device"]
        )
    if tp.get("allgather_fp32_bytes"):
        out["quantized_allgather_savings"] = round(
            1.0 - tp["allgather_wire_bytes"] / tp["allgather_fp32_bytes"], 4
        )
    print(json.dumps(out))
    return 0


def _attach_parallelism_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.parallelism (ddp / zero3 / zero3+tp / zero3+tp+pp
    step time, state bytes, collective bytes, roofline verdicts).
    RLT_BENCH_PARALLELISM_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_PARALLELISM_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_parallelism_sweep"],
        _env_timeout("RLT_BENCH_PARALLELISM_TIMEOUT", 600.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "configs" in sweep:
        detail["parallelism"] = sweep
    else:
        detail["parallelism"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _speculative_sweep(args: argparse.Namespace) -> int:
    """Child: the self-speculation sweep (--_speculative_sweep).

    Serves a copy-heavy workload (repetitive prompts on a tiny float32
    model — the regime prompt-lookup speculation exists for) at
    ``speculate_k`` in {0, 2, 4} and reports tokens/s, decode ticks and
    accepted-tokens-per-slot-tick at each k, plus the token-identity
    verdict across all k (the promises_decode_parity contract: k must
    never change a token). CPU-pinned like the other sweeps — this
    measures the acceptance math and the tick-count win, not chip FLOPs.
    """
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    # small vocab + periodic prompts push greedy decode into loops the
    # n-gram proposer can ride — the copy-heavy regime
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, vocab_size=32
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        [3, 7, 11, 3, 7, 11, 3, 7],
        [5, 5, 9, 5, 5, 9, 5, 5],
        [2, 4, 6, 8, 2, 4, 6, 8],
        [13, 1, 13, 1, 13, 1, 13, 1],
        [6, 6, 6, 6, 6, 6, 6, 6],
        [9, 2, 7, 9, 2, 7, 9, 2],
    ]
    max_new = int(os.environ.get("RLT_BENCH_SPECULATIVE_TOKENS", "40"))
    k_levels = []
    streams = {}
    for k in (0, 2, 4):
        engine = InferenceEngine(
            params,
            cfg,
            EngineConfig(
                num_slots=4, max_prompt_len=8, max_len=64,
                temperature=0.0, speculate_k=k,
            ),
        )
        comps = [
            engine.submit(p, max_new_tokens=max_new) for p in prompts
        ]
        # compile off the clock: one step builds both programs
        engine.step()
        t0 = time.perf_counter()
        engine.run_until_idle()
        wall = time.perf_counter() - t0
        streams[k] = [c.tokens for c in comps]
        st = engine.stats
        level = {
            "k": k,
            "tokens_per_sec": round(st["tokens_out"] / max(wall, 1e-9), 2),
            "decode_ticks": int(st["decode_steps"]),
            "tokens_out": int(st["tokens_out"]),
            "compile_stats": engine.compile_stats(),
        }
        if k > 0:
            level["accepted_per_tick"] = round(
                st["accepted_tokens"] / max(st["spec_row_ticks"], 1), 3
            )
        k_levels.append(level)
    payload = {
        "platform": "cpu",
        "preset": "copy-heavy",
        "k_levels": k_levels,
        "accepted_per_tick_k4": next(
            lvl.get("accepted_per_tick") for lvl in k_levels if lvl["k"] == 4
        ),
        "token_identical": all(
            streams[k] == streams[0] for k in (2, 4)
        ),
    }
    print(json.dumps(payload))
    return 0


def _attach_speculative_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.speculative (self-speculation acceptance + tokens/s
    at k in {0, 2, 4} and the cross-k token-identity verdict).
    RLT_BENCH_SPECULATIVE_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_SPECULATIVE_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_speculative_sweep"],
        _env_timeout("RLT_BENCH_SPECULATIVE_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "k_levels" in sweep:
        detail["speculative"] = sweep
    else:
        detail["speculative"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _disagg_sweep(args: argparse.Namespace) -> int:
    """Child: the disaggregated-serving sweep (--_disagg_sweep).

    Serves the same burst through a colocated 2-replica fleet and a
    1-prefill + 1-decode disaggregated fleet (same total replicas, paged
    KV) and reports TTFT p95 / ITL p99 / tokens/s per mode, the
    migration counters (attempts, migrated, fallback rate), and the
    cross-mode token-identity verdict — the tentpole contract that the
    handoff never changes a token. CPU-pinned like the other sweeps:
    this measures the handoff plumbing and scheduling interleave, not
    chip FLOPs."""
    import dataclasses

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from ray_lightning_tpu import observability as _obs
    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.serving import LocalReplicaFleet

    # request-scoped tracing on: the sweep reports the per-request TTFT
    # decomposition (queue_wait/prefill/transfer/decode medians) per mode
    _obs.enable()

    cfg = dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, vocab_size=64
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = __import__("numpy").random.default_rng(7)
    max_new = int(os.environ.get("RLT_BENCH_DISAGG_TOKENS", "16"))
    reqs = [
        [int(t) for t in rng.integers(1, 64, 6)] for _ in range(8)
    ]
    engine_kwargs = dict(
        num_slots=4, max_prompt_len=8, max_len=48, max_queue=64,
        kv_layout="paged", block_size=4,
    )

    def pct(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(int(q * len(vals)), len(vals) - 1)]

    def med(vals):
        return pct(vals, 0.5)

    def ttft_decomposition(records):
        """Median seconds per lineage component over the first-token hop
        records of the burst (the hop whose record carries the telescoped
        ``ttft_components``; see docs/observability.md)."""
        by_comp = {}
        totals = []
        for rec in records:
            comps = rec.get("ttft_components")
            if not comps or "ttft_total_s" not in rec:
                continue
            totals.append(rec["ttft_total_s"])
            for name, secs in comps.items():
                by_comp.setdefault(name, []).append(secs)
        if not totals:
            return None
        out = {
            name: round(med(vals), 6)
            for name, vals in sorted(by_comp.items())
        }
        out["ttft_total_s"] = round(med(totals), 6)
        return out

    def serve(prefill_replicas):
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=engine_kwargs,
            initial_replicas=2,
            prefill_replicas=prefill_replicas,
        )
        try:
            arrivals = {i: [] for i in range(len(reqs))}
            t0 = time.perf_counter()
            entries = [
                fleet.submit(
                    p, max_new_tokens=max_new,
                    on_token=lambda _rid, _t, i=i: arrivals[i].append(
                        time.perf_counter()
                    ),
                )
                for i, p in enumerate(reqs)
            ]
            streams = [e.result(timeout=600) for e in entries]
            wall = time.perf_counter() - t0
            records = fleet.drain_request_records()
            ttfts = [
                (ts[0] - t0) * 1e3 for ts in arrivals.values() if ts
            ]
            itls = [
                (b - a) * 1e3
                for ts in arrivals.values()
                for a, b in zip(ts, ts[1:])
            ]
            stats = fleet.stats()
            out = {
                "mode": (
                    "disaggregated" if prefill_replicas else "colocated"
                ),
                "requests": len(reqs),
                "completed": stats["completed"],
                "tokens_per_sec": round(
                    sum(len(s) for s in streams) / max(wall, 1e-9), 2
                ),
                "ttft_p95_ms": round(pct(ttfts, 0.95), 2),
                "itl_p99_ms": round(pct(itls, 0.99), 2),
            }
            decomp = ttft_decomposition(records)
            if decomp is not None:
                out["ttft_decomposition_s"] = decomp
            if prefill_replicas:
                m = stats["migration"]
                out["migration"] = m
                out["fallback_rate"] = round(
                    m["fallbacks"] / max(m["attempts"], 1), 3
                )
            return out, streams
        finally:
            fleet.shutdown()

    colo, colo_streams = serve(0)
    disagg, disagg_streams = serve(1)
    payload = {
        "platform": "cpu",
        "configs": [colo, disagg],
        "token_identical": colo_streams == disagg_streams,
    }
    print(json.dumps(payload))
    return 0


def _attach_disagg_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.disagg (colocated vs disaggregated prefill/decode
    serving: TTFT p95 / ITL p99 / per-component TTFT decomposition
    medians / migration fallback rate and the cross-mode token-identity
    verdict). RLT_BENCH_DISAGG_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_DISAGG_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_disagg_sweep"],
        _env_timeout("RLT_BENCH_DISAGG_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "configs" in sweep:
        detail["disagg"] = sweep
    else:
        detail["disagg"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _paged_kernel_sweep(args: argparse.Namespace) -> int:
    """Child: the fused paged-attention kernel sweep (--_paged_kernel_sweep).

    Times one paged decode step through ``decode_step_paged`` with the
    Pallas kernel forced ON vs OFF (the lax gather baseline) on the same
    cache/pool state, checks greedy-token parity between the two, and
    places the measured step on the roofline (bandwidth_util / MFU via
    the cost-analysis pass). On CPU the kernel runs in interpret mode, so
    the ratio is a correctness/plumbing signal there — the bandwidth
    story is the TPU run's."""
    import dataclasses
    import functools

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.generation import decode_step_paged
    from ray_lightning_tpu.models.llama import LlamaConfig, init_params
    from ray_lightning_tpu.observability import profiler as _prof
    from ray_lightning_tpu.ops.rope import rope_angles
    from ray_lightning_tpu.serving.paged_kv import PagedKVPool

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    num_slots, max_len = 4, 64
    pool = PagedKVPool(cfg, num_slots, max_len, block_size=8, num_blocks=64)
    rng = np.random.default_rng(0)
    pos_host = np.zeros((num_slots,), np.int32)
    for i in range(num_slots):
        slot = pool.acquire(f"r{i}", prompt_len=24, max_new_tokens=30)
        slot.pos = 23
        pool.ensure_writable(slot)
        pos_host[slot.index] = slot.pos
    table = rope_angles(max_len, cfg.head_dim, cfg.rope_theta)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, num_slots), jnp.int32)
    pos = jnp.asarray(pos_host)
    tables = jnp.asarray(pool.kinds["full"].block_tables)
    reps = max(1, int(os.environ.get("RLT_BENCH_PAGED_KERNEL_STEPS", "20")))

    out = {}
    toks = {}
    for name, use_kernel in (("kernel", True), ("lax", False)):
        fn = jax.jit(functools.partial(
            decode_step_paged, cfg=cfg, rope_table=table, kernel=use_kernel
        ))
        logits, _, _ = fn(params, pool.cache, tokens, pos, tables)
        jax.block_until_ready(logits)  # compile off the clock
        t0 = time.perf_counter()
        for _ in range(reps):
            logits, _, _ = fn(params, pool.cache, tokens, pos, tables)
        jax.block_until_ready(logits)
        step_s = (time.perf_counter() - t0) / reps
        toks[name] = np.asarray(jnp.argmax(logits, axis=-1)).tolist()
        entry = {"decode_step_ms": round(step_s * 1e3, 3)}
        rep = _prof.analyze_jitted(
            fn, params, pool.cache, tokens, pos, tables,
            program=f"paged_decode_{name}",
        )
        if rep is not None:
            roof = _prof.roofline(rep, step_time_s=step_s)
            entry["bandwidth_util"] = roof.get("bandwidth_util")
            entry["mfu"] = roof.get("mfu")
            entry["measured_bound"] = roof.get("measured_bound")
        out[name] = entry
    payload = {
        "platform": "cpu",
        "interpret": True,
        "kernel": out["kernel"],
        "lax": out["lax"],
        "tokens_identical": toks["kernel"] == toks["lax"],
        "kernel_vs_lax": round(
            out["lax"]["decode_step_ms"]
            / max(out["kernel"]["decode_step_ms"], 1e-9), 3
        ),
    }
    print(json.dumps(payload))
    return 0


def _attach_paged_kernel_sweep(result: dict, here: str, env: dict) -> None:
    """Attach detail.paged_kernel (fused paged-attention decode step ms +
    roofline placement, kernel vs lax, with the greedy-token parity
    verdict). RLT_BENCH_PAGED_KERNEL_SWEEP=0 disables."""
    if os.environ.get("RLT_BENCH_PAGED_KERNEL_SWEEP", "1") == "0":
        return
    sweep_env = dict(env)
    sweep_env["JAX_PLATFORMS"] = "cpu"
    ok, sweep, serr = _run(
        [sys.executable, here, "--_paged_kernel_sweep"],
        _env_timeout("RLT_BENCH_PAGED_KERNEL_TIMEOUT", 300.0),
        sweep_env,
    )
    detail = result.setdefault("detail", {})
    if ok and isinstance(sweep, dict) and "kernel" in sweep:
        detail["paged_kernel"] = sweep
    else:
        detail["paged_kernel"] = {
            "error": (sweep or {}).get("error")
            or serr
            or "sweep produced no JSON"
        }


def _last_json_dict(stdout: str):
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict):
            return parsed
    return None


def _run(cmd: list, timeout: float, env: dict) -> tuple:
    """Run a child; return (ok, last_json_or_None, error_string_or_None).

    stdout/stderr go to temp files, not pipes: a grandchild holding an
    inherited pipe fd (or a child wedged in uninterruptible device I/O that
    SIGKILL cannot reap) must never block the orchestrator on a drain. The
    child runs in its own session so the whole process group can be killed.
    """
    import signal
    import tempfile

    with tempfile.TemporaryFile(mode="w+") as out_f, \
            tempfile.TemporaryFile(mode="w+") as err_f:
        proc = subprocess.Popen(
            cmd, stdout=out_f, stderr=err_f, env=env,
            start_new_session=True,
        )
        timed_out = False
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                rc = proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                rc = -9  # unreapable (D-state); files are still readable
        out_f.seek(0)
        stdout = out_f.read()
        err_f.seek(0)
        stderr = err_f.read()
    result = _last_json_dict(stdout)
    if timed_out:
        return False, None, f"timeout after {timeout:.0f}s"
    if rc != 0:
        tail = (stderr or stdout or "").strip().splitlines()[-6:]
        return False, None, f"rc={rc}: " + " | ".join(tail)
    if result is None:
        return False, None, "child produced no JSON"
    return True, result, None


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--preset", default="mini", choices=["tiny", "mini", "small"],
    )
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_dcn_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_input_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_serve_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_compile_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_arbitration_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_goodput_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_zero_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_parallelism_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_speculative_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_disagg_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_paged_kernel_sweep", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--_replay_sweep", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args._child:
        return _child(args)
    if args._dcn_sweep:
        return _dcn_sweep(args)
    if args._input_sweep:
        return _input_sweep(args)
    if args._serve_sweep:
        return _serve_sweep(args)
    if args._compile_sweep:
        return _compile_sweep(args)
    if args._arbitration_sweep:
        return _arbitration_sweep(args)
    if args._goodput_sweep:
        return _goodput_sweep(args)
    if args._zero_sweep:
        return _zero_sweep(args)
    if args._parallelism_sweep:
        return _parallelism_sweep(args)
    if args._speculative_sweep:
        return _speculative_sweep(args)
    if args._disagg_sweep:
        return _disagg_sweep(args)
    if args._paged_kernel_sweep:
        return _paged_kernel_sweep(args)
    if args._replay_sweep:
        return _replay_sweep(args)

    bench_timeout = _env_timeout("RLT_BENCH_TIMEOUT", 1800.0)
    here = os.path.abspath(__file__)
    env = dict(os.environ)
    cmd = [sys.executable, here, "--_child", "--preset", args.preset,
           "--steps", str(args.steps), "--warmup", str(args.warmup)]
    if args.batch:
        cmd += ["--batch", str(args.batch)]
    # all on-chip work (flash autotune, ceiling, measurement) happens
    # inside ONE child — see module docstring
    ok, result, err = _run(cmd, bench_timeout, env)
    if not ok:
        print(f"bench: FAILED: {err}", file=sys.stderr)
        return 1
    _attach_dcn_sweep(result, here, env)
    _attach_input_sweep(result, here, env)
    _attach_serve_sweep(result, here, env)
    _attach_compile_sweep(result, here, env)
    _attach_arbitration_sweep(result, here, env)
    _attach_goodput_sweep(result, here, env)
    _attach_zero_sweep(result, here, env)
    _attach_parallelism_sweep(result, here, env)
    _attach_speculative_sweep(result, here, env)
    _attach_disagg_sweep(result, here, env)
    _attach_paged_kernel_sweep(result, here, env)
    _attach_replay_sweep(result, here, env)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
