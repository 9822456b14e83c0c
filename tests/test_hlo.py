"""Compiled-HLO assertions: chip-free evidence for the perf-critical
lowering properties.

``jit(...).lower().compile().as_text()`` on the CPU runs the SAME XLA GSPMD
partitioner the TPU uses, so the collective structure of every parallelism
path is assertable on the 8-device CPU mesh. These tests lock the claimed optimizations against
regression:

- ring attention rotates KV with a fixed number of ``collective-permute``
  sites and nothing else (no accidental full-sequence all-gather);
- the zigzag layout only ever moves half-length sequence chunks (the
  mechanism of its causal load balance);
- fsdp gathers params per LAYER inside the scan body — never the stacked
  whole-model buffer per step;
- 1F1B lowers with no more collectives than GPipe (same boundary sends,
  no extra grad reductions from the f/g interleave);
- ZeRO-3 cuts per-device train-step memory to ~1/mesh of the replicated
  lowering (the property reduce-scatter exists to serve — asserted via
  ``memory_analysis()`` because the CPU pass pipeline expresses the
  sharded grad reduction as variadic all-reduce + slice rather than a
  literal reduce-scatter op, a backend scheduling choice, not a semantic
  one);
- tensor parallelism is megatron-shaped: exactly two activation
  all-reduces per layer body (post-attention, post-MLP), both inside the
  layer scan;
- the pipeline schedules trace to their exact tick counts (GPipe: two
  M+P-1-tick scans; 1F1B: one 2P+M-2-tick scan) — the span model behind
  the interleaved-1F1B rejection in docs/parallelism.md;
- expert parallelism moves TOKENS, not weights: no collective in the MoE
  step materializes a full expert-stacked leaf.

Reference frame: the reference has no compiled-graph assertions at all
(its CI asserts behavior only, e.g. tests/test_ddp.py); this tier is the
TPU-native analogue of asserting NCCL call counts.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_lightning_tpu.models.llama import (
    LlamaConfig,
    forward as llama_forward,
    init_params,
    lm_loss,
    shardings_for_mesh,
)
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.ring_attention import ring_attention
from ray_lightning_tpu.parallel.sharding import (
    ShardingPolicy,
    batch_sharding,
    infer_param_shardings,
)

COLLECTIVES = (
    "collective-permute",
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
)


def count_collectives(hlo: str) -> dict:
    """Instruction-site counts per collective op (async ``-start`` forms
    count once; ``-done`` is the pair's consumer, not a second site)."""
    return {
        op: len(re.findall(rf"(?<![\w-]){re.escape(op)}(?:-start)?\(", hlo))
        for op in COLLECTIVES
    }


def result_shapes(hlo: str, op: str):
    """Result shape strings of every ``op`` site, with variadic (tuple)
    results flattened to their component shapes."""
    shapes = []
    for line in hlo.splitlines():
        if not re.search(rf"(?<![\w-]){re.escape(op)}(?:-start)?\(", line):
            continue
        # result type sits between '=' and the op name
        m = re.search(rf"=\s*(.+?)\s*{re.escape(op)}(?:-start)?\(", line)
        if not m:
            continue
        shapes.extend(re.findall(r"(?:f|bf|s|u)\d+\[[\d,]*\]", m.group(1)))
    return shapes


def dims(shape: str):
    inner = shape.split("[", 1)[1].rstrip("]")
    return tuple(int(d) for d in inner.split(",") if d)


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# --------------------------------------------------------------------- #
# ring attention
# --------------------------------------------------------------------- #

_SP, _DP, _S, _D_PAD = 4, 2, 512, 128  # head dim 64 lane-pads to 128


def _ring_fn(load_balance):
    mesh = build_mesh(MeshSpec(axes={"sp": _SP, "dp": _DP}))
    q = jnp.zeros((2, 4, _S, 64), jnp.float32)

    def f(q, k, v):
        return ring_attention(
            q, k, v, mesh, impl="flash", interpret=True,
            load_balance=load_balance,
        )

    return f, q


def test_ring_flash_ppermute_count_and_no_gathers():
    """The plain flash ring's ONLY collectives are the KV rotation: one
    ppermute site each for K and V in the loop body (forward), plus
    dK/dV accumulator rotation in the backward — and nothing that would
    materialize the full sequence on one device."""
    f, q = _ring_fn(load_balance=False)
    fwd = count_collectives(compiled_text(f, q, q, q))
    # k + v rotation, one site each (the fori_loop body lowers once)
    assert fwd["collective-permute"] == 2, fwd
    assert fwd["all-gather"] == fwd["all-reduce"] == 0, fwd
    assert fwd["reduce-scatter"] == fwd["all-to-all"] == 0, fwd

    grad = count_collectives(
        compiled_text(
            jax.grad(lambda a, b, c: f(a, b, c).sum(), argnums=(0, 1, 2)),
            q, q, q,
        )
    )
    # fwd replay (k, v) + bwd loop (k, v, dk, dv)
    assert grad["collective-permute"] == 6, grad
    assert grad["all-gather"] == grad["all-reduce"] == 0, grad


def test_ring_zigzag_moves_only_half_chunks():
    """Zigzag re-lays each shard as two half-chunks (head + mirrored
    tail) so every causal ring step does equal work on every device. The
    lowering must show it: every permuted block has sequence length
    S/(2*sp) — half the plain path's S/sp — and the site counts are the
    layout (3 tensors x 2 halves) + rotation (k1,v1,k2,v2) + unlayout
    (2 halves). Per rotation step the moved volume equals the plain
    path's (4 half blocks vs 2 full), so balance costs no bandwidth."""
    f, q = _ring_fn(load_balance=True)
    txt = compiled_text(f, q, q, q)
    fwd = count_collectives(txt)
    assert fwd["collective-permute"] == 12, fwd  # 6 layout + 4 ring + 2 un
    assert fwd["all-gather"] == fwd["all-reduce"] == 0, fwd

    # permuted blocks are [B/dp, H, seq, D_pad]; seq sits at index 2
    half = _S // (2 * _SP)
    cp_shapes = result_shapes(txt, "collective-permute")
    assert cp_shapes, "no ppermute shapes parsed"
    for s in cp_shapes:
        assert dims(s)[2] == half, (
            f"zigzag permuted a non-half chunk: {s} (want seq {half})"
        )

    gtxt = compiled_text(
        jax.grad(lambda a, b, c: f(a, b, c).sum(), argnums=(0, 1, 2)),
        q, q, q,
    )
    grad = count_collectives(gtxt)
    # fwd 12 + bwd ring (k1,v1,k2,v2,dk1,dv1,dk2,dv2) + dq/dk/dv unlayout
    assert grad["collective-permute"] == 26, grad
    for s in result_shapes(gtxt, "collective-permute"):
        assert dims(s)[2] == half, s


# --------------------------------------------------------------------- #
# llama lowerings (slow: full-model grad compiles)
# --------------------------------------------------------------------- #

_L = 4  # distinctive stacked-layer leading dim for shape checks


def _llama_grad_text(mesh_axes, **cfg_over):
    cfg_over.setdefault("n_layers", _L)
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), dtype=jnp.float32, **cfg_over
    )
    mesh = build_mesh(MeshSpec(axes=mesh_axes))
    params = jax.tree_util.tree_map(
        jax.device_put,
        init_params(jax.random.key(0), cfg),
        shardings_for_mesh(cfg, mesh),
    )
    tokens = jnp.zeros((8, cfg.max_seq), jnp.int32)
    txt = compiled_text(
        jax.grad(lambda p: lm_loss(p, tokens, cfg, mesh)[0]), params
    )
    return txt, cfg, params


@pytest.mark.slow
def test_fsdp_gathers_per_layer_not_per_step():
    """Under fsdp the scan-over-layers body gathers ONE layer's slice per
    iteration; gathering the stacked [n_layers, ...] leaf up front would
    be the whole-model-resident-per-step anti-pattern ZeRO-3 exists to
    avoid. No all-gather result (and no collective result at all) may
    carry the stacked leading dim."""
    txt, cfg, params = _llama_grad_text({"fsdp": 4, "dp": 2})
    counts = count_collectives(txt)
    assert counts["all-gather"] > 0, counts

    stacked_shapes = {
        np.asarray(leaf).shape
        for leaf in jax.tree_util.tree_leaves(params)
        if getattr(leaf, "ndim", 0) > 0 and leaf.shape[0] == _L
    }
    for op in COLLECTIVES:
        for s in result_shapes(txt, op):
            d = dims(s)
            assert d not in stacked_shapes, (
                f"{op} materialized a stacked whole-model leaf {s}"
            )
            # per-layer gathers: results never lead with the layer dim
            if op == "all-gather":
                assert d[0] != _L or len(d) <= 2, (
                    f"all-gather looks stacked-leaf-shaped: {s}"
                )


@pytest.mark.slow
def test_1f1b_no_extra_collectives_vs_gpipe():
    """1F1B reorders microbatch work to shrink the bubble; it must not
    ADD communication. Same boundary ppermute sites as GPipe, and no
    collective category exceeds GPipe's count."""
    results = {}
    for schedule in ("gpipe", "1f1b"):
        txt, _, _ = _llama_grad_text(
            {"pp": 2, "dp": 4},
            n_layers=2, pp_microbatches=2, pp_schedule=schedule,
        )
        results[schedule] = count_collectives(txt)
    g, o = results["gpipe"], results["1f1b"]
    assert o["collective-permute"] == g["collective-permute"], (g, o)
    for op in COLLECTIVES:
        assert o[op] <= g[op], (op, g, o)


@pytest.mark.slow
def test_tp_forward_is_megatron_shaped():
    """Column->row sharded attention and MLP each need exactly ONE
    activation all-reduce (after out-proj, after down-proj); both sit in
    the layer-scan body, so the whole forward shows exactly 2 all-reduce
    sites, activation-shaped — and the embedding lookup stays local (no
    all-to-all, no vocab-dim collective on the gather)."""
    cfg = dataclasses.replace(
        # n_kv_heads == n_heads == tp so head resharding can't blur the
        # collective picture with fractional-head all-to-alls
        LlamaConfig.tiny(), dtype=jnp.float32, n_layers=_L,
        n_heads=4, n_kv_heads=4,
    )
    mesh = build_mesh(MeshSpec(axes={"tp": 4, "dp": 2}))
    params = jax.tree_util.tree_map(
        jax.device_put,
        init_params(jax.random.key(0), cfg),
        shardings_for_mesh(cfg, mesh),
    )
    tokens = jnp.zeros((8, cfg.max_seq), jnp.int32)
    txt = compiled_text(
        lambda p, t: llama_forward(p, t, cfg, mesh), params, tokens
    )
    counts = count_collectives(txt)
    assert counts["all-reduce"] == 2, counts
    assert counts["all-to-all"] == 0, counts
    b, s, d = 8 // 2, cfg.max_seq, cfg.dim
    for shape in result_shapes(txt, "all-reduce"):
        assert dims(shape) == (b, s, d), (
            f"tp all-reduce is not activation-shaped: {shape}"
        )


def test_zero3_train_step_memory_is_sharded():
    """THE ZeRO-3 property: params, grads and adam state live sharded
    through the whole train step. Per-device argument+output bytes of the
    compiled step must be ~1/mesh of the replicated (DDP) lowering — this
    holds regardless of whether the backend spells the grad reduction
    reduce-scatter or all-reduce+slice."""
    mesh = build_mesh(MeshSpec(axes={"dp": 8}))
    rng = jax.random.key(0)
    params = {
        "w1": jax.random.normal(rng, (1024, 2048)),
        "b1": jnp.zeros((2048,)),
        "w2": jax.random.normal(rng, (2048, 1024)),
        "b2": jnp.zeros((1024,)),
    }
    tx = optax.adam(1e-3)
    x = jnp.zeros((64, 1024))
    y = jnp.zeros((64, 1024))

    def train_step(p, s, x, y):
        def loss_fn(p):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] + p["b2"] - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    stats = {}
    for stage in (0, 3):
        policy = (
            ShardingPolicy.zero(3, axes=("dp",))
            if stage
            else ShardingPolicy.ddp()
        )
        psh, opt_rule = infer_param_shardings(mesh, params, policy)
        ps = jax.tree_util.tree_map(jax.device_put, params, psh)
        ss = jax.jit(
            lambda p: tx.init(p),
            out_shardings=opt_rule(tx.init(jax.eval_shape(lambda: ps))),
        )(ps)
        bs = batch_sharding(mesh, ("dp",))
        compiled = (
            jax.jit(train_step, donate_argnums=(0, 1))
            .lower(ps, ss, jax.device_put(x, bs), jax.device_put(y, bs))
            .compile()
        )
        ma = compiled.memory_analysis()
        assert ma is not None
        stats[stage] = ma.argument_size_in_bytes + ma.output_size_in_bytes
    ratio = stats[3] / stats[0]
    # exact sharded ratio is ~1/8 plus replicated biases/batch; anything
    # over ~1/3 means some family (params/grads/adam moments) went
    # replicated again
    assert ratio < 0.30, (stats, ratio)


def _scan_lengths(fn, *args):
    """Static trip counts of every scan in ``fn``'s jaxpr (fori_loop with
    static bounds lowers to scan) — the schedule-span evidence that needs
    no wall clock. Traverses jaxpr-valued params including those nested
    in tuples/lists (e.g. lax.cond's ``branches``)."""
    out = []

    def visit_param(v):
        if isinstance(v, (tuple, list)):
            for item in v:
                visit_param(item)
            return
        inner = getattr(v, "jaxpr", None)
        if inner is not None:
            walk(inner)
        elif hasattr(v, "eqns"):
            walk(v)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                out.append(int(eqn.params["length"]))
            for v in eqn.params.values():
                visit_param(v)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def test_pipeline_schedule_tick_counts():
    """The lockstep-SPMD span model behind the interleaved-1F1B rejection
    (docs/parallelism.md): GPipe traces as TWO M+P-1-tick scans (the
    forward loop and its autodiff transpose — per-tick cost t_f then
    t_b); 1F1B as ONE 2P+M-2-tick scan whose body runs both phases
    (per-tick cost t_f+t_b). Total tick-cost: GPipe (M+P-1)(t_f+t_b) vs
    1F1B (2P+M-2)(t_f+t_b) — 1F1B pays exactly P-1 extra tick-
    equivalents; its win is the activation-residency bound, not time."""
    P = 2
    mesh = build_mesh(MeshSpec(axes={"pp": P, "dp": 4}))
    tokens = jnp.zeros((32, LlamaConfig.tiny().max_seq), jnp.int32)
    for M in (4, 8):
        spans = {}
        for schedule in ("gpipe", "1f1b"):
            cfg = dataclasses.replace(
                LlamaConfig.tiny(), dtype=jnp.float32, n_layers=2,
                pp_microbatches=M, pp_schedule=schedule,
            )
            params = init_params(jax.random.key(0), cfg)
            lens = _scan_lengths(
                jax.grad(lambda p: lm_loss(p, tokens, cfg, mesh)[0]), params
            )
            # drop the per-stage layer scans (length n_layers/pp == 1)
            spans[schedule] = sorted(l for l in lens if l > 1)
        assert spans["gpipe"] == [M + P - 1, M + P - 1], spans
        assert spans["1f1b"] == [2 * P + M - 2], spans


@pytest.mark.slow
def test_moe_expert_weights_never_cross_devices():
    """Under 'ep' the expert-stacked weights are the thing sharded; the
    whole point is that TOKENS (dispatch/combine activations, gate
    tensors) move between devices while expert weights stay put. No
    collective may materialize a full expert-stacked weight leaf (or its
    gradient) — that would be the all-experts-resident anti-pattern that
    caps n_experts at single-chip HBM."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny_moe(), dtype=jnp.float32, n_layers=2
    )
    mesh = build_mesh(MeshSpec(axes={"ep": 4, "dp": 2}))
    params = jax.tree_util.tree_map(
        jax.device_put,
        init_params(jax.random.key(0), cfg),
        shardings_for_mesh(cfg, mesh),
    )
    tokens = jnp.zeros((8, cfg.max_seq), jnp.int32)
    txt = compiled_text(
        jax.grad(lambda p: lm_loss(p, tokens, cfg, mesh)[0]), params
    )

    expert_shapes = set()
    for leaf in jax.tree_util.tree_leaves(params["layers"]["moe"]):
        shape = tuple(leaf.shape)
        if cfg.n_experts in shape and len(shape) >= 3:
            expert_shapes.add(shape)        # stacked [L, E, ...]
            expert_shapes.add(shape[1:])    # per-layer [E, ...]
    assert expert_shapes, "no expert-stacked leaves found"

    for op in COLLECTIVES:
        for s in result_shapes(txt, op):
            assert dims(s) not in expert_shapes, (
                f"{op} materialized a full expert stack: {s}"
            )
