"""The Llama family's expert layer at inference computes the routed pairs
only (``parallel/moe.py::moe_ffn_routed`` over the expert stacks of all
layers held as one stack, ``models/generation.py::_scanned_layers``): held
here to an all-experts evaluation written plainly, to the benchmark's plain
reference, and to the routing of the all-experts path it replaced, a copy
of which stays below."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import generation as gen
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.parallel.moe import (
    _gmm_tiles,
    grouped_matmul,
    init_moe_params,
    moe_ffn_routed,
    route_softmax_top_k,
)
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

# a small expert configuration under the benchmark family's own key names
# (tests/bench_harness/tiny.py's, a layer deeper): the family's weights and
# its plain reference are made from these sizes and a seed
SIZES = {
    "family": "llama", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_hidden_layers": 3, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "sliding_window": None, "dtype": "float32",
    "num_local_experts": 4, "num_experts_per_tok": 2,
}
SEED = 2147483659


def _all_experts_ffn_as_it_was(params, x, top_k=2):
    """``moe_ffn_lossless`` as the repository had it until PR 33, kept as the
    router's and the routed path's reference: every expert on every token, a
    scan over the experts, combined with the normalised top-k gates. Returns
    (out, the chosen experts, their weights)."""
    b, s, d = x.shape
    e = params["router"].shape[-1]
    xt = x.reshape(b * s, d)

    logits = (xt.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # [T, E]
    top_vals, top_idx = jax.lax.top_k(gates, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    sel = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # [T, K, E]
    w = (sel * top_vals[..., None]).sum(axis=1)  # [T, E]

    def body(acc, expert):
        wg, wu, wd, gate_col = expert  # [D,F], [D,F], [F,D], [T]
        h = jax.nn.silu(xt @ wg) * (xt @ wu)
        return acc + gate_col[:, None] * (h @ wd).astype(jnp.float32), None

    acc0 = jnp.zeros((b * s, d), jnp.float32)
    out, _ = jax.lax.scan(
        body, acc0,
        (params["w_gate"], params["w_up"], params["w_down"], w.T),
    )
    return out.reshape(b, s, d).astype(x.dtype), top_idx, top_vals


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
@pytest.mark.parametrize("experts, top_k", [(4, 2), (8, 2), (8, 3)])
def test_router_is_the_all_experts_paths_bit_for_bit(seed, experts, top_k):
    """The same choice from the same arithmetic: ``route_softmax_top_k``
    returns the experts and the weights ``moe_ffn_lossless`` computed, to the
    bit, jitted as the serving programs run it; and the routed pairs sum to
    what all experts under (mostly zero) gates summed to."""
    keys = jax.random.split(jax.random.key(seed), 2)
    params = init_moe_params(keys[0], 64, 96, experts, jnp.float32)
    x = jax.random.normal(keys[1], (3, 11, 64), jnp.float32)
    want, want_idx, want_w = jax.jit(
        lambda p, x: _all_experts_ffn_as_it_was(p, x, top_k))(params, x)
    idx, w = jax.jit(lambda x, r: route_softmax_top_k(x, r, top_k))(
        x.reshape(-1, 64), params["router"])
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(w), np.asarray(want_w))
    got, sizes = moe_ffn_routed(params, x.reshape(-1, 64), idx, w)
    assert int(sizes.sum()) == 33 * top_k
    np.testing.assert_allclose(
        np.asarray(got).reshape(want.shape), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------- #
# the four serving functions against all experts and the plain reference
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def model():
    """(cfg, params, tokens [B, S], the reference's teacher-forced logits
    [B, S, V]): the benchmark family's seeded weights in float32."""
    from benchmarks import loader

    family = loader.Manifest().family("llama")
    cfg = family.program.model_config(SIZES, max_seq=64, remat=False)
    params = family.weights.make_params(SIZES, family.weights.seed_keys(SIZES, SEED))
    tokens = np.random.default_rng(5).integers(0, SIZES["vocab_size"], (2, 12))
    want = family.reference.teacher_forced_logits(SIZES, SEED, tokens)
    return cfg, params, jnp.asarray(tokens, jnp.int32), np.asarray(want, np.float32)


def _all_experts_logits(params, tokens, cfg, chosen=None):
    """Teacher-forced logits with every expert run on every token, a loop
    over layers and experts and nothing else: the gates are zero outside a
    token's top two. ``chosen``: a list that takes each layer's choice
    ``[B, S, K]``."""
    from ray_lightning_tpu.ops.rmsnorm import rmsnorm

    b, s = tokens.shape
    hd = cfg.head_dim
    cos, sin = gen.rope_angles(s, hd, cfg.rope_theta)
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for l in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = gen._rope((h @ lp["wq"]).reshape(b, s, -1, hd), cos, sin)
        k = gen._rope((h @ lp["wk"]).reshape(b, s, -1, hd), cos, sin)
        v = (h @ lp["wv"]).reshape(b, s, -1, hd)
        group = q.shape[2] // k.shape[2]
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        x = x + att @ lp["wo"]
        h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        moe = lp["moe"]
        gates = jax.nn.softmax(h @ moe["router"], axis=-1)
        vals, idx = jax.lax.top_k(gates, cfg.expert_top_k)
        vals = vals / vals.sum(-1, keepdims=True)
        if chosen is not None:
            chosen.append(np.asarray(idx))
        for e in range(cfg.n_experts):
            gate = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=-1)[..., None]
            x = x + gate * (
                (jax.nn.silu(h @ moe["w_gate"][e]) * (h @ moe["w_up"][e])) @ moe["w_down"][e])
    return rmsnorm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]


def _paged_pool(cfg, rows, blocks_per_row, block_size):
    shape = (cfg.n_layers, 1 + rows * blocks_per_row, cfg.n_kv_heads, block_size,
             cfg.head_dim)
    cache = {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}
    tables = 1 + np.arange(rows * blocks_per_row, dtype=np.int32).reshape(rows, -1)
    return cache, jnp.asarray(tables)


@pytest.mark.parametrize("step", ["prefill", "contiguous", "paged-gather",
                                  "paged-kernel-interpreted", "verify"])
def test_serving_functions_agree_with_all_experts_and_the_reference(model, step):
    """``prefill`` (a prompt at a time, its last position's logits),
    ``decode_step``, ``decode_step_paged`` under both reads and
    ``decode_step_verify`` (four positions a call), at three layers of four
    experts in float32: each position's logits equal the all-experts loop's
    and the benchmark reference's (``benchmarks/families/llama/reference.py``:
    other code, other weights' owner, every expert on every token too)."""
    cfg, params, tokens, reference = model
    B, S = tokens.shape
    plain = np.asarray(_all_experts_logits(params, tokens, cfg), np.float32)
    assert np.abs(plain - reference).max() < 2e-3
    got = np.zeros_like(plain)
    seen = list(range(S))  # the positions whose logits the step returns
    if step == "prefill":
        seen = [0, 4, 8, S - 1]
        for last in seen:
            logits, _ = gen.prefill(
                params, tokens[:, : last + 1], cfg, gen.init_kv_cache(cfg, B, S))
            got[:, last] = np.asarray(logits)
    elif step == "contiguous":
        cache = gen.init_kv_cache(cfg, B, S)
        run = jax.jit(lambda c, tok, t: gen.decode_step(params, c, tok, t, cfg))
        for t in range(S):
            logits, cache = run(cache, tokens[:, t], jnp.int32(t))
            got[:, t] = np.asarray(logits)
    else:
        cache, tables = _paged_pool(cfg, B, S // 4, 4)
        at = lambda t: jnp.full((B,), t, jnp.int32)
        if step == "verify":
            run = jax.jit(lambda c, tok, t: gen.decode_step_verify(
                params, c, tok, at(t), tables, cfg))
            for t in range(0, S, 4):
                logits, cache = run(cache, tokens[:, t: t + 4], jnp.int32(t))
                got[:, t: t + 4] = np.asarray(logits)
        else:
            run = jax.jit(lambda c, tok, t: gen.decode_step_paged(
                params, c, tok, at(t), tables, cfg,
                kernel=step == "paged-kernel-interpreted"))
            for t in range(S):
                logits, cache, counters = run(cache, tokens[:, t], jnp.int32(t))
                got[:, t] = np.asarray(logits)
                hits, pairs, fullest = np.asarray(counters).tolist()
                assert pairs == cfg.n_layers * B * cfg.expert_top_k
                assert cfg.n_layers * cfg.expert_top_k <= hits <= pairs
                assert cfg.n_layers <= fullest <= cfg.n_layers * B
    assert np.abs(got - plain)[:, seen].max() < 1e-3
    assert np.abs(got - reference)[:, seen].max() < 2e-3


def test_the_expert_stacks_are_closed_over_and_never_scanned(model):
    """What ``_scanned_layers`` is for: in the traced decode step the three
    expert stacks enter the layer loop whole, as ``[L * E, ...]``, and the
    loop slices a layer of everything else; a dense configuration's layers
    are handed back as they are."""
    cfg, params, _, _ = model
    layers, experts = gen._scanned_layers(params)
    le = cfg.n_layers * cfg.n_experts
    assert {k: v.shape for k, v in experts.items()} == {
        "w_gate": (le, 128, 256), "w_up": (le, 128, 256), "w_down": (le, 256, 128)}
    assert sorted(layers["moe"]) == ["layer", "router"]
    assert np.asarray(layers["moe"]["layer"]).tolist() == list(range(cfg.n_layers))
    dense_cfg = LlamaConfig.tiny()
    dense = jax.eval_shape(lambda: init_params(jax.random.key(0), dense_cfg))
    assert gen._scanned_layers(dense) == (dense["layers"], None)

    cache, tables = _paged_pool(cfg, 2, 3, 4)
    jaxpr = jax.make_jaxpr(lambda c, tok, pos: gen.decode_step_paged(
        params, c, tok, pos, tables, cfg, kernel=False))(
            cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    consts = scans[0].params["num_consts"]
    whole = [v.aval.shape for v in scans[0].invars[:consts]]
    sliced = [v.aval.shape for v in scans[0].invars[consts + scans[0].params["num_carry"]:]]
    for stack in experts.values():
        assert stack.shape in whole
        assert (cfg.n_layers, cfg.n_experts) + stack.shape[1:] not in sliced


# ---------------------------------------------------------------------- #
# the grouped matmul under its row tile, and its tiles
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("pairs", [64, 200], ids=["under-a-tile", "ragged-last-tile"])
def test_pairs_off_the_row_tile_are_padded_to_it_for_the_kernel(pairs):
    """A 32-row decode tick with two experts a token makes 64 pairs, half a
    row tile: the interpreted kernel, which takes whole row tiles, gets them
    padded with rows of no group and gives what ``lax.ragged_dot`` gives;
    through ``moe_ffn_routed`` the same."""
    keys = jax.random.split(jax.random.key(3), 4)
    e, d, f = 8, 128, 256
    xs = jax.random.normal(keys[0], (pairs, d), jnp.float32)
    w = jax.random.normal(keys[1], (e, d, f), jnp.float32) / np.sqrt(d)
    sizes = jnp.asarray(np.bincount(
        np.random.default_rng(pairs).integers(0, e, pairs), minlength=e), jnp.int32)
    assert int(sizes.sum()) == pairs
    got = grouped_matmul(xs, w, sizes, kernel=True)
    want = grouped_matmul(xs, w, sizes, kernel=False)
    assert got.shape == want.shape == (pairs, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    params = init_moe_params(keys[2], d, f, e, jnp.float32)
    xt = jax.random.normal(keys[3], (pairs // 2, d), jnp.float32)
    idx, weights = route_softmax_top_k(xt, params["router"], 2)
    got, got_sizes = moe_ffn_routed(params, xt, idx, weights, kernel=True)
    want, want_sizes = moe_ffn_routed(params, xt, idx, weights, kernel=False)
    assert np.array_equal(np.asarray(got_sizes), np.asarray(want_sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("k, n, tiles", [
    (2048, 768, (128, 2048, 768)),      # serve-mla-moe-reason: w_gate, w_up, whole
    (768, 2048, (128, 768, 2048)),      # its w_down, whole
    (4096, 4096, (128, 4096, 512)),     # serve-swa-moe-doc: all three stacks
    (4096, 14336, (128, 4096, 512)),    # serve-moe-batch: w_gate, w_up
    (14336, 4096, (128, 1024, 2048)),   # its w_down: K cut too
], ids=["reason-up", "reason-down", "doc", "batch-up", "batch-down"])
def test_gmm_tiles_at_the_expert_cells_shapes(k, n, tiles):
    """One rule over (K, N, itemsize): the tiles the other two expert cells
    have run on since PRs 26 and 30, and the batch cell's; a weight tile
    never over 4 MiB, whole lanes, dividing the slab."""
    got = _gmm_tiles(k, n, 2)
    assert got == tiles
    _, tk, tn = got
    assert tk * tn * 2 <= 4 * 1024 * 1024 and k % tk == 0 and n % tn == 0
    assert tn % 128 == 0 and (tk == k or tk % 128 == 0)


# ---------------------------------------------------------------------- #
# the engine's counters
# ---------------------------------------------------------------------- #
def test_engine_counts_the_routing_of_an_expert_configuration(model):
    """``moe_expert_hits`` / ``moe_routed_pairs`` / ``moe_max_expert_rows``
    of the engine equal a count made by hand from the all-experts loop's
    router: one request at a time in three slots, so a decode tick's rows
    are the request's newest position and two free slots' dummy rows (token
    0 at position 0), summed over layers and ticks."""
    cfg, params, _, _ = model
    assert cfg.serving().counters == gen.DECODE_COUNTERS
    engine = InferenceEngine(params, cfg, EngineConfig(
        num_slots=3, max_prompt_len=8, max_len=16, block_size=4, prefix_cache=False))
    assert all(engine.stats[name] == 0 for name in gen.DECODE_COUNTERS)

    def choices(context):
        """[layers, K]: what the last position of ``context`` is routed to."""
        chosen = []
        _all_experts_logits(params, jnp.asarray([context], jnp.int32), cfg, chosen)
        return [layer[0, -1] for layer in chosen]

    dummy = choices([0])
    hits = pairs = fullest = 0
    for prompt in ([5, 9, 2, 7, 1], [3, 3, 8]):
        before = engine.stats["decode_steps"]
        out = engine.submit(prompt, max_new_tokens=4)
        engine.run_until_idle()
        seq = prompt + list(out.result())
        assert engine.stats["decode_steps"] - before == 4
        for tick in range(4):  # tick j feeds position P - 1 + j
            row = choices(seq[: len(prompt) + tick])
            for layer in range(cfg.n_layers):
                counts = np.bincount(
                    np.concatenate([row[layer], dummy[layer], dummy[layer]]),
                    minlength=cfg.n_experts)
                hits += int((counts > 0).sum())
                pairs += int(counts.sum())
                fullest += int(counts.max())
    assert pairs == 8 * cfg.n_layers * 3 * cfg.expert_top_k
    assert engine.stats["moe_expert_hits"] == hits
    assert engine.stats["moe_routed_pairs"] == pairs
    assert engine.stats["moe_max_expert_rows"] == fullest


def test_a_dense_configuration_has_no_counters():
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    assert cfg.serving().counters == ()
    params = init_params(jax.random.key(0), cfg)
    cache, tables = _paged_pool(cfg, 2, 2, 4)
    zeros = jnp.zeros((2,), jnp.int32)
    assert gen.decode_step_paged(
        params, cache, zeros, zeros, tables, cfg, kernel=False)[2] is None
    engine = InferenceEngine(params, cfg, EngineConfig(
        num_slots=2, max_prompt_len=8, max_len=16, block_size=4))
    assert not any(name.startswith("moe_") for name in engine.stats)


# ---------------------------------------------------------------------- #
# the benchmark's two readers of this path
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("counters, hit_share", [
    ({"decode_steps": 10.0, "moe_expert_hits": 115.0}, 100.0 * 115 / (4 * 3 * 10)),
    ({"decode_steps": 10.0}, None),  # the parent: a program without the counters
    ({}, None),
], ids=["routed", "no-routing-counters", "empty"])
def test_batch_cell_readers_on_facts_with_and_without_the_counters(counters, hit_share):
    """``expert_hit_share.batch`` is the engine's ``moe_expert_hits`` over
    experts x layers x decode ticks and gives no reading, without raising,
    where the program returned no routing counters; ``decode_hbm_share.batch``
    needs none of them (the family's bytes of a tick over the median decode
    tick over the memory's speed)."""
    import functools

    from benchmarks import loader

    manifest = loader.Manifest()
    counts = manifest.family("llama").counts
    facts = {
        "counters": counters, "peaks": {"hbm_gbps": 819.0},
        "decode_tick_bytes": functools.partial(counts.decode_tick_bytes, SIZES),
        # (start, end, prefills, rows decoded, live context): two decode ticks
        "ticks": [(0.0, 0.020, 0, 3, 40.0), (0.020, 0.040, 0, 3, 44.0), (0.04, 0.09, 1, 3, 50.0)],
    }
    got = manifest.reader("expert_hit_share.batch")(facts)
    assert got == (None if hit_share is None else pytest.approx(hit_share))
    assert manifest.reader("expert_hit_share.batch")({}) is None
    need = counts.decode_tick_bytes(SIZES, 42.0)
    assert manifest.reader("decode_hbm_share.batch")(facts) == pytest.approx(
        100.0 * need / 0.020 / 819e9)
    assert manifest.reader("decode_hbm_share.batch")({}) is None
