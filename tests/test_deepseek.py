"""The latent-attention MoE decoder (models/deepseek.py) and what it brought:
the sigmoid-bias router and the routed expert path (parallel/moe.py), the
latent paged decode kernel (ops/paged_attention.py), attention with values
narrower than keys (ops/attention.py), and the engine serving it through the
latent pool. Everything at a small size on the CPU, float32, seeded weights;
the plain reference this is held against lives with the benchmark
(tests/bench_harness/test_deepseek_family.py)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import deepseek as ds
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.ops.attention import attention, reference_attention
from ray_lightning_tpu.parallel.moe import (
    grouped_matmul,
    moe_ffn_routed,
    route_sigmoid_bias,
)
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving import migration
from ray_lightning_tpu.serving.paged_kv import PagedKVPool

# hidden 64, 4 heads of 16 + 8 / 16, ranks 32 / 16, 8 experts top-2 of width
# 32 and one shared, one dense + 2 expert layers
CFG = ds.DeepseekConfig(
    vocab_size=97, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, ffn_dim=96, moe_ffn_dim=32, n_experts=8, n_shared_experts=1,
    expert_top_k=2, max_seq=64, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return ds.init_params(jax.random.key(0), CFG)


# ---------------------------------------------------------------------- #
# the router
# ---------------------------------------------------------------------- #
def _router_case():
    x = jnp.eye(4, dtype=jnp.float32)  # token t reads row t of the router
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -9.0],
                          [0.0, 0.0, 0.0, 0.0, -9.0],  # a four-way tie
                          [3.0, -3.0, 1.0, 0.5, -9.0],
                          [-1.0, 0.2, 0.1, 0.0, -9.0]], jnp.float32)
    return x, router


def test_router_bias_moves_the_choice_and_not_the_weight():
    x, router = _router_case()
    s = np.asarray(jax.nn.sigmoid(router))
    idx0, w0 = route_sigmoid_bias(x, router, jnp.zeros(5), 2, scale=1.0, renormalize=False)
    assert np.asarray(idx0)[0].tolist() == [0, 1] and np.asarray(idx0)[2].tolist() == [0, 2]
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9, 0.0])  # lifts expert 3 into token 0's pair
    idx, w = route_sigmoid_bias(x, router, bias, 2, scale=1.0, renormalize=False)
    assert np.asarray(idx)[0].tolist() == [3, 0]  # chosen by score + bias, 3 now first
    # ... and weighed by the score alone: the bias is in no weight
    np.testing.assert_allclose(np.asarray(w)[0], [s[0, 3], s[0, 0]], rtol=1e-6)
    # an expert whose score is hopeless is picked if the bias says so
    idx, _ = route_sigmoid_bias(x, router, jnp.asarray([0, 0, 0, 0, 5.0]), 2)
    assert (np.asarray(idx)[:, 0] == 4).all()


def test_router_renormalises_scales_and_breaks_a_tie_to_the_lower_index():
    x, router = _router_case()
    bias = jnp.asarray([0.0, 0.1, 0.0, 0.0, 0.0])
    idx, w = route_sigmoid_bias(x, router, bias, 2, scale=2.5, renormalize=True)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)  # sums to the scale
    s = np.asarray(jax.nn.sigmoid(router))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), 2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    tied, _ = route_sigmoid_bias(x, router, jnp.zeros(5), 2)
    assert np.asarray(tied)[1].tolist() == [0, 1]  # four equal scores: the two lowest indices
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32


# ---------------------------------------------------------------------- #
# the routed expert path
# ---------------------------------------------------------------------- #
def _experts(rng, e=8, d=16, f=8):
    return {k: jnp.asarray(rng.normal(size=s), jnp.float32)
            for k, s in dict(w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d)).items()}


def _per_token_loop(p, x, idx, w):
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e, xe = int(idx[t, j]), np.asarray(x[t])
            h = np.asarray(jax.nn.silu(xe @ np.asarray(p["w_gate"][e]))) * (xe @ np.asarray(p["w_up"][e]))
            out[t] += float(w[t, j]) * (h @ np.asarray(p["w_down"][e]))
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas-interpreted"])
@pytest.mark.parametrize("routing", ["mixed", "all-to-one", "one-gets-none"])
def test_routed_experts_match_a_per_token_loop_and_drop_nothing(routing, kernel):
    """64 tokens x top-2 = 128 pairs (one row tile of the kernel). The worst
    imbalance sends every token's first choice to expert 5: it takes 64 rows
    where the mean is 16, and nothing is dropped."""
    rng = np.random.default_rng(3)
    p, x = _experts(rng), jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    idx = rng.integers(0, 8, size=(64, 2)).astype(np.int32)
    idx[:, 1] = (idx[:, 0] + 1 + rng.integers(0, 7, size=64)) % 8  # two distinct experts
    if routing == "all-to-one":
        idx[:, 0], idx[:, 1] = 5, rng.integers(0, 5, size=64)
    if routing == "one-gets-none":
        idx[idx == 3] = 4
    w = jnp.asarray(rng.uniform(0.2, 1.0, size=(64, 2)), jnp.float32)
    out, sizes = moe_ffn_routed(p, x, jnp.asarray(idx), w, kernel=kernel)
    want = _per_token_loop(p, x, idx, np.asarray(w))
    # float32 both; the grouped matmul sums in another order than the loop
    assert np.abs(np.asarray(out) - want).max() < 1e-4 * np.abs(want).max()
    assert np.asarray(sizes).tolist() == np.bincount(idx.reshape(-1), minlength=8).tolist()
    assert int(sizes.sum()) == 128  # every pair computed: no capacity, no drop
    if routing == "all-to-one":
        assert int(sizes[5]) == 64
    if routing == "one-gets-none":
        assert int(sizes[3]) == 0


def test_grouped_matmul_kernel_is_the_ragged_dot():
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 128, 128)), jnp.float32)
    sizes = jnp.asarray([100, 0, 29, 127, 0, 0], jnp.int32)
    got = grouped_matmul(xs, w, sizes, kernel=True)
    want = grouped_matmul(xs, w, sizes, kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------- #
# attention: keys wider than values; the latent kernel
# ---------------------------------------------------------------------- #
def test_flash_attention_takes_values_narrower_than_keys_forward_and_backward():
    k = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k[0], (1, 4, 64, 24))
    kk = jax.random.normal(k[1], (1, 4, 64, 24))
    v = jax.random.normal(k[2], (1, 4, 64, 16))
    kw = dict(sm_scale=24 ** -0.5, block_q=32, block_k=32, interpret=True)
    out = attention(q, kk, v, impl="flash", **kw)
    assert out.shape == (1, 4, 64, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        reference_attention(q, kk, v, sm_scale=24 ** -0.5)), atol=2e-6)
    loss = lambda impl: lambda q, k, v: jnp.sum(jnp.sin(attention(q, k, v, impl=impl, **kw)))
    got = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, kk, v)
    want = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _latent_reference(q, kv, bt, pos, v_width, scale):
    b, _, w = q.shape
    rows = kv[bt].reshape(b, -1, w).astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * scale
    s = jnp.where((jnp.arange(rows.shape[1])[None, :] <= pos[:, None])[:, None, :], s, -jnp.inf)
    return jnp.einsum("bht,btv->bhv", jax.nn.softmax(s, axis=-1), rows[..., :v_width])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_latent_paged_kernel_matches_jax_numpy(dtype, tol):
    """Interpreted. Rows at position 0, inside a page, on a page's last
    position, in a second group of pages and at the table's end; the pages
    past a row's position hold NaN, which must not reach the result.
    bfloat16: the kernel casts the queries to the pool's type, as the chip's
    matmul wants (2e-2 of values of size 1 is bfloat16's own step)."""
    rng = np.random.default_rng(0)
    b, h, w, vw, bs, n, cols = 5, 4, 128, 64, 8, 256, 40  # 32 pages a group: 256 tokens
    q = jnp.asarray(rng.normal(size=(b, h, w)), dtype)
    kv = np.asarray(rng.normal(size=(n, bs, w)), np.float32)
    bt = rng.permutation(np.arange(1, n))[: b * cols].reshape(b, cols)  # no page in two rows
    pos = np.asarray([0, 5, 7, 300, cols * bs - 1], np.int32)
    for r in range(b):  # what lies past a row's position is poison
        kv[bt[r, pos[r] // bs], pos[r] % bs + 1:] = np.nan
        kv[bt[r, pos[r] // bs + 1:]] = np.nan
    kv_j, bt_j, pos_j = jnp.asarray(kv, dtype), jnp.asarray(bt, jnp.int32), jnp.asarray(pos)
    out = pa.mla_paged_decode_attention(q, kv_j, bt_j, pos_j, v_width=vw, sm_scale=0.1,
                                        interpret=True)
    clean = jnp.nan_to_num(kv_j.astype(jnp.float32))
    want = _latent_reference(q, clean, bt_j, pos_j, vw, 0.1)
    assert out.shape == (b, h, vw) and out.dtype == jnp.float32
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < tol


def test_absorbed_decode_equals_the_decompressed_form(params):
    """One layer's attention of the last position, both ways: the prefill
    form over the whole sequence, and the absorbed form over the cached rows
    that the prefill form returns. Equal in exact arithmetic; float32 leaves
    1e-5 of values of size 1 (other products, other order)."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["moe_layers"])
    t = 24
    x = jax.random.normal(jax.random.key(1), (2, t, CFG.dim), jnp.float32)
    cos, sin = ds.rope_table(CFG, t)
    y, latent = ds._attend_prefill(x, lp, CFG, cos, sin)
    assert latent.shape == (2, t, CFG.latent_width)
    # the absorbed form for the last position
    h = ds.rmsnorm(x[:, -1], lp["attn_norm"], CFG.norm_eps)
    q_nope, q_rope = ds._queries(h, lp, CFG)
    q_rope = ds._rope(q_rope, cos[-1][None, None, :], sin[-1][None, None, :])
    w_kb, w_vb = ds._wkv_b(lp, CFG)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kb)
    u = ds.absorbed_attention(q_lat, q_rope, latent, jnp.ones((2, t), bool), CFG)
    att = jnp.einsum("bhr,rhd->bhd", u, w_vb).reshape(2, -1)
    got = x[:, -1] + att @ lp["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(y[:, -1]), atol=2e-5)


# ---------------------------------------------------------------------- #
# the engine over the latent pool
# ---------------------------------------------------------------------- #
ENGINE = dict(num_slots=3, max_prompt_len=16, max_len=32, block_size=4)


@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel-interpreted"])
def test_engine_serves_it_token_for_token_with_the_forward(params, monkeypatch, kernel):
    """Prompts of 2, 5 and 11 tokens, so rows sit at different positions and
    cross block boundaries at different ticks; greedy tokens equal the
    teacher-forced forward's argmax at every served position (float32: no
    near-tie falls differently), with the kernel interpreted and without."""
    monkeypatch.setenv("RLT_PAGED_KERNEL", kernel)
    engine = InferenceEngine(params, CFG, EngineConfig(**ENGINE))
    prompts = [[5, 9, 2, 7, 1], [3] * 11, [8, 4]]
    outs = [engine.submit(p, max_new_tokens=12) for p in prompts]
    engine.run_until_idle()
    for p, o in zip(prompts, outs):
        toks = list(p) + o.result()
        logits = ds.forward(params, jnp.asarray([toks]), CFG)[0]
        want = np.argmax(np.asarray(logits), -1)[len(p) - 1: len(toks) - 1]
        assert o.result() == want.tolist()
    assert engine.compile_stats() == {"prefill_compiles": 1, "decode_compiles": 1}
    # the routing counters came back with the tokens
    steps = engine.stats["decode_steps"]
    assert engine.stats["moe_routed_pairs"] == steps * 3 * 2 * CFG.n_moe_layers
    assert 0 < engine.stats["moe_expert_hits"] <= steps * CFG.n_experts * CFG.n_moe_layers
    assert engine.stats["moe_max_expert_rows"] >= steps * CFG.n_moe_layers
    pool = engine.pool.stats()
    assert pool["bytes_per_position"] == CFG.n_layers * 128 * 4  # 24 values in 128 lanes, float32
    assert set(engine.pool.cache) == {"dense", "moe"}
    assert engine.pool.cache["moe"].shape == (2, engine.pool.kinds["full"].allocator.num_blocks, 4, 128)


@pytest.mark.parametrize("setting,names", [
    (dict(kv_layout="slot"), ["kv_layout='slot'", "removed in PR 28"]),  # EngineConfig's own
    (dict(speculate_k=4), ["speculate_k=4", "verify"]),
    (dict(role="prefill"), ["role='prefill'", "K and V"]),
    (dict(role="decode"), ["role='decode'", "K and V"]),
])
def test_engine_refuses_by_name_what_this_model_cannot_do(params, setting, names):
    with pytest.raises(ValueError) as err:
        InferenceEngine(params, CFG, EngineConfig(**dict(ENGINE, **setting)))
    for name in names:
        assert name in str(err.value)
    with pytest.raises(NotImplementedError, match="mesh"):
        ds.forward(params, jnp.zeros((1, 8), jnp.int32), CFG,
                   mesh=type("M", (), {"size": 4})())


def test_a_latent_pool_has_no_migration_fingerprint(params):
    pool = PagedKVPool(CFG, 2, 16, block_size=4)
    with pytest.raises(migration.ShipmentMismatch, match="latent pool"):
        migration.kv_fingerprint(4, (3, 4, 128), "float32", 16,
                                 leaves=tuple(pool.cache))
    engine = InferenceEngine(params, CFG, EngineConfig(**ENGINE))
    with pytest.raises(migration.ShipmentMismatch, match=r"\('dense', 'moe'\)"):
        engine.kv_fingerprint()
    # the K/V pool's fingerprint is what it was
    assert migration.kv_fingerprint(4, (2, 2, 4, 16), "float32", 16) == \
        migration.kv_fingerprint(4, (2, 2, 4, 16), "float32", 16, leaves=("k", "v"))


def test_module_holds_the_selection_bias_fixed(params):
    module = ds.DeepseekModule(CFG, lr=1e-2, warmup_steps=1, total_steps=10)
    opt = module.configure_optimizers()
    state = opt.init(params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 97, size=(2, 16)), jnp.int32)
    loss, grads = jax.value_and_grad(lambda p: ds.lm_loss(p, tokens, CFG)[0])(params)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["moe_layers"]["moe"]["bias"]).max()) == 0.0  # it picks, it does not weigh
    for _ in range(2):  # the first update's rate is 0 (warm-up)
        updates, state = opt.update(grads, state, params)
    assert float(jnp.abs(updates["moe_layers"]["moe"]["bias"]).max()) == 0.0  # nor does decay move it
    assert float(jnp.abs(updates["moe_layers"]["moe"]["router"]).max()) > 0.0
