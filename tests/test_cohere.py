"""The parallel-block decoder with window and full attention layers and a
share of the experts (``models/cohere.py``) through ``InferenceEngine``, and
what it forced below it: a pool of two kinds of leaf, the windowed paged
decode kernel, the expert layer that holds a share."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import cohere as co
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.parallel import moe
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.paged_kv import (
    BlockAllocator,
    OutOfBlocks,
    PagedKVPool,
    window_blocks,
)

# two periods of three window layers (12 positions) and a full one; 4 of 16
# experts held (ids 4-7), top-4, 2 shared experts
CFG = co.CohereConfig(
    vocab_size=97, dim=64, n_layers=8, period=4, n_heads=8, n_kv_heads=2, head_dim=16,
    sliding_window=12, ffn_dim=32, n_experts=16, experts_held=4, first_expert=4,
    n_shared_experts=2, expert_top_k=4, max_seq=64, dtype=jnp.float32)
ENGINE = dict(num_slots=3, max_prompt_len=36, max_len=64, block_size=4, prefix_cache=False)


@pytest.fixture(scope="module")
def params():
    return co.init_params(jax.random.key(0), CFG)


# ---------------------------------------------------------------------- #
# the engine
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel"])
def test_engine_serves_the_models_own_greedy_tokens(params, monkeypatch, kernel):
    """Prompts of 5, 20, 33 and 9 tokens against a window of 12 and blocks of
    4, 12 to 30 new tokens each on 3 slots: every served token is the argmax
    of the teacher-forced forward at its position; the window kind never held
    more than the window and a block a slot, gave back the blocks that fell
    out, and the full kind held every position."""
    monkeypatch.setenv("RLT_PAGED_KERNEL", kernel)
    engine = InferenceEngine(params, CFG, EngineConfig(**ENGINE))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, n).tolist() for n in (5, 20, 33, 9)]
    new, outs = [30, 25, 20, 12], {}
    for i, (p, n) in enumerate(zip(prompts, new)):
        engine.submit(p, max_new_tokens=n, request_id=f"r{i}",
                      on_token=lambda rid, t: outs.setdefault(rid, []).append(t))
    engine.run_until_idle()
    for i, (p, n) in enumerate(zip(prompts, new)):
        seq = list(p) + outs[f"r{i}"]
        logits = co.forward(params, jnp.asarray([seq], jnp.int32), CFG)[0]
        want = np.argmax(np.asarray(logits), -1)[len(p) - 1: len(seq) - 1]
        assert want.tolist() == outs[f"r{i}"], i
    stats = engine.pool.stats()
    assert stats["window.num_blocks"] == 3 * window_blocks(12, 4) + 1 == 13
    assert stats["window.blocks_highwater"] <= 3 * window_blocks(12, 4)
    assert stats["window.given_back_total"] >= 10 and stats["given_back_total"] == 0
    assert stats["blocks_highwater"] > stats["window.blocks_highwater"]
    assert stats["window.blocks_used"] == stats["blocks_used"] == 0  # all released
    assert engine.stats["moe_choices"] == engine.stats["decode_steps"] * 3 * 4 * 8
    assert 0 < engine.stats["moe_expert_hits"] <= engine.stats["moe_routed_pairs"]
    assert engine.stats["moe_routed_pairs"] < engine.stats["moe_choices"]
    assert 0 < engine.stats["kv_positions_window"] < engine.stats["kv_positions_full"]
    assert engine.compile_stats() == {"prefill_compiles": 1, "decode_compiles": 1}


def test_what_the_engine_has_no_code_for_is_refused_by_name(params):
    with pytest.raises(ValueError, match="prefix_cache=True.*window kind"):
        InferenceEngine(params, CFG, EngineConfig(**dict(ENGINE, prefix_cache=True)))
    with pytest.raises(ValueError, match="speculate_k=2.*no verify step"):
        InferenceEngine(params, CFG, EngineConfig(**ENGINE, speculate_k=2))
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="KV migration ships K and V blocks"):
            InferenceEngine(params, CFG, EngineConfig(**ENGINE, role=role))

    class Mesh:
        size = 4
    with pytest.raises(NotImplementedError, match="mesh"):
        co.forward(params, jnp.zeros((1, 4), jnp.int32), CFG, mesh=Mesh())
    with pytest.raises(ValueError, match="periods"):
        co.CohereConfig(n_layers=6)
    with pytest.raises(ValueError, match="not among the router's"):
        co.CohereConfig(first_expert=120, experts_held=16)


def test_a_uniform_sliding_window_stays_refused_by_the_pool():
    import dataclasses

    from ray_lightning_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(), sliding_window=8)
    with pytest.raises(ValueError, match="dense-causal configs, or a model that states"):
        PagedKVPool(cfg, 2, 32, block_size=4)


# ---------------------------------------------------------------------- #
# the pool and the allocator, by kind
# ---------------------------------------------------------------------- #
def test_window_blocks_is_the_window_and_one_block():
    assert window_blocks(4096, 16) == 257 and window_blocks(12, 4) == 4
    assert window_blocks(5, 4) == 2 and window_blocks(1, 16) == 1


def test_a_window_kind_reserves_its_peak_and_gives_back_what_falls_out():
    alloc = BlockAllocator(20, 4, prefix_cache=False, window=12)
    a = alloc.admit("a", prompt_len=30, max_new_tokens=40)
    # positions [18, 30) are the window's tail: blocks 4..7; 69 positions in
    # all are 18 blocks, of which never more than 4 at once
    assert (a.first, len(a.blocks), a.total, a.peak, a.reserved) == (4, 4, 18, 4, 0)
    assert alloc.used_blocks == 4 and alloc.available() == 19 - 4
    # the step at position 32 attends [21, 32]: block 4 (16..19) is out
    assert alloc.give_back("a", 21 // 4) == 1
    assert (a.first, len(a.blocks), a.reserved) == (5, 3, 1)
    assert alloc.available() == 19 - 4  # the block given back is reserved again
    alloc.grow("a")
    assert len(a.blocks) == 4 and a.reserved == 0
    with pytest.raises(OutOfBlocks):
        alloc.grow("a")  # past the peak without giving back
    # a short request's whole life fits under the window: it reserves that
    b = alloc.admit("b", prompt_len=3, max_new_tokens=4)
    assert (b.first, len(b.blocks), b.total, b.peak, b.reserved) == (0, 1, 2, 2, 1)
    assert alloc.blocks_highwater == 5
    alloc.release("a")
    alloc.release("b")
    assert alloc.used_blocks == 0 and alloc.available() == 19 and alloc.given_back_total == 1
    # at the end of a life nothing is reserved for blocks that will never be
    c = alloc.admit("c", prompt_len=20, max_new_tokens=6)  # 25 positions: 7 blocks
    assert (c.first, len(c.blocks), c.total, c.peak) == (2, 3, 7, 4)
    alloc.grow("c")
    assert alloc.give_back("c", 5) == 3 and c.reserved == 1  # block 6 is the last


def test_a_window_kind_defers_by_its_own_blocks_and_shares_no_prefix():
    with pytest.raises(ValueError, match="prefix sharing over a window kind"):
        BlockAllocator(20, 4, prefix_cache=True, window=12)
    with pytest.raises(ValueError, match="give_back on a full kind"):
        BlockAllocator(20, 4).give_back("a", 1)
    alloc = BlockAllocator(6, 4, prefix_cache=False, window=12)  # 5 data blocks
    assert alloc.admit("a", 30, 40) is not None  # holds 4, peak 4
    assert alloc.admit("b", 30, 40) is None and alloc.deferred_total == 1
    assert alloc.admit("c", 3, 2) is not None  # one block is left


def test_the_pool_admits_by_every_kind_and_keeps_a_table_a_kind():
    """3 slots of max_len 64 in blocks of 4. Each kind is given what the
    engine's one setting says, but a window kind never more than every slot
    at the window and a block. A request that one kind refuses keeps no
    block of any."""
    worst = PagedKVPool(CFG, 3, 64, block_size=4, prefix_cache=False)
    assert worst.kinds["full"].allocator.num_blocks == 3 * 16 + 1
    assert worst.kinds["window"].allocator.num_blocks == 3 * 4 + 1
    assert PagedKVPool(CFG, 3, 64, block_size=4, num_blocks=30, prefix_cache=False
                       ).kinds["window"].allocator.num_blocks == 13
    pool = PagedKVPool(CFG, 3, 64, block_size=4, num_blocks=9, prefix_cache=False)
    assert [k.allocator.num_blocks for k in pool.kinds.values()] == [9, 9]
    assert pool.cache["k_full"].shape == (2, 9, 2, 4, 16)
    assert pool.cache["v_window"].shape == (6, 9, 2, 4, 16)
    assert pool.acquire("big", 30, 4) is None  # 33 positions: 9 blocks of the full kind
    assert all(k.allocator.used_blocks == 0 for k in pool.kinds.values())
    slot = pool.acquire("fits", 20, 8)  # 27 positions: 7 blocks
    assert slot is not None
    tables = pool.program_tables()
    assert set(tables) == {"full", "window"}
    assert (tables["full"][slot.index, :5] > 0).all() and tables["full"][slot.index, 5] == 0
    # the window kind holds the blocks of positions [8, 20): 2, 3, 4
    assert (tables["window"][slot.index, :2] == 0).all()
    assert (tables["window"][slot.index, 2:5] > 0).all()
    write = pool.prompt_write_tables(slot.index, 8)
    # the table not by kind is a pool of one kind's (test_serving.py reads it there)
    with pytest.raises(ValueError, match="a block table a kind"):
        pool.block_tables
    with pytest.raises(ValueError, match="a block table a kind"):
        pool.prompt_write_table(slot.index, 8)
    assert (write["window"] > 0).tolist() == [False, False, True, True, True] + [False] * 3
    assert (write["full"] > 0).tolist() == [True] * 5 + [False] * 3
    slot.pos = 26
    pool.ensure_writable(slot)  # attends [15, 26]: blocks 3..6
    assert (tables["window"][slot.index, :3] == 0).all()
    assert (tables["window"][slot.index, 3:7] > 0).all()
    assert (tables["full"][slot.index, :7] > 0).all()
    stats = pool.stats()
    assert stats["window.given_back_total"] == 1 and stats["layers"] == 2
    assert stats["window.layers"] == 6 and stats["window.window"] == 12
    assert stats["num_blocks"] == 9 and "full.num_blocks" not in stats  # the first kind's, once
    pool.release(slot.index)
    assert all(k.allocator.used_blocks == 0 for k in pool.kinds.values())


# ---------------------------------------------------------------------- #
# the kernel
# ---------------------------------------------------------------------- #
def _paged_case(rng, rows=4, hkv=2, group=4, hd=128, bs=16, cols=40, pages=200):
    q = jnp.asarray(rng.standard_normal((rows, hkv, group, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((pages, hkv, bs, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((pages, hkv, bs, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(pages - 1)[: rows * cols].reshape(rows, cols) + 1, jnp.int32)
    return q, k, v, bt


def test_windowed_paged_decode_attention_equals_a_gather():
    """Rows at positions 5, 300, 513 and 639 with windows that start inside
    the first group, on a group's edge and groups later; the table's columns
    before the first live block name the trash block, as a pool that gave
    them back leaves them."""
    rng = np.random.default_rng(0)
    q, k, v, bt = _paged_case(rng)
    pos = jnp.asarray([5, 300, 513, 639], jnp.int32)
    first = jnp.asarray([0, 300 - 99, 256, 639 - 299], jnp.int32)
    dead = jnp.arange(bt.shape[1])[None, :] < (first // 16)[:, None]
    bt = jnp.where(dead, 0, bt)
    k = k.at[0].set(jnp.nan)  # the trash block may hold anything
    out = pa.paged_decode_attention(q, k, v, bt, pos, first=first, interpret=True)
    cols = jnp.arange(bt.shape[1] * 16)[None, :]
    valid = (cols <= pos[:, None]) & (cols >= first[:, None])
    gather = lambda pool: pool[bt].transpose(0, 2, 1, 3, 4).reshape(4, 2, -1, 128)
    kg = jnp.where(valid[:, None, :, None], gather(k), 0.0)
    s = jnp.einsum("bhgd,bhtd->bhgt", q, kg) / np.sqrt(128.0)
    p = jax.nn.softmax(jnp.where(valid[:, None, None, :], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhgt,bhtd->bhgd", p, jnp.where(valid[:, None, :, None], gather(v), 0.0))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_without_a_first_live_position_the_kernel_is_the_one_it_was():
    """No third scalar operand in the call, and the output bit for bit what a
    window from position 0 gives (the same walk, a mask that hides nothing)."""
    rng = np.random.default_rng(1)
    q, k, v, bt = _paged_case(rng)
    pos = jnp.asarray([5, 300, 513, 639], jnp.int32)
    plain = pa.paged_decode_attention(q, k, v, bt, pos, interpret=True)
    zero = pa.paged_decode_attention(
        q, k, v, bt, pos, first=jnp.zeros((4,), jnp.int32), interpret=True)
    assert (np.asarray(plain) == np.asarray(zero)).all()

    def calls(**kw):
        jaxpr = jax.make_jaxpr(
            lambda *a: pa.paged_decode_attention(*a, interpret=True, **kw))(q, k, v, bt, pos)
        return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]

    (call,) = calls()
    assert call.params["grid_mapping"].num_index_operands == 2
    (call,) = calls(first=pos)
    assert call.params["grid_mapping"].num_index_operands == 3


# ---------------------------------------------------------------------- #
# the expert layer that holds a share
# ---------------------------------------------------------------------- #
def test_the_shares_of_the_routed_experts_add_up_to_holding_them_all():
    rng = np.random.default_rng(2)
    t, d, f, e, k = 24, 16, 8, 8, 3
    stacks = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.3
              for n, shape in (("w_gate", (e, d, f)), ("w_up", (e, d, f)), ("w_down", (e, f, d)))}
    xt = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    idx, w = moe.route_sigmoid_bias(xt, router, None, k)
    same, _ = moe.route_sigmoid_bias(xt, router, jnp.zeros((e,)), k)
    assert (np.asarray(idx) == np.asarray(same)).all()
    whole, sizes = moe.moe_ffn_routed(stacks, xt, idx, w)
    assert int(sizes.sum()) == t * k
    total, counted = jnp.zeros_like(whole), 0
    for first in (0, 2, 4, 6):
        part = {n: a[first: first + 2] for n, a in stacks.items()}
        out, got = moe.moe_ffn_routed(part, xt, idx, w, held=(first, 2))
        assert got.shape == (2,) and (np.asarray(got) == np.asarray(sizes[first: first + 2])).all()
        total, counted = total + out, counted + int(got.sum())
    assert counted == t * k
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-5, atol=1e-6)
    # stacked layer after layer: the third layer's share sits at groups 4, 5
    layered = {n: jnp.concatenate([jnp.zeros_like(a[:4]), a[2:4], a[:2]]) for n, a in stacks.items()}
    out, got = moe.moe_ffn_routed(layered, xt, idx, w, held=(2, 2, 2))
    mine, _ = moe.moe_ffn_routed({n: a[2:4] for n, a in stacks.items()}, xt, idx, w, held=(2, 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(mine), rtol=1e-6, atol=1e-7)
    assert np.asarray(got).tolist()[:4] == [0, 0, 0, 0] and int(got[6:].sum()) == 0
    assert (np.asarray(moe.held_groups(jnp.asarray([1, 2, 3, 4]), 2, 2, 3)) == [-1, 6, 7, -1]).all()


def test_the_grouped_matmul_kernel_skips_the_pairs_that_belong_to_no_group():
    """The Pallas grouped matmul, interpreted: 256 pairs of which about a
    quarter fall on the 4 held experts; the rows behind the last group are
    reached by no step and add nothing."""
    rng = np.random.default_rng(4)
    t, d, f, e, k = 64, 128, 256, 16, 4
    stacks = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
              for n, shape in (("w_gate", (4, d, f)), ("w_up", (4, d, f)), ("w_down", (4, f, d)))}
    xt = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    idx, w = moe.route_sigmoid_bias(xt, jnp.asarray(rng.standard_normal((d, e)), jnp.float32), None, k)
    lax, sizes = moe.moe_ffn_routed(stacks, xt, idx, w, kernel=False, held=(4, 4))
    kernel, same = moe.moe_ffn_routed(stacks, xt, idx, w, kernel=True, held=(4, 4))
    assert 0 < int(sizes.sum()) < t * k and (np.asarray(sizes) == np.asarray(same)).all()
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(lax), rtol=1e-5, atol=1e-5)


def test_rope_on_adjacent_pairs_is_one_function_for_both_models():
    from ray_lightning_tpu.models import deepseek as ds
    from ray_lightning_tpu.ops.rope import rope_adjacent

    assert ds._rope is rope_adjacent and co.rope_adjacent is rope_adjacent
    x = jnp.arange(8.0).reshape(1, 8)
    c, s = jnp.full((1, 4), 0.0), jnp.full((1, 4), 1.0)  # a quarter turn
    assert np.asarray(rope_adjacent(x, c, s)).tolist() == [[-1, -3, -5, -7, 0, 2, 4, 6]]


def test_the_router_reads_float32_rows_at_the_highest_precision():
    """A bfloat16 model: the one product of the expert branch that scores the
    128 (here 16) experts takes float32 operands at the highest precision (a
    default float32 product is one bfloat16 pass on the chip), every other
    product bfloat16 operands; and the chosen experts are those of the
    float32 norm of the rows, which a bfloat16 rounding of them is not in
    every row."""
    import dataclasses

    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    p = co.init_params(jax.random.key(1), cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], p["window_layers"])
    x = jax.random.normal(jax.random.key(2), (512, cfg.dim), jnp.float32).astype(jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x: co._ffn_rows(x, lp, cfg, co._expert_stack(p), 0))(x)

    def dots(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    scores = [e for e in dots(jaxpr.jaxpr) if e.outvars[0].aval.shape[-1] == cfg.n_experts]
    assert len(scores) == 1
    assert {v.aval.dtype for v in scores[0].invars} == {jnp.dtype(jnp.float32)}
    assert scores[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    h32 = co.layernorm(x, lp["norm"], cfg.norm_eps, jnp.float32)
    want = np.argsort(-np.asarray(h32, np.float64) @ np.asarray(lp["router"], np.float64),
                      axis=-1, kind="stable")[:, : cfg.expert_top_k]
    got, _ = moe.route_sigmoid_bias(
        h32, lp["router"], None, cfg.expert_top_k, precision=jax.lax.Precision.HIGHEST)
    assert (np.sort(np.asarray(got), -1) == np.sort(want, -1)).all()
    rounded, _ = moe.route_sigmoid_bias(
        h32.astype(jnp.bfloat16), lp["router"].astype(jnp.bfloat16).astype(jnp.float32),
        None, cfg.expert_top_k)
    assert (np.sort(np.asarray(rounded), -1) != np.sort(want, -1)).any()
