"""The ladder of prefill lengths (serving/engine.py): prefill runs at the
shortest of a few padded lengths that holds the prompt, chosen by the
prompt's own length, and not at ``max_prompt_len`` whatever the prompt.

The ladder is a pure function of ``max_prompt_len`` and the block size; an
engine on it emits what ``generate()`` (the teacher-forced forward, for the
latent-attention model) emits, writes the blocks the last rung alone would
write, resolves every rung in ``warmup()`` and none afterwards, and counts
the positions it really computed. Everything on the CPU at a tiny size,
float32, so no near-tie falls differently between two padded lengths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models import deepseek as ds
from ray_lightning_tpu.models.generation import generate
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.runtime import compile_cache as cc
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.engine import prefill_rungs, rung_for

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------- #
# the ladder as a function
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("max_prompt_len, block_size, rungs", [
    (2048, 16, (256, 512, 1024, 2048)),
    (1536, 16, (256, 512, 1024, 1536)),  # the last rung need be no power of two
    (1024, 16, (256, 512, 1024)),
    (512, 16, (256, 512)),
    (257, 16, (256, 257)),
    (256, 16, (256,)),
    (64, 16, (64,)),
    (8, 16, (8,)),  # shorter than a block: the one shape there was
    (2048, 128, (256, 512, 1024, 2048)),
    (1000, 24, (264, 528, 1000)),  # rounded up to whole blocks
    (600, 512, (512, 600)),  # 256 and 512 round up to the same block
])
def test_rungs_double_from_256_up_to_max_prompt_len(max_prompt_len, block_size, rungs):
    got = prefill_rungs(max_prompt_len, block_size)
    assert got == rungs
    assert got[-1] == max_prompt_len
    assert list(got) == sorted(set(got))
    assert all(r % block_size == 0 for r in got[:-1])


@pytest.mark.parametrize("max_prompt_len", [2048, 1536, 256, 64])
def test_every_rung_of_the_cells_sizes_is_whole_blocks(max_prompt_len):
    assert all(r % 16 == 0 for r in prefill_rungs(max_prompt_len, 16))


@pytest.mark.parametrize("prompt_len, rung", [
    (1, 256), (64, 256), (255, 256), (256, 256), (257, 512), (511, 512),
    (512, 512), (513, 1024), (1024, 1024), (1025, 2048), (2047, 2048),
    (2048, 2048),
])
def test_a_prompt_runs_at_the_shortest_rung_that_holds_it(prompt_len, rung):
    assert rung_for(prefill_rungs(2048, 16), prompt_len) == rung


def test_an_engine_of_256_or_less_keeps_its_one_prefill_program():
    cfg = _llama()
    engine = InferenceEngine(
        init_params(jax.random.key(0), cfg), cfg,
        EngineConfig(num_slots=2, max_prompt_len=40, max_len=64))
    prefills = [a for name, _, a in engine._program_specs() if name == "serve_prefill"]
    assert [a[2].shape for a in prefills] == [(1, 40)]  # as before the ladder
    assert engine.warmup() == {"prefill_compiles": 1, "decode_compiles": 1}


# ---------------------------------------------------------------------- #
# an engine whose prompts span every rung
# ---------------------------------------------------------------------- #
ENGINE = dict(num_slots=3, max_prompt_len=1024, max_len=1088, block_size=16)
RUNGS = (256, 512, 1024)
NEW = 5
# on and beside every edge, shortest first so each rung's first use follows
# a shorter one's
LENGTHS = (3, 256, 257, 512, 513, 1024)


def _llama():
    return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)


def _deepseek():
    return ds.DeepseekConfig(
        vocab_size=97, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, ffn_dim=96, moe_ffn_dim=32, n_experts=8, n_shared_experts=1,
        expert_top_k=2, max_seq=ENGINE["max_len"], dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module", params=["llama", "deepseek"])
def model(request):
    if request.param == "llama":
        cfg = _llama()
        return request.param, init_params(jax.random.key(0), cfg), cfg
    cfg = _deepseek()
    return request.param, ds.init_params(jax.random.key(0), cfg), cfg


def _prompts(cfg, lengths, shared=0):
    """Seeded prompts of the given lengths; the first ``shared`` tokens are
    the same in all of them."""
    rng = np.random.default_rng(7)
    head = [int(t) for t in rng.integers(1, cfg.vocab_size, shared)]
    return [(head + [int(t) for t in rng.integers(1, cfg.vocab_size, n)])[:n]
            for n in lengths]


def _reference(family, params, cfg, prompt, served):
    """What the reference emits after ``prompt``: ``generate()``'s tokens, or
    for the latent-attention model (which has no ``generate()``) the greedy
    choices of its teacher-forced forward over the prompt and ``served``."""
    if family == "llama":
        out = generate(params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=NEW)
        return [int(t) for t in np.asarray(out)[0, len(prompt):]]
    toks = list(prompt) + list(served)
    logits = ds.forward(params, jnp.asarray([toks]), cfg)[0]
    return np.argmax(np.asarray(logits), -1)[len(prompt) - 1: len(toks) - 1].tolist()


def _serve(engine, prompts):
    outs = [engine.submit(p, max_new_tokens=NEW) for p in prompts]
    engine.run_until_idle()
    return [o.result() for o in outs]


@pytest.mark.parametrize("shared", [0, 40], ids=["distinct", "shared-prefix"])
def test_engine_on_the_ladder_emits_the_references_tokens_and_the_last_rungs_blocks(
    model, shared
):
    """Prompts on and beside every edge of (256, 512, 1024), with and without
    40 tokens in common (two whole blocks a later request finds cached and
    its shorter write table sends to the trash block): the tokens are the
    reference's, and the pool ends as it does in an engine that has the last
    rung alone, which is the engine before the ladder."""
    family, params, cfg = model
    prompts = _prompts(cfg, LENGTHS, shared)
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    assert engine._rungs == RUNGS
    one = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    one._rungs = RUNGS[-1:]
    got = _serve(engine, prompts)
    assert got == _serve(one, prompts)
    for p, tokens in zip(prompts[::2], got[::2]):  # one prompt a rung
        assert tokens == _reference(family, params, cfg, p, tokens)
    assert engine.stats["prefills"] == one.stats["prefills"] == len(prompts)
    assert engine.stats["prefill_positions"] == 256 * 2 + 512 * 2 + 1024 * 2
    assert one.stats["prefill_positions"] == 1024 * len(prompts)
    if shared:
        assert engine.pool.stats()["prefix_hits_total"] > 0
    for name in engine.pool.cache:  # block 0 is the trash block
        np.testing.assert_allclose(
            np.asarray(engine.pool.cache[name])[:, 1:],
            np.asarray(one.pool.cache[name])[:, 1:], atol=1e-5)


def test_warmup_resolves_every_rung_and_serving_them_resolves_no_more(model):
    """No rung compiles inside a window: ``compile_stats()`` after
    ``warmup()`` counts the rungs, and serving a prompt on each leaves it
    there (the shared cache's misses with it)."""
    _, params, cfg = model
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    warm = engine.warmup()
    assert warm == {"prefill_compiles": len(RUNGS), "decode_compiles": 1}
    misses = cc.get_cache().stats["misses"]
    _serve(engine, _prompts(cfg, LENGTHS))
    assert engine.compile_stats() == warm
    assert cc.get_cache().stats["misses"] == misses
    assert engine.warmup() == warm  # and asking again resolves nothing


def test_cost_summary_reports_prefill_at_its_last_rung(model):
    _, params, cfg = model
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    names = [name for name, _, _ in engine._program_specs()]
    assert names == ["serve_prefill"] * len(RUNGS) + ["serve_decode"]
    shapes = [a[2].shape for name, _, a in engine._program_specs()
              if name == "serve_prefill"]
    assert shapes == [(1, r) for r in RUNGS]
    cost = engine.cost_summary()
    assert set(cost) == {"serve_prefill", "serve_decode"}
    # a shorter rung computes less: the report is the longest one's
    short = InferenceEngine(
        params, cfg, EngineConfig(**dict(ENGINE, max_prompt_len=RUNGS[0])))
    assert (cost["serve_prefill"]["step_flops"]
            > 2 * short.cost_summary()["serve_prefill"]["step_flops"])


def test_prefill_positions_is_the_sum_of_the_rungs_used(model):
    _, params, cfg = model
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    lengths = (10, 200, 256, 300, 1000)
    _serve(engine, _prompts(cfg, lengths))
    assert engine.stats["prefills"] == len(lengths)
    assert engine.stats["prefill_positions"] == 256 * 3 + 512 + 1024
    assert engine.stats["prefill_tokens"] == sum(lengths)
    padded_share = 1 - engine.stats["prefill_tokens"] / engine.stats["prefill_positions"]
    assert padded_share == pytest.approx(1 - 1766 / 2304)


def test_a_wrapper_round_prefill_fn_sees_every_prefill_at_its_rung(model):
    """The serve driver's traced run replaces ``engine._prefill_fn`` with a
    wrapper: it is still ONE attribute whose call starts ``(params, cache,
    prompt_row, where)`` (``where``: a write table a kind of leaf), so
    the wrapper sees every prefill, whatever its rung. At the rung whose
    program steps the decode rows too (PR 43: the first, in an engine of the
    Llama family), that step's arguments follow, as ``_decode_fn`` takes
    them; the longer rungs' calls are the prompt's alone."""
    _, params, cfg = model
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    engine.warmup()
    inner, seen = engine._prefill_fn, []

    def spanned(params, cache, prompt_row, where, *rows):
        seen.append((prompt_row.shape, where["full"].shape, len(rows)))
        return inner(params, cache, prompt_row, where, *rows)

    engine._prefill_fn = spanned
    _serve(engine, _prompts(cfg, (5, 300, 900)))
    with_rows = 5 * (engine._fused_rung == 256)  # token, pos, tables, key, prev
    assert seen == [((1, 256), (16,), with_rows), ((1, 512), (32,), 0),
                    ((1, 1024), (64,), 0)]
    assert engine.stats["prefills"] == 3
    assert engine.stats["fused_prefill_steps"] == (with_rows > 0)


def test_a_program_called_at_several_shapes_keeps_an_executable_for_each():
    """What the ladder rests on (runtime/compile_cache.py): a wrapped program
    specialises by its arguments' shapes as jit does, resolves each shape
    once, and goes back to an earlier shape without resolving again."""
    prog = cc.wrap(jax.jit(lambda x: x * 2.0), "by_shape")
    a, b = jnp.ones((8,), jnp.float32), jnp.ones((16,), jnp.float32)
    prog.warmup(a)
    prog.warmup(jax.ShapeDtypeStruct(b.shape, b.dtype))  # by its shape alone
    assert prog._cache_size() == 2
    for x in (a, b, a, b, a):
        np.testing.assert_array_equal(np.asarray(prog(x)), np.asarray(x) * 2.0)
    assert prog._cache_size() == 2 and not prog._polymorphic
    assert prog.cached_compiled(a) is not prog.cached_compiled(b)
    prog(jnp.ones((4,), jnp.float32))  # a shape never warmed resolves at its first call
    assert prog._cache_size() == 3
