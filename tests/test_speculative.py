"""Self-speculation (prompt-lookup proposer + decode_step_verify) and
the fused Pallas paged-decode kernels (ops/paged_attention.py).

The acceptance bar: with ``speculate_k=4`` and the kernels forced on,
staggered multi-request serving stays TOKEN-IDENTICAL to sequential
``generate()`` with flat jit caches — speculation and kernels are pure
performance knobs, never correctness knobs (the promises_decode_parity
contract).
"""
import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generation import (
    decode_step_paged,
    decode_step_verify,
    generate,
    init_kv_cache,
    prefill,
)
from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.speculative import ngram_propose

pytestmark = [pytest.mark.serving, pytest.mark.speculative]


def _cfg(**over):
    # float32 so greedy argmax ties cannot fall differently between the
    # batched serving path and the sequential generate() reference
    return dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32, **over)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return init_params(jax.random.key(0), cfg), cfg


def _reference(params, cfg, prompt, n_new):
    out = generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, max_new_tokens=n_new
    )
    return [int(t) for t in np.asarray(out)[0, len(prompt):]]


@contextlib.contextmanager
def _env(name, value):
    old = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


# --------------------------------------------------------------------- #
# prompt-lookup proposer (pure host code)
# --------------------------------------------------------------------- #
def test_ngram_propose_continues_repeated_pattern():
    # history ends in (7, 8); the earlier (7, 8) was followed by 9, 7, 8
    hist = [1, 7, 8, 9, 7, 8]
    assert ngram_propose(hist, 3) == [9, 7, 8]


def test_ngram_propose_empty_and_tiny_history():
    assert ngram_propose([], 4) == []
    assert ngram_propose([5], 4) == []  # no earlier occurrence possible
    assert ngram_propose([5, 5], 4) == [5]  # 1-gram self-match


def test_ngram_propose_no_match():
    assert ngram_propose([1, 2, 3, 4, 5], 4) == []


def test_ngram_propose_shorter_than_budget():
    # the only earlier match sits 2 tokens from the end: the proposal is
    # just those 2 continuation tokens, shorter than the budget of 8
    hist = [9, 1, 2, 7, 7, 1, 2]
    assert ngram_propose(hist, 8) == [7, 7, 1, 2]
    assert ngram_propose([3, 4, 3], 8) == [4, 3]


def test_ngram_propose_prefers_most_recent_match():
    # two earlier (2, 3) occurrences with different continuations: the
    # scan walks right-to-left, so the RECENT continuation (5) wins
    hist = [2, 3, 4, 2, 3, 5, 2, 3]
    assert ngram_propose(hist, 1) == [5]


def test_ngram_propose_budget_and_validation():
    assert ngram_propose([1, 2, 1, 2], 0) == []
    with pytest.raises(ValueError):
        ngram_propose([1, 2], 2, min_ngram=0)
    with pytest.raises(ValueError):
        ngram_propose([1, 2], 2, max_ngram=1, min_ngram=2)


# --------------------------------------------------------------------- #
# decode_step_verify: the k-position verification program
# --------------------------------------------------------------------- #
def _prefill_rows(params, cfg, prompts, max_len):
    """Batched prefill of equal-length prompts into a fresh cache."""
    cache = init_kv_cache(cfg, len(prompts), max_len)
    _, cache = prefill(
        params, jnp.asarray(prompts, jnp.int32), cfg, cache
    )
    return cache


def _paged_rows(params, cfg, prompts, max_len, block_size=4):
    """``_prefill_rows`` cut into the paged pool's blocks, with the
    identity block table: row b's logical block j is physical block
    ``1 + b * blocks + j`` (block 0 is the trash block)."""
    rows = _prefill_rows(params, cfg, prompts, max_len)
    n, blocks = len(prompts), max_len // block_size

    def pool(leaf):  # [L, B, Hkv, C, hd] -> [L, 1 + B * blocks, Hkv, bs, hd]
        layers, _, heads, _, hd = leaf.shape
        cut = leaf.reshape(layers, n, heads, blocks, block_size, hd)
        cut = cut.transpose(0, 1, 3, 2, 4, 5).reshape(
            layers, n * blocks, heads, block_size, hd)
        return jnp.concatenate([jnp.zeros_like(cut[:, :1]), cut], axis=1)

    tables = 1 + jnp.arange(n * blocks, dtype=jnp.int32).reshape(n, blocks)
    return {"k": pool(rows["k"]), "v": pool(rows["v"])}, tables


def test_verify_matches_sequential_decode_bitwise(model):
    """K sequential decode_step_paged calls and ONE decode_step_verify
    call over the same proposals produce bitwise-identical logits and
    cache — the verify program IS the decode program, k times."""
    params, cfg = model
    B, P, K, max_len = 3, 5, 4, 24
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab_size, (B, P)).tolist()

    cache_seq, tables = _paged_rows(params, cfg, prompts, max_len)
    cache_ver = jax.tree.map(jnp.copy, cache_seq)

    # proposals = the actual greedy continuation, so every sequential
    # step consumes exactly what verify consumes
    toks = jnp.asarray([p[-1] for p in prompts], jnp.int32)
    pos = jnp.asarray([P - 1] * B, jnp.int32)
    seq_logits = []
    chain = [toks]
    for i in range(K):
        lg, cache_seq, _ = decode_step_paged(
            params, cache_seq, chain[-1], pos + i, tables, cfg, kernel=False
        )
        seq_logits.append(lg)
        chain.append(jnp.argmax(lg, axis=-1).astype(jnp.int32))

    tokens = jnp.stack(chain[:K], axis=1)  # [B, K]
    ver_logits, cache_ver = decode_step_verify(
        params, cache_ver, tokens, pos, tables, cfg
    )
    np.testing.assert_array_equal(
        np.asarray(ver_logits),
        np.stack([np.asarray(l) for l in seq_logits], axis=1),
    )
    np.testing.assert_array_equal(
        np.asarray(cache_ver["k"]), np.asarray(cache_seq["k"])
    )
    np.testing.assert_array_equal(
        np.asarray(cache_ver["v"]), np.asarray(cache_seq["v"])
    )


def test_verify_zero_accept_position_zero_is_exact(model):
    """With GARBAGE proposals, out[0] (the correction token) is still
    bitwise the sequential next token — a 0-accepted tick degenerates to
    the classic one-token tick."""
    params, cfg = model
    B, P, K, max_len = 2, 4, 4, 24
    rng = np.random.default_rng(1)
    prompts = rng.integers(1, cfg.vocab_size, (B, P)).tolist()

    cache, tables = _paged_rows(params, cfg, prompts, max_len)
    toks = jnp.asarray([p[-1] for p in prompts], jnp.int32)
    pos = jnp.asarray([P - 1] * B, jnp.int32)
    ref_logits, _, _ = decode_step_paged(
        params, cache, toks, pos, tables, cfg, kernel=False)

    garbage = jnp.concatenate(
        [toks[:, None], jnp.zeros((B, K - 1), jnp.int32)], axis=1
    )
    ver_logits, _ = decode_step_verify(
        params, jax.tree.map(jnp.copy, cache), garbage, pos, tables, cfg
    )
    np.testing.assert_array_equal(
        np.asarray(ver_logits[:, 0]), np.asarray(ref_logits)
    )


def test_verify_rejects_sliding_window(model):
    params, _ = model
    cfg = _cfg(sliding_window=8)
    cache = init_kv_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="sliding"):
        decode_step_verify(
            params, cache, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32), cfg,
        )


# --------------------------------------------------------------------- #
# EngineConfig knob
# --------------------------------------------------------------------- #
def test_speculate_k_validation():
    with pytest.raises(ValueError, match="speculate_k"):
        EngineConfig(speculate_k=1).validate()
    with pytest.raises(ValueError, match="speculate_k"):
        EngineConfig(speculate_k=-2).validate()
    with pytest.raises(ValueError, match="greedy"):
        EngineConfig(speculate_k=4, temperature=0.7).validate()
    EngineConfig(speculate_k=4, temperature=0.0).validate()
    EngineConfig(speculate_k=0, temperature=0.7).validate()


def test_speculate_k_env_resolution():
    assert EngineConfig().resolved_speculate_k() == 0
    with _env("RLT_SERVE_SPECULATE_K", "4"):
        assert EngineConfig().resolved_speculate_k() == 4
        # the explicit field beats the env
        assert EngineConfig(speculate_k=2).resolved_speculate_k() == 2


# --------------------------------------------------------------------- #
# engine e2e: speculation is token-invisible
# --------------------------------------------------------------------- #
def _staggered_run(params, cfg, ecfg, prompts, n_new):
    eng = InferenceEngine(params, cfg, engine_config=ecfg)
    comps = [eng.submit(prompts[0], max_new_tokens=n_new[0]),
             eng.submit(prompts[1], max_new_tokens=n_new[1])]
    for _ in range(3):
        eng.step()
    comps += [eng.submit(p, max_new_tokens=n)
              for p, n in zip(prompts[2:], n_new[2:])]
    eng.run_until_idle()
    return eng, comps


def test_engine_speculative_token_identity(model):
    """Staggered multi-request serving at k=4 == sequential generate(),
    with flat jit caches (zero steady-state recompiles even though per-row
    acceptance varies every tick)."""
    params, cfg = model
    prompts = [[5, 9, 5, 9, 5, 9, 5], [3, 3, 3, 3],
               [7, 1, 2, 7, 1, 2], [11, 12, 13]]
    n_new = [10, 8, 12, 6]
    ecfg = EngineConfig(
        num_slots=2, max_len=32, max_prompt_len=8, temperature=0.0,
        speculate_k=4, num_kv_blocks=64,
    )
    eng, comps = _staggered_run(params, cfg, ecfg, prompts, n_new)
    for c, p, n in zip(comps, prompts, n_new):
        assert c.tokens == _reference(params, cfg, p, n)
    assert eng.compile_stats() == {
        "prefill_compiles": 1, "decode_compiles": 1
    }
    # the accounting accepted tokens per row tick is built on
    assert eng.stats["spec_row_ticks"] > 0
    assert eng.stats["accepted_tokens"] >= eng.stats["spec_row_ticks"]
    # fewer decode ticks than tokens is the whole point
    assert eng.stats["decode_steps"] < eng.stats["tokens_out"]


def test_periodic_prompts_take_fewer_ticks_as_k_grows():
    """The regime prompt-lookup speculation exists for: a small vocabulary
    and periodic prompts push greedy decoding into loops the n-gram proposer
    rides. As `speculate_k` goes 0 -> 2 -> 4 the same tokens take fewer
    decode ticks, and a speculating row lands more than one token a tick."""
    cfg = _cfg(vocab_size=32)
    params = init_params(jax.random.key(0), cfg)
    prompts = [
        [3, 7, 11, 3, 7, 11, 3, 7], [5, 5, 9, 5, 5, 9, 5, 5],
        [2, 4, 6, 8, 2, 4, 6, 8], [13, 1, 13, 1, 13, 1, 13, 1],
        [6, 6, 6, 6, 6, 6, 6, 6], [9, 2, 7, 9, 2, 7, 9, 2],
    ]
    streams, ticks, per_row_tick = {}, {}, {}
    for k in (0, 2, 4):
        eng = InferenceEngine(params, cfg, engine_config=EngineConfig(
            num_slots=4, max_prompt_len=8, max_len=64, temperature=0.0,
            speculate_k=k,
        ))
        comps = [eng.submit(p, max_new_tokens=40) for p in prompts]
        eng.run_until_idle()
        streams[k] = [c.tokens for c in comps]
        ticks[k] = eng.stats["decode_steps"]
        assert eng.stats["tokens_out"] == 6 * 40
        if k:
            per_row_tick[k] = (
                eng.stats["accepted_tokens"] / eng.stats["spec_row_ticks"])
    assert streams[2] == streams[0] and streams[4] == streams[0]
    assert ticks[0] > ticks[2] > ticks[4]
    assert per_row_tick[4] > per_row_tick[2] > 1.2


def test_engine_eos_mid_burst_truncates(model):
    """An EOS landing inside an accepted burst ends the request THERE:
    the tokens past it are never delivered, and the stream equals the
    unspeculated engine's bit for bit."""
    params, cfg = model
    prompt, n_new = [5, 9, 5, 9, 5, 9, 5], 12
    base = _reference(params, cfg, prompt, n_new)
    # pick an EOS id that greedy decode actually emits mid-stream, so
    # the speculative engine must cut a burst at it
    eos = base[len(base) // 2]
    want = base[: base.index(eos) + 1]

    for k in (0, 4):
        eng = InferenceEngine(
            params, cfg,
            engine_config=EngineConfig(
                num_slots=2, max_len=32, max_prompt_len=8,
                temperature=0.0, speculate_k=k,
            ),
        )
        streamed = []
        comp = eng.submit(
            prompt, max_new_tokens=n_new, eos_id=eos,
            on_token=lambda rid, t: streamed.append(t),
        )
        eng.run_until_idle()
        assert comp.tokens == want, f"k={k}"
        assert streamed == want, f"k={k}"
        assert comp.finish_reason == "eos", f"k={k}"


def test_engine_speculative_respects_length_budget(model):
    """max_new_tokens caps a burst exactly — proposing past the budget
    must not deliver past it (the n_prop <= remaining-1 clamp)."""
    params, cfg = model
    prompt = [6, 6, 6, 6, 6, 6]  # maximally speculation-friendly
    for n_new in (1, 2, 5):
        eng = InferenceEngine(
            params, cfg,
            engine_config=EngineConfig(
                num_slots=2, max_len=32, max_prompt_len=8,
                temperature=0.0, speculate_k=4,
            ),
        )
        comp = eng.submit(prompt, max_new_tokens=n_new)
        eng.run_until_idle()
        assert comp.tokens == _reference(params, cfg, prompt, n_new)
        assert comp.finish_reason in ("length", "eos")


# --------------------------------------------------------------------- #
# fused Pallas kernels (interpret mode on CPU)
# --------------------------------------------------------------------- #
def test_paged_kernel_env_knob():
    with _env(pa.PAGED_KERNEL_ENV, None):
        # unset: follows the platform default (off on CPU tier-1)
        import jax as _jax
        expect = _jax.default_backend() == "tpu"
        assert pa.paged_kernel_enabled() is expect
    with _env(pa.PAGED_KERNEL_ENV, "1"):
        assert pa.paged_kernel_enabled() is True
    for off in ("0", "", "false", "off", "no"):
        with _env(pa.PAGED_KERNEL_ENV, off):
            assert pa.paged_kernel_enabled() is False


def _gather_softmax_reference(q, k_cache, v_cache, tables, pos):
    """Plain gather + mask + softmax in float32 numpy: the logical cache
    rows of every row of the batch, whatever page they sit in. A V row
    past ``pos`` is taken as zero, as its probability is."""
    q, tables, pos = np.asarray(q, np.float32), np.asarray(tables), np.asarray(pos)
    k_cache = np.asarray(k_cache.astype(jnp.float32))
    v_cache = np.asarray(v_cache.astype(jnp.float32))
    B, Hkv, _, hd = q.shape
    C = tables.shape[1] * k_cache.shape[2]
    # [B, maxb, Hkv, bs, hd] -> [B, Hkv, C, hd]
    kg = k_cache[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, C, hd)
    vg = v_cache[tables].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, C, hd)
    mask = (np.arange(C)[None, :] <= pos[:, None])[:, None, :]
    vg = np.where(mask[..., None], vg, 0.0)
    with np.errstate(invalid="ignore"):
        s = np.einsum("bhgd,bhtd->bhgt", q, kg) / np.sqrt(hd)
    s = np.where(mask[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhgt,bhtd->bhgd", p, vg)


def test_paged_decode_attention_matches_lax_gather():
    """The Pallas kernel vs the plain gather+softmax reference: same
    argmax everywhere, logits equal to float tolerance (online-softmax
    accumulation order differs, values must not)."""
    rng = np.random.default_rng(2)
    B, Hkv, G, hd, bs, nblk, maxb = 3, 2, 2, 16, 8, 12, 4
    q = jnp.asarray(rng.standard_normal((B, Hkv, G, hd)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((nblk, Hkv, bs, hd)), jnp.float32
    )
    v_cache = jnp.asarray(
        rng.standard_normal((nblk, Hkv, bs, hd)), jnp.float32
    )
    tables = jnp.asarray(
        rng.integers(0, nblk, (B, maxb)), jnp.int32
    )
    pos = jnp.asarray([5, 17, 30], jnp.int32)

    out = pa.paged_decode_attention(
        q, k_cache, v_cache, tables, pos, interpret=True
    )
    ref = _gather_softmax_reference(q, k_cache, v_cache, tables, pos)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


# (block_size, G, Hkv, hd, pool dtype, table width): every block size,
# group size, head count and pool dtype the issue names, with widths that
# the pages of a step do and do not divide
_PAGED_CASES = [
    (8, 1, 1, 16, jnp.float32, 40),
    (8, 2, 2, 16, jnp.float32, 64),
    (8, 4, 8, 16, jnp.float32, 33),
    (8, 8, 2, 16, jnp.float32, 4),
    (8, 2, 1, 16, jnp.bfloat16, 40),
    (8, 4, 2, 16, jnp.bfloat16, 32),
    (8, 8, 8, 16, jnp.bfloat16, 7),
    (16, 1, 2, 16, jnp.float32, 20),
    (16, 2, 8, 16, jnp.float32, 16),
    (16, 4, 1, 16, jnp.float32, 35),
    (16, 8, 2, 16, jnp.float32, 3),
    (16, 1, 8, 16, jnp.bfloat16, 19),
    (16, 2, 1, 16, jnp.bfloat16, 32),
    (16, 4, 8, 128, jnp.bfloat16, 24),
    (16, 8, 2, 16, jnp.bfloat16, 17),
    (128, 1, 1, 16, jnp.float32, 5),
    (128, 2, 2, 16, jnp.float32, 4),
    (128, 4, 8, 16, jnp.float32, 3),
    (128, 8, 1, 16, jnp.float32, 1),
    (128, 1, 2, 16, jnp.bfloat16, 3),
    (128, 2, 8, 16, jnp.bfloat16, 5),
    (128, 4, 2, 128, jnp.bfloat16, 4),
    (128, 8, 8, 16, jnp.bfloat16, 2),
]


@pytest.mark.parametrize(
    "bs,G,Hkv,hd,dtype,width", _PAGED_CASES,
    ids=[
        f"bs{c[0]}-g{c[1]}-h{c[2]}-d{c[3]}-{jnp.dtype(c[4]).name}-w{c[5]}"
        for c in _PAGED_CASES
    ],
)
def test_paged_decode_attention_cases(bs, G, Hkv, hd, dtype, width):
    """One batch a case, its rows at the edges of a page and of a step's
    group of pages: ``pos`` of 0, ``bs-1``, ``bs``, ``P*bs-1``, ``P*bs``
    and the table's last position, two rows that share physical pages,
    and a free slot (``pos`` 0, every column the trash page). Every table
    column past a row's last live page names a page filled with NaN — in
    the last live group and in the dead groups alike — so a finite result
    equal to the reference shows no page past ``pos // bs`` reached it.
    Inside the last live page the columns past ``pos`` hold finite
    values, as they do in the engine."""
    rng = np.random.default_rng(bs * 1000 + G * 100 + Hkv * 10 + width)
    P = pa._pages_per_step(width, Hkv, bs, hd, dtype)
    last = width * bs - 1
    edges = sorted({
        min(p, last)
        for p in (0, bs - 1, bs, P * bs - 1, P * bs, last // 2, last)
    })
    shared = last // 2  # the two sharing rows: same pages, other queries
    pos = np.asarray([0] + edges + [shared, shared], np.int32)
    B = len(pos)
    n_pages = 2 * width + 2
    trash, poison = n_pages - 2, n_pages - 1
    tables = np.full((B, width), poison, np.int32)
    tables[0] = trash
    for b in range(1, B - 2):
        live = pos[b] // bs + 1
        tables[b, :live] = rng.permutation(trash)[:live]
    live = shared // bs + 1
    tables[B - 2, :live] = tables[B - 1, :live] = rng.permutation(trash)[:live]

    def pool():
        x = rng.standard_normal((n_pages, Hkv, bs, hd)).astype(np.float32)
        x[poison] = np.nan
        return jnp.asarray(x).astype(dtype)

    k_cache, v_cache = pool(), pool()
    # queries that are values of the pool's dtype, as the engine's are
    q = jnp.asarray(
        rng.standard_normal((B, Hkv, G, hd)), jnp.float32
    ).astype(dtype).astype(jnp.float32)

    out = np.asarray(pa.paged_decode_attention(
        q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(pos),
        interpret=True,
    ))
    assert out.shape == (B, Hkv, G, hd) and out.dtype == np.float32
    assert np.isfinite(out).all()
    ref = _gather_softmax_reference(q, k_cache, v_cache, tables, pos)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # the sharing rows read the same pages with their own queries
    assert not np.allclose(out[B - 2], out[B - 1])


@pytest.mark.parametrize(
    "width,Hkv,bs,hd,dtype,pages",
    [
        (160, 8, 16, 128, jnp.bfloat16, 16),   # the serve cells
        (128, 4, 16, 128, jnp.bfloat16, 16),
        (16, 4, 128, 128, jnp.bfloat16, 2),
        (4, 2, 8, 16, jnp.float32, 4),         # no wider than the table
        (64, 2, 8, 16, jnp.float32, 32),
        (16, 8, 128, 256, jnp.float32, 1),     # the buffers' budget binds
    ],
)
def test_pages_per_step_follows_the_static_shapes(
    width, Hkv, bs, hd, dtype, pages
):
    got = pa._pages_per_step(width, Hkv, bs, hd, dtype)
    assert got == pages
    page_bytes = Hkv * bs * hd * jnp.dtype(dtype).itemsize
    assert got == 1 or 4 * got * page_bytes <= pa._KV_SCRATCH_BYTES


def test_decode_step_paged_kernel_vs_lax_token_parity(model):
    """decode_step_paged with kernel=True vs kernel=False: identical
    greedy tokens, close logits — the RLT_PAGED_KERNEL fallback
    contract."""
    params, cfg = model
    from ray_lightning_tpu.serving.paged_kv import PagedKVPool

    pool = PagedKVPool(cfg, 2, 32, block_size=8, num_blocks=32)
    rng = np.random.default_rng(3)
    prompts = rng.integers(1, cfg.vocab_size, (2, 6)).tolist()
    for i in range(2):
        slot = pool.acquire(f"r{i}", prompt_len=6, max_new_tokens=8)
        slot.pos = 5
        pool.ensure_writable(slot)
    cache = _prefill_rows(params, cfg, prompts, 32)
    # pack the prefilled rows into the paged pool's physical blocks
    k = np.array(pool.cache["k"])
    v = np.array(pool.cache["v"])
    for b in range(2):
        # only block 0 is physical at pos=5 (the rest of the table is
        # trash until ensure_writable grows it) — and only positions
        # <= pos are ever exposed by the mask anyway
        dst = pool.kinds["full"].block_tables[b, 0]
        k[:, dst] = np.asarray(cache["k"][:, b, :, 0:8])
        v[:, dst] = np.asarray(cache["v"][:, b, :, 0:8])
    paged_cache = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    toks = jnp.asarray([p[-1] for p in prompts], jnp.int32)
    pos = jnp.asarray([5, 5], jnp.int32)
    tables = jnp.asarray(pool.kinds["full"].block_tables)

    lg_lax, _, _ = decode_step_paged(
        params, paged_cache, toks, pos, tables, cfg, kernel=False
    )
    lg_ker, _, _ = decode_step_paged(
        params, paged_cache, toks, pos, tables, cfg, kernel=True
    )
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(lg_lax, -1)), np.asarray(jnp.argmax(lg_ker, -1))
    )
    np.testing.assert_allclose(
        np.asarray(lg_lax), np.asarray(lg_ker), rtol=2e-5, atol=2e-5
    )


def test_fused_greedy_sample_bitwise():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((5, 512)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pa.fused_greedy_sample(x)),
        np.asarray(jnp.argmax(x, axis=-1).astype(jnp.int32)),
    )
    # tie-break: first max wins, same as jnp.argmax
    t = jnp.zeros((1, 512), jnp.float32).at[0, 7].set(3.0).at[0, 300].set(3.0)
    assert int(pa.fused_greedy_sample(t)[0]) == 7


def test_fused_temperature_sample_bitwise():
    """The in-kernel gumbel argmax is bitwise jax.random.categorical on
    temperature-scaled logits — the exact sampler the lax path uses."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
    key = jax.random.key(42)
    got = pa.fused_sample(x, key, temperature=0.8)
    want = jax.random.categorical(key, x / 0.8, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_sample_supported_gates():
    assert pa.fused_sample_supported(0.0, 0, 1.0)
    assert pa.fused_sample_supported(0.9, None, None)
    assert not pa.fused_sample_supported(0.9, 40, 1.0)   # top-k
    assert not pa.fused_sample_supported(0.9, 0, 0.9)    # top-p
    with pytest.raises(ValueError, match="fused_sample supports"):
        pa.fused_sample(
            jnp.zeros((1, 8), jnp.float32), jax.random.key(0),
            temperature=0.9, top_k=40,
        )


def test_drop_stream_mid_burst_resumes_bitwise(model):
    """Satellite regression: a scripted drop-stream fault firing INSIDE
    an accepted burst (k=4) kills the stream at the budget boundary, and
    the journal resume replays bitwise — the client sees every token
    exactly once, none duplicated by the burst that died mid-delivery."""
    from ray_lightning_tpu.runtime import faults
    from ray_lightning_tpu.serving import LocalReplicaFleet

    params, cfg = model
    # a speculation-friendly prompt so bursts of >1 token actually
    # happen, and a drop budget (3) that cannot line up with a burst
    # boundary every time
    prompt, n_new = [5, 9, 5, 9, 5, 9, 5], 10
    old = os.environ.get(faults.FAULT_ENV)
    old_fuse = os.environ.pop("RLT_FAULT_FUSE", None)
    os.environ[faults.FAULT_ENV] = "replica0:drop-stream@req1:3"
    faults._serve_cache = (None, [])
    try:
        fleet = LocalReplicaFleet(
            lambda: (params, cfg),
            engine_kwargs=dict(
                num_slots=2, max_prompt_len=16, max_len=32,
                temperature=0.0, speculate_k=4,
            ),
            initial_replicas=1,
            max_retries=3,
        )
        try:
            streamed = []
            entry = fleet.submit(
                prompt, max_new_tokens=n_new,
                on_token=lambda rid, t: streamed.append(t),
            )
            want = _reference(params, cfg, prompt, n_new)
            assert entry.result(timeout=180) == want
            assert streamed == want  # exactly once, in order
            assert entry.retries == 1
            assert fleet.stats()["failed"] == 0
        finally:
            fleet.shutdown()
    finally:
        if old is None:
            os.environ.pop(faults.FAULT_ENV, None)
        else:
            os.environ[faults.FAULT_ENV] = old
        if old_fuse is not None:
            os.environ["RLT_FAULT_FUSE"] = old_fuse
        faults._serve_cache = (None, [])


def test_engine_kernel_knob_token_identity(model):
    """RLT_PAGED_KERNEL=1 vs =0 around engine construction: identical
    token streams e2e (paged layout, greedy)."""
    params, cfg = model
    prompts = [[5, 9, 5, 9, 5], [3, 3, 3, 3]]
    outs = {}
    for knob in ("1", "0"):
        with _env(pa.PAGED_KERNEL_ENV, knob):
            eng = InferenceEngine(
                params, cfg,
                engine_config=EngineConfig(
                    num_slots=2, max_len=32, max_prompt_len=8,
                    temperature=0.0, num_kv_blocks=64,
                ),
            )
            comps = [eng.submit(p, max_new_tokens=8) for p in prompts]
            eng.run_until_idle()
            outs[knob] = [c.tokens for c in comps]
    assert outs["1"] == outs["0"]
    assert outs["0"][0] == _reference(params, cfg, prompts[0], 8)
