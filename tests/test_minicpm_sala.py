"""The sparse / lightning attention decoder (``models/minicpm_sala.py``)
through ``InferenceEngine``, and what it forced below it: the state kind of
the paged pool, a pooled-key leaf with a block shape of its own, selection
inside paged attention, and the two forms of lightning attention; all held
against the family's plain reference on seeded weights at a small size
(hidden 64, one period of a sparse and three lightning layers, pooling 4
stride 2, blocks of 8, top-4, window 16, ``dense_len`` 32), with contexts of
several times ``dense_len`` so that the selection really drops blocks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader
from benchmarks import reference as shared_reference
from ray_lightning_tpu.models import minicpm_sala as ms
from ray_lightning_tpu.ops import lightning_attention as la
from ray_lightning_tpu.ops import sparse_attention as sa
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.paged_kv import STATE, PagedKVPool

SIZES = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
    "lightning_head_dim": 16, "num_hidden_layers": 4, "published_num_hidden_layers": 32,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn",
                    "lightning-attn", "minicpm4"],
    "vocab_size": 97, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "dtype": "float32",
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
                      "window_size": 16, "init_blocks": 1, "dense_len": 32},
}
# a window of 8: two or three forced blocks, so one or two of the four are chosen by score
FREER = dict(SIZES, sparse_config=dict(SIZES["sparse_config"], window_size=8))
ENGINE = dict(num_slots=3, max_prompt_len=128, max_len=192, block_size=8, prefix_cache=False)
SEED = 7


@pytest.fixture(scope="module")
def family():
    return loader.Manifest().family("minicpm_sala")


def _model(family, sizes=SIZES, max_seq=192):
    cfg = family.program.model_config(sizes, max_seq=max_seq, remat=False)
    return cfg, family.program.engine_params(sizes, SEED)


@pytest.fixture(scope="module")
def model(family):
    return _model(family)


def _serve(engine, prompts, new):
    outs = {}
    for i, (p, n) in enumerate(zip(prompts, new)):
        engine.submit(p, max_new_tokens=n, request_id=f"r{i}",
                      on_token=lambda rid, t: outs.setdefault(rid, []).append(t))
    engine.run_until_idle()
    return [outs[f"r{i}"] for i in range(len(prompts))]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 97, n).tolist() for n in lengths]


def _reference_logits(family, sizes, seq, quant=None):
    return np.asarray(family.reference.teacher_forced_logits(
        sizes, SEED, np.asarray([seq], np.int32), quant=quant)[0])


# ---------------------------------------------------------------------- #
# the engine against the plain reference
# ---------------------------------------------------------------------- #
# float32 program against float32 reference, both exact products on the CPU:
# what is left is the order of the sums (the chunked scan against the
# recurrence, flash order against one softmax), a few 1e-5 of logits whose
# spread is about 0.3
LOGIT_TOL = 2e-4


@pytest.mark.parametrize("sizes", [SIZES, FREER], ids=["window16", "window8"])
@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel"])
def test_engine_serves_the_references_greedy_tokens(family, monkeypatch, kernel, sizes):
    """Prompts of 100, 5, 70 and 37 tokens (padded to the one rung of 128),
    20 to 50 new tokens each, on 3 slots, so a row 140 positions long decodes
    beside one of 6 and a slot is used twice: every served token is the
    argmax of the REFERENCE's teacher-forced logits at its position (cache,
    chunks, selection through tables and state on one side, none of them on
    the other), and the selection dropped pages."""
    monkeypatch.setenv("RLT_PAGED_KERNEL", kernel)
    cfg, params = _model(family, sizes)
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    prompts, new = _prompts((100, 5, 70, 37)), [40, 20, 30, 50]
    served = _serve(engine, prompts, new)
    for p, toks in zip(prompts, served):
        logits = _reference_logits(family, sizes, p + toks)
        rows = logits[len(p) - 1: len(p) + len(toks) - 1]
        assert np.argmax(rows, -1).tolist() == toks
    st = engine.stats
    assert 0 < st["kv_positions_selected"] < st["kv_positions_live"]
    assert 0 < st["indexer_keys_scanned"]
    assert st["state_bytes_touched"] == st["decode_steps"] * 2 * 3 * (3 * 4 * 16 * 16 * 4)
    assert engine.compile_stats() == {"prefill_compiles": 1, "decode_compiles": 1}
    assert engine.pool.stats()["blocks_used"] == 0


def test_forward_equals_the_reference_and_the_bfloat16_control_does_not(family, model):
    """The program's teacher-forced logits over 150 positions against the
    reference's, within ``LOGIT_TOL``; the reference computed in bfloat16
    (the control for a float32 configuration) is fifty times further off and
    fails the same tolerance."""
    cfg, params = model
    seq = _prompts((150,), seed=3)[0]
    got = np.asarray(ms.forward(params, jnp.asarray([seq], jnp.int32), cfg)[0])
    want = _reference_logits(family, SIZES, seq)
    assert np.abs(got - want).max() < LOGIT_TOL
    control = _reference_logits(family, SIZES, seq, quant=shared_reference.bf16)
    assert np.abs(control - want).max() > 10 * LOGIT_TOL


def test_prefill_then_decode_gives_the_references_logits(family, model):
    """Prefill of 70 positions padded to 128 and then 40 paged decode steps,
    by hand, the logits of every step against the reference's at that
    position within ``LOGIT_TOL``."""
    cfg, params = model
    serving = cfg.serving()
    table = serving.rope_table(192)
    pool = PagedKVPool(cfg, 2, 192, block_size=8, prefix_cache=False)
    seq = _prompts((110,), seed=5)[0]
    want = _reference_logits(family, SIZES, seq)
    slot = pool.acquire("a", 70, 41)
    row = np.zeros((1, 128), np.int32)
    row[0, :70] = seq[:70]
    blocks = serving.prefill_blocks(params, jnp.asarray(row), 16, 8, table, length=70)
    where = pool.prompt_write_tables(slot.index, 16)
    cache = dict(pool.cache)
    for name in ("k_full", "v_full", "kp_full"):
        cache[name] = cache[name].at[:, where["full"]].set(blocks[name])
    cache["s_state"] = cache["s_state"].at[:, slot.index].set(blocks["s_state"])
    step = jax.jit(lambda c, t, p, tb: serving.decode_paged(params, c, t, p, {"full": tb}, table))
    worst = 0.0
    for pos in range(69, 109):
        slot.pos = pos
        pool.ensure_writable(slot)
        token = np.zeros((2,), np.int32)
        at = np.zeros((2,), np.int32)
        token[slot.index], at[slot.index] = seq[pos], pos
        logits, cache, _ = step(cache, jnp.asarray(token), jnp.asarray(at),
                                jnp.asarray(pool.block_tables))
        worst = max(worst, float(np.abs(np.asarray(logits[slot.index]) - want[pos]).max()))
    assert worst < LOGIT_TOL


# ---------------------------------------------------------------------- #
# the lightning state
# ---------------------------------------------------------------------- #
def _recurrence(q, k, v, slopes, n_valid):
    """S_t = lam S_{t-1} + k_t^T v_t, o_t = q_t S_t / sqrt(hd), in float64."""
    h, t, hd = q.shape
    lam = np.exp(-np.asarray(slopes, np.float64))[:, None, None]
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    state, out, kept = np.zeros((h, hd, hd)), np.zeros((h, t, hd)), None
    for i in range(t):
        state = lam * state + k[:, i, :, None] * v[:, i, None, :]
        out[:, i] = np.einsum("hd,hde->he", q[:, i], state) / np.sqrt(hd)
        if i == n_valid - 1:
            kept = state.copy()
    return out, kept


def _qkv(shape, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype) for k in keys)


@pytest.mark.parametrize("n_valid", [40, 33, 16, 7, 1])
@pytest.mark.parametrize("kernel", [False, True], ids=["lax", "kernel"])
def test_the_chunked_scan_equals_the_recurrence(kernel, n_valid):
    """40 positions in chunks of 16, ``n_valid`` of them real: the outputs of
    the real positions and the state after the last of them are the plain
    recurrence's (order of float32 sums apart: 1e-4 of values up to 20);
    the positions behind feed nothing, wherever a chunk's edge falls."""
    q, k, v = _qkv((4, 40, 16))
    slopes = la.lightning_slopes(4)
    want_o, want_s = _recurrence(q, k, v, slopes, n_valid)
    o, state = la.lightning_prefill(q, k, v, slopes, n_valid, chunk=16, kernel=kernel)
    np.testing.assert_allclose(np.asarray(o)[:, :n_valid], want_o[:, :n_valid], atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), want_s, atol=1e-4)


def test_the_scan_in_bfloat16_keeps_a_float32_state():
    """bfloat16 inputs through the kernel's bfloat16 path (two halves where
    the float32 state meets the MXU): the state is within 1e-3 of the float64
    recurrence over the same bfloat16 values, the outputs within their own
    rounding to bfloat16 (2 ** -8 of values up to 20)."""
    q, k, v = _qkv((4, 64, 16), seed=1, dtype=jnp.bfloat16)
    slopes = la.lightning_slopes(4)
    want_o, want_s = _recurrence(*(np.asarray(a, np.float32) for a in (q, k, v)), slopes, 50)
    for kernel in (False, True):
        o, state = la.lightning_prefill(q, k, v, slopes, 50, chunk=16, kernel=kernel)
        assert state.dtype == jnp.float32 and o.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(state), want_s, atol=1e-3)
        np.testing.assert_allclose(
            np.asarray(o, np.float32)[:, :50], want_o[:, :50], atol=0.1, rtol=2 ** -7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_decode_update_moves_its_rows_of_the_stack_and_no_other(dtype):
    """Three rows of the second layer of a stack of two: the kernel and the
    ``jax.numpy`` form agree, the rows move on by one position of the
    recurrence, and the first layer's rows are untouched."""
    q, k, v = _qkv((3, 4, 16), seed=2, dtype=dtype)
    states = jax.random.normal(jax.random.key(9), (6, 4, 16, 16), jnp.float32)
    slopes = la.lightning_slopes(4)
    lam = np.exp(-slopes)[None, :, None, None]
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    want = lam * np.asarray(states[3:]) + f32(k)[..., :, None] * f32(v)[..., None, :]
    want_o = np.einsum("bhd,bhde->bhe", f32(q), want) / 4.0
    for kernel in (False, True):
        o, new = la.lightning_decode(q, k, v, states, slopes, first_row=3, kernel=kernel)
        np.testing.assert_allclose(np.asarray(new[3:]), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-4 if dtype == jnp.bfloat16 else 1e-5)
        assert (np.asarray(new[:3]) == np.asarray(states[:3])).all()


@pytest.mark.parametrize("padded", [72, 128, 192])
def test_a_padded_prefill_leaves_the_state_of_the_unpadded_one(model, padded):
    """A prompt of 70 tokens alone, and padded to 72, 128 and 192: the same
    lightning state (the padding neither decays nor feeds it), the same last
    real position's logits, and K, V and pooled keys equal where they are
    the prompt's."""
    cfg, params = model
    table = ms.rope_table(cfg, 192)
    prompt = np.asarray(_prompts((70,), seed=4)[0], np.int32)
    logits, cache = ms.prefill(params, jnp.asarray(prompt[None]), cfg, table)
    row = np.zeros((1, padded), np.int32)
    row[0, :70] = prompt
    got_logits, got = ms.prefill(params, jnp.asarray(row), cfg, table, length=70)
    # two chunkings of the same float32 sums, of values up to 30
    np.testing.assert_allclose(
        np.asarray(got["state"]), np.asarray(cache["state"]), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(cache["state"]).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(logits), atol=1e-5)
    for name, n in (("k", 70), ("v", 70), ("kp", 34)):  # pooled key 33 ends at position 69
        np.testing.assert_allclose(
            np.asarray(got[name][:, :n]), np.asarray(cache[name][:, :n]), atol=1e-6)


def test_the_slots_state_is_the_one_before_the_prompts_last_token(model):
    """``prefill_blocks`` hands the engine the state as of position ``length
    - 2``: the first decode step feeds the last prompt token again, which K
    and V take as it is and a state would take twice."""
    cfg, params = model
    table = ms.rope_table(cfg, 192)
    row = np.zeros((1, 128), np.int32)
    row[0, :70] = _prompts((70,), seed=4)[0]
    blocks = cfg.serving().prefill_blocks(params, jnp.asarray(row), 16, 8, table, length=70)
    _, short = ms.prefill(params, jnp.asarray(row[:, :69]), cfg, table)
    np.testing.assert_allclose(
        np.asarray(blocks["s_state"]), np.asarray(short["state"]), rtol=1e-5, atol=1e-5)
    assert blocks["k_full"].shape == (1, 16, 2, 8, 16)
    assert blocks["kp_full"].shape == (1, 16, 2, 4, 16)


# ---------------------------------------------------------------------- #
# the indexer: pooled keys and the chosen blocks
# ---------------------------------------------------------------------- #
def _layer0(family, sizes, seq):
    """The first (sparse) layer's q [T, Hkv, G, hd] and k [T, Hkv, hd] of a
    sequence, by the reference's own functions on the seeded weights."""
    ref, w = family.reference, family.weights
    m, keys = w.dims(sizes), w.seed_keys(sizes, SEED)
    lp = {n: a.astype(jnp.float32) for n, a in w.layer_leaves(sizes, keys, 0).items()}
    x = m["scale_emb"] * w.top_leaves(sizes, keys)["embed"][jnp.asarray(seq)].astype(jnp.float32)
    h = ref.rmsnorm(x, lp["attn_norm"], m["eps"])
    t = len(seq)
    q = ref.rmsnorm((h @ lp["wq"]).reshape(t, 2, 2, 16), lp["q_norm"], m["eps"])
    k = ref.rmsnorm((h @ lp["wk"]).reshape(t, 2, 16), lp["k_norm"], m["eps"])
    return m, q, k


def _reference_choice(family, sizes, seq):
    """{(position, key/value head): the sorted blocks the reference's query
    chose}, for the positions that select."""
    m, q, k = _layer0(family, sizes, seq)
    pos = jnp.arange(len(seq))
    out = {}
    for head in range(2):
        kp = family.reference.pooled_keys(k[:, head], m)
        picked = np.sort(np.asarray(
            family.reference.chosen_blocks(q[:, head], kp, pos, m)), -1)
        out.update({(i, head): picked[i].tolist()
                    for i in range(m["dense_len"] - 1, len(seq))})
    return out


@pytest.mark.parametrize("sizes", [SIZES, FREER], ids=["window16", "window8"])
def test_the_programs_chosen_blocks_are_the_references_on_every_row_and_step(
        family, monkeypatch, sizes):
    """Two rows decoded side by side through the engine from prompts of 60
    and 33 tokens to 120 and 90 positions; what ``compose_tables`` was handed
    on every step (the blocks chosen from the cached, incrementally completed
    pooled keys) against the reference's choice from scratch at the same
    position, as sets; and a prompt's block mask against the same."""
    cfg, params = _model(family, sizes)
    seen = []
    compose = ms.compose_tables

    def spy(tables, chosen, pos, spec):
        jax.debug.callback(lambda c, p: seen.append((np.array(c), np.array(p))), chosen, pos)
        return compose(tables, chosen, pos, spec)

    monkeypatch.setattr(ms, "compose_tables", spy)
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    prompts = _prompts((60, 33), seed=6)
    served = _serve(engine, prompts, [61, 58])
    jax.effects_barrier()
    wants = [_reference_choice(family, sizes, p + t) for p, t in zip(prompts, served)]
    lengths = [len(p) + len(t) for p, t in zip(prompts, served)]
    checked = 0
    for chosen, pos in seen:
        for slot in range(2):  # the two requests took slots 0 and 1 in order
            i = int(pos[slot])
            if i + 1 < 32 or i >= lengths[slot] - 1:
                continue
            for head in range(2):
                assert sorted(chosen[slot, head].tolist()) == wants[slot][(i, head)], (slot, i)
                checked += 1
    assert checked >= 2 * (120 - 60 + 90 - 33 - 4)
    free = sum(len(set(w) - {0} - set(range((i - sizes["sparse_config"]["window_size"] + 1) // 8,
                                            i // 8 + 1))) for (i, _), w in wants[0].items())
    assert free > 0  # some block was chosen by its score, not by a rule
    # the same choice where a whole prompt is prefilled
    seq = prompts[0] + served[0]
    _, q, k = _layer0(family, sizes, seq)
    t = len(seq)
    kp = sa.pooled_keys(jnp.pad(k, ((0, t % 2), (0, 0), (0, 0))), cfg.sparse)
    mask = np.asarray(sa.prompt_block_mask(
        q.transpose(1, 2, 0, 3), kp.swapaxes(0, 1), cfg.sparse))
    for (i, head), blocks in wants[0].items():
        assert np.flatnonzero(mask[head, i]).tolist() == blocks


def test_the_cached_pooled_keys_are_the_means_of_the_cached_keys(family, model):
    """After a prefill of 37 tokens and 50 decode steps the pool's pooled-key
    leaf holds, for every complete pooled key of the request, the mean of its
    4 cached keys: those prefill wrote and those decode completed, one every
    second step."""
    cfg, params = model
    engine = InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, num_slots=1)))
    engine.submit(_prompts((37,), seed=8)[0], max_new_tokens=60)
    for _ in range(50):
        engine.step()
    slot = engine.pool.slots[0]
    table = engine.pool.block_tables[0]
    rows = lambda leaf: np.asarray(leaf)[0][table].transpose(1, 0, 2, 3).reshape(2, -1, 16)
    k, kp = rows(engine.pool.cache["k_full"]), rows(engine.pool.cache["kp_full"])
    complete = (slot.pos - 4) // 2 + 1  # decode has written position pos - 1
    assert complete > 35
    want = np.stack([k[:, 2 * j: 2 * j + 4].mean(axis=1) for j in range(complete)], axis=1)
    np.testing.assert_allclose(kp[:, :complete], want, atol=1e-6)
    engine.shutdown(drain=False)


def test_a_block_scores_the_pooled_keys_that_overlap_it():
    spec = sa.SparseSpec(kernel=4, stride=2, block=8, topk=4, window=16, dense_len=32)
    s = jnp.arange(16.0)[None]  # pooled key j starts at 2 j and reaches 2 j + 3
    # block 1 (positions 8..15) is reached by pooled keys 3 (6..9) to 7 (14..17)
    assert np.asarray(sa._block_scores(s, spec)).tolist() == [[3.0, 7.0, 11.0, 15.0]]
    s = s.at[0, 3].set(99.0)
    assert np.asarray(sa._block_scores(s, spec)).tolist() == [[99.0, 99.0, 11.0, 15.0]]


def test_composed_tables_end_at_the_rows_own_block():
    """A selecting row's chosen blocks in ascending order with its own block
    last and its position counted in that order; a row under ``dense_len``
    keeps its own table and position."""
    spec = sa.SparseSpec(kernel=4, stride=2, block=8, topk=4, window=16, dense_len=32)
    tables = jnp.asarray([np.arange(100, 124), np.arange(200, 224)], jnp.int32)
    chosen = jnp.asarray([[[5, 0, 4, 2], [5, 4, 3, 0]], [[0, 1, 2, 3], [0, 1, 2, 3]]], jnp.int32)
    composed, last = sa.compose_tables(tables, chosen, jnp.asarray([43, 20]), spec)
    assert np.asarray(composed[0]).tolist() == [[100, 102, 104, 105], [100, 103, 104, 105]]
    assert np.asarray(composed[1]).tolist() == [[200, 201, 202, 203]] * 2
    assert np.asarray(last).tolist() == [3 * 8 + 3, 20]
    assert np.asarray(sa.selected_positions(jnp.asarray([43, 20, 31, 30]), spec)).tolist() == [
        28, 21, 32, 31]


def test_the_selected_attention_kernel_equals_the_masked_softmax():
    """``flash_fwd_selected`` interpreted, 64 positions in tiles of 32 with
    4 blocks a tile: each query row's bits say which blocks of a tile it
    chose; against one masked softmax."""
    spec = sa.SparseSpec(kernel=4, stride=2, block=8, topk=4, window=16, dense_len=32)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((4, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 64, 16)), jnp.float32)
    kp = sa.pooled_keys(k.swapaxes(0, 1), spec).swapaxes(0, 1)
    mask = sa.prompt_block_mask(q.reshape(2, 2, 64, 16), kp, spec)
    assert mask.shape == (2, 64, 8) and bool(mask[:, :31].all())
    assert int(mask[0, 63].sum()) == 4 and bool(mask[:, :, 0].all())
    bits = sa.tile_bits(mask, 4)
    assert bits.shape == (2, 2, 64, 1)
    assert int(bits[0, 1, 63, 0]) == sum(int(mask[0, 63, 4 + c]) << c for c in range(4))
    want = sa.selected_attention(q, k, v, mask, spec, kernel=False)
    got = sa.selected_attention(q, k, v, mask, spec, kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_indexer_scores_in_float32_at_the_highest_precision(family):
    """A bfloat16 model: the one product that scores the pooled keys takes
    float32 operands at the highest precision (a default float32 product is
    one bfloat16 pass on the chip, and a rounded score flips near-ties)."""
    cfg, params = _model(family, dict(SIZES, dtype="bfloat16"))
    serving = cfg.serving()
    pool = PagedKVPool(cfg, 2, 192, block_size=8, prefix_cache=False)
    jaxpr = jax.make_jaxpr(lambda c, t, p, tb: serving.decode_paged(
        params, c, t, p, {"full": tb}, serving.rope_table(192)))(
        pool.cache, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.asarray(pool.block_tables))

    def dots(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    pooled = 24 * 4  # every pooled key of a row's table: the scores' last axis
    scores = [e for e in dots(jaxpr.jaxpr) if e.outvars[0].aval.shape[-1] == pooled]
    assert len(scores) == 1
    assert {v.aval.dtype for v in scores[0].invars} == {jnp.dtype(jnp.float32)}
    assert scores[0].params["precision"] == (jax.lax.Precision.HIGHEST,) * 2


# ---------------------------------------------------------------------- #
# the cache manager: the state kind
# ---------------------------------------------------------------------- #
def test_the_pool_holds_state_beside_blocks_and_reports_it_by_name(model):
    cfg, _ = model
    pool = PagedKVPool(cfg, 3, 192, block_size=8, prefix_cache=False)
    assert sorted(pool.kinds) == ["full"] and pool.state_leaves == ["s_state"]
    assert pool.leaf_kind["s_state"] == STATE and pool.leaf_kind["kp_full"] == "full"
    assert pool.cache["s_state"].shape == (3, 3, 4, 16, 16)
    assert pool.cache["s_state"].dtype == jnp.float32
    assert pool.cache["k_full"].shape == (1, 73, 2, 8, 16)
    assert pool.cache["kp_full"].shape == (1, 73, 2, 4, 16)  # 4 pooled keys a block of 8
    slot = pool.acquire("a", 20, 10)
    stats = pool.stats()
    assert stats["state.layers"] == 3 and stats["state.slots_used"] == 1
    assert stats["state.bytes_per_slot"] == 3 * 4 * 16 * 16 * 4
    write = pool.prompt_write_tables(slot.index, 4)
    assert write[STATE].tolist() == [slot.index, 20] and (write["full"][:3] > 0).all()
    assert sorted(pool.program_tables()) == ["full"]  # the state has no table
    pool.release(slot.index)
    assert pool.stats()["state.slots_used"] == 0
    # what a position adds: K, V and an eighth... a stride-th of a pooled key
    assert pool.bytes_per_position == 2 * 2 * 16 * 4 + 2 * 16 * 4 // 2


def test_acquire_zeroes_the_slots_state_whoever_held_it(model):
    cfg, _ = model
    pool = PagedKVPool(cfg, 2, 192, block_size=8, prefix_cache=False)
    pool.cache["s_state"] = jnp.ones_like(pool.cache["s_state"])
    slot = pool.acquire("a", 20, 10)
    state = np.asarray(pool.cache["s_state"])
    assert (state[:, slot.index] == 0).all() and (state[:, 1 - slot.index] == 1).all()
    pool.cache["s_state"] = jnp.ones_like(pool.cache["s_state"])
    pool.release(slot.index)
    assert pool.acquire("b", 5, 5).index == slot.index
    assert (np.asarray(pool.cache["s_state"])[:, slot.index] == 0).all()


def test_a_slot_released_by_expiry_starts_its_next_request_from_zero(family, model):
    """A request expires mid-decode on the engine's one slot and leaves its
    state behind; the next request on that slot is served the reference's
    tokens, which a state that was not zeroed and rewritten would not give."""
    cfg, params = model
    engine = InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, num_slots=1)))
    first = engine.submit(_prompts((50,), seed=10)[0], max_new_tokens=60, deadline_ms=1e6)
    for _ in range(10):
        engine.step()
    assert float(jnp.abs(engine.pool.cache["s_state"][:, 0]).max()) > 0.1
    engine.pool.slots[0].deadline = 0.0  # past
    engine.step()
    assert first.finish_reason == "expired" and engine.pool.occupancy == 0
    prompt = _prompts((40,), seed=11)[0]
    (served,) = _serve(engine, [prompt], [25])
    logits = _reference_logits(family, SIZES, prompt + served)
    assert np.argmax(logits[39:-1], -1).tolist() == served
    assert engine.pool.tenancies[0] == [first.request_id, "r0"]


@pytest.mark.parametrize("setting,match", [
    (dict(prefix_cache=True), "prefix_cache=True.*state kind"),
    (dict(speculate_k=2), "speculate_k=2.*state kind"),
    (dict(role="prefill"), "role='prefill'.*state kind"),
    (dict(role="decode"), "role='decode'.*state kind"),
], ids=["prefix-cache", "speculation", "migration-out", "migration-in"])
def test_what_the_engine_cannot_do_over_a_state_kind_is_refused_by_name(model, setting, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, **setting)))


def test_a_mesh_and_what_the_config_cannot_run_are_refused_by_name(model):
    cfg, params = model

    class Mesh:
        size = 4
    with pytest.raises(NotImplementedError, match="mesh"):
        ms.forward(params, jnp.zeros((1, 4), jnp.int32), cfg, mesh=Mesh())
    with pytest.raises(ValueError, match="prefix sharing over a state kind"):
        PagedKVPool(cfg, 2, 192, block_size=8, prefix_cache=True)
    with pytest.raises(ValueError, match="block_size=16"):
        PagedKVPool(cfg, 2, 192, block_size=16, prefix_cache=False)
    with pytest.raises(ValueError, match="mixer_types"):
        dataclasses.replace(cfg, mixer_types=("minicpm4", "mamba", "minicpm4", "minicpm4"))
    with pytest.raises(ValueError, match="lightning_nkv"):
        dataclasses.replace(cfg, lightning_kv_heads=2)
    with pytest.raises(ValueError, match="dense_len"):
        sa.SparseSpec(block=64, topk=64, dense_len=2048)
    with pytest.raises(ValueError, match="topk"):
        sa.SparseSpec(kernel=4, stride=2, block=8, topk=3, window=16, dense_len=32)


def test_the_residual_scale_is_made_of_the_published_depth(model):
    cfg, _ = model
    assert cfg.n_layers == 4 and cfg.published_layers == 32
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg.kinds == ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
    assert cfg.layers_of("minicpm4") == 1 and cfg.layers_of("lightning-attn") == 3
