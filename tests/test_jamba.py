"""The state-space / attention decoder (``models/jamba.py``) through
``InferenceEngine``, and what it forced below it: two state leaves of unlike
shape and type in the paged pool, the two forms of the selective scan, one
key/value head under a group of query heads that is no multiple of 8 and no
rope; all held against the family's plain reference on seeded weights at a
small size (hidden 64, five layers of which layer 1 is attention and the
Mamba layers run as scans of 1 and 3, 128 channels of 16 states, a
convolution of 4, 5 query heads over 1 key/value head)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import loader
from benchmarks import reference as shared_reference
from ray_lightning_tpu.models import jamba
from ray_lightning_tpu.ops import selective_scan as ss
from ray_lightning_tpu.ops.attention import attention, reference_attention
from ray_lightning_tpu.ops.paged_attention import paged_decode_attention
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.serving.paged_kv import STATE, PagedKVPool

SIZES = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 5,
    "num_key_value_heads": 1, "head_dim": 16, "num_hidden_layers": 5,
    "attn_layer_period": 4, "attn_layer_offset": 1, "vocab_size": 97,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "rms_norm_eps": 1e-6, "dtype": "float32",
}
ENGINE = dict(num_slots=3, max_prompt_len=64, max_len=96, block_size=8, prefix_cache=False)
SEED = 7
# float32 program against float32 reference, both exact products on the CPU:
# what is left is the order of the sums, a few 1e-5 of logits of order 3
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def family():
    return loader.Manifest().family("jamba")


@pytest.fixture(scope="module")
def model(family):
    cfg = family.program.model_config(SIZES, max_seq=96, remat=False)
    return cfg, family.program.engine_params(SIZES, SEED)


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


def _reference_logits(family, seq, quant=None):
    return np.asarray(family.reference.teacher_forced_logits(
        SIZES, SEED, np.asarray([seq], np.int32), quant=quant)[0])


def _highest(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


# ---------------------------------------------------------------------- #
# the model against the plain reference
# ---------------------------------------------------------------------- #
def test_the_layer_pattern_is_the_model_types(model):
    cfg, params = model
    assert cfg.kinds == ("mamba", "attention", "mamba", "mamba", "mamba")
    assert cfg.runs() == [("mamba", 0, 1), ("attention", 0, 1), ("mamba", 1, 3)]
    full = jamba.JambaConfig()
    assert [i for i, k in enumerate(full.kinds) if k == "attention"] == [7, 21]
    assert [n for k, _, n in full.runs() if k == "mamba"] == [7, 13, 6]
    assert params["mamba"]["a_log"].shape == (4, 16, 128) and len(params["attn"]) == 1


def test_forward_equals_the_reference_and_both_controls_do_not(family, model):
    cfg, params = model
    seq = _prompts((40,), seed=1)[0]
    want = _reference_logits(family, seq)
    got = np.asarray(_highest(jamba.forward, params, jnp.asarray([seq], jnp.int32), cfg)[0])
    assert np.abs(got - want).max() < LOGIT_TOL
    low = _reference_logits(family, seq, quant=shared_reference.bf16)
    assert np.abs(low - want).max() > 50 * LOGIT_TOL
    state16 = _reference_logits(family, seq, quant=family.reference.StateOnly())
    assert np.abs(state16 - want).max() > 10 * LOGIT_TOL


def test_init_params_has_the_weights_tree(family, model):
    cfg, params = model
    made = jamba.init_params(jax.random.key(0), cfg)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shapes(made) == shapes(params)
    steps = jax.nn.softplus(made["mamba"]["b_dt"].astype(jnp.float32))
    assert 1e-3 * 0.99 <= float(steps.min()) and float(steps.max()) <= 1e-1 * 1.01
    logits = _highest(jamba.forward, made, jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32), cfg)
    assert logits.shape == (1, 5, 97) and bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("kernel", ["0", "1"], ids=["gather", "kernel"])
def test_engine_serves_the_references_logits(family, model, monkeypatch, kernel):
    """Three requests of mixed lengths side by side (one joins the running
    decode of the others), prefill at two rungs, then decode through the
    pool: every served token is the reference's first choice at its position
    up to the float tolerance, held on the LOGITS of the reference, not on
    equal tokens."""
    monkeypatch.setenv("RLT_PAGED_KERNEL", kernel)
    cfg, params = model
    engine = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    prompts = _prompts((37, 5, 64), seed=2)
    served = _highest(_serve, engine, prompts, [30, 20, 12])
    engine.shutdown(drain=False)
    for prompt, tokens in zip(prompts, served):
        seq = prompt + tokens
        logits = _reference_logits(family, seq)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        gaps = logits[at].max(axis=-1) - logits[at, np.asarray(tokens)]
        assert gaps.max() < LOGIT_TOL, gaps.max()


def test_a_row_in_a_batch_of_mixed_lengths_equals_the_same_request_alone(model):
    cfg, params = model
    prompts = _prompts((23, 50, 9), seed=3)
    together = InferenceEngine(params, cfg, EngineConfig(**ENGINE))
    batch = _serve(together, prompts, [16, 16, 16])
    together.shutdown(drain=False)
    alone = InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, num_slots=1)))
    for prompt, tokens in zip(prompts, batch):
        assert _serve(alone, [prompt], [16]) == [tokens]
    alone.shutdown(drain=False)


def test_prefill_then_decode_gives_the_references_logits(family, model):
    """The serving contract below the engine: ``prefill_blocks`` of a prompt
    padded to a rung written into the pool, then ``decode_paged`` from the
    prompt's last token on: each step's logits against the reference's at
    that position."""
    cfg, params = model
    serving = cfg.serving()
    seq = _prompts((45,), seed=4)[0]
    length = 29
    want = _reference_logits(family, seq)
    pool = PagedKVPool(cfg, 2, 96, block_size=8, prefix_cache=False)
    slot = pool.acquire("a", length, len(seq) - length)
    row = np.zeros((1, 32), np.int32)
    row[0, :length] = seq[:length]
    blocks = _highest(serving.prefill_blocks, params, jnp.asarray(row), 4, 8, (), length=length)
    write = pool.prompt_write_tables(slot.index, 4)
    cache = dict(pool.cache)
    for name, new in blocks.items():
        at = slot.index if pool.leaf_kind[name] == STATE else write[pool.leaf_kind[name]]
        cache[name] = cache[name].at[:, at].set(new)
    step = jax.jit(lambda c, t, p, tb: serving.decode_paged(params, c, t, p, {"full": tb}, ()))
    for pos in range(length - 1, len(seq) - 1):
        slot.pos = pos
        pool.ensure_writable(slot)
        token = np.zeros((2,), np.int32)
        at = np.zeros((2,), np.int32)
        token[slot.index], at[slot.index] = seq[pos], pos
        logits, cache, counters = _highest(
            step, cache, jnp.asarray(token), jnp.asarray(at), jnp.asarray(pool.block_tables))
        assert counters is None
        assert np.abs(np.asarray(logits[slot.index]) - want[pos]).max() < LOGIT_TOL, pos


# ---------------------------------------------------------------------- #
# the state: as of length - 2, whatever the rung
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("padded", [24, 32, 64])
def test_a_padded_prefill_leaves_the_state_and_tail_of_the_unpadded_one(model, padded):
    cfg, params = model
    serving = cfg.serving()
    prompt = _prompts((21,), seed=5)[0]
    row = np.zeros((1, padded), np.int32)
    row[0, :21] = prompt
    got = _highest(serving.prefill_blocks, params, jnp.asarray(row), padded // 8, 8, (), length=21)
    # the unpadded prompt less its last token, every position real
    _, want = _highest(jamba.prefill, params, jnp.asarray([prompt[:20]], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(got["ssm_state"]), np.asarray(want["ssm"]), atol=1e-5)
    np.testing.assert_allclose(  # the pool's tail is flat: [layers, 3 * C]
        np.asarray(got["conv_state"]).reshape(4, 3, 128), np.asarray(want["conv"]), atol=1e-5)
    assert got["ssm_state"].shape == (4, 16, 128) and got["ssm_state"].dtype == jnp.float32
    assert got["conv_state"].shape == (4, 3 * 128)
    assert float(jnp.abs(got["ssm_state"]).max()) > 0
    k = np.asarray(got["k_full"])  # [1, blocks, Hkv, 8, hd]: positions [0, 20) are real
    np.testing.assert_allclose(
        k.transpose(0, 2, 1, 3, 4).reshape(1, 1, -1, 16)[:, :, :20].transpose(0, 2, 1, 3),
        np.asarray(want["k"]), atol=1e-5)
    assert np.isfinite(k).all()  # the padding's rows are read by nobody, and are numbers


def test_the_tail_is_the_last_inputs_with_zeros_before_the_start(model):
    """A prompt of 2 tokens: the slot's state is as of position 0, so the
    tail holds one real input behind two zeros; of 1 token: nothing."""
    cfg, params = model
    serving = cfg.serving()
    row = np.zeros((1, 8), np.int32)
    row[0, :2] = [5, 9]
    two = _highest(serving.prefill_blocks, params, jnp.asarray(row), 1, 8, (), length=2)
    tail = np.asarray(two["conv_state"]).reshape(4, 3, 128)
    assert not tail[:, :2].any() and np.abs(tail[:, 2]).max() > 0
    one = _highest(serving.prefill_blocks, params, jnp.asarray(row), 1, 8, (), length=1)
    assert not np.asarray(one["conv_state"]).any() and not np.asarray(one["ssm_state"]).any()


# ---------------------------------------------------------------------- #
# the two kernels against their jax.numpy forms
# ---------------------------------------------------------------------- #
def _scan_inputs(n_pos, channels=256, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (n_pos, channels))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n_pos, channels)) - 2.0)
    b = jax.random.normal(ks[2], (n_pos, n))
    c = jax.random.normal(ks[3], (n_pos, n))
    a = -jnp.exp(0.5 * jax.random.normal(ks[4], (n, channels)))
    return x, dt, b, c, a, jnp.full((channels,), 0.5)


# chunks of 16 positions: n_valid at a chunk's edge (32, 16), inside a chunk
# and inside a group of 8 (37, 3), one short of the end, and nothing real
@pytest.mark.parametrize("n_valid", [None, 63, 37, 32, 16, 3, 0])
def test_the_scan_kernel_equals_the_recurrence(n_valid):
    args = _scan_inputs(64)
    y0, s0 = ss.mamba_scan(*args, n_valid, kernel=False)
    y1, s1 = ss.mamba_scan(*args, n_valid, chunk=16, kernel=True, interpret=True)
    real = 64 if n_valid is None else n_valid
    np.testing.assert_allclose(np.asarray(y1[:real]), np.asarray(y0[:real]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(y1)))
    if n_valid:  # the padding reaches neither: the unpadded sequence's state
        _, alone = ss.mamba_scan(*(t[:n_valid] for t in args[:4]), *args[4:], kernel=False)
        np.testing.assert_allclose(np.asarray(s0), np.asarray(alone), atol=1e-6)


def test_the_scan_takes_shapes_the_kernel_does_not():
    """20 positions of 48 channels are no whole groups and no lane tile: the
    ``jax.numpy`` form runs, asked for the kernel or not."""
    args = _scan_inputs(20, channels=48, n=4)
    y0, s0 = ss.mamba_scan(*args, 11, kernel=False)
    y1, s1 = ss.mamba_scan(*args, 11, kernel=True, interpret=True)
    assert np.array_equal(np.asarray(y0), np.asarray(y1)) and s1.shape == (4, 48)


@pytest.mark.parametrize("rows", [8, 16, 4])
def test_the_decode_kernel_moves_its_layers_rows_and_no_other(rows):
    x, dt, b, c, a, d = _scan_inputs(rows, seed=1)
    states = jax.random.normal(jax.random.key(9), (3, rows, 16, 256))
    y0, st0 = ss.mamba_decode(x, dt, b, c, a, d, states, 1, kernel=False)
    y1, st1 = jax.jit(lambda *t: ss.mamba_decode(*t, kernel=True, interpret=True))(
        x, dt, b, c, a, d, states, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st0), atol=1e-5)
    assert np.array_equal(np.asarray(st1[0]), np.asarray(states[0]))
    assert np.array_equal(np.asarray(st1[2]), np.asarray(states[2]))
    # one step of the recurrence, written out
    want = jnp.exp(dt[:, None, :] * a[None]) * states[1] + (dt * x)[:, None, :] * b[:, :, None]
    np.testing.assert_allclose(np.asarray(st0[1]), np.asarray(want), atol=1e-6)


def test_decode_steps_from_a_prefills_state_continue_its_scan():
    """The state and tail a scan of 20 positions hands over, moved on by 12
    single steps, are those of a scan of 32; and so are the outputs."""
    k, channels = 4, 128
    ks = jax.random.split(jax.random.key(3), 3)
    raw = jax.random.normal(ks[0], (32, channels))
    w, bias = jax.random.normal(ks[1], (k, channels)) * 0.5, jax.random.normal(ks[2], (channels,))
    _, dt, b, c, a, d = _scan_inputs(32, channels=channels, seed=4)
    x_all, tail_all = ss.causal_conv(raw, w, bias)
    y_all, s_all = ss.mamba_scan(x_all, dt, b, c, a, d, kernel=False)
    x20, tail = ss.causal_conv(raw, w, bias, 20)
    np.testing.assert_allclose(np.asarray(x20), np.asarray(x_all), atol=1e-6)
    assert np.array_equal(np.asarray(tail), np.asarray(raw[17:20]))
    _, state = ss.mamba_scan(x20, dt, b, c, a, d, 20, kernel=False)
    states, tails = state[None, None], tail.reshape(1, 1, -1)  # one layer, one row
    for t in range(20, 32):
        x, tails = ss.conv_step(raw[t][None], tails, 0, w, bias)
        np.testing.assert_allclose(np.asarray(x[0]), np.asarray(x_all[t]), atol=1e-5)
        y, states = ss.mamba_decode(x, dt[t][None], b[t][None], c[t][None], a, d, states, 0,
                                    kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_all[t]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(states[0, 0]), np.asarray(s_all), atol=1e-5)
    assert np.array_equal(np.asarray(tails[0, 0]).reshape(3, channels), np.asarray(tail_all))


# ---------------------------------------------------------------------- #
# attention at this head ratio: 20 (here 5) query heads over 1, no rope
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("group", [20, 5])
def test_paged_decode_attention_at_a_group_that_is_no_multiple_of_8(group):
    rng = np.random.default_rng(0)
    rows, bs, hd, pages = 3, 8, 128, 10
    q = jnp.asarray(rng.standard_normal((rows, 1, group, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((pages, 1, bs, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((pages, 1, bs, hd)), jnp.bfloat16)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    pos = jnp.asarray([20, 9, 0], jnp.int32)
    got = np.asarray(paged_decode_attention(q, k, v, tables, pos, interpret=True))
    assert got.shape == (rows, 1, group, hd)
    for r in range(rows):
        n = int(pos[r]) + 1
        kr = np.asarray(k[tables[r]], np.float32).transpose(1, 0, 2, 3).reshape(-1, hd)[:n]
        vr = np.asarray(v[tables[r]], np.float32).transpose(1, 0, 2, 3).reshape(-1, hd)[:n]
        s = np.asarray(q[r, 0]) @ kr.T / np.sqrt(hd)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        np.testing.assert_allclose(got[r, 0], (p / p.sum(axis=-1, keepdims=True)) @ vr,
                                   atol=2e-2, rtol=2e-2)


def test_flash_fwd_at_twenty_query_heads_over_one():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 20, 64, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 64, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, 64, 128)), jnp.float32)
    want = reference_attention(q, k, v, causal=True)
    got = attention(q, k, v, causal=True, impl="flash", interpret=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------- #
# the cache manager: two state leaves of unlike shape and type
# ---------------------------------------------------------------------- #
def test_the_pool_holds_two_state_leaves_beside_blocks_and_reports_them(model):
    cfg, _ = model
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    pool = PagedKVPool(half, 3, 96, block_size=8, prefix_cache=False)
    assert sorted(pool.kinds) == ["full"]
    assert pool.state_leaves == ["ssm_state", "conv_state"]
    assert pool.leaf_kind["ssm_state"] == STATE and pool.leaf_kind["k_full"] == "full"
    assert pool.cache["ssm_state"].shape == (4, 3, 16, 128)
    assert pool.cache["ssm_state"].dtype == jnp.float32
    assert pool.cache["conv_state"].shape == (4, 3, 3 * 128)
    assert pool.cache["conv_state"].dtype == jnp.bfloat16
    assert pool.cache["k_full"].shape == (1, 37, 1, 8, 16)
    slot = pool.acquire("a", 20, 10)
    stats = pool.stats()
    assert stats["state.layers"] == 4 and stats["state.leaves"] == 2
    assert stats["state.bytes_per_slot"] == 4 * (16 * 128 * 4 + 3 * 128 * 2)
    assert stats["state.slots_used"] == 1
    write = pool.prompt_write_tables(slot.index, 4)
    assert write[STATE].tolist() == [slot.index, 20] and (write["full"][:3] > 0).all()
    assert sorted(pool.program_tables()) == ["full"]  # the state has no table
    assert pool.bytes_per_position == 2 * 16 * 2  # K and V of one head, one layer


def test_acquire_zeroes_both_leaves_of_the_slot_and_no_other(model):
    cfg, _ = model
    pool = PagedKVPool(cfg, 2, 96, block_size=8, prefix_cache=False)
    for name in pool.state_leaves:
        pool.cache[name] = jnp.ones_like(pool.cache[name])
    slot = pool.acquire("a", 20, 10)
    for name in pool.state_leaves:
        leaf = np.asarray(pool.cache[name])
        assert not leaf[:, slot.index].any() and leaf[:, 1 - slot.index].all()


def test_a_slot_released_by_expiry_starts_its_next_request_from_zero(family, model):
    """A request that expires mid-decode leaves its state and tail in the
    slot; the next request in that slot is served as if alone."""
    cfg, params = model
    engine = InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, num_slots=1)))
    first, second = _prompts((30, 17), seed=6)
    engine.submit(first, max_new_tokens=40, deadline_ms=1e7)
    for _ in range(10):
        engine.step()
    slot = engine.pool.slots[0]
    assert float(jnp.abs(engine.pool.cache["ssm_state"][:, 0]).max()) > 0
    slot.deadline = 0.0  # long past
    engine.step()
    assert not slot.occupied
    served = _highest(_serve, engine, [second], [12])[0]
    engine.shutdown(drain=False)
    logits = _reference_logits(family, second + served)
    at = np.arange(len(second) - 1, len(second) + len(served) - 1)
    assert (logits[at].max(axis=-1) - logits[at, np.asarray(served)]).max() < LOGIT_TOL


@pytest.mark.parametrize("setting,match", [
    (dict(speculate_k=4), "cannot be taken back out of a state"),
    (dict(prefix_cache=True), "says nothing of the state behind it"),
    (dict(role="prefill"), "which no shipment carries"),
])
def test_what_the_engine_cannot_do_over_a_state_kind_is_refused_by_name(model, setting, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        InferenceEngine(params, cfg, EngineConfig(**dict(ENGINE, **setting)))


def test_a_mesh_and_what_the_config_cannot_run_are_refused_by_name(model):
    cfg, params = model

    class _Mesh:
        size = 2

    with pytest.raises(NotImplementedError, match="mesh"):
        jamba.forward(params, jnp.zeros((1, 4), jnp.int32), cfg, mesh=_Mesh())
    for setting, match in [(dict(num_experts=16), "num_experts"),
                           (dict(mamba_proj_bias=True), "mamba_proj_bias"),
                           (dict(tie_word_embeddings=False), "tie_word_embeddings"),
                           (dict(sliding_window=4096), "sliding_window"),
                           (dict(attn_layer_offset=14), "attn_layer_offset")]:
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **setting)
