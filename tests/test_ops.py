"""Numerical tests for the pallas ops (interpret mode on CPU) against
reference implementations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.ops.attention import attention, reference_attention
from ray_lightning_tpu.ops.rmsnorm import _rmsnorm_ref, rmsnorm
from ray_lightning_tpu.ops.rope import apply_rope, rope_angles


def _qkv(b, hq, hkv, s, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(kq, (b, hq, s, d), dtype),
        jax.random.normal(kk, (b, hkv, s, d), dtype),
        jax.random.normal(kv, (b, hkv, s, d), dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(2, 4, 4, 256, 128)
    ref = reference_attention(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal, impl="flash", interpret=True)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4


def test_flash_gqa():
    q, k, v = _qkv(1, 8, 2, 256, 128)
    ref = reference_attention(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="flash", interpret=True)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4


@pytest.mark.parametrize(
    "hq,hkv,causal,blocks",
    [
        (2, 2, True, None),
        (8, 2, True, (64, 64)),  # GQA fold crosses q-block boundaries
        (8, 2, False, (64, 64)),  # non-causal branch of the folded grid
        (4, 1, True, None),  # maximal group
    ],
    ids=["mha", "gqa_multiblock", "gqa_noncausal", "gqa_group4"],
)
def test_flash_gradients_match(hq, hkv, causal, blocks):
    """All grads vs the reference. The dK/dV kernel folds the GQA group
    reduction into its accumulator (grid over KV heads), so dk/dv must
    equal the reference's repeat-then-sum across group sizes, causal
    modes, and block boundaries."""
    q, k, v = _qkv(1, hq, hkv, 256, 128)
    bq, bk = blocks or (None, None)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(
        loss(lambda q, k, v: reference_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_fl = jax.grad(
        loss(lambda q, k, v: attention(
            q, k, v, causal=causal, impl="flash", interpret=True,
            block_q=bq, block_k=bk,
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 1e-4, name


def test_attention_auto_dispatch_untileable_shapes():
    # seq 100 does not divide into blocks -> reference path, still correct
    q, k, v = _qkv(2, 2, 2, 100, 64)
    out = attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-5


@pytest.mark.parametrize("causal", [True, False])
def test_flash_head_dim_64(causal):
    """head_dim 64 (BERT-base) takes the flash path via lane padding —
    numerically exact because padded q/k columns contribute zero scores and
    padded v columns carry zero values/gradients (VERDICT r1 #5)."""
    from ray_lightning_tpu.ops.attention import flash_supported

    q, k, v = _qkv(2, 4, 4, 512, 64)
    assert flash_supported(q.shape, k.shape)
    ref = reference_attention(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal, impl="flash", interpret=True)
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(
        loss(lambda q, k, v: reference_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_fl = jax.grad(
        loss(lambda q, k, v: attention(q, k, v, causal=causal, impl="flash", interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_fl):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 2e-3


def test_bert_base_shape_dispatches_flash():
    """The BASELINE config-3 model (BERT-base: 12 heads, head_dim 64,
    seq 512) must auto-dispatch to the flash path, not the O(S^2) einsum."""
    from ray_lightning_tpu.models.bert import BertConfig
    from ray_lightning_tpu.ops.attention import flash_supported

    cfg = BertConfig.base()
    hd = cfg.dim // cfg.n_heads
    assert hd == 64
    shape = (2, cfg.n_heads, cfg.max_seq, hd)
    assert flash_supported(shape, shape)


def test_rmsnorm_matches_reference():
    x = jax.random.normal(jax.random.key(0), (4, 64, 256), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (256,), jnp.float32)
    out = rmsnorm(x, w)  # CPU -> reference path
    ref = _rmsnorm_ref(x, w, 1e-6)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    # gradient exists
    g = jax.grad(lambda w: rmsnorm(x, w).sum())(w)
    assert g.shape == w.shape


def test_rope_rotation_preserves_norm():
    cos, sin = rope_angles(16, 64)
    x = jax.random.normal(jax.random.key(0), (2, 16, 4, 64), jnp.float32)
    out = apply_rope(x, cos, sin)
    assert out.shape == x.shape
    norm_in = jnp.linalg.norm(x, axis=-1)
    norm_out = jnp.linalg.norm(out, axis=-1)
    assert float(jnp.max(jnp.abs(norm_in - norm_out))) < 1e-4


def test_sliding_window_flash_parity():
    """The banded (sliding-window) flash path matches a masked einsum
    reference — forward and all three grads — including windows that do
    not align with block boundaries and GQA grouping."""
    for (s, w, bq, bk) in [(256, 64, 64, 64), (256, 100, 64, 64),
                           (256, 7, 64, 64), (512, 128, 128, 128)]:
        q, k, v = _qkv(1, 4, 2, s, 64)
        ref = reference_attention(q, k, v, causal=True, window=w)
        out = attention(q, k, v, causal=True, window=w, impl="flash",
                        interpret=True, block_q=bq, block_k=bk)
        assert float(jnp.max(jnp.abs(ref - out))) < 1e-4, (s, w)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        gr = jax.grad(
            loss(lambda q, k, v: reference_attention(
                q, k, v, causal=True, window=w)),
            argnums=(0, 1, 2),
        )(q, k, v)
        gf = jax.grad(
            loss(lambda q, k, v: attention(
                q, k, v, causal=True, window=w, impl="flash",
                interpret=True, block_q=bq, block_k=bk)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gr, gf):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-3, (s, w)


def test_sliding_window_edge_semantics():
    """W >= S degrades to plain causal; W=1 is attend-self-only;
    non-causal banding and W < 1 refuse loudly."""
    q, k, v = _qkv(1, 2, 2, 64, 64)
    dense = attention(q, k, v, causal=True, interpret=True)
    wide = attention(q, k, v, causal=True, window=64, interpret=True)
    assert float(jnp.max(jnp.abs(dense - wide))) < 1e-6

    self_only = attention(q, k, v, causal=True, window=1, impl="flash",
                          interpret=True, block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=True, window=1)
    assert float(jnp.max(jnp.abs(self_only - ref))) < 1e-4

    with pytest.raises(NotImplementedError, match="causal"):
        attention(q, k, v, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        attention(q, k, v, causal=True, window=0, interpret=True)

    # the W>=S no-op shortcut keys on the KV length: with cached-decode
    # shapes (skv > sq) a window larger than sq but smaller than skv must
    # still mask old positions, not silently go dense
    kq, kk, kv2 = jax.random.split(jax.random.key(7), 3)
    qs = jax.random.normal(kq, (1, 2, 4, 64), jnp.float32)
    ks = jax.random.normal(kk, (1, 2, 100, 64), jnp.float32)
    vs = jax.random.normal(kv2, (1, 2, 100, 64), jnp.float32)
    banded = attention(qs, ks, vs, causal=True, window=8,
                       impl="reference", interpret=True)
    ref_banded = reference_attention(qs, ks, vs, causal=True, window=8)
    dense2 = reference_attention(qs, ks, vs, causal=True)
    assert float(jnp.max(jnp.abs(banded - ref_banded))) < 1e-6
    assert float(jnp.max(jnp.abs(banded - dense2))) > 1e-3


def test_yarn_rope_matches_transformers():
    """The yarn inv_freq blend AND the inferred attention_factor match
    transformers' _compute_yarn_parameters across its branches (explicit
    attention_factor, inferred-from-factor, mscale/mscale_all_dim)."""
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    import numpy as np
    from types import SimpleNamespace

    from transformers.modeling_rope_utils import _compute_yarn_parameters

    from ray_lightning_tpu.ops.rope import _yarn_scale, rope_angles

    head_dim, theta = 64, 10000.0
    base_inv = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    cases = [
        {"rope_type": "yarn", "factor": 4.0,
         "original_max_position_embeddings": 2048},
        {"rope_type": "yarn", "factor": 8.0, "beta_fast": 64,
         "beta_slow": 2, "original_max_position_embeddings": 4096},
        {"rope_type": "yarn", "factor": 4.0, "attention_factor": 1.3,
         "original_max_position_embeddings": 2048},
        # the DeepSeek-style mscale pair
        {"rope_type": "yarn", "factor": 40.0, "mscale": 1.0,
         "mscale_all_dim": 0.8, "original_max_position_embeddings": 4096},
    ]
    for scaling in cases:
        cfg = SimpleNamespace(
            rope_theta=theta, hidden_size=head_dim * 4,
            num_attention_heads=4, head_dim=head_dim,
            max_position_embeddings=scaling["original_max_position_embeddings"]
            * int(scaling["factor"]),
            rope_scaling=dict(scaling),
        )
        ref_inv, ref_att = _compute_yarn_parameters(cfg, device="cpu")
        ours_inv, ours_att = _yarn_scale(base_inv, scaling, head_dim, theta)
        assert np.allclose(ref_inv.numpy(), np.asarray(ours_inv),
                           rtol=1e-6), scaling
        assert abs(ref_att - ours_att) < 1e-6, scaling
        # and the tables carry the magnitude correction
        cos, _ = rope_angles(4, head_dim, theta, scaling=scaling)
        assert abs(float(cos[0, 0]) - ours_att) < 1e-6  # cos(0)*factor


def test_yarn_requires_original_max_positions():
    from ray_lightning_tpu.ops.rope import normalize_rope_scaling

    with pytest.raises(ValueError, match="original_max_position"):
        normalize_rope_scaling({"rope_type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="original_max_position"):
        normalize_rope_scaling({"rope_type": "longrope",
                                "long_factor": [1.0], "short_factor": [1.0]})
    with pytest.raises(ValueError, match="long_factor"):
        normalize_rope_scaling({"rope_type": "longrope",
                                "original_max_position_embeddings": 64})


def test_longrope_matches_transformers():
    """longrope inv_freq and the inferred attention factor match
    transformers' _compute_longrope_parameters in both regimes (seq_len
    under/over the pretrain context selects short/long factors)."""
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    import numpy as np
    from types import SimpleNamespace

    from transformers.modeling_rope_utils import _compute_longrope_parameters

    from ray_lightning_tpu.ops.rope import _longrope_scale, rope_angles

    head_dim, theta, orig = 16, 10000.0, 64
    long_f = [2.0 + 0.5 * i for i in range(head_dim // 2)]
    short_f = [1.0 + 0.05 * i for i in range(head_dim // 2)]
    cfg = SimpleNamespace(
        rope_theta=theta, hidden_size=head_dim * 4, num_attention_heads=4,
        head_dim=head_dim, max_position_embeddings=256,
        original_max_position_embeddings=orig,
        rope_scaling={"rope_type": "longrope", "long_factor": long_f,
                      "short_factor": short_f},
    )
    scaling = {"rope_type": "longrope", "long_factor": long_f,
               "short_factor": short_f,
               "original_max_position_embeddings": orig,
               "factor": 256 / orig}  # hf_import injects max/orig
    for seq_len in (32, 128):
        ref_inv, ref_att = _compute_longrope_parameters(
            cfg, device="cpu", seq_len=seq_len
        )
        ours_inv, ours_att = _longrope_scale(scaling, head_dim, theta, seq_len)
        assert np.allclose(ref_inv.numpy(), np.asarray(ours_inv),
                           rtol=1e-6), seq_len
        assert abs(ref_att - ours_att) < 1e-6, seq_len
        cos, _ = rope_angles(seq_len, head_dim, theta, scaling=scaling)
        assert abs(float(cos[0, 0]) - ours_att) < 1e-6  # factor on tables


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_multiblock_grid(causal):
    """Small explicit blocks so the grid really iterates (4 q-blocks x 4
    kv-blocks): exercises the scratch-accumulator handoff across grid steps
    that makes VMEM O(block^2) instead of O(S)."""
    q, k, v = _qkv(1, 2, 1, 256, 128)  # GQA group 2 as well

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    def flash(q, k, v):
        return attention(q, k, v, causal=causal, impl="flash", interpret=True,
                         block_q=64, block_k=64)

    assert _traced_grids(flash, q, k, v)["flash_fwd"][-1] > 1
    assert float(jnp.max(jnp.abs(ref(q, k, v) - flash(q, k, v)))) < 1e-4

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 1e-4


def test_flash_explicit_block_args():
    """Explicit block_q/block_k args (the single-process autotune path:
    static ints, distinct values retrace) match the reference, including
    asymmetric blocks and gradients."""
    q, k, v = _qkv(1, 2, 2, 256, 128)
    ref = reference_attention(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="flash", interpret=True,
                    block_q=128, block_k=64)
    assert float(jnp.max(jnp.abs(ref - out))) < 1e-4

    g_ref = jax.grad(lambda q: (reference_attention(q, k, v, causal=True) ** 2).sum())(q)
    g_fl = jax.grad(
        lambda q: (attention(q, k, v, causal=True, impl="flash", interpret=True,
                             block_q=128, block_k=64) ** 2).sum()
    )(q)
    rel = float(jnp.max(jnp.abs(g_ref - g_fl)) / (jnp.max(jnp.abs(g_ref)) + 1e-9))
    assert rel < 1e-4


def test_llama_config_flash_blocks_plumbed():
    """LlamaConfig.flash_block_q/k reach the kernel: two configs produce
    identical losses (numerics don't depend on blocking)."""
    import numpy as np

    from ray_lightning_tpu.models.llama import LlamaConfig, init_params, lm_loss

    cfg = LlamaConfig(
        vocab_size=64, dim=128, n_layers=1, n_heads=1, n_kv_heads=1,
        ffn_dim=64, max_seq=128, remat=False, attn_impl="flash",
    )
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 128)), jnp.int32
    )
    base, _ = lm_loss(params, tokens, cfg)
    from dataclasses import replace

    small, _ = lm_loss(params, tokens, replace(cfg, flash_block_q=64, flash_block_k=64))
    assert abs(float(base) - float(small)) < 1e-3


# ---------------------------------------------------------------------- #
# the forward pass's schedule: a pure function of the shapes
# ---------------------------------------------------------------------- #
def _kept(s, causal, window):
    """The mask itself, position by position: [s, s] bool."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    if not causal:
        return np.ones((s, s), bool)
    keep = rows >= cols
    if window:
        keep &= (rows - cols) < window
    return keep


@pytest.mark.parametrize(
    "s,bq,bk,causal,window",
    [
        (512, 64, 64, True, None),
        (512, 128, 64, True, None),     # bq != bk, both ways
        (512, 64, 128, True, None),
        (512, 512, 512, True, None),    # one tile: one pair, nothing to skip
        (512, 64, 64, False, None),
        (512, 128, 64, False, None),
        (1024, 64, 64, True, 256),      # a window of whole tiles
        (1024, 64, 64, True, 200),      # no multiple of the tile
        (1024, 64, 64, True, 40),       # both edges of the band in one tile
        (1024, 64, 64, True, 1),
        (1024, 128, 64, True, 200),
        (1024, 64, 128, True, 200),
        (1024, 128, 128, True, 129),
    ],
)
def test_flash_schedule_is_the_masks_tiles(s, bq, bk, causal, window):
    """Against the mask written out position by position: the visited
    pairs are exactly the tiles with a kept score, a q tile's pairs are
    adjacent and ascending, and first / last mark their ends."""
    from ray_lightning_tpu.ops.attention import _FIRST, _LAST, flash_schedule

    sched = flash_schedule(s, s, bq, bk, causal, window)
    tiles = _kept(s, causal, window).reshape(s // bq, bq, s // bk, bk)
    any_kept = tiles.any(axis=(1, 3))
    pairs = list(zip(sched.q_tile.tolist(), sched.kv_tile.tolist()))
    # row-major order IS "a q tile's pairs adjacent, kv ascending"
    assert pairs == [tuple(p) for p in np.argwhere(any_kept).tolist()]
    q_tiles = sched.q_tile.tolist()
    assert ((sched.flags & _FIRST) != 0).tolist() == [
        t == 0 or q_tiles[t - 1] != q for t, q in enumerate(q_tiles)]
    assert ((sched.flags & _LAST) != 0).tolist() == [
        t == len(q_tiles) - 1 or q_tiles[t + 1] != q
        for t, q in enumerate(q_tiles)]
    assert sched.visited == len(pairs)
    assert sched.skipped == any_kept.size - len(pairs)
    if not causal:
        assert sched.skipped == 0


# bfloat16 heads of 128 columns: 256 bytes a row; the latent family's keys
# are 192 columns padded to 256 over values of 128: 512 bytes
_ROW = 2 * 128


_WIDE, _BASE = (1024, 1024), (512, 512)


@pytest.mark.parametrize(
    "s,row_bytes,window,fwd,bwd",
    [
        (4096, _ROW, None, _WIDE, _WIDE),         # train-dense-4k
        (16384, _ROW, None, _WIDE, _WIDE),        # the doc cell's full layer
        (16384, _ROW, 4096, _WIDE, _BASE),        # its three window layers
        (8192, _ROW, 4096, _WIDE, _BASE),
        (256, _ROW, None, (256, 256), (256, 256)),  # the first two rungs: one
        (512, _ROW, None, _BASE, _BASE),            # tile, as before
        (1024, _ROW, None, _WIDE, _WIDE),         # three pairs become one tile
        (2048, _ROW, None, _WIDE, _WIDE),         # ten pairs become three
        (2048, 2 * 256, None, _WIDE, _WIDE),      # the latent shape, 192 / 128
        (1536, _ROW, None, _BASE, _BASE),         # no 1,024 divides it
        (3072, _ROW, None, _WIDE, _WIDE),
        (384, _ROW, None, (384, 384), (384, 384)),
        (4096, 2 * 512, None, _BASE, _BASE),      # wider rows than were compiled
        (4096, 4 * 256, None, _BASE, _BASE),      # float32 rows of 256 columns
    ],
)
def test_each_pass_reads_its_tile_off_the_shapes(s, row_bytes, window, fwd, bwd):
    """`_fwd_tile` and `_bwd_tile` at the cells' shapes (PERF.md section 6,
    PR 42 has the chip's sweeps), clamped to the sequence as `_pick_blocks`
    clamps them; every pick divides the sequence, and so does the 512 x 512
    `flash_supported` asks about."""
    from ray_lightning_tpu.ops.attention import _bwd_tile, _fwd_tile, _pick_blocks

    assert _pick_blocks(s, default=_fwd_tile(s, s, row_bytes)) == fwd
    assert _pick_blocks(s, default=_bwd_tile(s, s, row_bytes, window)) == bwd
    assert all(s % side == 0 for side in (*fwd, *bwd, *_pick_blocks(s)))


# s, block_q, block_k, the pass's own choice, what `_pick_blocks` returns
_EXPLICIT_TILE_CASES = [
    (4096, 256, None, _WIDE, (256, 1024)),   # one side asked for, the
    (4096, None, 128, _WIDE, (1024, 128)),   # other the pass's own
    (4096, 256, 128, _BASE, (256, 128)),
    (4096, None, 256, _WIDE, (1024, 256)),
    (4096, None, 256, _BASE, (512, 256)),
    (4096, None, 2048, _WIDE, (1024, 2048)),
    (4096, 64, 256, _WIDE, (64, 256)),
    (4096, None, None, _WIDE, _WIDE),        # nothing asked for
    (256, 512, 512, _WIDE, (256, 256)),      # clamped to the sequence
]


@pytest.mark.parametrize("s,block_q,block_k,default,picked", _EXPLICIT_TILE_CASES)
def test_explicit_tiles_win_over_the_rule(s, block_q, block_k, default, picked):
    """A tile is the caller's explicit `block_q` / `block_k` or the pass's own
    choice, side by side, clamped to the sequence."""
    from ray_lightning_tpu.ops.attention import _pick_blocks

    assert _pick_blocks(s, block_q, block_k, default=default) == picked


@pytest.mark.parametrize("s,block_q,block_k,default,picked", _EXPLICIT_TILE_CASES)
def test_the_bench_knobs_and_the_tile_pins_are_gone(
        s, block_q, block_k, default, picked, monkeypatch):
    """PR 44 took `bench.py`'s knobs and the two tile pins only its autotune
    needed out of the registry; the pins, set, change no pick (they were read
    at trace time and were no jit cache key)."""
    from ray_lightning_tpu.analysis.knobs import KNOBS
    from ray_lightning_tpu.ops.attention import _pick_blocks

    assert not [k for k in KNOBS
                if k.startswith(("RLT_BENCH_", "RLT_FLASH_BLOCK_"))]
    monkeypatch.setenv("RLT_FLASH_BLOCK_Q", "64")
    monkeypatch.setenv("RLT_FLASH_BLOCK_K", "128")
    assert _pick_blocks(s, block_q, block_k, default=default) == picked


def _traced_grids(fn, *args):
    """name -> grid of every `pallas_call` in `fn`'s jaxpr."""
    from tests.utils import pallas_calls

    return {e.params["name"]: e.params["grid_mapping"].grid
            for e in pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)}


def test_forward_and_backward_choose_their_tiles_separately():
    """Through the public op at the train cell's sequence: each kernel's grid
    is its own pass's tile, the same 1,024 x 1,024 with no window and
    different ones under a window; an explicit block reaches all three."""
    from ray_lightning_tpu.ops.attention import flash_schedule

    q = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16)

    def grads(**kw):
        return jax.grad(lambda q, k, v: attention(
            q, k, v, causal=True, impl="flash", interpret=True, **kw,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))

    assert _traced_grids(grads(), q, kv, kv) == {
        "flash_fwd": (1, 4, flash_schedule(4096, 4096, 1024, 1024, True).visited),
        "flash_bwd_dq": (1, 4, 4, 4),
        "flash_bwd_dkv": (1, 2, 4, 2 * 4),
    }
    assert _traced_grids(grads(window=2048), q, kv, kv) == {
        "flash_fwd": (1, 4, flash_schedule(4096, 4096, 1024, 1024, True, 2048).visited),
        "flash_bwd_dq": (1, 4, 8, 8),
        "flash_bwd_dkv": (1, 2, 8, 2 * 8),
    }
    assert _traced_grids(grads(block_q=256, block_k=1024), q, kv, kv) == {
        "flash_fwd": (1, 4, 40),
        "flash_bwd_dq": (1, 4, 16, 4),
        "flash_bwd_dkv": (1, 2, 4, 2 * 16),
    }


@pytest.mark.parametrize(
    "s,window,visited,skipped",
    [
        (4096, None, 10, 6),         # train-dense-4k
        (16384, None, 136, 120),     # the doc cell's full layer
        (16384, 4096, 70, 186),      # each of its three window layers
    ],
    ids=["train_4096", "doc_full_16384", "doc_window_4096"],
)
def test_flash_schedule_counts_at_the_cells_shapes(s, window, visited, skipped):
    """How often the mechanism engages is static: the counts of the cells'
    shapes at the tiles `_fwd_tile` picks for them (docs/performance.md
    lists them, beside the 36 / 528 / 252 pairs of 512 x 512 they were)."""
    from ray_lightning_tpu.ops.attention import _fwd_tile, flash_schedule

    tile = _fwd_tile(s, s, _ROW)
    sched = flash_schedule(s, s, *tile, True, window)
    assert (sched.visited, sched.skipped) == (visited, skipped)


@pytest.mark.parametrize(
    "hq,hkv,s,window",
    [
        (2, 1, 2048, None),     # GQA, three wide tiles
        (2, 1, 2048, 300),      # the band's lower edge inside a wide tile
        (2, 2, 2048, 1500),     # and across two of them
    ],
    ids=["causal_gqa", "window_inside_a_tile", "window_across_tiles"],
)
def test_flash_parity_at_the_wide_tile(hq, hkv, s, window):
    """The tiles the rules pick at 2,048 positions, 1,024 x 1,024 for all
    three kernels with no window and, under one, for the forward kernel over
    the backward kernels' 512 x 512 (two passes on different tiles over one
    saved logsumexp): forward and all three gradients against the masked
    einsum."""
    from ray_lightning_tpu.ops.attention import _bwd_tile, _fwd_tile

    assert _fwd_tile(s, s, 4 * 128) == (1024, 1024)
    assert _bwd_tile(s, s, 4 * 128, window) == ((512, 512) if window else (1024, 1024))
    q, k, v = _qkv(1, hq, hkv, s, 128)
    ref = lambda q, k, v: reference_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    flash = lambda q, k, v: attention(  # noqa: E731
        q, k, v, causal=True, window=window, impl="flash", interpret=True)
    assert float(jnp.max(jnp.abs(ref(q, k, v) - flash(q, k, v)))) < 1e-4

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gf):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 1e-4, name


@pytest.mark.parametrize(
    "s,blocks,causal,window,grid",
    [
        (131072, (512, 512), True, None, (1, 1, 32896)),   # 386 KiB: rides as it is
        # past `_MAX_SCHEDULE_BYTES` the rectangular grid, at the tiles asked
        # for and at any length they divide
        (151552, (512, 512), True, None, (1, 1, 296, 296)),
        (200192, (512, 512), True, None, (1, 1, 391, 391)),
        (1048576, (512, 512), True, None, (1, 1, 2048, 2048)),
        (1048576, (512, 512), True, 4096, (1, 1, 18396)),  # a band grows with s, not s^2
        # the tiles the rule picks: a quarter of the pairs, so four times the
        # positions ride before the grid takes over
        (131072, None, True, None, (1, 1, 8256)),
        (200192, None, True, None, (1, 1, 391, 391)),      # no 1,024 divides it
        (1048576, None, True, None, (1, 1, 1024, 1024)),
        (4096, None, True, None, (1, 1, 10)),              # the train cell
        (2048, None, True, None, (1, 1, 3)),
        (1024, None, True, None, (1, 1, 1, 1)),            # one tile now
        # nothing to skip: the schedule is the rectangular grid
        (512, None, True, None, (1, 1, 1, 1)),       # one tile (the first rungs)
        (128, (128, 128), True, None, (1, 1, 1, 1)),
        (256, (128, 128), True, None, (1, 1, 3)),
        (2048, None, False, None, (1, 1, 2, 2)),     # not causal
    ],
)
def test_flash_fwd_walks_the_schedule_where_it_skips_and_fits(
        s, blocks, causal, window, grid):
    """The forward kernel's grid, read off the traced `pallas_call`: the
    schedule's pairs with its three lists as scalar-prefetch operands, or
    the rectangular grid and no list where the schedule skips nothing (one
    tile lowers as before PR 40: a branch on a loaded mark cost 0.2-0.6 us a
    step on the chip) or would pass `_MAX_SCHEDULE_BYTES` of scalar memory."""
    from ray_lightning_tpu.ops.attention import _MAX_SCHEDULE_BYTES, _flash_fwd

    x = jax.ShapeDtypeStruct((1, 1, s, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: _flash_fwd(
        q, k, v, causal, 0.125, True, blocks, window))(x, x, x)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == grid
    assert mapping.num_index_operands == (3 if len(grid) == 3 else 0)
    if len(grid) == 3:
        assert 3 * 4 * grid[2] <= _MAX_SCHEDULE_BYTES


@pytest.mark.parametrize(
    "s,w,bq,bk",
    [
        (256, 40, 64, 64),     # the band's lower edge and the diagonal in one tile
        (256, 1, 64, 64),
        (512, 200, 64, 64),    # no multiple of the tile, interior tiles between
        (512, 200, 128, 64),
        (512, 129, 64, 128),
    ],
)
def test_flash_window_edges_inside_and_across_tiles(s, w, bq, bk):
    """Windows whose lower edge shares a tile with the diagonal
    (`window < bk`) and windows that are no multiple of the tile: forward
    and all three gradients against the masked einsum."""
    q, k, v = _qkv(1, 4, 2, s, 128)
    ref = reference_attention(q, k, v, causal=True, window=w)
    flash = lambda q, k, v: attention(  # noqa: E731
        q, k, v, causal=True, window=w, impl="flash", interpret=True,
        block_q=bq, block_k=bk)
    assert float(jnp.max(jnp.abs(ref - flash(q, k, v)))) < 1e-4

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    gr = jax.grad(loss(lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=w)), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3
