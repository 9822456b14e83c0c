"""The ``lfm2_moe`` model (``models/lfm2.py``) at a tiny size on the CPU: its
loss and every leaf of its gradient against the family's plain reference on
seeded weights; the share of experts tied to the model (the shares' outputs
and gradients add up to the uncut layer's); the grouped product's kernel
interpreted, value and both gradients, against ``lax.ragged_dot``; the short
convolution against a loop; routing that drops nothing; flash attention at
32 query heads over 8 of 64 with rope; the module through ``Trainer.fit``.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_lightning_tpu as rlt
from benchmarks import loader
from ray_lightning_tpu.callbacks.base import Callback
from ray_lightning_tpu.core.data import DataLoader, DictDataset
from ray_lightning_tpu.models import lfm2
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.rope import apply_rope, rope_angles
from ray_lightning_tpu.ops.selective_scan import causal_conv
from ray_lightning_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = loader.Manifest(REPO).family("lfm2")
weights, reference, program = FAMILY.weights, FAMILY.reference, FAMILY.program
# the cut's pattern: a dense conv layer, then conv, attention, conv, conv with
# 8 experts top-2; hidden 64, 4 query heads of 16 over 2
TINY = {
    "source": "test", "family": "lfm2", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5, "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_experts": 8, "num_experts_per_tok": 2, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "rope_theta": 10000.0, "vocab_size": 97, "max_position_embeddings": 64,
    "weights_seed": 7, "dtype": "float32",
}
HIGHEST = jax.default_matmul_precision("highest")


def _program(sizes, **model):
    cfg = program.model_config(sizes, max_seq=32, remat=False, **model)
    params = jax.jit(lambda keys: weights.make_params(sizes, keys))(weights.seed_keys(sizes, 0))
    return cfg, params


def _tokens(rows=2, length=32, seed=3):
    return jax.random.randint(jax.random.key(seed), (rows, length), 0, TINY["vocab_size"])


def _flat(tree, prefix=""):
    out = {}
    for name, value in tree.items():
        if isinstance(value, dict):
            out.update(_flat(value, prefix + name + "/"))
        else:
            out[prefix + name] = value
    return out


def _reference_loss(tree, tokens, sizes):
    """The family's reference, composed as its ``TrainReference`` composes it."""
    m = weights.dims(sizes)
    x = tree["embed"][tokens]
    for l in range(m["layers"]):
        x = reference.layer(x, _flat(tree["layers"][weights.place(l)]), sizes, l)
    logits = reference.rmsnorm(x, tree["final_norm"], m["eps"]) @ tree["embed"].T
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


# ---------------------------------------------------------------------- #
# the model against the reference
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("chunks", [0, 4], ids=["whole-logits", "chunked-loss"])
def test_the_loss_and_every_leaf_of_its_gradient_are_the_references(chunks):
    cfg, params = _program(TINY, loss_chunks=chunks)
    tokens = _tokens()
    with HIGHEST:
        (loss, logs), grads = jax.value_and_grad(
            lambda p: lfm2.lm_loss(p, tokens, cfg), has_aux=True)(params)
        want, want_grads = jax.value_and_grad(_reference_loss)(params, tokens, TINY)
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    got, ref = _flat(grads), _flat(want_grads)
    assert sorted(got) == sorted(ref) and len(got) == 2 + 8 + 3 * 10 + 13
    scale = float(np.median([np.linalg.norm(np.asarray(g)) for g in ref.values()]))
    for name in ref:
        gap = np.linalg.norm(np.asarray(got[name]) - np.asarray(ref[name]))
        assert gap < 2e-5 * max(np.linalg.norm(np.asarray(ref[name])), scale), name
    # the selection bias is a buffer: chosen by it, never moved by the loss
    assert all(float(jnp.abs(g).max()) == 0 for n, g in got.items() if n.endswith("expert_bias"))
    assert logs["moe_sizes"].shape == (4, 8) and int(logs["moe_sizes"].sum()) == 4 * 2 * 64


def test_the_logits_are_the_references_teacher_forced_logits():
    cfg, params = _program(TINY)
    tokens = _tokens()
    with HIGHEST:
        got, sizes = jax.jit(lambda p, t: lfm2.forward(p, t, cfg))(params, tokens)
    want, picked = reference.teacher_forced_logits(TINY, 0, tokens, choices=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    counts = [np.bincount(np.asarray(idx).reshape(-1), minlength=8) for idx in picked]
    np.testing.assert_array_equal(np.asarray(sizes), np.stack(counts))


def test_train_reference_steps_as_the_modules_optimizer_does():
    """Two steps of the module's AdamW on the program's loss against the
    family's ``TrainReference``: losses, the first gradient's norms a leaf,
    the change's norms a leaf, by the harness's own arithmetic."""
    import optax

    from benchmarks import program as bench_program
    from benchmarks.drivers.train import worst_leaf_gap

    opt = {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "warmup_steps": 2, "total_steps": 100, "state_dtype": "float32"}
    cfg = program.model_config(TINY, max_seq=32, remat=True, loss_chunks=2)
    module = program.make_module(cfg, TINY, 5, opt)
    params = start = jax.jit(module.init_params)(None)
    tx = module.configure_optimizers()
    state = tx.init(params)
    ref = reference.TrainReference(TINY, 5, opt)
    rows = np.asarray(_tokens(rows=6, seed=9))

    @jax.jit
    def step(params, state, tokens):
        with HIGHEST:
            (loss, _), grads = jax.value_and_grad(
                lambda p: lfm2.lm_loss(p, tokens, cfg), has_aux=True)(params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss, grads

    for k in range(2):
        params, state, loss, grads = step(params, state, rows[2 * k: 2 * k + 2])
        want, norms = ref.step(rows[2 * k: 2 * k + 2])
        assert abs(float(loss) - want) < 1e-5 * want
        got = {n: float(jnp.linalg.norm(g)) for n, g in bench_program.leaf_names(grads).items()}
        assert worst_leaf_gap(got, norms)[0] < 1e-4
    change = {n: float(jnp.linalg.norm(a - b)) for (n, a), b in zip(
        bench_program.leaf_names(params).items(), jax.tree_util.tree_leaves(start))}
    assert worst_leaf_gap(change, ref.change_norms())[0] < 1e-4
    assert ref.held_pairs == [[128] * 4, [128] * 4]  # every expert is held: every pair


def test_two_run_seeds_give_the_same_weights_and_other_rows():
    from benchmarks import lm_data

    a = jax.jit(lambda k: weights.make_params(TINY, k))(weights.seed_keys(TINY, 11))
    b = jax.jit(lambda k: weights.make_params(TINY, k))(weights.seed_keys(TINY, 2 ** 31 + 12))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    other = jax.jit(lambda k: weights.make_params(dict(TINY, weights_seed=8), k))(
        weights.seed_keys(dict(TINY, weights_seed=8), 11))
    assert not bool(jnp.array_equal(a["embed"], other["embed"]))
    assert not np.array_equal(lm_data.rows(11, 4, 32, 97), lm_data.rows(2 ** 31 + 12, 4, 32, 97))


# ---------------------------------------------------------------------- #
# a share of the experts, tied to the model
# ---------------------------------------------------------------------- #
def test_the_two_shares_outputs_and_gradients_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of expert layer 1, each as a configuration of its
    own: the expert branch's outputs, added, are the uncut reference's, and
    so are the gradients in the branch's input (everything else of the block
    every holder computes alike, and is left out of the sum)."""
    shares = [dict(TINY, num_experts=4, published_num_experts=8, first_expert=f) for f in (0, 4)]
    u = jax.random.normal(jax.random.key(1), (2, 32, 64), jnp.float32)
    ct = jax.random.normal(jax.random.key(2), (2, 32, 64), jnp.float32)
    m = weights.dims(TINY)
    whole = {n: a.astype(jnp.float32) for n, a in weights.whole_layer(
        TINY, weights.seed_keys(TINY, 0), 1).items()}

    def uncut(u):
        return reference.experts(u.reshape(-1, 64), whole, m, None)[0].reshape(u.shape)

    with HIGHEST:
        want, pull = jax.vjp(uncut, u)
        want_du = pull(ct)[0]
        got, got_du, held = 0.0, 0.0, 0
        for sizes in shares:
            cfg, params = _program(sizes)
            lp = params["layers"]["01"]
            # a share is a slice of the one model
            first = sizes["first_expert"]
            np.testing.assert_array_equal(
                np.asarray(lp["experts"]["w_up"]), np.asarray(whole["experts/w_up"][first: first + 4]))
            (out, sz), pull = jax.vjp(lambda a: lfm2._experts(a, lp, cfg), u)
            got, got_du = got + out, got_du + pull((ct, np.zeros(sz.shape, jax.dtypes.float0)))[0]
            held += int(sz.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_du), np.asarray(want_du), atol=2e-5)
    assert held == 2 * 64  # between them the shares computed every pair, once


def test_no_pair_is_dropped_when_every_token_chooses_one_expert():
    """A capacity-bounded dispatch would keep ``capacity_factor * K * T / E``
    rows of the 64 that all go to expert 2; this path keeps them all, and
    the dropping control does not."""
    t, d, f, e = 64, 32, 16, 4
    ks = jax.random.split(jax.random.key(0), 4)
    stacks = {"w_gate": jax.random.normal(ks[0], (e, d, f)) / 6, "w_up": jax.random.normal(ks[1], (e, d, f)) / 6,
              "w_down": jax.random.normal(ks[2], (e, f, d)) / 4}
    xt = jax.random.normal(ks[3], (t, d))
    idx, w = jnp.full((t, 1), 2, jnp.int32), jnp.ones((t, 1), jnp.float32)
    out, sizes = moe.moe_ffn_routed(stacks, xt, idx, w)
    assert sizes.tolist() == [0, 0, 64, 0]
    want = (jax.nn.silu(xt @ stacks["w_gate"][2]) * (xt @ stacks["w_up"][2])) @ stacks["w_down"][2]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    kept = reference.kept(idx, {"routed": e}, 1.25)
    assert int(kept.sum()) == int(1.25 * t / e) == 20  # what the control drops to


# ---------------------------------------------------------------------- #
# the grouped product's kernel, forward and backward
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("sizes,rows", [
    ([128, 128, 128], 384),  # whole tiles
    ([100, 0, 57, 90, 13], 300),  # an empty group, a ragged last tile, 40 rows of no group
    ([0, 0, 5], 5),  # fewer rows than a tile
    ([0, 0, 0], 256),  # rows, and not one of them in a group
], ids=["whole-tiles", "empty-group-ragged-tile-no-group", "under-a-tile", "no-row-in-a-group"])
def test_the_interpreted_kernel_and_both_its_gradients_are_ragged_dots(sizes, rows):
    k, n = 256, 384
    sizes = jnp.asarray(sizes, jnp.int32)
    xs = jax.random.normal(jax.random.key(0), (rows, k), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (len(sizes), k, n), jnp.float32) / 16
    ct = jax.random.normal(jax.random.key(2), (rows, n), jnp.float32)
    in_a_group = (jnp.arange(rows) < int(sizes.sum()))[:, None]

    def run(kernel):
        def loss(xs, w):
            y = moe.grouped_matmul(xs, w, sizes, kernel=kernel, differentiable=True)
            return jnp.sum(jnp.where(in_a_group, y, 0.0) * ct), y
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(xs, w)

    (_, want), (want_dx, want_dw) = run(False)
    (_, got), (got_dx, got_dw) = run(True)
    np.testing.assert_allclose(
        np.where(in_a_group, got, 0.0), np.where(in_a_group, want, 0.0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dx), np.asarray(want_dx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw), atol=1e-4)
    # a row of no group gets a zero gradient, an empty group's weights too
    assert float(jnp.abs(got_dx[int(sizes.sum()):]).max(initial=0.0)) == 0.0
    assert all(float(jnp.abs(got_dw[g]).max()) == 0.0 for g in range(len(sizes)) if sizes[g] == 0)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "kernel"])
def test_the_routed_path_differentiates_in_rows_weights_and_stacks(kernel):
    """Against every held expert on every token, masked by the choice."""
    t, d, f, e, k = 96, 128, 128, 8, 2
    ks = jax.random.split(jax.random.key(4), 5)
    stacks = {"w_gate": jax.random.normal(ks[0], (4, d, f)) / 11,
              "w_up": jax.random.normal(ks[1], (4, d, f)) / 11,
              "w_down": jax.random.normal(ks[2], (4, f, d)) / 11}
    xt = jax.random.normal(ks[3], (t, d))
    router = jax.random.normal(ks[4], (d, e)) / 11

    def routed(stacks, xt, router):
        idx, w = moe.route_sigmoid_bias(xt, router, None, k)
        out, sizes = moe.moe_ffn_routed(
            stacks, xt, idx, w, kernel=kernel, held=(2, 4), differentiable=True)
        return jnp.sum(out * out), sizes

    def dense(stacks, xt, router):
        idx, w = moe.route_sigmoid_bias(xt, router, None, k)
        out = 0.0
        for j in range(4):
            gate = jnp.sum((idx == 2 + j) * w, axis=-1)
            h = jax.nn.silu(xt @ stacks["w_gate"][j]) * (xt @ stacks["w_up"][j])
            out = out + gate[:, None] * (h @ stacks["w_down"][j])
        return jnp.sum(out * out)

    with HIGHEST:
        (got, sizes), got_g = jax.value_and_grad(routed, argnums=(0, 1, 2), has_aux=True)(
            stacks, xt, router)
        want, want_g = jax.value_and_grad(dense, argnums=(0, 1, 2))(stacks, xt, router)
    assert 0 < int(sizes.sum()) < t * k  # some pairs are held, some leave
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4 * float(jnp.abs(b).max()))


def test_the_stacks_gradient_tile_keeps_its_accumulator_small():
    """``tgmm``'s tile is a float32 accumulator: the slab rule at four bytes."""
    assert moe._gmm_tiles(2048, 1792, 4) == (128, 512, 1792)
    assert moe._gmm_tiles(1792, 2048, 4) == (128, 1792, 512)


# ---------------------------------------------------------------------- #
# the short convolution; attention at this model's heads
# ---------------------------------------------------------------------- #
def test_causal_conv_without_bias_or_activation_is_the_loop():
    x = jax.random.normal(jax.random.key(0), (20, 8))
    w = jax.random.normal(jax.random.key(1), (3, 8))
    got, tail = causal_conv(x, w, None, activation=False)
    want = np.zeros((20, 8), np.float32)
    for t in range(20):
        for j in range(3):
            if t - 2 + j >= 0:
                want[t] += np.asarray(w[j]) * np.asarray(x[t - 2 + j])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(x[-2:]))
    # with both, it is the convolution it always was
    b = jax.random.normal(jax.random.key(2), (8,))
    np.testing.assert_allclose(
        np.asarray(causal_conv(x, w, b)[0]), np.asarray(jax.nn.silu(want + np.asarray(b))), atol=1e-6)


def test_the_gated_short_convolution_is_the_references():
    cfg, params = _program(TINY)
    lp = params["layers"]["00"]
    u = jax.random.normal(jax.random.key(5), (2, 32, 64))
    with HIGHEST:
        got = lfm2._short_conv(u, lp, cfg)
        want = reference.short_conv(u, lp, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # causal: what a position gets does not move with what follows it
    later = u.at[:, 20:].set(0.0)
    with HIGHEST:
        np.testing.assert_allclose(np.asarray(lfm2._short_conv(later, lp, cfg)[:, :20]),
                                   np.asarray(got[:, :20]), atol=1e-5)


def test_flash_forward_and_backward_at_32_query_heads_over_8_of_64_with_rope():
    """The kernels interpreted, lanes padded from 64 to 128, against the
    einsum reference: value and the three gradients."""
    s, hd = 256, 64
    ks = jax.random.split(jax.random.key(0), 4)
    cos, sin = rope_angles(s, hd, 1e6)
    turn = lambda x: apply_rope(x, cos, sin).swapaxes(1, 2)
    q = jax.random.normal(ks[0], (1, s, 32, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, 8, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, 8, hd), jnp.float32).swapaxes(1, 2)
    ct = jax.random.normal(ks[3], (1, 32, s, hd), jnp.float32)

    def run(impl):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention(turn(q), turn(k), v, causal=True, impl=impl) * ct),
            argnums=(0, 1, 2))(q, k, v)

    with HIGHEST:
        want, want_g = run("reference")
        got, got_g = run("flash")
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---------------------------------------------------------------------- #
# the module through Trainer.fit
# ---------------------------------------------------------------------- #
class _Reads(Callback):
    def __init__(self, read):
        self.read, self.losses = read, []

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
        if self.read:
            self.losses.append(float(outputs["loss"]))


@pytest.mark.parametrize("read", [True, False], ids=["loss-read", "nothing-read"])
def test_fit_opens_the_routing_span_once_the_outputs_have_arrived(tmp_path, monkeypatch, read):
    spans = []

    class _Span:
        def __init__(self, name, **args):
            spans.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(lfm2, "phase_span", _Span)
    cfg = dataclasses.replace(lfm2.Lfm2Config.tiny(), experts_held=4, first_expert=4)
    module = lfm2.Lfm2Module(cfg, lr=1e-2, warmup_steps=1, total_steps=50)
    bias = np.linspace(-0.1, 0.1, 8).astype(np.float32)

    class Biased(lfm2.Lfm2Module):
        def init_params(self, rng):
            params = super().init_params(rng)
            for lp in params["layers"].values():
                if "expert_bias" in lp:
                    lp["expert_bias"] = jnp.asarray(bias)
            return params

    module.__class__ = Biased
    probe = _Reads(read)
    rows = np.tile(np.arange(32, dtype=np.int32)[None, :] * 3 % 97, (16, 1))
    rows = (rows + np.arange(16, dtype=np.int32)[:, None]) % 97
    trainer = rlt.Trainer(
        strategy=rlt.XLAStrategy(devices=1), max_epochs=1, max_steps=4, callbacks=[probe],
        enable_checkpointing=False, logger=False, enable_progress_bar=False,
        default_root_dir=str(tmp_path), num_sanity_val_steps=0)
    trainer.fit(module, train_dataloaders=DataLoader(
        DictDataset(input_ids=rows), batch_size=4, shuffle=False, drop_last=True))
    routing = [args for name, args in spans if name == "rlt.train.moe_routing"]
    if read:
        assert len(routing) == 4 and probe.losses[-1] < probe.losses[0]
        for args in routing:
            assert args["routed_pairs"] == 4 * 32 * 2 * 4 and args["experts_held"] == 4
            assert args["expert_layers"] == 4
            assert 0 < args["held_pairs"] < args["routed_pairs"]
            assert args["held_pairs"] / 4 <= args["max_expert_rows"] <= args["held_pairs"]
    # the selection bias chose, and neither a gradient nor the decay moved it
    for lp in trainer._params["layers"].values():
        if "expert_bias" in lp:
            np.testing.assert_array_equal(np.asarray(lp["expert_bias"]), bias)
    assert float(jnp.abs(trainer._params["layers"]["01"]["norm2"] - 1.0).max()) > 0


def test_the_config_refuses_what_the_model_cannot_run():
    for bad in ({"conv_bias": True}, {"tie_embedding": False}, {"layer_types": ("conv", "mamba")},
                {"experts_held": 5, "first_expert": 4}, {"num_experts_per_tok": 9}):
        with pytest.raises(ValueError):
            dataclasses.replace(lfm2.Lfm2Config.tiny(), **bad)
    cfg = lfm2.Lfm2Config.tiny()
    assert lfm2.Lfm2Config.from_dict(cfg.to_dict()) == cfg
    params = jax.eval_shape(lambda: lfm2.init_params(jax.random.key(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params)) == cfg.num_params()
