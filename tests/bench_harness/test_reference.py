"""The Llama family's plain reference against the repository's forward at
tiny size, dense and MoE; its seeded weights; what the references share (the
control's float8 rounding, the schedule, the gap arithmetic)."""
import glob
import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

from benchmarks import loader, program, reference  # noqa: E402
from benchmarks import weights as hashing  # noqa: E402

FAMILY = loader.Manifest(tiny.REPO).family("llama")
weights, decoder = FAMILY.weights, FAMILY.reference
SIZES = {"dense": tiny.TINY_DENSE, "moe": tiny.TINY_MOE}


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_reference_logits_match_the_programs_forward(kind):
    from ray_lightning_tpu.models.llama import forward

    sizes, seed = SIZES[kind], 2 ** 31 + 3
    cfg = FAMILY.program.model_config(sizes, max_seq=64, remat=False, capacity_factor=8.0)
    params = weights.make_params_on_device(sizes, seed)
    tokens = np.random.default_rng(0).integers(1, 512, size=(3, 64)).astype(np.int32)
    got = np.asarray(forward(params, jnp.asarray(tokens), cfg)[0])
    want = np.asarray(decoder.teacher_forced_logits(sizes, seed, tokens))
    assert got.shape == want.shape == (3, 64, 512)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()  # float32 both, other summation order


def _yardstick_files():
    bench = os.path.join(tiny.REPO, "benchmarks")
    families = glob.glob(os.path.join(bench, "families", "*", "*.py"))
    assert len(families) >= len(loader.PIECES)
    return sorted(f for f in families if os.path.basename(f) != "program.py") + [
        reference.__file__, hashing.__file__]


@pytest.mark.parametrize("path", _yardstick_files(),
                         ids=lambda p: os.path.relpath(p, tiny.REPO))
def test_reference_imports_nothing_of_the_program(path):
    """Every family's weights, reference and counts, and what they share:
    only a family's ``program.py`` may touch the program under test."""
    src = open(path).read()
    assert "ray_lightning_tpu" not in src.replace("``ray_lightning_tpu``", "")


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_one_layer_of_weights_is_the_trees_slice(kind):
    sizes, seed = dict(SIZES[kind], dtype="bfloat16"), 12345678901
    tree = program.leaf_names(weights.make_params_on_device(sizes, seed))
    keys = weights.seed_keys(sizes, seed)
    for l in (0, 1):
        for name, leaf in jax.jit(lambda k, l: weights.layer_leaves(sizes, k, l))(keys, l).items():
            assert (np.asarray(tree["layers/" + name][l]) == np.asarray(leaf)).all(), name
    assert tree["layers/moe/router"].dtype == jnp.float32 if kind == "moe" else True
    assert tree["embed"].dtype == jnp.bfloat16


def test_weights_repeat_from_a_seed_and_differ_across_seeds_and_layers():
    a = program.leaf_names(weights.make_params_on_device(tiny.TINY_DENSE, 5))
    b = program.leaf_names(weights.make_params_on_device(tiny.TINY_DENSE, 5))
    c = program.leaf_names(weights.make_params_on_device(tiny.TINY_DENSE, 6))
    for name in a:
        assert (np.asarray(a[name]) == np.asarray(b[name])).all()
        assert (np.asarray(a[name]) != np.asarray(c[name])).any()
    wq = np.asarray(a["layers/wq"])
    assert (wq[0] != wq[1]).mean() > 0.99
    assert wq.var() * 128 == pytest.approx(1.0, rel=0.05)  # variance 1 / fan_in
    norm = np.asarray(a["layers/attn_norm"])
    assert 0.75 <= norm.min() and norm.max() <= 1.25 and norm.std() > 0.1


def test_fp8_rounding_is_e4m3_bit_for_bit():
    x = np.asarray(jax.random.normal(jax.random.key(0), (256, 256), jnp.float32)) * 3.0
    x[0, :8] = [0.0, 1e-4, -1e-4, 2e-3, -2e-3, 5e-3, 0.02, -0.02]  # subnormals of the scaled grid
    got = np.asarray(jax.jit(reference.fp8)(x))
    scale = np.abs(x).max() / 448.0
    want = (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
    assert np.allclose(got, want, rtol=3e-7, atol=0)  # the same grid; the last multiply may round apart
    assert len(np.unique(np.abs(got / scale).round(4))) <= 127  # e4m3 has 126 magnitudes and zero
    rel = np.sqrt(((got - x) ** 2).mean() / (x ** 2).mean())
    assert 0.01 < rel < 0.05  # 3 bits of mantissa; bfloat16's 7 give 0.002


def test_schedule_is_warmup_then_cosine():
    opt = dict(tiny.OPT, lr=1.0, warmup_steps=2, total_steps=10)
    assert [reference.schedule(opt, c) for c in (0, 1, 2)] == [0.0, 0.5, 1.0]
    assert reference.schedule(opt, 6) == pytest.approx(0.5)
    assert reference.schedule(opt, 10) == pytest.approx(0.0, abs=1e-12)
    import optax
    sched = optax.warmup_cosine_decay_schedule(0.0, 1.0, 2, 10)
    for c in range(11):
        assert reference.schedule(opt, c) == pytest.approx(float(sched(c)), abs=1e-6)


def test_served_gaps_are_read_at_the_position_that_produced_the_token():
    logits = np.zeros((1, 6, 4), np.float32)
    logits[0, 2] = [0.0, 3.0, 1.0, 0.0]  # position 2 produces the token at 3
    logits[0, 3] = [5.0, 0.0, 0.0, 4.5]
    tokens = np.array([[1, 1, 1, 1, 3, 0]], np.int32)  # prompt of 3, served: 1, 3
    gaps = reference.served_token_gaps(logits, tokens, [3], [5])
    assert gaps.tolist() == [0.0, 0.5]
    low = logits.copy()
    low[0, 2] = [0.0, 1.0, 3.0, 0.0]  # the lower precision puts token 2 first there
    assert reference.first_choice_gaps(logits, low, [3], [5]).tolist() == [2.0, 0.0]


def test_one_compiled_program_makes_the_weights_of_every_seed():
    sizes = tiny.TINY_DENSE
    fn = jax.jit(lambda keys: weights.make_params(sizes, keys))
    a = fn(weights.seed_keys(sizes, 1))
    b = fn(weights.seed_keys(sizes, 2 ** 31 + 1))
    assert fn._cache_size() == 1  # the seed is an argument, not a constant
    assert (np.asarray(a["embed"]) != np.asarray(b["embed"])).any()
