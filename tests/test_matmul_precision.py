"""RLT_MATMUL_PRECISION: one shared matmul-precision policy applied at
trace time to BOTH the train step and the serving decode (the same
``matmul_precision_scope``/``round_matmul_inputs`` helpers wrap both jit
sites), with a greedy-decode token-parity guarantee wherever
``promises_decode_parity`` says so."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.llama import LlamaConfig, init_params
from ray_lightning_tpu.serving import EngineConfig, InferenceEngine
from ray_lightning_tpu.utils.precision import (
    matmul_precision_scope,
    parse_matmul_precision,
    promises_decode_parity,
    round_matmul_inputs,
)

pytestmark = pytest.mark.zero


def test_parse_matmul_precision_and_aliases(monkeypatch):
    assert parse_matmul_precision() == "default"
    assert parse_matmul_precision("FP8") == "fp8-emulated"
    assert parse_matmul_precision("tf32") == "tensorfloat32"
    assert parse_matmul_precision("fp32") == "highest"
    monkeypatch.setenv("RLT_MATMUL_PRECISION", "bf16")
    assert parse_matmul_precision() == "bf16"
    # explicit arg beats env
    assert parse_matmul_precision("highest") == "highest"
    monkeypatch.setenv("RLT_MATMUL_PRECISION", "int4")
    with pytest.raises(ValueError, match="RLT_MATMUL_PRECISION"):
        parse_matmul_precision()


def test_round_matmul_inputs_fp8_grid():
    x = jnp.asarray([1.0, 1.06, 240.0, 1e-9], jnp.float32)
    y = round_matmul_inputs("fp8-emulated", x)
    assert y.dtype == jnp.float32  # storage dtype unchanged, values snapped
    assert float(y[0]) == 1.0
    assert float(y[1]) != 1.06  # 1.06 is not on the e4m3 grid
    # identity for non-fp8 policies and non-float operands
    assert round_matmul_inputs("highest", x) is x
    ints = jnp.asarray([1, 2], jnp.int32)
    assert round_matmul_inputs("fp8-emulated", ints) is ints
    # pytree operands (what the train step and engine actually pass) get
    # every float leaf snapped; non-float leaves keep their identity
    tree = {"batch": (x, ints)}
    out = round_matmul_inputs("fp8-emulated", tree)
    assert float(out["batch"][0][1]) != 1.06
    assert out["batch"][1] is ints


def test_promises_decode_parity_matrix():
    assert promises_decode_parity("default", "default")
    assert not promises_decode_parity("default", "fp8-emulated")
    assert not promises_decode_parity("fp8-emulated", "highest")
    if jax.default_backend() == "cpu":
        # CPU lowers every non-fp8 hint identically
        assert promises_decode_parity("bf16", "highest")
        assert promises_decode_parity("default", "tensorfloat32")


def test_matmul_precision_scope_is_trace_scoped():
    # the scope must be a context manager for every policy (a no-op shim
    # for default/fp8 — jax has no hint to set there)
    for policy in ("default", "bf16", "highest", "fp8-emulated"):
        with matmul_precision_scope(policy):
            pass


def _decode_tokens(params, cfg, policy, monkeypatch):
    monkeypatch.setenv("RLT_MATMUL_PRECISION", policy)
    engine = InferenceEngine(
        params, cfg, EngineConfig(num_slots=1, max_prompt_len=8, max_len=24, block_size=8)
    )
    comp = engine.submit([3, 5, 7, 11], max_new_tokens=8)
    engine.run_until_idle()
    return comp.result(timeout=5)


@pytest.mark.serving
def test_greedy_decode_token_parity_across_policies(monkeypatch):
    """The satellite's acceptance: greedy decode emits token-identical
    completions under every pair of policies promising parity, and the
    fp8-emulated path (which snaps operand values on any backend) actually
    flows through the engine — same shared helper as the train step."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    tokens = {
        p: _decode_tokens(params, cfg, p, monkeypatch)
        for p in ("default", "bf16", "highest", "fp8-emulated")
    }
    for a in tokens:
        for b in tokens:
            if promises_decode_parity(a, b):
                assert tokens[a] == tokens[b], (a, b)
    # fp8 produced a real completion of the requested length
    assert len(tokens["fp8-emulated"]) == 8


def test_trainer_rejects_bad_matmul_precision(monkeypatch, tmp_path):
    import ray_lightning_tpu as rlt
    from tests.utils import BoringModel

    monkeypatch.setenv("RLT_MATMUL_PRECISION", "int4")
    trainer = rlt.Trainer(
        default_root_dir=str(tmp_path),
        max_steps=1,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    with pytest.raises(ValueError, match="RLT_MATMUL_PRECISION"):
        trainer.fit(BoringModel())


def test_train_step_runs_under_each_policy(monkeypatch, tmp_path):
    import ray_lightning_tpu as rlt
    from tests.utils import BoringModel

    flats = {}
    for policy in ("bf16", "highest", "fp8-emulated"):
        monkeypatch.setenv("RLT_MATMUL_PRECISION", policy)
        trainer = rlt.Trainer(
            default_root_dir=str(tmp_path),
            max_steps=2,
            enable_progress_bar=False,
            enable_checkpointing=False,
            logger=False,
            seed=0,
        )
        trainer.fit(BoringModel())
        assert trainer.global_step == 2
        assert trainer._matmul_precision == policy
        flats[policy] = np.concatenate(
            [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(
                jax.device_get(trainer._params))]
        )
    # fp8-emulated actually snaps operand values — the trained params must
    # diverge from the full-precision run (guards the helper being wired
    # into the step, not just parsed)
    assert float(np.max(np.abs(flats["fp8-emulated"] - flats["highest"]))) > 0
