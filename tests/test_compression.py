"""Compressed DCN gradient collectives (parallel/compression.py).

Covers the wire format (block-scaled int8 round-trip, payload accounting),
the error-feedback invariant (emitted + residual telescopes to the exact
gradient sum), the two-phase shard_map reduction against the true mean on
the 8-device virtual mesh, the trainer integration (parity in mode "none",
convergence within 2% in mode "int8", knob validation), and a real
2-process subprocess run of the reduction (the DCN hop exercised across
process boundaries, CPU-only)."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import tempfile
import textwrap
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.parallel.compression import (
    DEFAULT_BLOCK_SIZE,
    MIN_COMPRESS_SIZE,
    ErrorFeedbackState,
    dequantize_int8,
    int8_payload_bytes,
    payload_bytes,
    quantize_int8,
    two_phase_dcn_reduce,
    with_error_feedback,
)
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh, split_dcn_axes
from ray_lightning_tpu.strategies.base import XLAStrategy

from tests.utils import BoringModel, get_trainer


# --------------------------------------------------------------------- #
# wire format
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "shape", [(17,), (3, 5), (256,), (1000,), (4, 4, 33)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_roundtrip(shape, dtype):
    """Round-trip error is bounded by half a quantization step per element
    (amax/127 per block, plus bf16 scale rounding), shape and dtype are
    restored exactly, and padding never leaks into the output."""
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=shape), dtype
    )
    q = quantize_int8(x, block_size=64)
    assert q.payload.dtype == jnp.int8
    assert q.scales.dtype == jnp.bfloat16
    assert q.payload.shape[1] == 64
    out = dequantize_int8(q, shape, dtype)
    assert out.shape == shape and out.dtype == dtype
    # per-block bound: half a step, padded by bf16 scale rounding (~0.4%)
    amax = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
    bound = (amax / 127.0) * 0.5 * 1.01 + 1e-6
    err = float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - x.astype(jnp.float32)))
    )
    # bf16 inputs additionally round on the way back to bf16
    if dtype == jnp.bfloat16:
        bound += amax / 128.0
    assert err <= bound, (shape, err, bound)


def test_quantize_all_zero_blocks_are_exact():
    x = jnp.zeros((300,), jnp.float32)
    q = quantize_int8(x, block_size=128)
    assert float(jnp.max(jnp.abs(q.scales.astype(jnp.float32)))) == 1.0
    assert np.array_equal(
        np.asarray(dequantize_int8(q, (300,))), np.zeros((300,), np.float32)
    )


def test_quantize_rejects_bad_block_size():
    with pytest.raises(ValueError, match="block_size"):
        quantize_int8(jnp.ones((4,)), block_size=0)


def test_payload_bytes_accounting():
    # 2048 fp32 elements -> 8 blocks of 256 int8 + 8 bf16 scales
    assert int8_payload_bytes(2048, 256) == 2048 + 16
    # padding: 2049 elements needs 9 blocks
    assert int8_payload_bytes(2049, 256) == 9 * 256 + 18
    tree = {
        "big": jnp.zeros((2048,), jnp.float32),  # compressed
        "small": jnp.zeros((10,), jnp.float32),  # below MIN_COMPRESS_SIZE
        "ints": jnp.zeros((2048,), jnp.int32),  # non-float
    }
    unc, comp = payload_bytes(tree, block_size=256)
    assert unc == 2048 * 4 + 10 * 4 + 2048 * 4
    assert comp == (2048 + 16) + 10 * 4 + 2048 * 4
    assert comp < unc


# --------------------------------------------------------------------- #
# error feedback
# --------------------------------------------------------------------- #
def test_error_feedback_telescopes():
    """With a local quantization round-trip as the compressor, the EF
    invariant holds over K steps: sum(emitted) + residual == K * g — no
    gradient signal is ever lost, only delayed."""

    def roundtrip(tree):
        outs = jax.tree_util.tree_map(
            lambda p: dequantize_int8(
                quantize_int8(p, 64), p.shape, p.dtype
            ),
            tree,
        )
        errs = jax.tree_util.tree_map(lambda p, o: p - o, tree, outs)
        return outs, errs

    tx = with_error_feedback(roundtrip)
    g = {
        "w": jnp.asarray(
            np.random.default_rng(1).normal(size=(130,)), jnp.float32
        ),
        "b": jnp.asarray([0.3, -0.7], jnp.float32),
    }
    state = tx.init(g)
    assert isinstance(state, ErrorFeedbackState)
    assert float(jnp.max(jnp.abs(state.residual["w"]))) == 0.0

    K = 12
    total = jax.tree_util.tree_map(jnp.zeros_like, g)
    for _ in range(K):
        emitted, state = tx.update(g, state)
        total = jax.tree_util.tree_map(lambda t, e: t + e, total, emitted)
    for k in g:
        recovered = np.asarray(total[k] + state.residual[k])
        np.testing.assert_allclose(
            recovered, np.asarray(g[k]) * K, rtol=0, atol=1e-4
        )
    # and the compression is genuinely lossy per step (EF is doing work)
    assert float(jnp.max(jnp.abs(state.residual["w"]))) > 0.0


# --------------------------------------------------------------------- #
# two-phase shard_map reduction (8 virtual devices, conftest.py)
# --------------------------------------------------------------------- #
def _dcn_mesh(n):
    return build_mesh(
        MeshSpec(axes={"dp": n}, dcn_axes=("dp",)), jax.devices()[:n]
    )


def test_two_phase_reduce_matches_mean_with_ef_identity():
    """shard_map'd two_phase_dcn_reduce approximates the true per-rank mean
    (int8-bounded error) and satisfies the EF identity exactly:
    out + mean_over_ranks(residual) == true mean."""
    n = 8
    mesh = _dcn_mesh(n)
    reducer = two_phase_dcn_reduce(
        ici_axes=(), dcn_axis="dp", dcn_size=n, block_size=64, min_size=64
    )
    data = jnp.asarray(
        np.random.default_rng(2).normal(size=(n, 2048)), jnp.float32
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P("dp"),
        out_specs=(P("dp"), P("dp")),
        check_rep=False,
    )
    def run(x):
        out, err = reducer(x)  # local [1, 2048]
        return out, err

    out, err = run(data)
    true_mean = np.asarray(jnp.mean(data, axis=0))
    # every rank holds the same approximate mean
    outs = np.asarray(out)
    for j in range(1, n):
        np.testing.assert_array_equal(outs[j], outs[0])
    # int8 error bound: two quantization hops of a ~N(0,1) tensor
    assert float(np.max(np.abs(outs[0] - true_mean))) < 0.05
    # EF identity: the residual mean recovers the quantization error exactly
    recovered = outs[0] + np.asarray(err).mean(axis=0)
    np.testing.assert_allclose(recovered, true_mean, rtol=0, atol=1e-5)


def test_two_phase_small_and_integer_leaves_are_exact():
    """Leaves below min_size and non-float leaves bypass quantization:
    full-precision pmean, zero residual."""
    n = 4
    mesh = _dcn_mesh(n)
    reducer = two_phase_dcn_reduce(
        ici_axes=(), dcn_axis="dp", dcn_size=n, block_size=64, min_size=1024
    )
    small = jnp.arange(n * 8, dtype=jnp.float32).reshape(n, 8)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P("dp"),
        out_specs=(P("dp"), P("dp")),
        check_rep=False,
    )
    def run(x):
        return reducer(x)

    out, err = run(small)
    np.testing.assert_allclose(
        np.asarray(out)[0], np.asarray(jnp.mean(small, axis=0)), rtol=1e-6
    )
    assert float(jnp.max(jnp.abs(err))) == 0.0


def test_two_phase_requires_multislice():
    with pytest.raises(ValueError, match="size >= 2"):
        two_phase_dcn_reduce(ici_axes=(), dcn_axis="dp", dcn_size=1)


def test_split_dcn_axes():
    mesh = build_mesh(
        MeshSpec(axes={"dp": 2, "fsdp": 4}, dcn_axes=("dp",)), jax.devices()
    )
    spec = MeshSpec(axes={"dp": 2, "fsdp": 4}, dcn_axes=("dp",))
    ici, dcn = split_dcn_axes(spec, mesh, ("dp", "fsdp"))
    assert ici == ("fsdp",)
    assert dcn == ("dp",)
    # without declared dcn axes everything is in-slice
    spec2 = MeshSpec(axes={"dp": 2, "fsdp": 4})
    mesh2 = build_mesh(spec2, jax.devices())
    ici2, dcn2 = split_dcn_axes(spec2, mesh2, ("dp", "fsdp"))
    assert ici2 == ("dp", "fsdp")
    assert dcn2 == ()


# --------------------------------------------------------------------- #
# trainer integration
# --------------------------------------------------------------------- #
class WideBoringModel(BoringModel):
    """BoringModel with a >= MIN_COMPRESS_SIZE kernel (32 x 64 = 2048) so
    the int8 path actually quantizes something."""

    def __init__(self):
        super().__init__()
        self.model = _WideNet()


class _WideNet(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(2)(nn.tanh(nn.Dense(64)(x)))


def _strategy(mode):
    return XLAStrategy(
        mesh_spec=MeshSpec(axes={"dp": 8}, dcn_axes=("dp",)),
        dcn_grad_compression=mode,
    )


def test_strategy_knob_resolution(monkeypatch):
    assert XLAStrategy().dcn_grad_compression == "none"
    assert _strategy("int8").dcn_grad_compression == "int8"
    monkeypatch.setenv("RLT_DCN_COMPRESSION", "INT8")
    assert XLAStrategy().dcn_grad_compression == "int8"
    # the constructor wins over the environment
    assert _strategy("none").dcn_grad_compression == "none"
    monkeypatch.setenv("RLT_DCN_COMPRESSION", "float8")
    with pytest.raises(ValueError, match="float8"):
        _ = XLAStrategy().dcn_grad_compression


def test_mode_none_is_the_standard_path(tmp_path):
    """dcn_grad_compression='none' must not touch the train step: no
    compression context, no error-feedback state in the optimizer — the
    bitwise-parity guarantee is taken by construction, not by tolerance."""
    model = BoringModel()
    trainer = get_trainer(
        str(tmp_path), strategy=_strategy("none"), checkpoint_callback=False
    )
    trainer.fit(model)
    assert trainer._dcn_ctx is None
    assert not any(
        isinstance(s, ErrorFeedbackState)
        for s in jax.tree_util.tree_leaves(
            trainer._opt_state, is_leaf=lambda x: isinstance(x, ErrorFeedbackState)
        )
    )


@pytest.mark.slow
def test_int8_compression_converges_within_2pct(tmp_path):
    """The acceptance bar: int8-compressed training lands within 2% of the
    uncompressed loss on a model whose kernel actually takes the quantized
    path, and the EF residual is stacked [n_dcn, ...] and sharded over dp."""

    def run(mode, sub):
        model = WideBoringModel()
        trainer = get_trainer(
            str(tmp_path / sub),
            max_epochs=2,
            strategy=_strategy(mode),
            checkpoint_callback=False,
        )
        trainer.fit(model)
        return float(trainer.callback_metrics["train_loss_epoch"]), trainer

    base, _ = run("none", "off")
    loss, trainer = run("int8", "on")
    assert trainer._dcn_ctx is not None
    ef = trainer._opt_state[0]
    assert isinstance(ef, ErrorFeedbackState)
    leaf = jax.tree_util.tree_leaves(ef.residual)[0]
    assert leaf.shape[0] == 8  # stacked over the dcn axis
    assert "dp" in str(leaf.sharding)
    assert abs(loss - base) <= 0.02 * max(abs(base), 1e-8), (loss, base)


def test_compression_rejects_zero_stage(tmp_path):
    from ray_lightning_tpu.parallel.sharding import ShardingPolicy

    strat = XLAStrategy(
        mesh_spec=MeshSpec(axes={"dp": 8}, dcn_axes=("dp",)),
        sharding_policy=ShardingPolicy(zero_stage=2),
        dcn_grad_compression="int8",
    )
    trainer = get_trainer(
        str(tmp_path), strategy=strat, checkpoint_callback=False
    )
    with pytest.raises(ValueError, match="zero_stage"):
        trainer.fit(BoringModel())


def test_compression_without_dcn_axes_falls_back(tmp_path, caplog):
    """int8 on a single-slice mesh (no MeshSpec.dcn_axes) is a documented
    no-op: warn and train uncompressed."""
    import logging

    strat = XLAStrategy(
        mesh_spec=MeshSpec(axes={"dp": 8}), dcn_grad_compression="int8"
    )
    trainer = get_trainer(
        str(tmp_path), strategy=strat, checkpoint_callback=False
    )
    with caplog.at_level(logging.WARNING):
        trainer.fit(BoringModel())
    assert trainer._dcn_ctx is None
    assert any("no data axis rides DCN" in r.getMessage() for r in caplog.records)


def test_bad_block_size_env_raises(tmp_path, monkeypatch):
    monkeypatch.setenv("RLT_DCN_BLOCK_SIZE", "huge")
    trainer = get_trainer(
        str(tmp_path), strategy=_strategy("int8"), checkpoint_callback=False
    )
    with pytest.raises(ValueError, match="RLT_DCN_BLOCK_SIZE"):
        trainer.fit(BoringModel())


# --------------------------------------------------------------------- #
# 2-process DCN hop (satellite: the collective crossing real process
# boundaries, CPU-only via the distributed CPU backend)
# --------------------------------------------------------------------- #
_WORKER = textwrap.dedent(
    """
    import os, sys
    import jax
    # cross-process CPU collectives need the gloo transport (the default
    # CPU backend refuses multiprocess computations)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:%(port)d",
        num_processes=2,
        process_id=int(sys.argv[1]),
    )
    import numpy as np
    import jax.numpy as jnp
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_lightning_tpu.parallel.compression import two_phase_dcn_reduce
    from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(axes={"dp": 2}, dcn_axes=("dp",)))
    reducer = two_phase_dcn_reduce(
        ici_axes=(), dcn_axis="dp", dcn_size=2, block_size=64, min_size=64
    )
    rows = np.stack(
        [np.full((2048,), 1.0, np.float32), np.full((2048,), 3.0, np.float32)]
    )
    sharding = NamedSharding(mesh, P("dp"))
    data = jax.make_array_from_callback(
        (2, 2048), sharding, lambda idx: rows[idx]
    )

    @partial(
        shard_map, mesh=mesh, in_specs=P("dp"),
        out_specs=(P("dp"), P("dp")), check_rep=False,
    )
    def run(x):
        return reducer(x)

    out, err = run(data)
    local = np.asarray(out.addressable_shards[0].data)[0]
    # mean of 1.0 and 3.0 constant rows: exactly representable per block
    assert np.allclose(local, 2.0, atol=0.05), local[:4]
    print("WORKER_OK", int(sys.argv[1]), float(local[0]), flush=True)
    """
)


@pytest.mark.slow
def test_two_process_dcn_reduction(tmp_path):
    """The reduction's all_to_all/all_gather actually cross a process
    boundary: two CPU processes form a dp=2 mesh over the distributed
    backend and both must agree on the compressed mean."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + [p for p in (os.environ.get("PYTHONPATH"),) if p]
        ),
    }
    script = _WORKER % {"port": port}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(i)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"WORKER_OK {i}" in out, out
    # both processes computed the same mean
    vals = sorted(
        line.split()[-1] for o in outs for line in o.splitlines()
        if line.startswith("WORKER_OK")
    )
    assert len(vals) == 2 and vals[0] == vals[1], vals
