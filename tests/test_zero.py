"""Explicit ZeRO sharded training (parallel/zero.py + the zero_train_step):
reduce-scattered grads, per-rank 1/N optimizer update, grouped (optionally
int8 block-scaled) param all-gather.

The acceptance bar: explicit ZeRO-2/3 losses and final params match DDP on
the same data, the quantized all-gather stays within error-feedback
tolerance while moving measurably fewer bytes, eligibility failures fall
back to the GSPMD path with a warning (or raise when quantization was
explicitly requested), and the layout survives checkpoint round-trips,
2-process gloo meshes, and elastic shrink/regrow with bitwise-identical
params.
"""
import glob
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import flax.linen as nn

import ray_lightning_tpu as rlt
from ray_lightning_tpu.parallel.sharding import ShardingPolicy
from ray_lightning_tpu.parallel.zero import PAD_UNIT, ZeroContext
from ray_lightning_tpu.strategies.base import XLAStrategy
from tests.utils import BoringModel, live_bytes

pytestmark = pytest.mark.zero


class _ZeroNet(nn.Module):
    @nn.compact
    def __call__(self, x):
        h = nn.tanh(nn.Dense(300)(x))
        return nn.Dense(10)(h)


class _ZeroModel(rlt.LightningModule):
    def __init__(self):
        super().__init__()
        self.net = _ZeroNet()

    def init_params(self, rng):
        return self.net.init(rng, jnp.zeros((1, 64)))

    def training_step(self, params, batch, batch_idx):
        x, y = batch
        loss = jnp.mean((self.net.apply(params, x) - y) ** 2)
        self.log("loss", loss)
        return loss

    def configure_optimizers(self):
        return optax.adam(1e-2)


def _loader(n=64):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 64).astype(np.float32)
    y = rng.randn(n, 10).astype(np.float32)
    return rlt.DataLoader(
        list(zip(x, y)),
        batch_size=16,
        collate_fn=lambda items: (
            np.stack([i[0] for i in items]),
            np.stack([i[1] for i in items]),
        ),
    )


class _LossTrace(rlt.Callback):
    def __init__(self):
        self.losses = []

    def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
        self.losses.append(float(np.asarray(trainer.logged_metrics["loss"])))


def _policy(stage, min_shard_size=1000):
    return ShardingPolicy(
        zero_stage=stage, data_axes=("dp",), min_shard_size=min_shard_size
    )


def _fit(policy, quant=False, clip=0.0, steps=6, telemetry=None, **tr_kw):
    model = _ZeroModel()
    trace = _LossTrace()
    trainer = rlt.Trainer(
        strategy=XLAStrategy(
            devices=4,
            sharding_policy=policy,
            zero_quantized_allgather=quant,
            telemetry=telemetry,
        ),
        max_steps=steps,
        max_epochs=20,
        gradient_clip_val=clip,
        callbacks=[trace],
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
        seed=0,
        **tr_kw,
    )
    trainer.fit(model, _loader())
    return trainer, jax.device_get(trainer._params), trace.losses


def _max_abs_diff(a, b):
    return max(
        float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


# --------------------------------------------------------------------- #
# ZeroContext layout invariants
# --------------------------------------------------------------------- #
def test_zero_context_padding_and_groups():
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params = {
        "a": jnp.zeros((130, 10)),  # 1300 elems: big, pads 1300 -> 1536
        "b": jnp.zeros((7,)),  # small: stays replicated
        "c": jnp.zeros((64, 32)),  # 2048 elems: already a PAD_UNIT multiple
    }
    ctx = ZeroContext(mesh, "dp", params, stage=3, min_shard_size=1000)
    assert [b.path for b in ctx.big_leaves] == ["a", "c"]
    for big in ctx.big_leaves:
        # world-independent padding: the padded GLOBAL shape is a PAD_UNIT
        # multiple, so elastic resizes to any n | PAD_UNIT re-place state
        assert big.padded % PAD_UNIT == 0
        assert big.chunk * 4 == big.padded
    assert ctx.big_leaves[0].padded == 1536
    assert ctx.big_leaves[1].padded == 2048
    assert ctx.gather_fp32_bytes() == 4 * (1536 + 2048)
    assert "stage" in ctx.describe() and "a" in ctx.describe()
    # quantized wire: 1 byte/elem + 2-byte scale per quant block
    qctx = ZeroContext(
        mesh, "dp", params, stage=3, min_shard_size=1000, quantized=True
    )
    assert qctx.gather_wire_bytes() < qctx.gather_fp32_bytes()


def test_quantized_gather_requires_stage3():
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    params = {"a": jnp.zeros((64, 32))}
    with pytest.raises(ValueError, match="stage"):
        ZeroContext(
            mesh, "dp", params, stage=2, min_shard_size=1000, quantized=True
        )


# --------------------------------------------------------------------- #
# numerics: the explicit step vs DDP
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def ddp_run():
    trainer, params, losses = _fit(_policy(0))
    assert trainer._train_program == "train_step"
    return params, losses


@pytest.mark.parametrize("stage", [2, 3])
def test_explicit_zero_matches_ddp(ddp_run, stage):
    ddp_params, ddp_losses = ddp_run
    trainer, params, losses = _fit(_policy(stage))
    assert trainer._train_program == "zero_train_step"
    assert trainer._zero_ctx is not None
    np.testing.assert_allclose(losses, ddp_losses, rtol=1e-4)
    assert _max_abs_diff(params, ddp_params) < 1e-4


def test_live_state_bytes_by_stage():
    """What four devices hold of params and optimizer state after a fit, to
    the byte. The explicit step keeps params replicated over the data axis at
    both stages and holds each big leaf's two Adam moments once, padded, where
    the replicated step holds them four times; stage 3 adds one fp32 master
    flat a big leaf. So replicated > ZeRO-3 > ZeRO-2: the stage buys the
    quantizable gather, not memory."""
    trainers = {stage: _fit(_policy(stage), steps=2)[0] for stage in (0, 2, 3)}
    live = {stage: live_bytes((t._params, t._opt_state))
            for stage, t in trainers.items()}
    big = trainers[3]._zero_ctx.big_leaves
    assert [b.path for b in big] == [
        "params/Dense_0/kernel", "params/Dense_1/kernel"]
    held = 4 * sum(4 * b.size for b in big)       # a replicated moment, 4 devices
    padded = sum(4 * b.padded for b in big)       # a sharded flat, once
    assert live[0] - live[2] == 2 * (held - padded)
    assert live[3] - live[2] == padded
    assert live[0] > live[3] > live[2]


def test_quantized_allgather_close_and_compressed(ddp_run):
    ddp_params, ddp_losses = ddp_run
    trainer, params, losses = _fit(_policy(3), quant=True, telemetry=True)
    assert trainer._train_program == "zero_train_step"
    ctx = trainer._zero_ctx
    # the compression is real: wire bytes measurably below the fp32 gather
    assert ctx.gather_wire_bytes() < 0.5 * ctx.gather_fp32_bytes()
    # ...and lossy-but-bounded: error feedback keeps training on track
    np.testing.assert_allclose(losses, ddp_losses, rtol=0.1)
    assert _max_abs_diff(params, ddp_params) < 0.05
    # wire-cost gauges published under the program label
    from ray_lightning_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.get_registry()
    wire = reg.gauge("rlt_zero_allgather_bytes", program="zero_train_step")
    fp32 = reg.gauge("rlt_zero_allgather_fp32_bytes", program="zero_train_step")
    assert 0 < wire.value < fp32.value
    assert reg.gauge("rlt_zero_sharded_params").value >= 1


def test_gradient_clipping_inside_shard_map(ddp_run):
    ddp_params, _ = ddp_run
    # a generous clip threshold is a no-op: the sharded global-norm clip
    # must reproduce DDP exactly, proving the norm is computed globally
    # (a shard-local norm would scale differently on every rank)
    _, params, _ = _fit(_policy(3), clip=1e6)
    assert _max_abs_diff(params, ddp_params) < 1e-4


# --------------------------------------------------------------------- #
# eligibility gates
# --------------------------------------------------------------------- #
def test_quantized_with_stage2_raises():
    with pytest.raises(ValueError, match="zero_stage >= 3"):
        _fit(_policy(1), quant=True)


def test_partition_rules_force_gspmd_fallback(recwarn):
    # rules that claim MODEL axes now compose with the explicit step
    # (tests/test_parallel3d.py); only a rule claiming the DATA axis —
    # like this one — still forces the GSPMD fallback, observably
    # (rlt_zero_fallback_total{reason="rules_claim_data_axis"})
    model = _ZeroModel()
    trainer = rlt.Trainer(
        strategy=XLAStrategy(
            devices=4,
            sharding_policy=_policy(2),
            partition_rules="Dense_0/kernel=None,dp",
        ),
        max_steps=2,
        max_epochs=20,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    trainer.fit(model, _loader())
    assert trainer._train_program == "train_step"
    assert trainer._zero_ctx is None


def test_quantized_with_rules_raises():
    # quantization demands the explicit step; a rule claiming the data
    # axis makes it ineligible, so this must raise rather than silently
    # training unquantized (model-axis rules would compose fine)
    model = _ZeroModel()
    trainer = rlt.Trainer(
        strategy=XLAStrategy(
            devices=4,
            sharding_policy=_policy(3),
            partition_rules="Dense_0/kernel=None,dp",
            zero_quantized_allgather=True,
        ),
        max_steps=2,
        max_epochs=20,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    with pytest.raises(ValueError, match="explicit ZeRO"):
        trainer.fit(model, _loader())


def test_small_model_falls_back(recwarn):
    # BoringModel's Dense(2) never reaches the default min_shard_size:
    # zero_stage=2 silently (warned) degrades to GSPMD propagation
    trainer = rlt.Trainer(
        strategy=XLAStrategy(devices=4, sharding_policy=ShardingPolicy(
            zero_stage=2, data_axes=("dp",)
        )),
        max_steps=2,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    trainer.fit(BoringModel())
    assert trainer._train_program == "train_step"


# --------------------------------------------------------------------- #
# checkpoint round-trip
# --------------------------------------------------------------------- #
def test_checkpoint_roundtrip_under_zero(tmp_path):
    trainer, params, _ = _fit(_policy(3), steps=3)
    path = os.path.join(str(tmp_path), "z.ckpt")
    trainer.save_checkpoint(path)

    model2 = _ZeroModel()
    trainer2 = rlt.Trainer(
        strategy=XLAStrategy(devices=4, sharding_policy=_policy(3)),
        max_steps=6,
        max_epochs=20,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
        seed=0,
    )
    trainer2.fit(model2, _loader(), ckpt_path=path)
    assert trainer2.global_step == 6
    assert trainer2._train_program == "zero_train_step"


# --------------------------------------------------------------------- #
# 2-process gloo mesh + elastic shrink/regrow (slow)
# --------------------------------------------------------------------- #
def _collate(items):
    return (
        np.stack([i[0] for i in items]),
        np.stack([i[1] for i in items]),
    )


class _DistZeroModel(_ZeroModel):
    """Picklable into worker actors: carries its own dataloader and uses
    the module-level collate fn (a lambda would not survive pickling)."""

    def train_dataloader(self):
        rng = np.random.RandomState(0)
        x = rng.randn(64, 64).astype(np.float32)
        y = rng.randn(64, 10).astype(np.float32)
        return rlt.DataLoader(list(zip(x, y)), batch_size=16, collate_fn=_collate)


def _dist_fit(tmp_root, strategy):
    model = _DistZeroModel()
    trainer = rlt.Trainer(
        strategy=strategy,
        max_epochs=2,
        seed=0,
        default_root_dir=tmp_root,
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
    )
    trainer.fit(model)
    assert trainer.state.status == "finished"
    return (
        jax.device_get(model.params),
        float(np.asarray(trainer.logged_metrics["loss"])),
    )


@pytest.mark.slow
def test_two_process_zero3_matches_ddp(tmp_root):
    """ZeRO-3's reduce-scatter/all-gather crossing a REAL process boundary:
    2 single-device CPU workers over the gloo backend. The quantized run
    doubles as the engagement proof — a fallback to GSPMD would raise
    instead of training (quantization demands the explicit step)."""
    ddp_params, ddp_loss = _dist_fit(
        tmp_root,
        rlt.RayStrategy(num_workers=2, platform="cpu", devices_per_worker=1),
    )
    z_params, z_loss = _dist_fit(
        tmp_root,
        rlt.RayShardedStrategy(
            num_workers=2,
            platform="cpu",
            devices_per_worker=1,
            zero_stage=3,
            sharding_policy=_policy(3),
        ),
    )
    np.testing.assert_allclose(z_loss, ddp_loss, rtol=1e-4)
    assert _max_abs_diff(z_params, ddp_params) < 1e-4

    q_params, q_loss = _dist_fit(
        tmp_root,
        rlt.RayShardedStrategy(
            num_workers=2,
            platform="cpu",
            devices_per_worker=1,
            zero_stage=3,
            sharding_policy=_policy(3),
            zero_quantized_allgather=True,
        ),
    )
    np.testing.assert_allclose(q_loss, ddp_loss, rtol=0.1)
    assert _max_abs_diff(q_params, ddp_params) < 0.05


class _ZeroProbeModel(BoringModel):
    """BoringModel with a leaf big enough for the explicit ZeRO path, plus
    the elastic e2e's probe protocol: world records per epoch, params hash
    at fit end (hash equality across members = bitwise-identical state)."""

    def __init__(self, probe_dir):
        super().__init__()
        self.model = nn.Dense(512)  # 32x512 kernel: a big leaf
        self._probe_dir = probe_dir

    def _write(self, name, text):
        with open(os.path.join(self._probe_dir, name), "a") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())

    def on_train_epoch_start(self):
        self._write(
            f"probe_{os.getpid()}.jsonl",
            json.dumps(
                {"pid": os.getpid(), "epoch": self.trainer.current_epoch,
                 "world": jax.process_count()}
            ) + "\n",
        )

    def on_fit_end(self):
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(
            jax.device_get(self.trainer._params)
        ):
            h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
        self._write(f"hash_{os.getpid()}", h.hexdigest())


@pytest.mark.slow
@pytest.mark.elastic
def test_elastic_shrink_regrow_explicit_zero(tmp_root, monkeypatch):
    """Elastic shrink to world 1 and regrow to 2 under the explicit ZeRO-3
    step: the PAD_UNIT padding makes global padded shapes world-independent,
    so the re-built ZeroContext re-places the same state and every member
    leaves fit with bitwise-identical params."""
    monkeypatch.setenv("RLT_FAULT", "rank1:crash@step2")
    monkeypatch.setenv("RLT_FAULT_FUSE", os.path.join(tmp_root, "fuses"))
    probe_dir = os.path.join(tmp_root, "probes")
    os.makedirs(probe_dir)

    strategy = rlt.RayShardedStrategy(
        num_workers=2, platform="cpu", devices_per_worker=1,
        zero_stage=3, sharding_policy=_policy(3, min_shard_size=1024),
        elastic=True, min_workers=1, max_failures=0,
        hang_timeout=15.0, heartbeat_interval=0.1,
    )
    trainer = rlt.Trainer(
        max_epochs=3, strategy=strategy, logger=False, seed=0,
        default_root_dir=tmp_root, enable_checkpointing=False,
        callbacks=[
            rlt.OrbaxModelCheckpoint(
                dirpath=os.path.join(tmp_root, "ob"),
                every_n_steps=1,
                async_save=False,
            )
        ],
        limit_train_batches=2, limit_val_batches=1, num_sanity_val_steps=0,
        enable_progress_bar=False,
    )
    trainer.fit(_ZeroProbeModel(probe_dir))

    assert trainer.state.status == "finished"
    assert os.path.exists(os.path.join(tmp_root, "fuses", "rank1-crash-at2"))

    records = []
    for path in glob.glob(os.path.join(probe_dir, "probe_*.jsonl")):
        with open(path) as f:
            records.extend(json.loads(line) for line in f if line.strip())
    assert {r["world"] for r in records} == {1, 2}, records

    hashes = {}
    for path in glob.glob(os.path.join(probe_dir, "hash_*")):
        with open(path) as f:
            hashes[path] = f.read().strip()
    assert len(hashes) >= 2, hashes  # survivor + re-admitted joiner
    assert len(set(hashes.values())) == 1, hashes
