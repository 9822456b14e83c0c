"""Composed 3D parallelism: explicit ZeRO (data axis) x tensor-parallel
partition rules (model axes) x 1F1B pipelining (stage axis), all inside the
trainer's compiled step.

The acceptance bar: zero3+tp losses/params match DDP on the same data
(rtol ~1e-4), the quantized all-gather still halves wire bytes under the
composition, the in-trainer pipelined step matches the sequential 1F1B
reference math, every engaged program keeps a flat jit cache, fallbacks are
observable (rlt_zero_fallback_total{reason} + describe_parallelism), and
elastic shrink/regrow re-engages the composed layout with bitwise params.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import ray_lightning_tpu as rlt
from ray_lightning_tpu.parallel.pipeline_1f1b import (
    identity_fwd_psum_bwd,
    psum_fwd_identity_bwd,
    sequential_1f1b_reference,
)
from ray_lightning_tpu.parallel.sharding import ShardingPolicy
from ray_lightning_tpu.parallel.zero import PAD_UNIT, ZeroContext
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.strategies.base import XLAStrategy
from tests.utils import live_bytes

pytestmark = pytest.mark.parallel3d

TP_RULES = "^w1$=None,tp;^b1$=tp;^w2$=tp,None"


# --------------------------------------------------------------------- #
# models
# --------------------------------------------------------------------- #
class _TpMLP(rlt.LightningModule):
    """Explicit-params MLP; ``tp=True`` switches the step to megatron
    column->row parallel math with the f/g operators (the shard_map'd
    composed step hands the module tp-LOCAL weight shards)."""

    def __init__(self, tp=False):
        super().__init__()
        self.tp = tp

    def init_params(self, rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": 0.2 * jax.random.normal(k1, (64, 256), jnp.float32),
            "b1": jnp.zeros((256,), jnp.float32),
            "w2": 0.2 * jax.random.normal(k2, (256, 16), jnp.float32),
            "b2": jnp.zeros((16,), jnp.float32),
        }

    def training_step(self, params, batch, batch_idx):
        x, y = batch
        if self.tp:
            # column-parallel w1 (f on entry), row-parallel w2 (g on exit)
            hin = identity_fwd_psum_bwd(x, "tp")
            h = jnp.tanh(hin @ params["w1"] + params["b1"])
            out = psum_fwd_identity_bwd(h @ params["w2"], "tp") + params["b2"]
        else:
            h = jnp.tanh(x @ params["w1"] + params["b1"])
            out = h @ params["w2"] + params["b2"]
        loss = jnp.mean((out - y) ** 2)
        self.log("loss", loss)
        return loss

    def configure_optimizers(self):
        return optax.adam(1e-2)


class _PipeModel(rlt.LightningModule):
    """2-stage pipelined MLP: init_params follows the pipeline contract
    ({"stages": leaves leading with the stage count, "last": head})."""

    def init_params(self, rng):
        k1, k2 = jax.random.split(rng)
        return {
            "stages": {"w": 0.3 * jax.random.normal(k1, (2, 32, 32), jnp.float32)},
            "last": {"head": 0.3 * jax.random.normal(k2, (32, 8), jnp.float32)},
        }

    def pipeline_stage(self, stage_params, x):
        return jnp.tanh(x @ stage_params["w"])

    def pipeline_last(self, last_params, y, targets):
        return jnp.mean((y @ last_params["head"] - targets) ** 2)

    def configure_optimizers(self):
        return optax.adam(1e-2)


class _PipeSeqRefModel(_PipeModel):
    """DDP reference: training_step IS the sequential 1F1B reference, so
    trainer-level parity proves the in-trainer pipelined step computes the
    same math as ``sequential_1f1b_reference`` (satellite: in-step parity)."""

    def training_step(self, params, batch, batch_idx):
        x, y = batch
        loss = sequential_1f1b_reference(
            self.pipeline_stage,
            self.pipeline_last,
            params["stages"],
            params["last"],
            x,
            y,
            num_microbatches=4,
        )
        self.log("loss", loss)
        return loss


# --------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------- #
def _loader(d_in, d_out, n=64):
    rng = np.random.RandomState(0)
    x = rng.randn(n, d_in).astype(np.float32)
    y = rng.randn(n, d_out).astype(np.float32)
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


def _fit(model, loader, strategy, steps=6, **tr_kw):
    trace = _LossTrace()
    trainer = rlt.Trainer(
        strategy=strategy,
        max_steps=steps,
        max_epochs=20,
        callbacks=[trace],
        enable_progress_bar=False,
        enable_checkpointing=False,
        logger=False,
        seed=0,
        **tr_kw,
    )
    # every build goes through the holder so the flat-cache invariant is
    # checkable after fit: one compile, zero steady-state recompiles
    built = {}
    orig = trainer._build_train_step
    trainer._build_train_step = lambda: built.setdefault("step", orig())
    trainer.fit(model, loader)
    return trainer, jax.device_get(trainer._params), trace.losses, built["step"]


def _max_abs_diff(a, b):
    return max(
        float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def _policy(stage, min_shard_size=1024):
    return ShardingPolicy(
        zero_stage=stage, data_axes=("dp",), min_shard_size=min_shard_size
    )


def _tp_strategy(stage=3, quant=False, telemetry=None, rules=TP_RULES, devices=4):
    return XLAStrategy(
        devices=devices,
        mesh_spec=MeshSpec(axes={"dp": -1, "tp": 2}),
        sharding_policy=_policy(stage),
        partition_rules=rules,
        zero_quantized_allgather=quant,
        telemetry=telemetry,
    )


def _ddp_tp_run(steps=6):
    return _fit(
        _TpMLP(tp=False),
        _loader(64, 16),
        XLAStrategy(devices=4, sharding_policy=ShardingPolicy.ddp()),
        steps=steps,
    )


# --------------------------------------------------------------------- #
# composed ZeroContext layout invariants
# --------------------------------------------------------------------- #
def test_composed_layout_pads_per_model_shard():
    mesh = build_mesh(MeshSpec(axes={"dp": 2, "tp": 2}), jax.devices()[:4])
    params = {
        "w1": jnp.zeros((64, 256)),  # tp-sharded on dim 1: local 8192
        "b1": jnp.zeros((256,)),  # small but tp-sharded: model path
        "w2": jnp.zeros((256, 16)),  # tp-sharded on dim 0: local 2048
        "b2": jnp.zeros((16,)),  # small replicated
    }
    specs = {"w1": P(None, "tp"), "b1": P("tp"), "w2": P("tp", None), "b2": P()}
    ctx = ZeroContext(
        mesh, "dp", params, stage=3, min_shard_size=1024, param_specs=specs
    )
    assert [b.path for b in ctx.big_leaves] == ["w1", "w2"]
    for big in ctx.big_leaves:
        # the pad unit applies to each MODEL shard independently, so the
        # global flat [n_model * padded] is world-size independent
        assert big.n_model == 2
        assert big.padded % PAD_UNIT == 0
        assert big.model_axes == ("tp",)
    assert ctx.big_leaves[0].padded == 8192 and ctx.big_leaves[0].chunk == 4096
    assert ctx.big_leaves[1].padded == 2048 and ctx.big_leaves[1].chunk == 1024
    # both big leaves share the ("tp",) signature: one gather group whose
    # flat is laid out model-shard-major and sharded over (tp, dp)
    assert len(ctx.groups) == 1
    assert ctx.flat_spec(("tp",)) == P(("tp", "dp"))
    # per-leaf fractions: big = 1/(n*n_model), small sharded = 1/n_model
    fr = {p: ctx.shard_fraction(i) for i, p in enumerate(ctx.leaf_paths)}
    assert fr["w1"] == pytest.approx(0.25) and fr["w2"] == pytest.approx(0.25)
    assert fr["b1"] == pytest.approx(0.5) and fr["b2"] == 1.0
    assert "tp" in ctx.describe()


# --------------------------------------------------------------------- #
# zero3 x tensor parallel inside the trainer
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def ddp_tp_run():
    return _ddp_tp_run()


def test_zero3_tp_matches_ddp(ddp_tp_run):
    _, ddp_params, ddp_losses, _ = ddp_tp_run
    trainer, params, losses, step = _fit(
        _TpMLP(tp=True), _loader(64, 16), _tp_strategy(stage=3)
    )
    assert trainer._train_program == "zero_train_step"
    assert trainer._zero_ctx is not None
    # rules own the model axis; ZeRO owns the data axis
    assert any(b.model_axes == ("tp",) for b in trainer._zero_ctx.big_leaves)
    np.testing.assert_allclose(losses, ddp_losses, rtol=1e-4, atol=1e-5)
    assert _max_abs_diff(params, ddp_params) < 1e-4
    # zero-recompile invariant: one trace at step 0, flat from step 2 on
    assert step._cache_size() == 1
    # params keep their model-axis placement on device
    w1 = trainer._params["w1"]
    assert w1.sharding.spec == P(None, "tp")


def test_tp_holds_less_live_state_than_zero3_alone():
    """Model-axis sharding shrinks what the four devices hold below
    data-axis-only ZeRO: under `tp` a leaf the rules claim is held once a
    data replica, in halves, where ZeRO alone keeps it whole on every
    device (the explicit step shards moments and masters, not params)."""
    zero3, _, _, _ = _fit(
        _TpMLP(tp=False), _loader(64, 16),
        XLAStrategy(devices=4, sharding_policy=_policy(3)), steps=2,
    )
    zero3_tp, _, _, _ = _fit(
        _TpMLP(tp=True), _loader(64, 16), _tp_strategy(stage=3), steps=2
    )
    assert zero3._train_program == zero3_tp._train_program == "zero_train_step"
    nbytes = {k: 4 * v.size for k, v in zero3._params.items()}
    assert live_bytes(zero3._params) == 4 * sum(nbytes.values())
    assert live_bytes(zero3_tp._params) == (
        2 * (nbytes["w1"] + nbytes["b1"] + nbytes["w2"]) + 4 * nbytes["b2"])
    assert live_bytes((zero3_tp._params, zero3_tp._opt_state)) < live_bytes(
        (zero3._params, zero3._opt_state))


def test_zero2_tp_matches_ddp(ddp_tp_run):
    _, ddp_params, _, _ = ddp_tp_run
    trainer, params, _, _ = _fit(
        _TpMLP(tp=True), _loader(64, 16), _tp_strategy(stage=2)
    )
    assert trainer._train_program == "zero_train_step"
    assert trainer._zero_ctx.stage == 2
    assert _max_abs_diff(params, ddp_params) < 1e-4


def test_composed_quantized_wire_reduction(ddp_tp_run):
    _, ddp_params, _, _ = ddp_tp_run
    trainer, params, _, step = _fit(
        _TpMLP(tp=True), _loader(64, 16), _tp_strategy(stage=3, quant=True)
    )
    assert trainer._train_program == "zero_train_step"
    ctx = trainer._zero_ctx
    # the int8 block-scaled payload must survive the multi-axis
    # composition at >= 50% wire savings vs an fp32 gather
    assert ctx.gather_wire_bytes() <= 0.5 * ctx.gather_fp32_bytes()
    # error feedback keeps the trajectory close to exact DDP
    assert _max_abs_diff(params, ddp_params) < 0.05
    assert step._cache_size() == 1


# --------------------------------------------------------------------- #
# 1F1B pipelining inside the trainer
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def seq_ref_run():
    # DDP trainer whose step IS sequential_1f1b_reference: the parity
    # baseline for the in-trainer pipelined programs
    return _fit(
        _PipeSeqRefModel(),
        _loader(32, 8),
        XLAStrategy(devices=4, sharding_policy=ShardingPolicy.ddp()),
    )


def test_pipeline_parity_in_trainer(seq_ref_run):
    _, ref_params, ref_losses, _ = seq_ref_run
    trainer, params, losses, step = _fit(
        _PipeModel(),
        _loader(32, 8),
        XLAStrategy(
            devices=4,
            mesh_spec=MeshSpec.composed(pp=2),
            sharding_policy=ShardingPolicy.ddp(),
            partition_rules="stages/.*=pp",  # rules place the stage axis
            pipeline_stages=2,
            pipeline_microbatches=4,
        ),
    )
    assert trainer._train_program == "pipeline_train_step"
    cfg = trainer._pp_cfg
    assert cfg["stages"] == 2 and cfg["microbatches"] == 4
    assert cfg["data_axis"] == "dp"
    # stage placement resolved through the rules engine
    stage_spec = jax.tree_util.tree_leaves(
        cfg["param_specs"]["stages"],
        is_leaf=lambda s: isinstance(s, P),
    )[0]
    assert stage_spec == P("pp")
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-5)
    assert _max_abs_diff(params, ref_params) < 1e-4
    assert step._cache_size() == 1


def test_pipeline_zero_composed(seq_ref_run):
    _, ref_params, ref_losses, _ = seq_ref_run
    trainer, params, losses, step = _fit(
        _PipeModel(),
        _loader(32, 8),
        XLAStrategy(
            devices=4,
            mesh_spec=MeshSpec.composed(pp=2),
            sharding_policy=_policy(3),
            pipeline_stages=2,
            pipeline_microbatches=4,
        ),
    )
    assert trainer._train_program == "pipeline_zero_train_step"
    ctx = trainer._zero_ctx
    assert ctx is not None
    # the stage tensor is sharded over BOTH the pp model axis and ZeRO's
    # data axis; the head stays replicated (below min_shard_size)
    assert [b.path for b in ctx.big_leaves] == ["stages/w"]
    assert ctx.big_leaves[0].model_axes == ("pp",)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-5)
    assert _max_abs_diff(params, ref_params) < 1e-4
    assert step._cache_size() == 1


def test_pipeline_misconfig_raises():
    # pipelining is an explicit opt-in: a module without the stage fns
    # must raise, not silently fall back
    with pytest.raises(ValueError, match="pipeline_stage"):
        _fit(
            _TpMLP(),
            _loader(64, 16),
            XLAStrategy(
                devices=4,
                mesh_spec=MeshSpec.composed(pp=2),
                sharding_policy=ShardingPolicy.ddp(),
                pipeline_stages=2,
            ),
        )
    # mesh without a pp axis of the right size
    with pytest.raises(ValueError, match="mesh"):
        _fit(
            _PipeModel(),
            _loader(32, 8),
            XLAStrategy(
                devices=4,
                sharding_policy=ShardingPolicy.ddp(),
                pipeline_stages=2,
            ),
        )


# --------------------------------------------------------------------- #
# observable fallbacks + the composed placement report
# --------------------------------------------------------------------- #
def test_zero_fallback_counter_and_describe(recwarn):
    trainer, _, _, _ = _fit(
        _TpMLP(tp=False),
        _loader(64, 16),
        XLAStrategy(
            devices=4,
            sharding_policy=_policy(2),
            partition_rules="^w1$=None,dp",  # claims the DATA axis
            telemetry=True,
        ),
        steps=2,
    )
    assert trainer._train_program == "train_step"
    assert trainer._zero_fallback_reason == "rules_claim_data_axis"
    from ray_lightning_tpu.observability import metrics as obs_metrics

    reg = obs_metrics.get_registry()
    counter = reg.counter(
        "rlt_zero_fallback_total", reason="rules_claim_data_axis"
    )
    assert counter.value >= 1
    desc = trainer.describe_parallelism()
    assert "train program: train_step" in desc
    assert "rules_claim_data_axis" in desc


def test_describe_composed_shard_fractions():
    trainer, _, _, _ = _fit(
        _TpMLP(tp=True), _loader(64, 16), _tp_strategy(stage=3), steps=2
    )
    desc = trainer.describe_parallelism()
    assert "train program: zero_train_step" in desc
    report = trainer.strategy.describe_shardings()
    assert "composed parallelism" in report
    assert "ZeRO shard fractions" in report
    # per-leaf fractions with their kind tags
    assert "w1: 0.25 [zero+model]" in report
    assert "b1: 0.5 [model]" in report
    assert "b2: 1 [replicated]" in report


def test_describe_pipeline_placement():
    trainer, _, _, _ = _fit(
        _PipeModel(),
        _loader(32, 8),
        XLAStrategy(
            devices=4,
            mesh_spec=MeshSpec.composed(pp=2),
            sharding_policy=ShardingPolicy.ddp(),
            pipeline_stages=2,
            pipeline_microbatches=4,
        ),
        steps=2,
    )
    desc = trainer.describe_parallelism()
    assert "pipeline: 2 stages x 4 microbatches over 'pp'" in desc
    report = trainer.strategy.describe_shardings()
    assert "pipeline: 2 stages x 4 microbatches" in report


# --------------------------------------------------------------------- #
# elastic resize under the composed layout
# --------------------------------------------------------------------- #
def _rebuild_at_world(trainer, strategy, n_devices, salvage):
    """Drive the exact _apply_resize seams for an in-process world change:
    rebuild mesh + ZeRO context + placed templates, then re-place state."""
    strategy._num_devices = n_devices
    strategy._mesh = None
    strategy.setup_environment()
    new_ctx = trainer._setup_zero()
    assert new_ctx is not None, trainer._zero_fallback_reason
    trainer._zero_ctx = new_ctx
    host_zeros = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), trainer._param_shape_tree
    )
    trainer._params = trainer._place_params(host_zeros)
    opt_shapes = jax.eval_shape(trainer._opt_init_fn, trainer._params)
    trainer._opt_state = jax.jit(
        trainer._opt_init_fn,
        out_shardings=trainer._opt_shardings_for(opt_shapes),
    )(trainer._params)
    trainer._place_host_state(salvage)


def test_elastic_resize_composed_bitwise():
    strategy = _tp_strategy(stage=3)
    trainer, params_before, _, _ = _fit(
        _TpMLP(tp=True), _loader(64, 16), strategy, steps=4
    )
    assert trainer._zero_ctx is not None and trainer._zero_ctx.n == 2
    salvage = trainer._salvage_live_state()
    assert salvage is not None
    opt_shapes_before = [
        l.shape for l in jax.tree_util.tree_leaves(jax.device_get(salvage[1]))
    ]

    # shrink: dp 2 -> 1 with the tp axis pinned; the explicit layout must
    # re-engage (PAD_UNIT padding is per MODEL shard, world-independent)
    _rebuild_at_world(trainer, strategy, 2, salvage)
    assert trainer._zero_ctx.n == 1
    mid = jax.device_get(trainer._params)
    for a, b in zip(
        jax.tree_util.tree_leaves(params_before), jax.tree_util.tree_leaves(mid)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # regrow: back to dp=2; state trees keep the same global shapes
    _rebuild_at_world(trainer, strategy, 4, salvage)
    assert trainer._zero_ctx.n == 2
    after = jax.device_get(trainer._params)
    for a, b in zip(
        jax.tree_util.tree_leaves(params_before), jax.tree_util.tree_leaves(after)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    opt_shapes_after = [
        l.shape
        for l in jax.tree_util.tree_leaves(jax.device_get(trainer._opt_state))
    ]
    assert opt_shapes_before == opt_shapes_after


def test_elastic_fallback_is_loud(recwarn):
    strategy = _tp_strategy(stage=3)
    trainer, _, _, _ = _fit(
        _TpMLP(tp=True), _loader(64, 16), strategy, steps=2
    )
    # force an ineligible layout at the new world: nothing reaches
    # min_shard_size, so re-engagement must decline with a recorded reason
    # (the real _apply_resize turns this into a RuntimeError naming it)
    strategy.sharding_policy = _policy(3, min_shard_size=10**9)
    assert trainer._setup_zero() is None
    assert trainer._zero_fallback_reason == "no_big_leaves"
