"""Shared test fixtures, mirroring the reference's test-model zoo
(reference: ray_lightning/tests/utils.py:16-272): BoringModel (tiny linear,
full hook surface), XORModel logging known constants to verify the metric
pipe end-to-end, a get_trainer factory, and the train/load/predict assertion
helpers.
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu import (
    DataLoader,
    DictDataset,
    LightningDataModule,
    LightningModule,
    RandomDataset,
    Trainer,
)


class BoringModel(LightningModule):
    """Tiny linear model with the full hook surface."""

    def __init__(self):
        super().__init__()
        self.model = nn.Dense(2)
        self.example_input_array = jnp.zeros((1, 32), jnp.float32)
        self.hook_calls = []

    def _record(self, name):
        self.hook_calls.append(name)

    def on_fit_start(self):
        self._record("on_fit_start")

    def on_train_epoch_start(self):
        self._record("on_train_epoch_start")

    def on_train_epoch_end(self):
        self._record("on_train_epoch_end")

    def on_validation_epoch_end(self):
        self._record("on_validation_epoch_end")

    def on_fit_end(self):
        self._record("on_fit_end")

    def loss_fn(self, params, batch):
        out = self.model.apply(params, batch)
        return jnp.mean(out**2)

    def training_step(self, params, batch, batch_idx):
        loss = self.loss_fn(params, batch)
        self.log("train_loss", loss, on_step=True, on_epoch=True)
        return loss

    def validation_step(self, params, batch, batch_idx):
        loss = self.loss_fn(params, batch)
        self.log("val_loss", loss)

    def test_step(self, params, batch, batch_idx):
        loss = self.loss_fn(params, batch)
        self.log("test_loss", loss)

    def configure_optimizers(self):
        return optax.sgd(0.1)

    def train_dataloader(self):
        return DataLoader(RandomDataset(32, 64), batch_size=8, drop_last=True)

    def val_dataloader(self):
        return DataLoader(RandomDataset(32, 32), batch_size=8)

    def test_dataloader(self):
        return DataLoader(RandomDataset(32, 32), batch_size=8)


class XORModel(LightningModule):
    """Logs exact constants so tests can assert the metric plumbing is
    faithful end-to-end (the reference's 1.234/5.678 pattern,
    tests/utils.py:151-210)."""

    VAL_LOSS = 1.234
    VAL_ACC = 5.678

    def __init__(self):
        super().__init__()
        self.model = _XORNet()
        self.example_input_array = jnp.zeros((1, 2), jnp.float32)

    def training_step(self, params, batch, batch_idx):
        x, y = batch
        logits = self.model.apply(params, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        self.log("train_loss", loss)
        return loss

    def validation_step(self, params, batch, batch_idx):
        self.log("val_loss", jnp.asarray(self.VAL_LOSS))
        self.log("val_acc", jnp.asarray(self.VAL_ACC))

    def configure_optimizers(self):
        return optax.adam(0.02)


class _XORNet(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.tanh(nn.Dense(8)(x))
        return nn.Dense(2)(x)


class XORDataModule(LightningDataModule):
    def setup(self, stage):
        x = np.array(
            [[0, 0], [0, 1], [1, 0], [1, 1]] * 16, dtype=np.float32
        )
        y = np.array([0, 1, 1, 0] * 16, dtype=np.int32)
        self.ds = DictDataset(x=x, y=y)

    def _loader(self):
        ds = self.ds
        return DataLoader(
            _TupleView(ds), batch_size=8, drop_last=True
        )

    def train_dataloader(self):
        return self._loader()

    def val_dataloader(self):
        return self._loader()


class _TupleView:
    def __init__(self, dict_ds):
        self.ds = dict_ds

    def __len__(self):
        return len(self.ds)

    def __getitem__(self, i):
        item = self.ds[i]
        return item["x"], item["y"]


def get_trainer(
    root_dir,
    max_epochs: int = 1,
    limit_train_batches: int = 10,
    limit_val_batches: int = 10,
    strategy=None,
    callbacks=None,
    checkpoint_callback: bool = True,
    **kwargs,
):
    """Trainer factory, parity with reference tests/utils.py:213-233."""
    return Trainer(
        default_root_dir=root_dir,
        max_epochs=max_epochs,
        limit_train_batches=limit_train_batches,
        limit_val_batches=limit_val_batches,
        strategy=strategy,
        callbacks=callbacks,
        enable_checkpointing=checkpoint_callback,
        enable_progress_bar=False,
        logger=False,
        seed=0,
        **kwargs,
    )


def flat_norm(tree) -> float:
    leaves = jax.tree_util.tree_leaves(tree)
    return float(
        np.sqrt(sum(np.sum(np.square(np.asarray(jax.device_get(l)))) for l in leaves))
    )


def train_test(trainer, model, datamodule=None):
    """Assert training actually moved the weights (reference
    tests/utils.py:236-245)."""
    initial = jax.device_get(model.init_params(jax.random.key(0)))
    trainer.fit(model, datamodule=datamodule)
    assert trainer.state.status == "finished"
    trained = jax.device_get(model.params)
    delta = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), trained, initial)
    assert flat_norm(delta) > 0.05, "model did not train"


def load_test(trainer, model_cls):
    """Assert the best checkpoint exists and is loadable (reference
    tests/utils.py:248-253)."""
    ckpt_path = trainer.checkpoint_callback.best_model_path
    assert ckpt_path, "no best_model_path recorded"
    loaded = model_cls.load_from_checkpoint(ckpt_path)
    assert loaded.params is not None


def predict_test(trainer, model, datamodule):
    """Assert prediction accuracy >= 0.5 (reference tests/utils.py:256-272)."""
    outputs = trainer.predict(model, datamodule=datamodule)
    preds = np.concatenate([np.asarray(o) for o in outputs])
    test_ds = datamodule.test_data
    labels = test_ds.arrays["label"][: len(preds)]
    acc = float(np.mean(preds == labels))
    assert acc >= 0.5, f"accuracy {acc} < 0.5"


def pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from pallas_calls(inner)


def live_bytes(tree) -> int:
    """Bytes the devices hold of a tree of arrays, every addressable shard
    counted: a replicated leaf counts once a device, a sharded leaf once."""
    return sum(
        shard.data.nbytes
        for leaf in jax.tree_util.tree_leaves(tree)
        for shard in leaf.addressable_shards
    )
