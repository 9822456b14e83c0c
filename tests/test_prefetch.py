"""Async input pipeline: AsyncLoader (threaded host batch assembly),
DevicePrefetcher (N-deep device lookahead), strategy knob resolution,
and the sync-free hot-loop contract.

The error tests pin the pipeline's core semantic promise: asynchrony
must not move WHERE an exception surfaces — a batch that fails to
assemble or shard raises at the same step the inline loop would have
raised it, after every earlier good batch trained.
"""
import csv
import os
import threading
import time

import numpy as np
import pytest

from ray_lightning_tpu.core.data import DataLoader, Dataset, RandomDataset
from ray_lightning_tpu.core.prefetch import (
    _THREAD_PREFIX,
    AsyncLoader,
    DevicePrefetcher,
    ensure_async,
)

pytestmark = pytest.mark.pipeline


def _input_threads():
    return [
        t for t in threading.enumerate() if t.name.startswith(_THREAD_PREFIX)
    ]


def _wait_no_input_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not _input_threads():
            return True
        time.sleep(0.02)
    return False


class _JitterDataset(Dataset):
    """Per-item sleep jitter so pooled workers genuinely race: without it
    an ordering bug could pass by accident because assembly is too fast
    to ever complete out of submission order."""

    def __init__(self, n=48):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        time.sleep(0.001 * (idx % 3))
        return np.full((4,), idx, dtype=np.float32)


class _PoisonDataset(Dataset):
    def __init__(self, n, poison_idx):
        self.n = n
        self.poison_idx = poison_idx

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        if idx == self.poison_idx:
            raise RuntimeError(f"poisoned sample {idx}")
        return np.full((4,), idx, dtype=np.float32)


@pytest.mark.parametrize("num_workers", [1, 2, 4])
def test_async_loader_preserves_batch_order(num_workers):
    """Pooled assembly must yield batches in plan order no matter how the
    worker threads interleave."""
    loader = DataLoader(_JitterDataset(48), batch_size=4, drop_last=True)
    sync = [b.copy() for b in loader]
    for _ in range(2):  # two epochs: per-__iter__ thread setup is reusable
        got = list(AsyncLoader(loader, num_workers=num_workers))
        assert len(got) == len(sync) == 12
        for s, g in zip(sync, got):
            np.testing.assert_array_equal(s, g)
    assert _wait_no_input_threads()


def test_async_loader_error_after_preceding_good_batches():
    """A batch that fails to assemble surfaces its exception at its own
    step: every earlier batch is yielded first, none after it."""
    # batch 2 (indices 8..11) contains the poisoned sample
    loader = DataLoader(
        _PoisonDataset(16, poison_idx=9), batch_size=4, drop_last=True,
        num_workers=2,
    )
    got = []
    with pytest.raises(RuntimeError, match="poisoned sample 9"):
        for batch in AsyncLoader(loader, num_workers=2):
            got.append(int(batch[0, 0]))
    assert got == [0, 4]
    assert _wait_no_input_threads()


def test_async_loader_set_epoch_reshuffles():
    """set_epoch forwards to the wrapped loader: epoch changes the
    shuffle, same epoch reproduces it."""
    loader = DataLoader(
        RandomDataset(4, 32), batch_size=4, shuffle=True, drop_last=True
    )
    wrapped = AsyncLoader(loader, num_workers=2)

    def epoch_order(epoch):
        wrapped.set_epoch(epoch)
        return np.concatenate([b for b in wrapped])

    e0, e1, e0_again = epoch_order(0), epoch_order(1), epoch_order(0)
    assert not np.array_equal(e0, e1)
    np.testing.assert_array_equal(e0, e0_again)
    assert _wait_no_input_threads()


def test_async_loader_early_break_leaks_no_threads():
    """Abandoning the iterator mid-epoch (a max_steps break) must stop
    the feeder and pool threads — generator close does the shutdown."""
    loader = DataLoader(_JitterDataset(64), batch_size=4, num_workers=2)
    for i, _batch in enumerate(AsyncLoader(loader, num_workers=2)):
        if i == 1:
            break
    assert _wait_no_input_threads(), f"leaked: {_input_threads()}"


def test_async_loader_serial_mode_for_plain_iterables():
    """Loaders without the plan/assemble split (foreign/torch loaders,
    generators) feed through one serial thread, order intact, errors at
    the same step."""

    class Gen:
        def __iter__(self):
            for i in range(5):
                if i == 3:
                    raise ValueError("bad batch 3")
                yield np.full((2,), i, dtype=np.float32)

    got = []
    with pytest.raises(ValueError, match="bad batch 3"):
        for b in AsyncLoader(Gen()):
            got.append(int(b[0]))
    assert got == [0, 1, 2]
    assert _wait_no_input_threads()


def test_ensure_async_is_idempotent():
    loader = DataLoader(RandomDataset(4, 8), batch_size=4)
    wrapped = ensure_async(loader, num_workers=2)
    assert isinstance(wrapped, AsyncLoader)
    assert ensure_async(wrapped) is wrapped


def test_device_prefetcher_lookahead_window_and_order():
    """The prefetcher shards at most depth batches beyond the one just
    yielded, in order, and counts starvation time."""
    sharded = []

    def shard(batch):
        sharded.append(int(batch[0]))
        return batch * 2

    pf = DevicePrefetcher(shard, depth=3)
    src = [np.full((2,), i, dtype=np.float32) for i in range(10)]
    seen = []
    for idx, host, dev in pf.iterate(src):
        assert int(host[0]) == idx
        np.testing.assert_array_equal(dev, host * 2)
        # never more than depth+1 sharded beyond what has been consumed
        assert len(sharded) - len(seen) <= pf.depth + 1
        seen.append(idx)
    assert seen == list(range(10))
    assert sharded == list(range(10))
    assert pf.batches == 10
    assert pf.starved_s >= 0.0


def test_device_prefetcher_limit_stops_loading():
    loads = []

    def gen():
        for i in range(100):
            loads.append(i)
            yield np.full((2,), i, dtype=np.float32)

    pf = DevicePrefetcher(lambda b: b, depth=2)
    out = [idx for idx, _h, _d in pf.iterate(gen(), limit=4)]
    assert out == [0, 1, 2, 3]
    assert len(loads) == 4  # limit bounds loading, not just yielding


def test_device_prefetcher_error_flushes_pending_first():
    """Ragged/poisoned batch with lookahead: the already-sharded good
    batches train first, then the original exception surfaces."""

    def gen():
        yield np.zeros((2,))
        yield np.ones((2,))
        raise RuntimeError("ragged final batch")

    pf = DevicePrefetcher(lambda b: b, depth=2)
    seen = []
    with pytest.raises(RuntimeError, match="ragged final batch"):
        for idx, _host, _dev in pf.iterate(gen()):
            seen.append(idx)
    assert seen == [0, 1]


def test_device_prefetcher_shard_error_same_step():
    def bad_shard(batch):
        if int(batch[0]) == 2:
            raise ValueError("unshardable")
        return batch

    pf = DevicePrefetcher(bad_shard, depth=2)
    src = [np.full((2,), i, dtype=np.float32) for i in range(5)]
    seen = []
    with pytest.raises(ValueError, match="unshardable"):
        for idx, _h, _d in pf.iterate(src):
            seen.append(idx)
    assert seen == [0, 1]


def test_strategy_knob_resolution(monkeypatch):
    """ctor > RLT_* env > default, validation on both knobs."""
    from ray_lightning_tpu.strategies.base import XLAStrategy

    monkeypatch.delenv("RLT_PREFETCH_DEPTH", raising=False)
    monkeypatch.delenv("RLT_LOADER_WORKERS", raising=False)
    s = XLAStrategy()
    assert s.prefetch_depth == 2
    assert s.loader_num_workers is None

    monkeypatch.setenv("RLT_PREFETCH_DEPTH", "5")
    monkeypatch.setenv("RLT_LOADER_WORKERS", "3")
    assert s.prefetch_depth == 5
    assert s.loader_num_workers == 3

    ctor = XLAStrategy(prefetch_depth=1, loader_num_workers=0)
    assert ctor.prefetch_depth == 1
    assert ctor.loader_num_workers == 0  # 0 = synchronous, not "unset"

    monkeypatch.setenv("RLT_PREFETCH_DEPTH", "-1")
    with pytest.raises(ValueError, match="prefetch_depth"):
        _ = s.prefetch_depth
    with pytest.raises(ValueError, match="loader_num_workers"):
        _ = XLAStrategy(loader_num_workers=-2).loader_num_workers


def test_trainer_fit_through_async_pipeline(tmp_path):
    """End-to-end: fit with pooled workers + depth-2 lookahead trains,
    finishes cleanly, and leaves no input threads behind."""
    import jax
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.strategies.base import XLAStrategy
    from tests.utils import BoringModel

    model = BoringModel()
    initial = jax.device_get(model.init_params(jax.random.key(0)))
    trainer = Trainer(
        default_root_dir=str(tmp_path),
        max_epochs=2,
        strategy=XLAStrategy(prefetch_depth=2, loader_num_workers=2),
        enable_progress_bar=False,
        logger=False,
        enable_checkpointing=False,
        seed=0,
    )
    trainer.fit(model)
    assert trainer.state.status == "finished"
    assert trainer.global_step == 16  # 8 batches x 2 epochs
    assert trainer._input_stats["batches"] == 16
    assert trainer._input_prefetcher is None  # pickle safety: dropped
    delta = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b),
        jax.device_get(model.params), initial,
    )
    assert max(
        float(np.max(np.abs(leaf)))
        for leaf in jax.tree_util.tree_leaves(delta)
    ) > 0.0
    assert _wait_no_input_threads()


def test_trainer_max_steps_break_leaks_no_threads(tmp_path):
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.strategies.base import XLAStrategy
    from tests.utils import BoringModel

    trainer = Trainer(
        default_root_dir=str(tmp_path),
        max_epochs=5,
        max_steps=3,
        strategy=XLAStrategy(prefetch_depth=2, loader_num_workers=2),
        enable_progress_bar=False,
        logger=False,
        enable_checkpointing=False,
        seed=0,
    )
    trainer.fit(BoringModel())
    assert trainer.global_step == 3
    assert trainer._input_stats["batches"] >= 3  # lookahead may load extra
    assert _wait_no_input_threads(), f"leaked: {_input_threads()}"


def test_hot_loop_never_syncs_host_device(tmp_path, monkeypatch):
    """The acceptance bar for the sync-free metrics path: with the default
    logger on and telemetry off, jax.device_get is never called between
    on_train_batch_start and on_train_batch_end."""
    import jax
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.callbacks.base import Callback
    from tests.utils import BoringModel

    window = {"open": False, "violations": 0, "outside": 0}

    class Watch(Callback):
        def on_train_batch_start(self, trainer, module, batch, batch_idx):
            window["open"] = True

        def on_train_batch_end(self, trainer, module, outputs, batch, batch_idx):
            window["open"] = False

    real_get = jax.device_get

    def spying_get(*args, **kwargs):
        if window["open"]:
            window["violations"] += 1
        else:
            window["outside"] += 1
        return real_get(*args, **kwargs)

    monkeypatch.setattr(jax, "device_get", spying_get)
    # trainer.py binds jax at module import; patch its reference too
    import ray_lightning_tpu.core.trainer as trainer_mod

    monkeypatch.setattr(trainer_mod.jax, "device_get", spying_get)

    trainer = Trainer(
        default_root_dir=str(tmp_path),
        max_epochs=1,
        log_every_n_steps=1,  # stress the deferred path on every step
        enable_progress_bar=True,  # the epoch line must not sync per step
        enable_checkpointing=False,
        callbacks=[Watch()],
        seed=0,
    )
    trainer.fit(BoringModel())  # default logger (CSV) stays ON
    assert trainer.global_step == 8
    assert window["violations"] == 0, (
        f"{window['violations']} host syncs inside the hot loop"
    )
    assert window["outside"] > 0  # the deferred drain did resolve metrics


def test_deferred_step_logs_reach_csv_in_order(tmp_path):
    """Deferring per-step metrics must not lose or reorder them: every
    step row lands in the CSV with its own step number."""
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.loggers import CSVLogger
    from tests.utils import BoringModel

    trainer = Trainer(
        default_root_dir=str(tmp_path),
        max_epochs=1,
        log_every_n_steps=1,
        logger=CSVLogger(str(tmp_path)),
        enable_progress_bar=False,
        enable_checkpointing=False,
        seed=0,
    )
    trainer.fit(BoringModel())
    csv_files = []
    for root, _dirs, files in os.walk(str(tmp_path)):
        csv_files += [os.path.join(root, f) for f in files if f == "metrics.csv"]
    assert csv_files, "CSVLogger wrote no metrics.csv"
    with open(csv_files[0]) as f:
        rows = list(csv.DictReader(f))
    step_rows = [r for r in rows if r.get("train_loss_step") not in (None, "")]
    steps = [int(r["step"]) for r in step_rows]
    assert steps == sorted(steps)
    assert len(steps) == 8  # one per training step, none dropped
    for r in step_rows:
        float(r["train_loss_step"])  # resolved to a host scalar, not repr junk


def _input_microbench(delay_ms, num_workers, prefetch_depth, steps):
    """Feed a small jitted step through the input pipeline, ``delay_ms`` of
    sleep in collate standing for a slow host loader. ``num_workers=0,
    prefetch_depth=0`` is the synchronous feed; anything else goes through
    AsyncLoader + DevicePrefetcher."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.core.data import default_collate

    def collate(items):
        time.sleep(delay_ms / 1e3)
        return default_collate(items)

    @jax.jit
    def step(w, x):
        for _ in range(8):
            x = jnp.tanh(x @ w)
        return w + 1e-4 * jnp.mean(x) * jnp.eye(w.shape[0], dtype=w.dtype), x

    batch, dim = 8, 256
    dataset = RandomDataset(dim, steps * batch)
    loader = DataLoader(
        dataset, batch_size=batch, collate_fn=collate, drop_last=True
    )
    w = jnp.eye(dim, dtype=jnp.float32)
    w, out = step(w, jnp.asarray(dataset.data[:batch]))  # compile outside timing
    jax.block_until_ready(out)

    src = (
        AsyncLoader(loader, num_workers=num_workers, prefetch_factor=2)
        if num_workers > 0
        else loader
    )
    pf = DevicePrefetcher(jax.device_put, depth=prefetch_depth)
    n = 0
    t0 = time.perf_counter()
    for _idx, _host, device_batch in pf.iterate(src):
        w, out = step(w, device_batch)
        n += 1
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return {
        "steps": n,
        "steps_per_sec": n / max(dt, 1e-9),
        "input_starved_ms": pf.starved_s * 1e3,
    }


def test_input_microbench_async_beats_sync():
    """With an emulated slow host loader, 2 workers + depth 2 beat
    synchronous feeding by >= 25% and shrink the starvation metric."""
    sync = _input_microbench(8.0, num_workers=0, prefetch_depth=0, steps=16)
    fast = _input_microbench(8.0, num_workers=2, prefetch_depth=2, steps=16)
    assert sync["steps"] == fast["steps"] == 16
    assert fast["steps_per_sec"] >= 1.25 * sync["steps_per_sec"], (sync, fast)
    assert fast["input_starved_ms"] < sync["input_starved_ms"]
    assert sync["input_starved_ms"] > 0.0  # the metric moves under load


def test_starvation_counter_published_with_recorder(tmp_path):
    """With telemetry on, the prefetcher publishes the starvation counter
    and per-batch host_batch/h2d spans through the flight recorder."""
    from ray_lightning_tpu import Trainer
    from ray_lightning_tpu.observability import metrics as obs_metrics
    from ray_lightning_tpu.strategies.base import XLAStrategy
    from tests.utils import BoringModel

    trainer = Trainer(
        default_root_dir=str(tmp_path),
        max_epochs=1,
        strategy=XLAStrategy(
            telemetry=True, prefetch_depth=2, loader_num_workers=2
        ),
        enable_progress_bar=False,
        logger=False,
        enable_checkpointing=False,
        seed=0,
    )
    trainer.fit(BoringModel())
    snap = obs_metrics.get_registry().snapshot()
    counters = {name: value for name, _labels, value in snap["counters"]}
    assert counters.get("rlt_input_starved_seconds", 0.0) > 0.0
    gauges = {name for name, _labels, _value in snap["gauges"]}
    assert "rlt_prefetch_queue_depth" in gauges
