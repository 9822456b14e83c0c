"""Host-side data pipeline: datasets, loaders, distributed sharding.

TPU-first rules applied here:
- batches are **host numpy** until the instant they're needed, then moved to
  device in one ``device_put`` with a ``NamedSharding`` over the mesh's data
  axis (no per-example transfers);
- training loaders drop the trailing partial batch by default so every jitted
  step sees one static shape (XLA recompiles on shape change);
- distributed sharding mirrors the reference's DistributedSampler injection
  (reference: ray_lightning/ray_ddp.py:315-324): worker ``rank`` of
  ``num_replicas`` takes every ``num_replicas``-th index after a seeded
  per-epoch shuffle.

Torch datasets/dataloaders are accepted and converted to numpy at the
boundary (torch here is CPU-only input tooling, never the compute path).
"""
from __future__ import annotations

import math
import os
import sys
from typing import Any, Callable, Iterator, Optional, Sequence

import numpy as np


class Dataset:
    """Minimal map-style dataset protocol."""

    def __len__(self) -> int:
        raise NotImplementedError

    def __getitem__(self, idx: int):
        raise NotImplementedError


class TensorDataset(Dataset):
    def __init__(self, *arrays):
        assert arrays and all(len(a) == len(arrays[0]) for a in arrays)
        self.arrays = [np.asarray(a) for a in arrays]

    def __len__(self):
        return len(self.arrays[0])

    def __getitem__(self, idx):
        items = tuple(a[idx] for a in self.arrays)
        return items[0] if len(items) == 1 else items


class DictDataset(Dataset):
    def __init__(self, **arrays):
        lens = {len(v) for v in arrays.values()}
        assert len(lens) == 1
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}

    def __len__(self):
        return len(next(iter(self.arrays.values())))

    def __getitem__(self, idx):
        return {k: v[idx] for k, v in self.arrays.items()}


class TokenFileDataset(Dataset):
    """LM pretraining over a memory-mapped token file — corpora larger
    than RAM stream from disk with zero copies until batch assembly.

    ``path``: a flat binary file of token ids (``dtype``, default
    uint16 — vocabularies to 65k; use uint32 beyond). Sample ``i`` is
    the window ``tokens[i * stride : i * stride + seq_len]`` as an
    ``{"input_ids": int32[seq_len]}`` dict (the llama module's batch
    shape). ``stride`` defaults to ``seq_len`` (disjoint windows);
    smaller strides overlap windows for more samples per token.

    Works with :class:`DistributedSampler` like any map-style dataset —
    each worker touches only the file pages its indices hit (the OS page
    cache is the shuffle-friendly prefetcher), so multi-worker training
    needs no up-front sharding of the corpus.

    ``np.memmap`` objects don't pickle; the mapping is reopened lazily
    after a cloudpickle hop to a worker actor.
    """

    def __init__(self, path: str, seq_len: int, dtype="uint16",
                 stride: Optional[int] = None):
        # absolute: the lazy reopen may run in a worker actor whose cwd
        # differs from the driver's — pin the file that was validated
        self.path = os.path.abspath(path)
        self.seq_len = int(seq_len)
        self.dtype = np.dtype(dtype)
        self.stride = int(stride) if stride is not None else self.seq_len
        if self.stride <= 0 or self.seq_len <= 0:
            raise ValueError("seq_len and stride must be positive")
        # floor: a trailing partial token (truncated write) is ignored —
        # the explicit shape below makes this flooring authoritative so
        # np.memmap never rejects a non-multiple file size at first read
        self._n_tokens = os.path.getsize(self.path) // self.dtype.itemsize
        if self._n_tokens < self.seq_len:
            raise ValueError(
                f"{path}: {self._n_tokens} tokens < seq_len {self.seq_len}"
            )
        self._n = 1 + (self._n_tokens - self.seq_len) // self.stride
        self._mm = None

    def __getstate__(self):
        state = dict(self.__dict__)
        state["_mm"] = None  # reopen on the other side
        return state

    def __len__(self):
        return self._n

    def __getitem__(self, idx):
        if not 0 <= idx < self._n:
            # a silent short window would only explode later in collate
            # (and a missing IndexError makes `for x in ds` loop forever)
            raise IndexError(f"index {idx} out of range for {self._n} windows")
        if self._mm is None:
            self._mm = np.memmap(
                self.path, dtype=self.dtype, mode="r", shape=(self._n_tokens,)
            )
        start = idx * self.stride
        window = self._mm[start:start + self.seq_len]
        return {"input_ids": np.asarray(window, dtype=np.int32)}


class RandomDataset(Dataset):
    """Gaussian features, parity with reference tests/utils.py:16-25."""

    def __init__(self, size: int, length: int, seed: int = 0):
        self.data = np.random.default_rng(seed).standard_normal(
            (length, size), dtype=np.float32
        )

    def __len__(self):
        return len(self.data)

    def __getitem__(self, idx):
        return self.data[idx]


class DistributedSampler:
    """Deterministic rank-sharded index sampler.

    ``set_epoch`` reshuffles per epoch with ``seed + epoch`` so all replicas
    agree on the permutation, then each takes a strided slice.
    """

    def __init__(
        self,
        data_len: int,
        num_replicas: int = 1,
        rank: int = 0,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
    ):
        if rank >= num_replicas:
            raise ValueError(f"rank {rank} >= num_replicas {num_replicas}")
        self.data_len = data_len
        self.num_replicas = num_replicas
        self.rank = rank
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        if drop_last:
            self.num_samples = data_len // num_replicas
        else:
            self.num_samples = math.ceil(data_len / num_replicas)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self):
        return self.num_samples

    def __iter__(self) -> Iterator[int]:
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            indices = rng.permutation(self.data_len)
        else:
            indices = np.arange(self.data_len)
        total = self.num_samples * self.num_replicas
        if not self.drop_last and total > len(indices):
            # pad by wrapping so every replica sees the same count
            indices = np.concatenate([indices, indices[: total - len(indices)]])
        indices = indices[: total]
        return iter(indices[self.rank :: self.num_replicas].tolist())


def default_collate(items: Sequence[Any]):
    """Stack a list of samples into a batch, preserving tuple/dict structure."""
    first = items[0]
    if isinstance(first, dict):
        return {k: default_collate([it[k] for it in items]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate(list(col)) for col in zip(*items))
    # a sample can be a torch tensor only in a process that has loaded torch
    torch = sys.modules.get("torch")
    if torch is not None and isinstance(first, torch.Tensor):
        return np.stack([it.detach().cpu().numpy() for it in items])
    return np.stack([np.asarray(it) for it in items])


def _to_numpy_tree(batch):
    """Convert any torch tensors in a (possibly nested) batch to numpy."""
    torch = sys.modules.get("torch")
    if torch is not None and isinstance(batch, torch.Tensor):
        return batch.detach().cpu().numpy()
    if isinstance(batch, dict):
        return {k: _to_numpy_tree(v) for k, v in batch.items()}
    if isinstance(batch, (tuple, list)):
        return type(batch)(_to_numpy_tree(v) for v in batch)
    return batch


class DataLoader:
    """Map-style batch loader emitting numpy batches.

    Accepts this package's :class:`Dataset` or any object with
    ``__len__``/``__getitem__`` (torch datasets included).
    """

    def __init__(
        self,
        dataset,
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        seed: int = 0,
        sampler: Optional[DistributedSampler] = None,
        num_workers: int = 0,
        prefetch_factor: int = 2,
    ):
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.collate_fn = collate_fn or default_collate
        self.seed = seed
        self.sampler = sampler
        self.num_workers = int(num_workers)
        self.prefetch_factor = int(prefetch_factor)
        if self.num_workers < 0:
            raise ValueError(f"num_workers must be >= 0, got {num_workers}")
        if self.prefetch_factor < 1:
            raise ValueError(f"prefetch_factor must be >= 1, got {prefetch_factor}")
        self._epoch = 0

    # the strategy re-wraps loaders with a rank-sharding sampler
    def with_sampler(self, sampler: DistributedSampler) -> "DataLoader":
        return DataLoader(
            self.dataset,
            batch_size=self.batch_size,
            shuffle=False,  # sampler owns shuffling
            drop_last=self.drop_last,
            collate_fn=self.collate_fn,
            seed=self.seed,
            sampler=sampler,
            num_workers=self.num_workers,
            prefetch_factor=self.prefetch_factor,
        )

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        if self.sampler is not None:
            self.sampler.set_epoch(epoch)

    def __len__(self):
        n = len(self.sampler) if self.sampler is not None else len(self.dataset)
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)

    # split iteration protocol, consumed by prefetch.AsyncLoader: the plan
    # (index chunks) is cheap and ordered, the assembly (__getitem__ +
    # collate + numpy conversion) is the parallelizable work
    def _batch_plan(self):
        if self.sampler is not None:
            indices = list(self.sampler)
        elif self.shuffle:
            rng = np.random.default_rng(self.seed + self._epoch)
            indices = rng.permutation(len(self.dataset)).tolist()
        else:
            indices = list(range(len(self.dataset)))
        bs = self.batch_size
        stop = len(indices) - len(indices) % bs if self.drop_last else len(indices)
        for start in range(0, stop, bs):
            chunk = indices[start : start + bs]
            if self.drop_last and len(chunk) < bs:
                break
            yield chunk

    def _assemble(self, chunk):
        return _to_numpy_tree(self.collate_fn([self.dataset[i] for i in chunk]))

    def __iter__(self):
        if self.num_workers > 0:
            # torch-parity: num_workers>0 moves assembly off the calling
            # thread (threads, not processes — the work is numpy/IO bound)
            from ray_lightning_tpu.core.prefetch import AsyncLoader

            yield from AsyncLoader(self)
            return
        for chunk in self._batch_plan():
            yield self._assemble(chunk)


class _ForeignLoader:
    """Wraps an arbitrary iterable (e.g. a torch DataLoader) into numpy."""

    def __init__(self, loader):
        self.loader = loader

    def set_epoch(self, epoch: int) -> None:
        sampler = getattr(self.loader, "sampler", None)
        if sampler is not None and hasattr(sampler, "set_epoch"):
            sampler.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        for batch in self.loader:
            yield _to_numpy_tree(batch)


def ensure_loader(loader):
    """Normalize user-supplied loaders to an object with our iteration API."""
    if loader is None or isinstance(loader, (DataLoader, _ForeignLoader)):
        return loader
    if hasattr(loader, "__iter__"):
        return _ForeignLoader(loader)
    raise TypeError(f"Cannot use {type(loader)!r} as a dataloader")
