"""Explicit ZeRO update sharding (2004.13336): reduce-scatter the grads,
update 1/N of the optimizer state per data replica, all-gather the params.

``parallel/sharding.py`` expresses ZeRO as GSPMD *placement* and leaves the
collective schedule to XLA; this module is the explicit counterpart for
``RayShardedStrategy(zero_stage>=2)``: the train step itself performs
``psum_scatter(grads) -> optax update on the local shard -> all_gather``
inside a ``shard_map``, which (a) guarantees the optimizer math runs on
1/N of the state regardless of what XLA's sharding propagation decides,
(b) lets the all-gather ride an int8 block-scaled payload with error
feedback (EQuARX, 2506.17615) via ``compression.quantized_all_gather``,
and (c) batches the gathers into layer groups so XLA can overlap them
with independent work instead of serialising one giant fused gather.

Layout
------
Every float param leaf with ``size >= min_shard_size`` ("big" leaf) is
flattened, zero-padded to a multiple of :data:`PAD_UNIT` (256 — world
size must divide it, which keeps the padded GLOBAL shapes identical
across elastic resizes so sharded optimizer state hands off between
worlds without a change of layout), and viewed as ``[n, c]``: rank ``r`` owns row
``r``. Consecutive big leaves are packed into *gather groups* of
``gather_group_size`` leaves; each group's shards concatenate into one
``[sum_c]`` vector so a group costs ONE all-gather.

The optimizer state is initialised on the *mixed tree*: big leaves
replaced by their padded fp32 flats (sharded over the data axis, so each
rank materialises ``[c]``), small leaves untouched. Elementwise optax
transforms (adam/sgd/rmsprop/…) are exact on this layout; per-TENSOR-norm
transforms (lamb/lars/adafactor) are not and are rejected by the
trainer's eligibility gate.

Composition with model-axis partition rules (3D parallelism)
------------------------------------------------------------
``param_specs`` hands the context a PartitionSpec per leaf describing its
placement over MODEL axes (tensor-parallel rules, a leading pipeline-stage
axis, …). The ZeRO machinery then operates *per model shard*: each
rule-sharded leaf's LOCAL shard is flattened and padded independently to
:data:`PAD_UNIT`, so ``padded``/``chunk`` are per-model-shard quantities
and the data-axis scatter/update/gather runs inside each model-shard
group of the multi-axis ``shard_map``. Global flats (masters, moments,
error feedback) carry the model axes as the leading split of their one
dimension — spec ``P((*model_axes, data_axis))`` — which keeps their
global shapes world-independent across elastic DATA resizes as long as
the model axes stay fixed. Specs must never name the data axis: params
stay replicated over it (the 1/N shards live in the ZeroState).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_lightning_tpu.parallel.compression import quantized_all_gather
from ray_lightning_tpu.parallel.sharding import path_str

# Padding unit for big-leaf flats. The world size must divide it (trainer
# eligibility gate), making padded global shapes independent of the world
# size — the invariant the elastic resize path relies on to hand sharded
# optimizer state between worlds of different sizes.
PAD_UNIT = 256


class ZeroLayoutError(ValueError):
    """The composed (rules x ZeRO) layout cannot be represented — e.g. the
    mirror rule for optimizer-state leaves would be ambiguous, or a spec
    names the data axis. The trainer's eligibility gate catches this and
    falls back to GSPMD placement loudly."""


class ZeroState(NamedTuple):
    """Optimizer state for the explicit-ZeRO train step.

    ``inner``: the wrapped optax state, initialised on the mixed tree
    (big-leaf moments are global ``[n_model * padded]`` fp32 flats,
    sharded ``P((*model_axes, axis))``).
    ``masters``: stage-3 only — fp32 master shards, one global flat per
    big leaf (empty tuple at stage 2, where the padded param itself is
    re-sliced each step).
    ``gather_ef``: per gather-group error-feedback residual for the
    quantized all-gather, global ``[n_model * n * shard_len]`` with the
    same flat spec (tuple of zeros-shaped placeholders when quantization
    is off).
    """

    inner: Any
    masters: Tuple[jnp.ndarray, ...]
    gather_ef: Tuple[jnp.ndarray, ...]


@dataclass(frozen=True)
class _BigLeaf:
    index: int  # position in the flattened params leaf list
    path: str
    shape: Tuple[int, ...]  # GLOBAL shape
    dtype: Any
    size: int  # global element count
    spec: Tuple[Any, ...]  # model-axis PartitionSpec entries (may be empty)
    model_axes: Tuple[str, ...]  # ordered model axes the spec mentions
    n_model: int  # number of model shards (prod of model axis sizes)
    local_shape: Tuple[int, ...]  # shape of one model shard
    local_size: int
    padded: int  # local_size rounded up to PAD_UNIT (per model shard)
    chunk: int  # padded // n — this data rank's slice of its model shard
    group: int  # gather-group id
    offset: int  # chunk offset inside the group's concatenated shard


@dataclass(frozen=True)
class _GatherGroup:
    index: int
    leaves: Tuple[_BigLeaf, ...]
    shard_len: int  # sum of member chunks
    model_axes: Tuple[str, ...]  # shared by every member
    n_model: int


def _spec_entries(spec) -> Tuple[Any, ...]:
    if spec is None:
        return ()
    return tuple(spec)


def _entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, tuple):
        return tuple(a for a in entry if a)
    return (entry,)


class ZeroContext:
    """Static layout + step-time helpers for the explicit ZeRO update.

    Built from the *host* params template (shapes/dtypes only); everything
    here is deterministic in (template, param_specs, mesh axis sizes), so
    a context can be rebuilt after an elastic resize and agree with
    checkpointed state.
    """

    def __init__(
        self,
        mesh: Mesh,
        axis: str,
        params_template: Any,
        *,
        stage: int = 2,
        min_shard_size: int = 2**14,
        quantized: bool = False,
        gather_group_size: int = 8,
        param_specs: Optional[Any] = None,
    ) -> None:
        if axis not in mesh.axis_names:
            raise ValueError(
                f"ZeRO axis {axis!r} not in mesh axes {tuple(mesh.axis_names)}"
            )
        n = int(mesh.shape[axis])
        if PAD_UNIT % n:
            raise ValueError(
                f"explicit ZeRO needs the data-axis size ({n}) to divide "
                f"{PAD_UNIT} so padded shapes stay world-independent"
            )
        if stage < 2:
            raise ValueError(f"explicit ZeRO starts at stage 2, got {stage}")
        if quantized and stage < 3:
            raise ValueError(
                "zero_quantized_allgather requires zero_stage >= 3: at "
                "stage 2 the master values are re-sliced from the gathered "
                "(lossy) params each step, so quantization error would "
                "compound instead of being absorbed by error feedback"
            )
        self.mesh = mesh
        self.axis = axis
        self.n = n
        self.stage = stage
        self.quantized = quantized
        self.min_shard_size = max(1, int(min_shard_size))
        self.gather_group_size = max(1, int(gather_group_size))
        # int8 block size that always divides a chunk: chunks are multiples
        # of PAD_UNIT // n by construction.
        self.quant_block = max(1, PAD_UNIT // n)

        flat, treedef = jax.tree_util.tree_flatten_with_path(params_template)
        self.treedef = treedef
        self.num_leaves = len(flat)
        if param_specs is None:
            spec_leaves: List[Tuple[Any, ...]] = [()] * len(flat)
        else:
            spec_flat = jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda s: isinstance(s, P)
            )
            if len(spec_flat) != len(flat):
                raise ZeroLayoutError(
                    f"param_specs has {len(spec_flat)} leaves for "
                    f"{len(flat)} params"
                )
            spec_leaves = [_spec_entries(s) for s in spec_flat]
        self.param_spec_tree = jax.tree_util.tree_unflatten(
            treedef, [P(*s) for s in spec_leaves]
        )

        bigs: List[_BigLeaf] = []
        self._model_spec_by_index: Dict[int, Tuple[Any, ...]] = {}
        shape_to_spec: Dict[Tuple[int, ...], Tuple[Any, ...]] = {}
        self.leaf_paths: Tuple[str, ...] = tuple(
            path_str(kp) for kp, _ in flat
        )
        for i, (key_path, leaf) in enumerate(flat):
            path = path_str(key_path)
            shape = tuple(getattr(leaf, "shape", ()))
            dtype = getattr(leaf, "dtype", None)
            size = int(math.prod(shape)) if shape else 0
            spec = spec_leaves[i]
            model_axes = self._model_axes(path, shape, spec)
            local_shape = self._local_shape(path, shape, spec)
            if model_axes:
                self._model_spec_by_index[i] = spec
            is_big = (
                dtype is not None
                and jnp.issubdtype(dtype, jnp.floating)
                and size >= self.min_shard_size
            )
            if model_axes and not is_big and (
                dtype is not None and jnp.issubdtype(dtype, jnp.floating)
            ):
                # mirror rule for the moments of SMALL model-sharded
                # leaves keys on the leaf shape — must be unambiguous
                prev = shape_to_spec.get(shape)
                if prev is not None and prev != spec:
                    raise ZeroLayoutError(
                        f"two model-sharded leaves share shape {shape} with "
                        f"different specs ({prev} vs {spec}); the optimizer-"
                        "state mirror rule cannot tell their moments apart"
                    )
                shape_to_spec[shape] = spec
            if not is_big:
                continue
            local_size = int(math.prod(local_shape)) if local_shape else 0
            padded = -(-local_size // PAD_UNIT) * PAD_UNIT
            n_model = 1
            for a in model_axes:
                n_model *= int(mesh.shape[a])
            bigs.append(
                _BigLeaf(
                    index=i,
                    path=path,
                    shape=shape,
                    dtype=dtype,
                    size=size,
                    spec=spec,
                    model_axes=model_axes,
                    n_model=n_model,
                    local_shape=local_shape,
                    local_size=local_size,
                    padded=padded,
                    chunk=padded // n,
                    group=0,  # fixed below
                    offset=0,  # fixed below
                )
            )
        # gather groups pack CONSECUTIVE big leaves that share a model-axes
        # signature (a group's concatenated shard must have one flat spec)
        groups: List[_GatherGroup] = []
        fixed: List[_BigLeaf] = []
        cur: List[_BigLeaf] = []

        def _close(cur):
            if not cur:
                return
            gid = len(groups)
            members, off = [], 0
            for b in cur:
                b = dataclass_replace(b, group=gid, offset=off)
                off += b.chunk
                members.append(b)
                fixed.append(b)
            groups.append(
                _GatherGroup(
                    index=gid,
                    leaves=tuple(members),
                    shard_len=off,
                    model_axes=members[0].model_axes,
                    n_model=members[0].n_model,
                )
            )

        for b in bigs:
            if cur and (
                b.model_axes != cur[0].model_axes
                or len(cur) >= self.gather_group_size
            ):
                _close(cur)
                cur = []
            cur.append(b)
        _close(cur)
        self.big_leaves: Tuple[_BigLeaf, ...] = tuple(fixed)
        self.groups: Tuple[_GatherGroup, ...] = tuple(groups)
        self._big_by_index = {b.index: b for b in self.big_leaves}
        self._shape_to_spec = shape_to_spec
        # global flat lengths — the mirror rule state_specs() keys on
        self._flat_len_to_axes: Dict[int, Tuple[str, ...]] = {}
        for b in self.big_leaves:
            length = b.n_model * b.padded
            prev = self._flat_len_to_axes.get(length)
            if prev is not None and prev != b.model_axes:
                raise ZeroLayoutError(
                    f"two big leaves produce global flats of length {length} "
                    f"with different model axes ({prev} vs {b.model_axes}); "
                    "the optimizer-state mirror rule cannot tell their "
                    "moments apart"
                )
            self._flat_len_to_axes[length] = b.model_axes

    # ------------------------------------------------------------------ #
    # spec helpers
    # ------------------------------------------------------------------ #
    def _model_axes(self, path, shape, spec) -> Tuple[str, ...]:
        """Ordered mesh axes a leaf's spec shards it over. The data axis is
        ZeRO's own — a spec naming it would fight the scatter/gather."""
        axes: List[str] = []
        for entry in spec:
            for a in _entry_axes(entry):
                if a == self.axis:
                    raise ZeroLayoutError(
                        f"param spec for {path!r} names the ZeRO data axis "
                        f"{self.axis!r}; rules may only claim model axes"
                    )
                if a not in self.mesh.axis_names:
                    raise ZeroLayoutError(
                        f"param spec for {path!r} names mesh axis {a!r}, "
                        f"but the mesh has {tuple(self.mesh.axis_names)}"
                    )
                if a in axes:
                    raise ZeroLayoutError(
                        f"param spec for {path!r} repeats axis {a!r}"
                    )
                axes.append(a)
        return tuple(axes)

    def _local_shape(self, path, shape, spec) -> Tuple[int, ...]:
        out = []
        for d, dim in enumerate(shape):
            div = 1
            if d < len(spec):
                for a in _entry_axes(spec[d]):
                    div *= int(self.mesh.shape[a])
            if dim % div:
                raise ZeroLayoutError(
                    f"param spec for {path!r} shards dim {d} of size {dim} "
                    f"over {div} devices: not divisible"
                )
            out.append(dim // div)
        return tuple(out)

    def _flat_dim_axes(self, model_axes: Tuple[str, ...]) -> Tuple[str, ...]:
        axes = tuple(a for a in model_axes if int(self.mesh.shape[a]) > 1)
        if self.n > 1:
            axes = axes + (self.axis,)
        return axes

    def flat_spec(self, model_axes: Tuple[str, ...]) -> P:
        """Spec of a global 1-D flat laid out model-shard-major then
        data-rank-minor — each device's local view is its contiguous
        ``[chunk]`` (or ``[shard_len]``) segment."""
        axes = self._flat_dim_axes(model_axes)
        return P(axes) if axes else P()

    # ------------------------------------------------------------------ #
    # layout predicates / host-side tree builders
    # ------------------------------------------------------------------ #
    def is_big(self, index: int) -> bool:
        return index in self._big_by_index

    def _map_leaves(self, params: Any, fn: Callable[[int, Any], Any]) -> Any:
        leaves = jax.tree_util.tree_leaves(params)
        if len(leaves) != self.num_leaves:
            raise ValueError(
                f"ZeroContext built for {self.num_leaves} leaves, got "
                f"{len(leaves)}"
            )
        out = [fn(i, leaf) for i, leaf in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def _pad_flat(self, big: _BigLeaf, leaf: jnp.ndarray) -> jnp.ndarray:
        """LOCAL (model-shard) leaf -> fp32 flat ``[padded]``. Inside the
        shard_map body a rule-sharded leaf arrives as its model shard, so
        this pads each model shard independently to PAD_UNIT."""
        flat = leaf.reshape(-1).astype(jnp.float32)
        if big.padded != big.local_size:
            flat = jnp.pad(flat, (0, big.padded - big.local_size))
        return flat

    def _to_shard_major(self, big: _BigLeaf, leaf: jnp.ndarray) -> jnp.ndarray:
        """GLOBAL leaf -> ``[n_model, padded]`` fp32, rows ordered by the
        model-shard index (model_axes order, leftmost major) — the layout
        whose 1-D reshape shards as :meth:`flat_spec` with each device's
        local view equal to what ``_pad_flat`` produces in-body."""
        x = leaf.astype(jnp.float32)
        if not big.model_axes:
            flat = x.reshape(-1)
            if big.padded != big.size:
                flat = jnp.pad(flat, (0, big.padded - big.size))
            return flat[None]
        new_shape: List[int] = []
        axis_pos: List[Tuple[str, int]] = []
        for d, dim in enumerate(big.shape):
            entry = big.spec[d] if d < len(big.spec) else None
            rem = dim
            for a in _entry_axes(entry):
                s = int(self.mesh.shape[a])
                new_shape.append(s)
                axis_pos.append((a, len(new_shape) - 1))
                rem //= s
            new_shape.append(rem)
        front = [pos for ax in big.model_axes
                 for (a, pos) in axis_pos if a == ax]
        rest = [i for i in range(len(new_shape)) if i not in front]
        x = x.reshape(new_shape).transpose(front + rest)
        x = x.reshape(big.n_model, big.local_size)
        if big.padded != big.local_size:
            x = jnp.pad(x, ((0, 0), (0, big.padded - big.local_size)))
        return x

    def to_mixed(self, params: Any) -> Any:
        """GLOBAL params tree with big leaves replaced by fp32 padded flats
        ``[n_model * padded]`` (model-shard-major) — the tree the optimizer
        state is initialised on."""
        return self._map_leaves(
            params,
            lambda i, leaf: self._to_shard_major(
                self._big_by_index[i], leaf
            ).reshape(-1)
            if i in self._big_by_index
            else leaf,
        )

    def from_mixed_leaf(self, big: _BigLeaf, flat: jnp.ndarray) -> jnp.ndarray:
        """LOCAL flat ``[padded]`` -> this device's model shard."""
        return (
            flat[: big.local_size].reshape(big.local_shape).astype(big.dtype)
        )

    def init_state(self, tx, params: Any) -> ZeroState:
        """Build the full ZeroState on host/abstract values (call under
        ``jax.jit``/``eval_shape`` with :meth:`state_shardings` as
        ``out_shardings`` to materialise it sharded)."""
        mixed = self.to_mixed(params)
        inner = tx.init(mixed)
        masters: Tuple[jnp.ndarray, ...] = ()
        if self.stage >= 3:
            leaves = jax.tree_util.tree_leaves(params)
            masters = tuple(
                self._to_shard_major(b, leaves[b.index]).reshape(-1)
                for b in self.big_leaves
            )
        gather_ef: Tuple[jnp.ndarray, ...] = ()
        if self.quantized:
            gather_ef = tuple(
                jnp.zeros((g.n_model * self.n * g.shard_len,), jnp.float32)
                for g in self.groups
            )
        return ZeroState(inner=inner, masters=masters, gather_ef=gather_ef)

    # ------------------------------------------------------------------ #
    # shardings / specs — the mirror rule
    # ------------------------------------------------------------------ #
    def _leaf_spec(self, leaf: Any) -> P:
        """Mirror rule: a 1-D float leaf whose length is one of the big
        global-flat lengths is a sharded flat (moments mirror the mixed
        tree); a float leaf shaped like a model-sharded small param
        mirrors that param's spec; everything else (step counters, small
        replicated moments) replicates."""
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None or not jnp.issubdtype(dtype, jnp.floating):
            return P()
        if len(shape) == 1 and shape[0] in self._flat_len_to_axes:
            return self.flat_spec(self._flat_len_to_axes[shape[0]])
        if shape in self._shape_to_spec:
            return P(*self._shape_to_spec[shape])
        return P()

    def state_specs(self, state: ZeroState) -> ZeroState:
        """PartitionSpecs for the whole ZeroState (shard_map in/out)."""
        inner = jax.tree_util.tree_map(self._leaf_spec, state.inner)
        return ZeroState(
            inner=inner,
            masters=tuple(
                self.flat_spec(b.model_axes) for b in self.big_leaves
            )[: len(state.masters)],
            gather_ef=tuple(
                self.flat_spec(g.model_axes) for g in self.groups
            )[: len(state.gather_ef)],
        )

    def state_shardings(self, state: ZeroState) -> ZeroState:
        specs = self.state_specs(state)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    # ------------------------------------------------------------------ #
    # step-time collectives (inside shard_map; ``self.axis`` is bound)
    # ------------------------------------------------------------------ #
    def scatter_grads(self, grads: Any) -> Any:
        """Mean-reduce grads over the DATA axis: big leaves via
        ``psum_scatter`` (each rank keeps its ``[chunk]`` slice of its
        model shard, fp32), small leaves via ``pmean``. Model-sharded
        grads are already per-shard — no model-axis collective; a module
        whose forward crosses model axes must use the f/g operators from
        ``parallel.pipeline_1f1b`` so its replicated-leaf grads come out
        replicated. Returns the mixed-tree-shaped (local view) grad tree."""
        leaves = jax.tree_util.tree_leaves(grads)
        shards: Dict[int, jnp.ndarray] = {}
        for g in self.groups:
            mat = jnp.concatenate(
                [
                    self._pad_flat(b, leaves[b.index]).reshape(self.n, b.chunk)
                    for b in g.leaves
                ],
                axis=1,
            )
            if self.n > 1:
                shard = (
                    lax.psum_scatter(
                        mat.reshape(-1), self.axis,
                        scatter_dimension=0, tiled=True,
                    )
                    / self.n
                )
            else:
                shard = mat.reshape(-1)
            for b in g.leaves:
                shards[b.index] = shard[b.offset : b.offset + b.chunk]

        def one(i, leaf):
            if i in shards:
                return shards[i]
            if self.n > 1:
                return lax.pmean(leaf, self.axis)
            return leaf

        return self._map_leaves(grads, one)

    def global_grad_norm(self, mixed_grads: Any) -> jnp.ndarray:
        """Global L2 norm of the scattered grads. Each leaf's local sumsq
        is psum'd over exactly the axes it is split over — big-leaf chunks
        over (model axes + data axis), model-sharded small leaves over
        their model axes, replicated leaves counted once."""
        leaves = jax.tree_util.tree_leaves(mixed_grads)
        buckets: Dict[Tuple[str, ...], jnp.ndarray] = {}
        for i, leaf in enumerate(leaves):
            s = jnp.sum(jnp.square(leaf.astype(jnp.float32)))
            big = self._big_by_index.get(i)
            if big is not None:
                axes = self._flat_dim_axes(big.model_axes)
            else:
                spec = self._model_spec_by_index.get(i, ())
                axes = tuple(
                    a
                    for entry in spec
                    for a in _entry_axes(entry)
                    if int(self.mesh.shape[a]) > 1
                )
            buckets[axes] = buckets.get(axes, jnp.zeros((), jnp.float32)) + s
        total = jnp.zeros((), jnp.float32)
        for axes, s in buckets.items():
            total = total + (lax.psum(s, axes) if axes else s)
        return jnp.sqrt(total)

    def current_mixed(
        self, params: Any, masters: Tuple[jnp.ndarray, ...]
    ) -> Any:
        """The values the optimizer updates: stage 3 uses the fp32 master
        shards; stage 2 re-slices this rank's ``[chunk]`` from its
        (model-shard) param each step."""

        if self.stage >= 3:
            by_pos = {b.index: k for k, b in enumerate(self.big_leaves)}
            return self._map_leaves(
                params,
                lambda i, leaf: masters[by_pos[i]] if i in by_pos else leaf,
            )

        def one(i, leaf):
            b = self._big_by_index.get(i)
            if b is None:
                return leaf
            flat = self._pad_flat(b, leaf)
            idx = lax.axis_index(self.axis) if self.n > 1 else 0
            return lax.dynamic_slice(flat, (idx * b.chunk,), (b.chunk,))

        return self._map_leaves(params, one)

    def gather_params(
        self,
        params: Any,
        new_mixed: Any,
        gather_ef: Tuple[jnp.ndarray, ...],
    ) -> Tuple[Any, Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...]]:
        """All-gather the updated big-leaf shards over the DATA axis and
        rebuild this device's (model-shard) params.

        Issues one all-gather per gather group — ALL gathers are emitted
        before any rebuild consumes their results, so XLA is free to
        overlap the collectives with each other and with whatever runs
        next (the double-buffered schedule of the overlap tentpole).
        Returns ``(new_params, new_masters, new_gather_ef)``.
        """
        new_leaves = jax.tree_util.tree_leaves(new_mixed)
        gathered: List[jnp.ndarray] = []
        new_ef: List[jnp.ndarray] = []
        group_shards: List[jnp.ndarray] = []
        for g in self.groups:
            shard = jnp.concatenate(
                [new_leaves[b.index] for b in g.leaves]
            ) if len(g.leaves) > 1 else new_leaves[g.leaves[0].index]
            group_shards.append(shard)
        # phase 1: issue every collective
        for gi, g in enumerate(self.groups):
            shard = group_shards[gi]
            if self.quantized:
                x = shard + gather_ef[gi]
                full, local = quantized_all_gather(
                    x, self.axis, block_size=self.quant_block
                )
                gathered.append(full)
                new_ef.append(x - local)
            else:
                if self.n > 1:
                    full = lax.all_gather(shard, self.axis, tiled=True)
                else:
                    full = shard
                gathered.append(full)
        # phase 2: rebuild leaves from the gathered group vectors
        rebuilt: Dict[int, jnp.ndarray] = {}
        for gi, g in enumerate(self.groups):
            mat = gathered[gi].reshape(self.n, g.shard_len)
            for b in g.leaves:
                flat = mat[:, b.offset : b.offset + b.chunk].reshape(-1)
                rebuilt[b.index] = self.from_mixed_leaf(b, flat)

        def one(i, leaf):
            if i in rebuilt:
                return rebuilt[i]
            return new_leaves[i]

        new_params = self._map_leaves(params, one)
        new_masters: Tuple[jnp.ndarray, ...] = ()
        if self.stage >= 3:
            new_masters = tuple(
                new_leaves[b.index] for b in self.big_leaves
            )
        return new_params, new_masters, tuple(new_ef)

    # ------------------------------------------------------------------ #
    # telemetry / reporting
    # ------------------------------------------------------------------ #
    def sharded_elems(self) -> int:
        """Per-model-shard padded element count (what one data-axis group
        actually moves per gather)."""
        return sum(b.padded for b in self.big_leaves)

    def gather_fp32_bytes(self) -> int:
        """Wire bytes of one unquantized param all-gather (all groups)."""
        return 4 * self.sharded_elems()

    def gather_wire_bytes(self) -> int:
        """Wire bytes of one param all-gather as configured (int8 payload
        + bf16 block scales when quantized; the block accounting is the
        compression layer's, so the telemetry's ratios stay consistent
        with the dcn-compression path's)."""
        if not self.quantized:
            return self.gather_fp32_bytes()
        from ray_lightning_tpu.parallel.compression import int8_payload_bytes

        return int8_payload_bytes(self.sharded_elems(), self.quant_block)

    def shard_fraction(self, index: int) -> float:
        """Fraction of a param (and its optimizer state) one device holds:
        ``1/(n * n_model)`` for big leaves, ``1/n_model`` for model-sharded
        small leaves, 1.0 for fully replicated leaves — the number that
        makes a mis-written rule silently replicating a hot tensor visible."""
        big = self._big_by_index.get(index)
        if big is not None:
            return 1.0 / (self.n * big.n_model)
        spec = self._model_spec_by_index.get(index)
        if spec:
            n_model = 1
            for entry in spec:
                for a in _entry_axes(entry):
                    n_model *= int(self.mesh.shape[a])
            return 1.0 / n_model
        return 1.0

    def describe(self) -> str:
        mode = "int8+EF" if self.quantized else "fp32"
        composed = sorted(
            {a for b in self.big_leaves for a in b.model_axes}
        )
        axes_note = (
            f", model axes {composed}" if composed else ""
        )
        lines = [
            f"explicit ZeRO stage {self.stage}: {len(self.big_leaves)} "
            f"sharded leaves in {len(self.groups)} gather groups over "
            f"{self.n} ranks (axis {self.axis!r}{axes_note}), all-gather "
            f"{mode} ({self.gather_wire_bytes()} B/step vs "
            f"{self.gather_fp32_bytes()} B fp32)"
        ]
        for g in self.groups:
            names = ", ".join(b.path for b in g.leaves)
            sig = f" x{g.n_model} model shards" if g.n_model > 1 else ""
            lines.append(
                f"  group {g.index}: shard {g.shard_len} elems{sig} — {names}"
            )
        return "\n".join(lines)


def dataclass_replace(b: _BigLeaf, **kw) -> _BigLeaf:
    from dataclasses import replace

    return replace(b, **kw)
