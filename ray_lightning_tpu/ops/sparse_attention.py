"""Block-sparse attention that selects its own pages: the indexer (pooled
keys, a query's scores over them, its chosen blocks) and causal attention
over the chosen blocks of a whole prompt.

A layer keeps, beside K and V, one POOLED key a ``stride`` positions a
key/value head: ``Kp_j = mean(k_t, stride j <= t < stride j + kernel)``,
complete once position ``stride j + kernel - 1`` is written. A query at
position ``i`` scores the complete pooled keys (``softmax_j(q_h . Kp_j /
sqrt(hd))``, summed over the query heads of a key/value head's group), a
block of ``block`` positions scores the largest of the pooled keys that
overlap it, the first ``init_blocks`` blocks and the blocks of the last
``window`` positions (the query's own among them) are always taken, and the
``topk`` best blocks, the forced among them, are attended, causally. A query
whose length ``i + 1`` is under ``dense_len`` attends everything.

Selection (the scores and the ``top_k``) is left to XLA, float32 at the
highest matmul precision under the scope ``sparse_select``: a score rounded
to bfloat16 flips near-ties. Decode reads the chosen blocks through the
paged kernel by a table composed from the row's own
(:func:`compose_tables`). Prefill has one Pallas kernel here,
``flash_fwd_selected``: flash attention's forward pass over one sequence
with, a query row and a tile of keys, one int32 whose bits say which of the
tile's blocks the row chose (:func:`tile_bits`); nothing is skipped but what
causality skips, so its time is the dense pass's.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "SparseSpec", "choose_blocks", "compose_tables", "pooled_keys",
    "selected_attention", "selected_positions", "tile_bits",
]

_HIGHEST = jax.lax.Precision.HIGHEST
_SCORE_BYTES = 2 ** 27  # float32 scores of one chunk of a prompt's queries


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


@dataclass(frozen=True)
class SparseSpec:
    kernel: int = 32  # positions a pooled key is the mean of
    stride: int = 16  # positions between two pooled keys
    block: int = 64  # positions a selectable block
    topk: int = 64  # blocks a query attends, the forced among them
    window: int = 2048  # the last positions, whose blocks are always taken
    init_blocks: int = 1
    dense_len: int = 8192  # a shorter request attends everything

    def __post_init__(self):
        if self.kernel % self.stride or self.block % self.stride:
            raise ValueError(
                f"pooling {self.kernel} and block {self.block} must be whole "
                f"multiples of the stride {self.stride}")
        if self.back > self.per_block:
            raise ValueError("a pooled key may reach into the next block only")
        if self.window < self.kernel:
            raise ValueError(
                "window < pooling: a block behind the last complete pooled "
                "key has to be one the window forces")
        forced = self.init_blocks + -(-(self.window - 1) // self.block) + 1
        if self.topk < forced:
            raise ValueError(
                f"topk={self.topk} is under the {forced} blocks the rules can "
                "force (the initial ones and those of the window): the "
                "query's own block has to be among the chosen")
        if self.dense_len < self.topk * self.block:
            raise ValueError(
                f"dense_len={self.dense_len} holds fewer than topk={self.topk} "
                f"blocks of {self.block}: a selecting query must have topk to choose")

    @property
    def per_block(self) -> int:
        """Pooled keys that start in one block."""
        return self.block // self.stride

    @property
    def back(self) -> int:
        """Pooled keys that start before a block and reach into it."""
        return (self.kernel - 1) // self.stride

    def complete(self, length):
        """How many pooled keys are complete in a sequence of ``length``."""
        return jnp.maximum(length - self.kernel, -self.stride) // self.stride + 1

    def columns(self) -> int:
        """Width of a composed table: ``topk`` blocks, or every block of a
        request still under ``dense_len``."""
        return max(self.topk, -(-(self.dense_len - 1) // self.block))


def pooled_keys(k: jnp.ndarray, spec: SparseSpec) -> jnp.ndarray:
    """k: [T, ...] keys by position (T a multiple of the stride) -> [T /
    stride, ...]: pooled key j is the float32 mean of positions ``[stride j,
    stride j + kernel)``, in k's type. Positions behind T count as zeros:
    such a pooled key is not complete and nothing may read it."""
    t = k.shape[0]
    n, reach = t // spec.stride, spec.kernel // spec.stride
    part = k.astype(jnp.float32).reshape((n, spec.stride) + k.shape[1:]).sum(axis=1)
    part = jnp.pad(part, ((0, reach - 1),) + ((0, 0),) * (k.ndim - 1))
    total = sum(part[r: r + n] for r in range(reach))
    return (total / spec.kernel).astype(k.dtype)


def _block_scores(s: jnp.ndarray, spec: SparseSpec) -> jnp.ndarray:
    """s: [..., blocks * per_block] scores of pooled keys (``-inf`` where
    not complete) -> [..., blocks]: the largest among those that overlap."""
    per = spec.per_block
    cut = s.reshape(s.shape[:-1] + (-1, per))  # [..., blocks, per]
    best = cut.max(axis=-1)
    for r in range(1, spec.back + 1):  # the block before's last r-th reaches in
        prev = jnp.pad(cut[..., :-1, per - r], [(0, 0)] * (cut.ndim - 2) + [(1, 0)],
                       constant_values=-jnp.inf)
        best = jnp.maximum(best, prev)
    return best


def choose_blocks(q: jnp.ndarray, kp: jnp.ndarray, pos: jnp.ndarray,
                  spec: SparseSpec) -> jnp.ndarray:
    """The blocks each query attends. q: [Hkv, G, Q, hd] (a key/value head's
    group of query heads, Q queries); kp: [Hkv, J, hd] pooled keys of the
    queries' sequence, J a multiple of ``per_block``; pos: [Q] int32, the
    queries' positions. Returns [Hkv, Q, topk] int32 block numbers, best
    first, ties to the lower number. Float32 at the highest precision. Only
    a query with ``pos + 1 >= dense_len`` has ``topk`` blocks to choose
    from; the rows of the others mean nothing."""
    hd = q.shape[-1]
    with jax.named_scope("sparse_select"):
        logits = jnp.einsum(
            "hgqd,hjd->hgqj", q.astype(jnp.float32), kp.astype(jnp.float32),
            precision=_HIGHEST) * (float(hd) ** -0.5)
        j = jnp.arange(kp.shape[1], dtype=jnp.int32)
        complete = (spec.stride * j + spec.kernel - 1)[None, :] <= pos[:, None]  # [Q, J]
        # a finite mask: a query before the first complete pooled key has
        # none to take a softmax over
        p = jax.nn.softmax(jnp.where(complete, logits, -1e30), axis=-1)
        s = jnp.where(complete, p.sum(axis=1), -jnp.inf)  # [Hkv, Q, J]
        score = _block_scores(s, spec)  # [Hkv, Q, blocks]
        b = jnp.arange(score.shape[-1], dtype=jnp.int32)[None, :]
        own = (pos // spec.block)[:, None]
        forced = (b < spec.init_blocks) | (
            b >= jnp.maximum(pos - spec.window + 1, 0)[:, None] // spec.block)
        score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(b <= own, score, -jnp.inf)
        return jax.lax.top_k(score, spec.topk)[1].astype(jnp.int32)


def selected_positions(pos: jnp.ndarray, spec: SparseSpec) -> jnp.ndarray:
    """Positions a query at ``pos`` attends in one key/value head: all
    ``pos + 1`` under ``dense_len``, else ``topk`` blocks whose last, its
    own, is cut at ``pos``."""
    sparse = pos + 1 >= spec.dense_len
    return jnp.where(sparse, (spec.topk - 1) * spec.block + pos % spec.block + 1, pos + 1)


def compose_tables(tables: jnp.ndarray, chosen: jnp.ndarray, pos: jnp.ndarray,
                   spec: SparseSpec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A decoding row's chosen blocks as a block table of their own.
    tables: [B, max_blocks] physical blocks; chosen: [B, Hkv, topk] logical
    blocks; pos: [B]. Returns (composed [B, Hkv, columns()]: the chosen
    blocks' physical blocks in ascending logical order, so that the row's own
    block, the one cut at ``pos``, is the last, or the row's table itself
    while it is under ``dense_len``; last [B]: the position the row stands at
    in that order: everything up to it is attended)."""
    cols = spec.columns()
    chosen = jnp.sort(chosen, axis=-1)
    chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, cols - spec.topk)))
    every = jnp.broadcast_to(jnp.arange(cols, dtype=jnp.int32), chosen.shape)
    sparse = (pos + 1 >= spec.dense_len)[:, None, None]
    logical = jnp.minimum(jnp.where(sparse, chosen, every), tables.shape[1] - 1)
    composed = jnp.take_along_axis(tables[:, None, :], logical, axis=2)
    return composed, selected_positions(pos, spec) - 1


# --------------------------------------------------------------------- #
# a prompt's queries: who attends which block
# --------------------------------------------------------------------- #
def prompt_block_mask(q: jnp.ndarray, kp: jnp.ndarray, spec: SparseSpec) -> jnp.ndarray:
    """q: [Hkv, G, T, hd] of one sequence, positions 0..T-1; kp: [Hkv, J,
    hd] its pooled keys (J >= blocks * per_block). Returns [Hkv, T, blocks]
    bool, blocks = ceil(T / block): whether the query attends the block (all
    True under ``dense_len``; causality is the caller's). The queries go
    through in chunks, so that the float32 scores stay under
    ``_SCORE_BYTES``."""
    hkv, g, t, hd = q.shape
    nb = -(-t // spec.block)
    kp = kp[:, : nb * spec.per_block]
    kp = jnp.pad(kp, ((0, 0), (0, nb * spec.per_block - kp.shape[1]), (0, 0)))
    most = max(1, _SCORE_BYTES // (4 * hkv * g * kp.shape[1]))
    step = max(d for d in range(1, min(most, t) + 1) if t % d == 0)

    def chunk(start):
        pos = start + jnp.arange(step, dtype=jnp.int32)
        qs = jax.lax.dynamic_slice_in_dim(q, start, step, axis=2)
        chosen = choose_blocks(qs, kp, pos, spec)  # [Hkv, step, topk]
        hit = (chosen[..., None] == jnp.arange(nb, dtype=jnp.int32)).any(axis=-2)
        return hit | (pos + 1 < spec.dense_len)[None, :, None]

    out = jax.lax.map(chunk, jnp.arange(t // step, dtype=jnp.int32) * step)
    return jnp.moveaxis(out, 0, 1).reshape(hkv, t, nb)


def tile_bits(mask: jnp.ndarray, blocks_per_tile: int) -> jnp.ndarray:
    """mask: [Hkv, T, blocks] bool -> [Hkv, tiles, T, 1] int32: bit c of
    entry (h, tile, t) says whether query t attends block ``tile *
    blocks_per_tile + c``."""
    hkv, t, nb = mask.shape
    tiles = -(-nb // blocks_per_tile)
    mask = jnp.pad(mask, ((0, 0), (0, 0), (0, tiles * blocks_per_tile - nb)))
    weights = jnp.left_shift(1, jnp.arange(blocks_per_tile, dtype=jnp.int32))
    bits = (mask.reshape(hkv, t, tiles, blocks_per_tile).astype(jnp.int32)
            * weights).sum(axis=-1)
    return jnp.moveaxis(bits, 2, 1)[..., None]


# --------------------------------------------------------------------- #
# attention over the chosen blocks of one sequence
# --------------------------------------------------------------------- #
def _selected_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, acc_scr, m_scr, l_scr,
                     *, scale, block_q, block_k, block, n_kv):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(kj * block_k <= (qi + 1) * block_q - 1)
    def _update():
        ks, vs = k_ref[:], v_ref[:]
        s = jax.lax.dot_general(
            q_ref[:], ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        local = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        chosen = jnp.bitwise_and(
            jnp.right_shift(bits_ref[:], local // block), 1) == 1
        # every row chose block 0 (init_blocks, or it attends everything),
        # which its first tile holds: -inf stays nan-safe as under causality
        s = jnp.where(chosen & (rows >= kj * block_k + local), s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def _tile(t: int, block: int) -> int:
    """Keys (and queries) a tile of the kernel: the most up to 512 that is
    whole blocks and divides T."""
    if t % block:
        raise ValueError(f"{t} positions are no whole blocks of {block}")
    return max(s for s in range(block, min(512, t) + 1, block) if t % s == 0)


def _selected_pallas(q, k, v, mask, spec, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hq, t, hd = q.shape
    group = hq // k.shape[0]
    size = _tile(t, spec.block)
    n = t // size
    bits = tile_bits(mask, size // spec.block)
    # a tile behind the diagonal is skipped: naming the diagonal's again
    # there keeps its copy from being made
    kv_idx = lambda h, i, j: (h // group, jnp.minimum(j, i), 0)
    return pl.pallas_call(
        functools.partial(
            _selected_kernel, scale=scale, block_q=size, block_k=size,
            block=spec.block, n_kv=n),
        grid=(hq, n, n),
        in_specs=[
            pl.BlockSpec((None, size, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((None, size, hd), kv_idx),
            pl.BlockSpec((None, size, hd), kv_idx),
            pl.BlockSpec((None, None, size, 1),
                         lambda h, i, j: (h // group, jnp.minimum(j, i), i, 0)),
        ],
        out_specs=pl.BlockSpec((None, size, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((size, hd), jnp.float32),
            pltpu.VMEM((size, 128), jnp.float32),
            pltpu.VMEM((size, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_selected",
    )(q, k, v, bits)


def _selected_lax(q, k, v, mask, spec, scale):
    hq, t, hd = q.shape
    hkv = k.shape[0]
    qg = q.reshape(hkv, hq // hkv, t, hd).astype(jnp.float32)
    s = jnp.einsum("hgqd,hkd->hgqk", qg, k.astype(jnp.float32),
                   precision=_HIGHEST) * scale
    seen = jnp.repeat(mask, spec.block, axis=-1)[..., :t]
    seen = seen & (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("hgqk,hkd->hgqd", p, v.astype(jnp.float32), precision=_HIGHEST)
    return out.reshape(hq, t, hd).astype(q.dtype)


def selected_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray,
    spec: SparseSpec, *, sm_scale: Optional[float] = None,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Causal attention of one sequence over the blocks its queries chose.
    q: [Hq, T, hd]; k, v: [Hkv, T, hd]; mask: [Hkv, T, blocks] bool
    (:func:`prompt_block_mask`), shared by the query heads of a key/value
    head's group. Every query must have chosen block 0. ``kernel`` None: the
    Pallas kernel where it is native, else a masked softmax in float32."""
    scale = float(q.shape[-1]) ** -0.5 if sm_scale is None else float(sm_scale)
    if interpret is None:
        interpret = _interpret_default()
    if kernel is None:
        kernel = not interpret
    if kernel:
        return _selected_pallas(q, k, v, mask, spec, scale, interpret)
    return _selected_lax(q, k, v, mask, spec, scale)
