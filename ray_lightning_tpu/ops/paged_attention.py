"""Pallas TPU kernels for the paged serving decode hot path.

``decode_step_paged`` (models/generation.py) attends, a layer, one query
position of every row over that row's pages of the paged pool. Its lax
path gathers every row's whole table into logical order
(``k_cache[block_tables]`` — a full [B, Hkv, C, hd] materialization,
whatever the rows hold) and runs a masked matvec over it. The fused
kernel here walks the block table IN-KERNEL instead: the table and the
row positions ride in as scalar-prefetch operands, the pools stay in
HBM, and a flash-style online softmax accumulates the output, so the
gathered intermediate never exists.

How it walks is what its time depends on. A grid step costs a fixed
0.2-0.35 us on the chip whether it does anything or not, and a copy
costs about as much to issue as 4 KB costs to move, so the kernel is
built to take few steps and large copies, and none for dead context:

- the grid is one step a row; inside it a loop runs over the row's live
  groups of ``P`` table columns only (``pos[b] // (P * bs) + 1`` of
  them), ``P`` chosen from the static shapes (``_pages_per_step``) so
  that a group is about 256 tokens;
- the pool keeps a physical page as one contiguous ``[Hkv, bs, hd]``
  slab, so one hand-started copy brings K of a page for ALL KV heads and
  one brings V; a group is ``2 * P`` such copies into one half of a
  double buffer, and the next group's — at a row's end the next row's
  first group's — are in flight while this one is computed;
- a group is computed for all KV heads at once (head-batched matmuls
  over ``[Hkv, P * bs, hd]``), scores, probabilities and accumulator in
  float32; positions past ``pos[b]`` are masked to -inf and their V rows
  selected to zero, so nothing past ``pos[b]`` reaches the result,
  whatever the pages there hold.

Measured on a v5e at the serve cells' shapes (32 rows, 8 KV heads of 4
queries, head 128, bf16 pages of 16, 160 columns; PERF.md, PR 24): 0.11
ms a layer for 24 live rows of a chat mix, 0.46 ms for 32 rows of 2,250
tokens (79 % of what the K/V bytes alone take at 819 GB/s). The kernel
it replaces took a grid step per (row, head, page) of the table's width
— 40,960 a layer at those shapes, over 90 % of them dead — and 7.4 and
15.9 ms for the same two mixes: it was bound by step overhead, not by
bytes.

Also here: a fused top-of-logits sampling kernel. Greedy sampling is a
blockwise argmax over the vocab (per-row running max + first-max index
in VMEM, every row of a tile at once; strict ``>`` across blocks
preserving ``jnp.argmax``'s first-max tie-break bit-for-bit);
temperature sampling reuses the same kernel via the Gumbel-max identity
``categorical(key, z) = argmax(z + gumbel)`` — the noise is added to the
logits block in-kernel in the logits' own dtype, and because binary
float addition is commutative the sampled token is bitwise identical to
``jax.random.categorical``. top-k / top-p filtering stays on the lax
path (``fused_sample_supported`` gates the callers).

Both kernels follow ops/attention.py's interpret-mode pattern: off-TPU
they run under ``interpret=True`` so the CPU tier-1 suite exercises the
real kernel logic. ``RLT_PAGED_KERNEL`` gates engagement from the
serving stack: unset -> kernels on only where they are native (tpu —
the CPU default path stays byte-identical to the lax implementation),
``1`` -> force on (interpret off-TPU; what the parity tests set), ``0``
-> force the lax fallback everywhere.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "fused_greedy_sample",
    "fused_sample",
    "fused_sample_supported",
    "mla_paged_decode_attention",
    "paged_decode_attention",
    "paged_kernel_enabled",
]

PAGED_KERNEL_ENV = "RLT_PAGED_KERNEL"


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


def paged_kernel_enabled() -> bool:
    """Trace-time gate for the serving stack (env ``RLT_PAGED_KERNEL``):
    unset -> native platforms only (CPU keeps the lax path, preserving
    byte-identical tier-1 behavior); ``"1"`` -> force on (interpret mode
    off-TPU); ``"0"``/empty/false -> force off."""
    raw = os.environ.get(PAGED_KERNEL_ENV)
    if raw is None:
        return jax.devices()[0].platform == "tpu"
    return raw.strip().lower() not in ("0", "", "false", "off", "no")


# --------------------------------------------------------------------- #
# fused paged decode attention
# --------------------------------------------------------------------- #
# tokens of one row a group of pages holds (P * block_size): long enough
# that a group's matmuls and its 2 * P copies outweigh its fixed cost,
# short enough that a 250-token row or a free slot wastes little of the
# one group it has. On the v5e at the serve cells' shapes 128 / 256 / 512
# / 1024 took 0.12 / 0.11 / 0.15 / 0.24 ms a layer on a chat mix and
# 0.56 / 0.46 / 0.46 / 0.52 on 32 long rows (PERF.md, PR 24)
_STEP_TOKENS = 256
# both K and V, double-buffered; a quarter of the v5e's default 16 MiB
# scoped VMEM, so no raised vmem_limit_bytes
_KV_SCRATCH_BYTES = 4 * 1024 * 1024


def _pages_per_step(n_cols, hkv, bs, hd, dtype) -> int:
    """Table columns (pages) a step fetches and computes: about
    ``_STEP_TOKENS`` tokens, no wider than the table, and small enough
    that the four page buffers fit ``_KV_SCRATCH_BYTES``."""
    page_bytes = hkv * bs * hd * jnp.dtype(dtype).itemsize
    p = max(1, min(n_cols, _STEP_TOKENS // bs))
    while p > 1 and 4 * p * page_bytes > _KV_SCRATCH_BYTES:
        p //= 2
    return p


def _paged_decode_kernel(
    bt_ref, pos_ref, *refs, scale, block_size, n_cols, windowed=False,
):
    """One row a grid step: walk the row's live page groups, the next
    group's (or the next row's first group's) copies in flight while this
    one is computed for all KV heads at once. ``windowed``: a third
    scalar-prefetch operand holds each row's first live position; the walk
    starts at the group that holds it and positions before it are masked
    as those past ``pos`` are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    first_ref = refs[0] if windowed else None
    (q_ref, k_hbm, v_hbm, o_ref,
     k_buf, v_buf, sems, slot_ref, acc_scr, m_scr, l_scr) = refs[int(windowed):]
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    hkv, pages = k_buf.shape[1:3]
    step_tokens = pages * block_size
    pos_b = pos_ref[b]
    # groups holding at least one valid position; group 0 always does
    n_live = pos_b // step_tokens + 1

    def first_group(row):
        """The first group of ``row``'s walk: 0, or the one that holds its
        first live position."""
        return first_ref[row] // step_tokens if windowed else 0

    def live(positions):
        if windowed:
            return (positions <= pos_b) & (positions >= first_ref[b])
        return positions <= pos_b

    def copies(row, group, half):
        """The 2 * P page copies of one (row, group) into buffer ``half``:
        a page is one contiguous [Hkv, bs, hd] slab of the pool, laid head
        by head into the buffer's [Hkv, P * bs, hd]. Every page of a live
        group is fetched (the table is trash-padded, so each column names
        a page); columns past the table's width, where P does not divide
        it, re-read its last column. Whatever lies past ``pos`` is masked
        below."""
        out = []
        for i in range(pages):
            page = bt_ref[row, jnp.minimum(group * pages + i, n_cols - 1)]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[half, :, i], sems.at[0, half]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[half, :, i], sems.at[1, half]))
        return out

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        for c in copies(0, first_group(0), 0):
            c.start()

    acc_scr[:] = jnp.zeros_like(acc_scr)
    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)

    def group_step(g, half):
        other = 1 - half

        # what is computed next: this row's next live group, else the
        # next row's first one
        row_ends = g + 1 == n_live

        @pl.when(jnp.logical_or(~row_ends, b + 1 < n_rows))
        def _prefetch():
            next_row = jnp.minimum(b + 1, n_rows - 1)
            for c in copies(
                jnp.where(row_ends, next_row, b),
                jnp.where(row_ends, first_group(next_row), g + 1),
                other,
            ):
                c.start()

        for c in copies(b, g, half):
            c.wait()

        ks = k_buf[half].reshape(hkv, step_tokens, -1)
        vs = v_buf[half].reshape(hkv, step_tokens, -1)
        # the queries are values of the pool's dtype (the rope returns
        # its input's dtype), so every product is exact
        s = (
            jax.lax.dot_general(
                q_ref[:].astype(ks.dtype), ks,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Hkv, Gp, T]
        base = g * step_tokens
        cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # a group of the walk holds a valid position (its first one, or
        # the row's first live one), so every row of the score block has a
        # finite column and -inf masking stays nan-safe
        s = jnp.where(live(cols), s, -jnp.inf)
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :, :1] * alpha + jnp.sum(p, axis=2, keepdims=True),
            l_scr.shape,
        )
        # a page past pos // bs may hold anything, NaN bits too, and
        # 0 * NaN is NaN: its V rows are selected to zero, not only its
        # scores to -inf
        rows = base + jax.lax.broadcasted_iota(jnp.int32, vs.shape, 1)
        vs = jnp.where(live(rows), vs.astype(jnp.float32), 0.0)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, vs, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        return other

    slot_ref[0] = jax.lax.fori_loop(
        first_group(b), n_live, group_step, slot_ref[0])
    o_ref[:] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    first: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Fused block-table-walking decode attention.

    q: [B, Hkv, G, hd] (GQA-folded queries, one position per row);
    k_cache / v_cache: [N, Hkv, bs, hd] paged pools; block_tables:
    [B, max_blocks] int32 (trash-padded: every column names a page of
    the pool); pos: [B] int32 per-row positions. Returns fp32
    [B, Hkv, G, hd] — the softmax(QK^T)V of each row over its logical
    positions [0, pos[b]], identical math to the gather path in
    ``decode_step_paged`` (flash accumulation order, so float-exact only
    per group; token-level parity is what the serving tests pin). The
    score matmul takes its operands in the pool's dtype (exact for
    queries that are values of that dtype, as the caller's are), scores,
    probabilities and the accumulator are float32.

    Grid is (B,): a step is one row, and inside it a loop runs over the
    row's LIVE page groups only (``pos[b] // (P * bs) + 1`` of them, P
    from ``_pages_per_step``). The pools stay in HBM; a group is 2 * P
    hand-started copies of whole ``[Hkv, bs, hd]`` pages, addressed from
    the scalar-prefetched table, into one half of a double buffer, while
    the other half is computed for all KV heads. Nothing past the last
    live group is fetched, and nothing past ``pos[b]`` reaches the
    result: the kernel's time follows the live context, not
    ``B * max_blocks``.

    ``first``: [B] int32, each row's first live position (a window layer:
    ``max(0, pos - W + 1)``). The row's logical positions are then
    ``[first[b], pos[b]]``: the walk starts at the group that holds
    ``first[b]``, nothing before that group is fetched (its table columns
    may name the trash block: the blocks were given back), and positions
    before ``first[b]`` inside it are masked. Absent, the kernel is the one
    without the operand, traced as it was.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, hkv, group, hd = q.shape
    bs = k_cache.shape[2]
    n_cols = block_tables.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)
    # pad the GQA group up to the pool dtype's sublane tile (8 rows of
    # float32, 16 of bfloat16) so the query block cast to that dtype is
    # a TPU-legal matmul operand; padded rows compute masked garbage
    # that is sliced off below
    sublanes = 32 // jnp.dtype(k_cache.dtype).itemsize
    gp = -(-group // sublanes) * sublanes
    if gp != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    pages = _pages_per_step(n_cols, hkv, bs, hd, k_cache.dtype)

    windowed = first is not None
    scalars = (block_tables.astype(jnp.int32), pos.astype(jnp.int32))
    if windowed:
        scalars += (first.astype(jnp.int32),)
    row_block = pl.BlockSpec(
        (None, hkv, gp, hd), lambda b, *scalar_refs: (b, 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=[
            row_block,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row_block,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, pages, bs, hd), k_cache.dtype),
            pltpu.VMEM((2, hkv, pages, bs, hd), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),       # (K | V, buffer half)
            pltpu.SMEM((1,), jnp.int32),           # half the next wait reads
            pltpu.VMEM((hkv, gp, hd), jnp.float32),   # acc
            pltpu.VMEM((hkv, gp, 128), jnp.float32),  # running max (lane-repl.)
            pltpu.VMEM((hkv, gp, 128), jnp.float32),  # running sum (lane-repl.)
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            scale=scale, block_size=bs, n_cols=n_cols, windowed=windowed,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, gp, hd), jnp.float32),
        # rows run in order: the double buffer is handed from row to row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q, k_cache, v_cache)
    return out[:, :, :group] if gp != group else out


# --------------------------------------------------------------------- #
# fused paged decode attention over a latent pool
# --------------------------------------------------------------------- #
def _mla_paged_decode_kernel(
    bt_ref, pos_ref, q_ref, kv_hbm, o_ref,
    kv_buf, sems, slot_ref, acc_scr, m_scr, l_scr,
    *, scale, block_size, n_cols, v_width,
):
    """The walk of ``_paged_decode_kernel`` over a pool whose page is one
    ``[bs, W]`` slab that every head reads: a position's compressed K/V
    (``v_width`` columns, which are also the values) and its one shared
    roped key. One copy a page, one score matmul ``[H, W] x [W, T]`` and
    one value matmul ``[H, T] x [T, v_width]`` a group, for all heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    pages = kv_buf.shape[1]
    step_tokens = pages * block_size
    pos_b = pos_ref[b]
    n_live = pos_b // step_tokens + 1

    def copies(row, group, half):
        out = []
        for i in range(pages):
            page = bt_ref[row, jnp.minimum(group * pages + i, n_cols - 1)]
            out.append(pltpu.make_async_copy(
                kv_hbm.at[page], kv_buf.at[half, i], sems.at[half]))
        return out

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    acc_scr[:] = jnp.zeros_like(acc_scr)
    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)

    def group_step(g, half):
        other = 1 - half
        row_ends = g + 1 == n_live

        @pl.when(jnp.logical_or(~row_ends, b + 1 < n_rows))
        def _prefetch():
            for c in copies(
                jnp.where(row_ends, jnp.minimum(b + 1, n_rows - 1), b),
                jnp.where(row_ends, 0, g + 1),
                other,
            ):
                c.start()

        for c in copies(b, g, half):
            c.wait()

        kv = kv_buf[half].reshape(step_tokens, -1)  # [T, W]
        s = (
            jax.lax.dot_general(
                q_ref[:].astype(kv.dtype), kv,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Hp, T]
        base = g * step_tokens
        cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos_b, s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape,
        )
        # as in the K/V kernel: rows past pos may hold anything, so the
        # values are selected to zero there, not only the scores masked
        vals = kv[:, :v_width]
        rows = base + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
        vals = jnp.where(rows <= pos_b, vals.astype(jnp.float32), 0.0)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, vals, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        return other

    slot_ref[0] = jax.lax.fori_loop(0, n_live, group_step, slot_ref[0])
    o_ref[:] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def mla_paged_decode_attention(
    q: jnp.ndarray,
    kv_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    v_width: int,
    sm_scale: float,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode attention of latent-attention heads over a latent paged pool.

    q: [B, H, W], a row's absorbed queries (``q_nope W_kb`` beside the
    roped columns); kv_cache: [N, bs, W], a position's normed compressed
    K/V in its first ``v_width`` columns and its roped shared key after
    them, one row for ALL heads. ``W`` is a multiple of the 128 lanes: the
    chip lays a 576-wide array out 640 wide anyway and refuses a copy of a
    576-wide slab out of it, so the caller pads both with zero columns,
    which add nothing to a score; block_tables [B, max_blocks] int32
    (trash-padded); pos [B] int32. Returns float32 [B, H, v_width]:
    ``softmax(q . kv * sm_scale) @ kv[:, :v_width]`` of each row over its
    positions [0, pos[b]]. The caller multiplies by ``W_vb`` and ``W_o``.

    Same walk as :func:`paged_decode_attention` (a grid step a row, a loop
    over the row's live groups of pages, the next group's copies in flight,
    nothing past ``pos[b]`` fetched or summed), but a page is one
    ``[bs, W]`` slab read once for all heads, where the K/V pool holds
    ``2 * Hkv`` such slabs a page: what a cached position costs is ``W``
    values a layer, and the kernel's bytes follow that.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, heads, width = q.shape
    bs = kv_cache.shape[1]
    n_cols = block_tables.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    sublanes = 32 // jnp.dtype(kv_cache.dtype).itemsize
    hp = -(-heads // sublanes) * sublanes
    if hp != heads:
        q = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    pages = _pages_per_step(n_cols, 1, bs, width, kv_cache.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((None, hp, width), lambda b, bt_ref, pos_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, hp, v_width), lambda b, bt_ref, pos_ref: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, width), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # buffer half
            pltpu.SMEM((1,), jnp.int32),            # half the next wait reads
            pltpu.VMEM((hp, v_width), jnp.float32),  # acc
            pltpu.VMEM((hp, 128), jnp.float32),      # running max (lane-repl.)
            pltpu.VMEM((hp, 128), jnp.float32),      # running sum (lane-repl.)
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mla_paged_decode_kernel,
            scale=sm_scale, block_size=bs, n_cols=n_cols, v_width=v_width,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hp, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="mla_paged_decode_attention",
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32), q, kv_cache)
    return out[:, :heads] if hp != heads else out


# --------------------------------------------------------------------- #
# fused top-of-logits sampling
# --------------------------------------------------------------------- #
_SAMPLE_BLOCK_V = 2048   # vocab columns per grid step (lane multiple)
_SAMPLE_BLOCK_B = 256    # rows per grid step (sublane multiple)


def _argmax_kernel(*refs, vocab, block_v, n_vb):
    """Running (max, first-max index) per row over vocab blocks.

    Every ref is a whole (rows, lanes) tile: rows are vectorised, never
    looped, so the only scalar in the kernel is the grid index."""
    from jax.experimental import pallas as pl

    *in_refs, o_ref, m_scr, i_scr = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        i_scr[:] = jnp.zeros_like(i_scr)

    x = in_refs[0][:]  # [bb, bv]
    if len(in_refs) == 2:
        # added in the logits' dtype, as jax.random.categorical does
        x = x + in_refs[1][:]
    x = x.astype(jnp.float32)
    # the vocab need not divide into blocks: columns past it (the last
    # block's ragged tail holds unspecified values) can never win
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(cols < vocab, x, -jnp.inf)
    bm = jnp.max(x, axis=1, keepdims=True)
    # first column holding the block max == jnp.argmax's tie-break; an
    # f32 min-reduce is exact for any index below 2**24
    bi = jnp.min(
        jnp.where(x == bm, cols.astype(jnp.float32), float(2 ** 24)),
        axis=1, keepdims=True,
    ).astype(jnp.int32)
    # strict > keeps the FIRST global maximum across blocks too
    better = bm > m_scr[:]
    i_scr[:] = jnp.where(better, bi, i_scr[:])
    m_scr[:] = jnp.where(better, bm, m_scr[:])

    @pl.when(j == n_vb - 1)
    def _finalize():
        o_ref[:] = i_scr[:]


def _blockwise_argmax(x: jnp.ndarray, noise: Optional[jnp.ndarray],
                      interpret: Optional[bool]) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, V = x.shape
    if V >= 2 ** 24:
        raise ValueError(f"vocab {V} exceeds the kernel's exact index range")
    if interpret is None:
        interpret = _interpret_default()
    # a block dim must be a tile multiple or span the whole array dim
    bb = B if B <= _SAMPLE_BLOCK_B else _SAMPLE_BLOCK_B
    bv = V if V <= _SAMPLE_BLOCK_V else _SAMPLE_BLOCK_V
    n_vb = pl.cdiv(V, bv)
    tile = pl.BlockSpec((bb, bv), lambda b, j: (b, j))
    args = (x,) if noise is None else (x, noise)
    out = pl.pallas_call(
        functools.partial(_argmax_kernel, vocab=V, block_v=bv, n_vb=n_vb),
        grid=(pl.cdiv(B, bb), n_vb),
        in_specs=[tile] * len(args),
        out_specs=pl.BlockSpec((bb, 1), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),  # running max
            pltpu.VMEM((bb, 1), jnp.int32),    # its index
        ],
        interpret=interpret,
        name="fused_argmax" if noise is None else "fused_sample",
    )(*args)
    return out[:, 0]


def fused_greedy_sample(
    logits: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Blockwise argmax over [B, V] logits -> [B] int32; bitwise
    equivalent to ``jnp.argmax(logits, axis=-1)`` including first-max
    tie-breaking."""
    return _blockwise_argmax(logits, None, interpret)


def fused_sample_supported(
    temperature: float, top_k: Optional[int], top_p: Optional[float]
) -> bool:
    """Sampling configs the fused kernel reproduces bit-for-bit: greedy,
    and plain-temperature categorical (Gumbel-max). top-k / top-p
    filtering keeps the lax path."""
    if top_k is not None and top_k > 0:
        return False
    if top_p is not None and 0.0 < top_p < 1.0:
        return False
    return True


def fused_sample(
    logits: jnp.ndarray,
    key,
    temperature: float,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused replacement for generation._sample_logits on the supported
    configs (see ``fused_sample_supported``): greedy is the argmax
    kernel; temperature > 0 adds ``jax.random.gumbel`` noise to the
    scaled logits IN-KERNEL and argmaxes — the Gumbel-max identity, with
    the same key -> same draw as ``jax.random.categorical``, so tokens
    are bitwise identical to the lax sampler."""
    if not fused_sample_supported(temperature, top_k, top_p):
        raise ValueError(
            "fused_sample supports greedy and plain-temperature sampling "
            "only (top_k/top_p filtering keeps the lax path); gate "
            "callers with fused_sample_supported()"
        )
    if temperature <= 0.0:
        return fused_greedy_sample(logits, interpret=interpret)
    scaled = logits / temperature
    noise = jax.random.gumbel(key, scaled.shape, scaled.dtype)
    return _blockwise_argmax(scaled, noise, interpret)
