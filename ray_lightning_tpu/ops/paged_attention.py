"""Pallas TPU kernels for the paged serving decode hot path.

``decode_step_paged`` (models/generation.py) is memory-bound: per step it
gathers every row's referenced KV blocks into logical order
(``k_cache[block_tables]`` — a full [B, Hkv, C, hd] materialization) and
then runs a masked matvec that reads most of that gather exactly once.
The fused kernel here walks the block table IN-KERNEL instead: the table
and the row positions ride in as scalar-prefetch operands, each grid
step DMAs one physical block directly from the paged cache, and a
flash-style online softmax accumulates the attention output — the
gathered intermediate never exists, and blocks past a row's position are
neither computed (``pl.when``) nor fetched (the index map clamps to the
last active block, re-referencing the resident block so the DMA elides).

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
def _paged_decode_kernel(
    bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, l_scr,
    *, scale, block_size, n_blocks,
):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(2)
    pos_b = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    # a block is active when it holds at least one valid position; its
    # first position (j * bs) valid means every row of the score block
    # has a finite column, so -inf masking stays nan-safe
    @pl.when(j * block_size <= pos_b)
    def _update():
        q = q_ref[:].astype(jnp.float32)  # [Gp, hd]
        ks = k_ref[:].astype(jnp.float32)  # [bs, hd]
        vs = v_ref[:].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, ks, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Gp, bs]
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(cols <= pos_b, s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused block-table-walking decode attention.

    q: [B, Hkv, G, hd] (GQA-folded queries, one position per row);
    k_cache / v_cache: [N, Hkv, bs, hd] paged pools; block_tables:
    [B, max_blocks] int32 (trash-padded); pos: [B] int32 per-row
    positions. Returns fp32 [B, Hkv, G, hd] — the softmax(QK^T)V of each
    row over its logical positions [0, pos[b]], identical math to the
    gather path in ``decode_step_paged`` (flash accumulation order, so
    float-exact only per block; token-level parity is what the serving
    tests pin).

    Grid is (B, Hkv, max_blocks) with the table and positions as
    scalar-prefetch operands: the KV index map resolves logical block j
    to ``block_tables[b, min(j, pos[b] // bs)]`` — physical gather
    without materializing [B, Hkv, C, hd], and the clamp parks inactive
    steps on the already-resident block so their DMA elides.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, hkv, group, hd = q.shape
    bs = k_cache.shape[2]
    n_blocks = block_tables.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)
    # pad the GQA group up to the sublane tile so tiny models (G < 8)
    # keep TPU-legal shapes; padded rows compute masked garbage that is
    # sliced off below
    gp = max(group, 8)
    if gp != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    def kv_idx(b, h, j, bt_ref, pos_ref):
        jj = jnp.minimum(j, pos_ref[b] // bs)
        return bt_ref[b, jj], h, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, hkv, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (None, None, gp, hd),
                lambda b, h, j, bt_ref, pos_ref: (b, h, 0, 0),
            ),
            pl.BlockSpec((None, None, bs, hd), kv_idx),
            pl.BlockSpec((None, None, bs, hd), kv_idx),
        ],
        out_specs=pl.BlockSpec(
            (None, None, gp, hd),
            lambda b, h, j, bt_ref, pos_ref: (b, h, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((gp, hd), jnp.float32),   # acc
            pltpu.VMEM((gp, 128), jnp.float32),  # running max (lane-repl.)
            pltpu.VMEM((gp, 128), jnp.float32),  # running sum (lane-repl.)
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            scale=scale, block_size=bs, n_blocks=n_blocks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, gp, hd), jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q, k_cache, v_cache)
    return out[:, :, :group] if gp != group else out


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
