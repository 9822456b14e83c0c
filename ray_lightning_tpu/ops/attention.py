"""Attention: blockwise (flash) pallas TPU kernels with custom VJP, plus a
reference einsum path.

Design (TPU-first):
- layout [B, H, S, D] so the inner dots are MXU-shaped [BQ, D] x [D, BK];
- FULLY BLOCKED grids: no ref ever pins a whole [S, D] tensor in VMEM —
  both sequence axes are grid dimensions, so VMEM use is O(block^2)
  regardless of S (8k+ sequences fit; the round-1 kernels pinned full
  K/V per q block and full Q/dO per kv block, which could not scale);
- forward: online softmax with fp32 scratch accumulators (acc/m/l)
  persisted across the innermost (KV) grid dimension — TPU grids iterate
  sequentially on a core, so scratch carries state between steps;
- the forward grid is (b, h, visited): `flash_schedule`, a pure function
  of the shapes evaluated at trace time, lists the (q tile, kv tile) pairs
  the mask admits, a q tile's pairs adjacent and in ascending kv order,
  each marked first / last of its q tile. The lists ride as
  scalar-prefetch operands and the index maps read them: no grid step is
  empty. Every pair of a causal pass pays `_mask_scores` (skipping it on
  the tiles the mask leaves whole gained nothing on the chip: PERF.md
  section 6, PR 40). Where the schedule skips nothing (not causal, or a
  sequence of one tile) or would not fit in scalar memory, the walk is the
  rectangular grid (b, h, n_q, n_kv) itself and the same kernel reads its
  place from the grid's indices, as the backward kernels do;
- the backward kernels keep their rectangular grids: a tile the mask
  empties is skipped with pl.when, its block index clamped so the skipped
  step fetches nothing;
- backward: recompute-based (no S x S materialization): a dQ kernel
  accumulating over KV blocks and a dK/dV kernel accumulating over Q
  blocks, seeded with the saved per-row logsumexp and
  delta = rowsum(dO * O);
- GQA: KV-head index derived in the BlockSpec index map (no repeat/copy);
- block sizes default 512x512, env-tunable (RLT_FLASH_BLOCK_Q/K) for
  on-chip sweeps;
- `interpret=True` runs the same kernels on CPU for numerical tests.

The reference project has no attention of its own (it wraps user torch
models); this is the hot op of our flagship model family (SURVEY §5
long-context: ring attention in parallel/ring_attention.py shards sequence
ACROSS chips and, on TPU, runs these flash kernels per ring step through a
ring-level custom VJP — einsum block math remains as the off-TPU fallback).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


# --------------------------------------------------------------------- #
# reference path
# --------------------------------------------------------------------- #
def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """q: [B, Hq, S, D]; k, v: [B, Hkv, S, D] with Hq % Hkv == 0.

    ``window``: sliding-window size W (requires ``causal``): position i
    attends positions [i-W+1, i] — HF Mistral semantics (i - j < W)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        skv = k.shape[2]
        qi = jnp.arange(sq)[:, None] + (skv - sq)
        ki = jnp.arange(skv)[None, :]
        keep = qi >= ki
        if window:
            keep &= (qi - ki) < window
        logits = jnp.where(keep, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v).astype(q.dtype)


# --------------------------------------------------------------------- #
# pallas forward: grid (b, h, visited) over the schedule's pairs (or the
# rectangular grid where that skips nothing); acc/m/l live in fp32 VMEM
# scratch carried across the pairs of one q tile
# --------------------------------------------------------------------- #
def _mask_scores(s, qi, kj, block_q, block_k, causal, window):
    """Apply the causal (and optional sliding-window band) mask to one
    [BQ, BK] score block at grid position (qi, kj). Shared by all three
    kernels so the mask cannot drift between forward and backward. Every
    computed tile of a causal pass takes it, the ones it leaves whole too:
    skipping it there gained nothing on the chip, forward or backward
    (PERF.md section 6, PR 40).

    Windowed masking uses a large FINITE negative instead of -inf: an
    active block can contain rows whose band lies entirely outside it
    (the block-level activity test is per-block, not per-row), and a
    fully -inf row would drive the online softmax through exp(inf-inf)
    = nan. With a finite mask value such a row's bogus uniform
    contribution is annihilated by the alpha = exp(m_prev - m_new)
    rescale as soon as its first real (diagonal-containing) block
    arrives — which always exists under causal+window. Pure causal keeps
    -inf: each row's first visited block always contains column 0."""
    if not causal:
        return s
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows >= cols
    neg = -jnp.inf
    if window:
        keep &= (rows - cols) < window
        neg = jnp.float32(-1e30)
    return jnp.where(keep, s, neg)


def _fwd_kernel(
    *refs, scale, causal, window, block_q, block_k, n_kv,
):
    """One (q tile, kv tile) step of the forward pass. `n_kv` None: the grid
    is (b, h, visited) and the step's place and marks are pair `t` of the
    schedule, three scalar-prefetch refs ahead of the others. Else the grid
    is (b, h, n_q, n_kv) and they are read off its indices, each where the
    kernel had it before there was a schedule (the program of a one-tile
    sequence lowers to the same text as then)."""
    from jax.experimental import pallas as pl

    on_grid = n_kv is not None
    if on_grid:
        qi, kj = pl.program_id(2), pl.program_id(3)
    else:
        q_tile_ref, kv_tile_ref, flags_ref, *refs = refs
        t = pl.program_id(2)
        qi, kj, flags = q_tile_ref[t], kv_tile_ref[t], flags_ref[t]
    q_ref, k_ref, v_ref, o_ref, l_ref, acc_scr, m_scr, l_scr = refs

    @pl.when(kj == 0 if on_grid else flags & _FIRST != 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)

    # on the grid a tile the mask empties is a step that does nothing; the
    # schedule lists no such pair
    @pl.when(_block_active(qi, kj, block_q, block_k, causal, window)
             if on_grid else True)
    def _update():
        q = q_ref[:]  # [BQ, D] input dtype; dots accumulate in fp32
        ks = k_ref[:]
        vs = v_ref[:]
        s = (
            jax.lax.dot_general(
                q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [BQ, BK] fp32
        s = _mask_scores(s, qi, kj, block_q, block_k, causal, window)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape,
        )
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kj == n_kv - 1 if on_grid else flags & _LAST != 0)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # logsumexp per row, columnar [BQ, 1] (TPU tiling wants the
        # blocked seq dim second-to-last)
        l_ref[:] = m_scr[:, :1] + jnp.log(l_safe)


# --------------------------------------------------------------------- #
# pallas backward: dQ — grid (b, h, n_q, n_kv), accumulating over KV
# --------------------------------------------------------------------- #
def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, window, block_q, block_k, n_kv,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    active = _block_active(qi, kj, block_q, block_k, causal, window)

    @pl.when(active)
    def _update():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:]  # [BQ, 1] fp32
        delta = delta_ref[:]
        ks = k_ref[:]
        vs = v_ref[:]
        s = (
            jax.lax.dot_general(
                q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        s = _mask_scores(s, qi, kj, block_q, block_k, causal, window)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(ks.dtype)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, ks, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


# --------------------------------------------------------------------- #
# pallas backward: dK, dV — grid (b, hkv, n_kv, group * n_q): the
# innermost dimension walks every (gqa-group member, q block) pair, so
# the GQA reduction happens IN the accumulator and dk/dv come out
# [B, Hkv, S, D] directly — group x less output HBM traffic than a
# per-Q-head output with a host-side reshape-sum
# --------------------------------------------------------------------- #
def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, window, block_q, block_k, n_q, group,
):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    t = pl.program_id(3)  # (group member, q block) folded
    qi = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: a q block entirely above the diagonal contributes nothing
    # (under a window, neither does one entirely below the band)
    active = _block_active(qi, ki, block_q, block_k, causal, window)

    @pl.when(active)
    def _update():
        ks = k_ref[:]  # [BK, D] input dtype
        vs = v_ref[:]
        qs = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = (
            jax.lax.dot_general(
                qs, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )
        s = _mask_scores(s, qi, ki, block_q, block_k, causal, window)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(qs.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(t == group * n_q - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# pallas_call wrappers
# --------------------------------------------------------------------- #
def _env_block(name: str, default: int, s: int) -> int:
    raw = os.environ.get(name, str(default))
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer block size")
    if value <= 0 or value % 8:
        raise ValueError(f"{name}={value}: block sizes must be positive multiples of 8")
    return min(value, s)


def _pick_blocks(s: int, block_q: Optional[int] = None, block_k: Optional[int] = None):
    """Explicit block sizes win; else env (RLT_FLASH_BLOCK_Q/K); else 512x512.

    Explicit args are part of the caller's trace (static python ints), so a
    single process can sweep block configs by retracing — one device
    acquisition per sweep instead of one process per config. Env vars
    remain for whole-run pins but are read at trace time and are NOT jit
    cache keys."""
    bq = min(block_q, s) if block_q else _env_block("RLT_FLASH_BLOCK_Q", 512, s)
    bk = min(block_k, s) if block_k else _env_block("RLT_FLASH_BLOCK_K", 512, s)
    return bq, bk


def _block_active(row_blk, col_blk, block_q: int, block_k: int, causal: bool,
                  window: Optional[int] = None):
    """Does q block `row_blk` intersect kv block `col_blk` under the causal
    (and optional sliding-window band) mask? (Trivially-true traced
    predicate when not causal, so the backward kernels' pl.when always
    receives a tracer.) One definition for `flash_schedule` (numpy index
    arrays) and the three kernels (traced grid indices)."""
    if causal:
        active = col_blk * block_k <= (row_blk + 1) * block_q - 1
        if window:
            # the block's last kv index must reach the band's lower edge
            # for the block's FIRST q row: col_end >= row_start - (W - 1)
            active &= (
                (col_blk + 1) * block_k - 1
                >= row_blk * block_q - (window - 1)
            )
        return active
    return col_blk >= 0


# what flash_schedule marks on a pair
_FIRST, _LAST = 1, 2


class FlashSchedule(NamedTuple):
    """The forward pass's walk over the tiles, from `flash_schedule`."""

    q_tile: np.ndarray   # [visited] int32, the pair's q tile
    kv_tile: np.ndarray  # [visited] int32, the pair's kv tile
    flags: np.ndarray    # [visited] int32, _FIRST | _LAST
    visited: int         # grid steps a (batch, head) pass takes
    skipped: int         # steps of the rectangular grid that are no pair


def flash_schedule(sq: int, skv: int, block_q: int, block_k: int, causal: bool,
                   window: Optional[int] = None) -> FlashSchedule:
    """The schedule of the forward pass, a pure function of the shapes
    (numpy, at trace time): the (q tile, kv tile) pairs `_block_active`
    admits, a q tile's pairs adjacent and in ascending kv order (the order
    of the rectangular grid, so every running sum is formed as it is on
    it), each marked `_FIRST` / `_LAST` of its q tile. Not causal: every
    pair, nothing skipped.

    Size: three int32 a pair in scalar memory (SMEM), which grows with the
    square of the tiles a side: 36 pairs at 4,096 / 512 causal, 528 at
    16,384, 32,896 at 131,072 (386 KiB of the 1 MiB a v5e core has; the
    chip's compiler refuses 1.5 MiB). Past `_MAX_SCHEDULE_BYTES` (43,690
    pairs: beyond 151,040 positions at 512 x 512 causal) `_flash_fwd` walks
    the rectangular grid, whose skipped steps need no list."""
    n_q, n_kv = sq // block_q, skv // block_k
    qi, kj = np.meshgrid(np.arange(n_q), np.arange(n_kv), indexing="ij")
    active = _block_active(qi, kj, block_q, block_k, causal, window)
    q_tile, kv_tile = (a.astype(np.int32) for a in np.nonzero(active))
    turns = q_tile[1:] != q_tile[:-1]
    first = np.concatenate(([True], turns))
    last = np.concatenate((turns, [True]))
    flags = (first * _FIRST + last * _LAST).astype(np.int32)
    return FlashSchedule(q_tile, kv_tile, flags, visited=len(q_tile),
                         skipped=n_q * n_kv - len(q_tile))


# half of the 1 MiB of scalar memory a v5e core has
_MAX_SCHEDULE_BYTES = 512 * 1024


def _kv_index_map(group: int, bq: int, bk: int, causal: bool,
                  window: Optional[int] = None):
    """KV BlockSpec index map for a rectangular grid (b, h, i, j): the dQ
    kernel's, and the forward pass's where it walks no schedule. Under
    the causal mask, masked steps CLAMP their kv index to the last active
    block (and, under a sliding window, below-band steps clamp UP to the
    first active block): revisiting the already-resident block elides the
    DMA, so skipped steps cost neither compute (pl.when in the kernel) nor
    HBM bandwidth."""
    if causal:
        def kv_idx(b_, h, i, j, g=group):
            hi = ((i + 1) * bq - 1) // bk
            j = jnp.minimum(j, hi)
            if window:
                lo = jnp.maximum(i * bq - (window - 1), 0) // bk
                j = jnp.maximum(j, lo)
            return b_, h // g, j, 0

        return kv_idx
    return lambda b_, h, i, j, g=group: (b_, h // g, j, 0)


def _q_index_map_for_dkv(bq: int, bk: int, causal: bool, group: int,
                         n_q: int, window: Optional[int] = None):
    """Q-side BlockSpec index map for the dK/dV grid (b, h, j, t) where h
    is the KV-head GRID INDEX and t folds (gqa group member, q block):
    the Q head is h * group + t // n_q and the q block t % n_q. Inactive
    leading steps of each head's segment (q blocks fully above the
    diagonal) clamp UP to the first active q block — and, under a
    sliding window, trailing steps (q blocks beyond the band) clamp DOWN
    to the last active one. Same DMA-eliding trick as _kv_index_map."""

    def q_block(j, t):
        i = t % n_q
        if not causal:
            return i
        i = jnp.maximum(i, (j * bk) // bq)
        if window:
            hi = ((j + 1) * bk - 1 + (window - 1)) // bq
            i = jnp.minimum(i, hi)
        return i

    return lambda b_, h, j, t: (
        b_, h * group + t // n_q, q_block(j, t), 0
    )


def _flash_fwd(q, k, v, causal, scale, interpret, blocks=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, sq, d = q.shape
    dv = v.shape[3]  # values (and the output) may have another width than q, k
    hkv = k.shape[1]
    group = hq // hkv
    skv = k.shape[2]
    bq, bk = _pick_blocks(sq, *(blocks or (None, None)))
    sched = flash_schedule(sq, skv, bq, bk, causal, window)
    if sched.skipped and 3 * sched.flags.nbytes <= _MAX_SCHEDULE_BYTES:
        # grid (b, h, t): pair t of the schedule, read from the prefetched lists
        lists = (sched.q_tile, sched.kv_tile, sched.flags)
        grid, n_kv = (b, hq, sched.visited), None

        def q_idx(b_, h, t, q_tile, kv_tile, flags):
            return b_, h, q_tile[t], 0

        def kv_idx(b_, h, t, q_tile, kv_tile, flags):
            return b_, h // group, kv_tile[t], 0
    else:
        # nothing to skip, or more pairs than scalar memory should hold: the
        # rectangular grid, its empty steps (if any) skipped and clamped
        lists = ()
        n_kv = skv // bk
        grid = (b, hq, sq // bq, n_kv)

        def q_idx(b_, h, i, j):
            return b_, h, i, 0

        kv_idx = _kv_index_map(group, bq, bk, causal, window)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, block_q=bq,
        block_k=bk, n_kv=n_kv,
    )

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(lists),
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, bq, d), q_idx),
                pl.BlockSpec((None, None, bk, d), kv_idx),
                pl.BlockSpec((None, None, bk, dv), kv_idx),
            ],
            out_specs=[
                pl.BlockSpec((None, None, bq, dv), q_idx),
                pl.BlockSpec((None, None, bq, 1), q_idx),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),   # acc
                pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-replicated)
                pltpu.VMEM((bq, 128), jnp.float32),  # running sum (lane-replicated)
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*lists, q, k, v)
    return out, lse


def _flash_bwd(q, k, v, out, lse, do, causal, scale, interpret, blocks=None,
               window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, sq, d = q.shape
    d_v = v.shape[3]  # the values' own width (see _flash_fwd)
    hkv = k.shape[1]
    group = hq // hkv
    skv = k.shape[2]
    bq, bk = _pick_blocks(sq, *(blocks or (None, None)))
    n_q = sq // bq
    n_kv = skv // bk

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1, keepdims=True)

    kv_idx = _kv_index_map(group, bq, bk, causal, window)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, n_kv=n_kv,
        ),
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((None, None, bk, d), kv_idx),
            pl.BlockSpec((None, None, bk, d_v), kv_idx),
            pl.BlockSpec((None, None, bq, d_v), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((None, None, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((None, None, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dK/dV: grid over KV heads with the GQA group folded into the
    # innermost dimension — the group reduction happens in the fp32
    # accumulator, dk/dv land [B, Hkv, S, D] directly
    q_idx = _q_index_map_for_dkv(bq, bk, causal, group, n_q, window)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            block_q=bq, block_k=bk, n_q=n_q, group=group,
        ),
        grid=(b, hkv, n_kv, group * n_q),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), q_idx),
            pl.BlockSpec((None, None, bk, d), lambda b_, h, j, t: (b_, h, j, 0)),
            pl.BlockSpec((None, None, bk, d_v), lambda b_, h, j, t: (b_, h, j, 0)),
            pl.BlockSpec((None, None, bq, d_v), q_idx),
            pl.BlockSpec((None, None, bq, 1), q_idx),
            pl.BlockSpec((None, None, bq, 1), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, d), lambda b_, h, j, t: (b_, h, j, 0)),
            pl.BlockSpec((None, None, bk, d_v), lambda b_, h, j, t: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, d_v), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d_v), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------- #
# public op with custom VJP
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, interpret, blocks, window=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, interpret, blocks, window)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, interpret, blocks, window=None):
    out, lse = _flash_fwd(q, k, v, causal, scale, interpret, blocks, window)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(causal, scale, interpret, blocks, window, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd(q, k, v, out, lse, g, causal, scale, interpret, blocks,
                      window)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def _lane_pad(d: int) -> int:
    """Head dim rounded up to the TPU lane width (128)."""
    return ((d + 127) // 128) * 128


def flash_supported(q_shape, k_shape, block_q=None, block_k=None) -> bool:
    """Whether the pallas flash kernels can serve these shapes: last-aligned
    self-attention (sq == skv), block-divisible lengths, TPU-tileable block
    rows. Head dims that are not lane-multiples are zero-padded to 128
    around the kernel (exact: padded q/k columns contribute zero scores and
    padded v columns carry zero values and gradients) — so head_dim 64
    (BERT-base and most small models) takes the flash path too."""
    sq, skv = q_shape[2], k_shape[2]
    bq, bk = _pick_blocks(sq, block_q, block_k)
    return (
        sq == skv
        and sq % bq == 0
        and skv % bk == 0
        and bq % 8 == 0
        and bk % 8 == 0
    )


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Dispatching attention op. q: [B, Hq, S, D]; k/v: [B, Hkv, S, D].

    impl: "flash" | "reference" | None (auto: flash when shapes are
    TPU-tileable, reference otherwise). block_q/block_k: explicit flash
    block sizes (static ints, so distinct values retrace — sweepable in
    one process); default env/512.

    window: sliding-window size W (static; requires causal): position i
    attends positions [i-W+1, i] — HF Mistral semantics. In the flash
    path the band is part of the tile schedule (an out-of-band tile is
    no grid step of the forward pass and a skipped one of the backward),
    so long-sequence work scales O(S*W) instead of O(S^2). W >= S is a
    no-op and drops to plain causal.
    """
    sq, d = q.shape[2], q.shape[3]
    if window is not None:
        if not causal:
            raise NotImplementedError(
                "sliding-window attention is causal-only (decoder bands)"
            )
        if window < 1:
            raise ValueError(f"window={window}: must be >= 1")
        if window >= k.shape[2]:
            # band covers every kv position: plain causal. Keyed to the KV
            # length — with skv > sq (reference-path cached decoding) a
            # window smaller than skv still masks old positions even when
            # it exceeds the query count
            window = None
    scale = sm_scale if sm_scale is not None else float(1.0 / np.sqrt(d))
    flash_ok = flash_supported(q.shape, k.shape, block_q, block_k)
    if impl is None:
        # auto mode never picks interpret-mode pallas: off-TPU the kernels
        # run in the (slow) interpreter, so the einsum reference is the
        # faster correct choice there; tests opt in with impl="flash"
        flash_fast = flash_ok and not (
            interpret if interpret is not None else _interpret_default()
        )
        impl = "flash" if flash_fast else "reference"
    elif impl == "flash" and not flash_ok:
        raise ValueError(
            "flash attention requires last-aligned self-attention (sq == "
            "skv) with sequence lengths divisible into 8-row-aligned "
            f"blocks; got q {q.shape}, k {k.shape}. "
            "Use impl='reference' for these shapes."
        )
    if impl == "reference":
        return reference_attention(
            q, k, v, causal=causal, sm_scale=scale, window=window
        )
    if interpret is None:
        interpret = _interpret_default()
    blocks = (block_q, block_k) if (block_q or block_k) else None
    dv = v.shape[3]
    d_pad, dv_pad = _lane_pad(d), _lane_pad(dv)
    if d_pad != d or dv_pad != dv:
        # scale already fixed from the true d; zero columns change nothing.
        # q and k share one width, v (and so the output) may have another:
        # latent attention scores 192 columns and sums values of 128
        qk_pad = ((0, 0), (0, 0), (0, 0), (0, d_pad - d))
        v_pad = ((0, 0), (0, 0), (0, 0), (0, dv_pad - dv))
        out = _flash_attention(
            jnp.pad(q, qk_pad), jnp.pad(k, qk_pad), jnp.pad(v, v_pad),
            causal, scale, interpret, blocks, window,
        )
        return out[..., :dv]
    return _flash_attention(q, k, v, causal, scale, interpret, blocks, window)
