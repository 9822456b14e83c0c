"""Lightning (decayed linear) attention: the chunked scan over a prompt and
the one-position update of the carried state.

A head keeps a state ``S`` of ``[hd, hd]`` float32 that does not grow with
the sequence: ``S_t = lam * S_{t-1} + k_t^T v_t`` and ``o_t = q_t S_t *
scale``, ``lam = exp(-slope)`` a constant of the head. Two forms:

- :func:`lightning_prefill`: a whole prompt ``[H, T, hd]`` in chunks of
  ``C`` positions. Inside a chunk the masked quadratic product with the
  decay's powers (``(q k^T * D) v``, ``D[i, j] = lam ** (i - j)`` for ``j <=
  i``), between chunks the carried state (``lam ** (i + 1) * (q_i S)``); the
  state moves on by ``S <- lam ** m * S + sum_j lam ** (m - 1 - j) k_j^T
  v_j``, ``m`` the positions of the chunk that are REAL: ``n_valid`` counts
  the positions that may feed and decay the state, and a position behind it
  (the padding up to a prefill rung) does neither, so the state that comes
  back is the one after position ``n_valid - 1`` whatever ``T`` is. The
  outputs behind ``n_valid`` are finite and mean nothing.
- :func:`lightning_decode`: one position of every row, over the rows' states
  ``[rows, H, hd, hd]`` inside a stack of them (``[layers * rows, ...]``, the
  layer's first row given), updated in place under the caller's donation.
  Bound by the state's bytes: it is read once and written once.

Each has a Pallas kernel (``name=`` ``lightning_prefill`` and
``lightning_decode``) and the same arithmetic in ``jax.numpy``; off the TPU
the kernels run in interpret mode when asked for. Precision: the state is
float32 throughout. With bfloat16 inputs every product of two inputs is
exact on the MXU; where the float32 state (or a float32 decayed key) meets
the MXU it goes in as two bfloat16 halves (``hi + lo``, 16 bits of
mantissa), the in-chunk scores as one, as flash attention's probabilities
do. With float32 inputs (the tests' small configurations) every product is
float32 at the highest precision.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["lightning_decode", "lightning_prefill", "lightning_slopes"]

CHUNK = 128  # positions a chunk: one MXU tile, and D's powers stay in range
_HEADS_PER_STEP = 8  # of the decode kernel: 8 states of 64 KiB in, 8 out


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


def lightning_slopes(n_heads: int) -> np.ndarray:
    """The lightning-attention papers' head slopes: ``2 ** (-8 (h + 1) /
    H)``; a head's decay is ``exp(-slope)`` a position."""
    return (2.0 ** (-8.0 * (np.arange(n_heads) + 1) / n_heads)).astype(np.float32)


def _hi_lo(x):
    """A float32 array as two bfloat16 ones whose sum keeps 16 bits of it."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(a, b, dims, exact: bool):
    """``dot_general`` of 2-D operands accumulated in float32. ``exact``:
    float32 operands at the highest precision. Else a float32 operand goes
    in as its two bfloat16 halves and a bfloat16 one as it is."""
    dn = (dims, ((), ()))
    if exact:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dn,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    parts_a = _hi_lo(a) if a.dtype == jnp.float32 else (a,)
    parts_b = _hi_lo(b) if b.dtype == jnp.float32 else (b,)
    out = None
    for pa in parts_a:
        for pb in parts_b:
            term = jax.lax.dot_general(
                pa.astype(jnp.bfloat16), pb.astype(jnp.bfloat16), dn,
                preferred_element_type=jnp.float32)
            out = term if out is None else out + term
    return out


_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


# --------------------------------------------------------------------- #
# prefill: the chunked scan
# --------------------------------------------------------------------- #
def _chunk_terms(q, k, v, state, slope, m, scale, exact):
    """One chunk of one head. q, k, v: [C, hd]; state: [hd, hd] float32;
    slope: the head's slope as a float32 ROW ``[1, >= max(C, hd)]`` (the
    chip's compiler broadcasts a row down the sublanes and no ``[1, 1]``
    both ways), so every decay below is a whole ``[C, C]`` or ``[C, hd]``
    block; m: float32 scalar, the chunk's real positions (0..C). Returns (o
    [C, hd] float32, the state after the chunk). Shared by the kernel and
    the ``jax.numpy`` path, so the two cannot drift."""
    c, hd = q.shape
    wide, row = slope[:, :c], slope[:, :hd]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lag = jnp.maximum(rows - cols, 0).astype(jnp.float32)
    decay = jnp.where(rows >= cols, jnp.exp(-wide * lag), 0.0)
    s = _dot(q, k, _NT, exact) * decay  # [C, C]
    intra = _dot(s if exact else s.astype(v.dtype), v, _NN, exact)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, hd), 0).astype(jnp.float32)
    inter = jnp.exp(-row * (i + 1.0)) * _dot(q, state, _NN, exact)
    # a real position j of the chunk reaches the chunk's last real position
    # through m - 1 - j decays; one behind them feeds nothing
    kdec = jnp.where(i < m, jnp.exp(-row * jnp.maximum(m - 1.0 - i, 0.0)), 0.0)
    fed = _dot(k.astype(jnp.float32) * kdec, v, _TN, exact)
    return (intra + inter) * scale, jnp.exp(-row * m) * state + fed


def _prefill_kernel(nv_ref, q_ref, k_ref, v_ref, slope_ref, o_ref, s_ref, s_scr,
                    *, scale, chunk, exact):
    from jax.experimental import pallas as pl

    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    m = jnp.clip(nv_ref[0] - c * chunk, 0, chunk).astype(jnp.float32)
    slope = slope_ref[0:1, :]  # one row of the head's tile
    o, state = _chunk_terms(
        q_ref[:], k_ref[:], v_ref[:], s_scr[:], slope, m, scale, exact)
    o_ref[:] = o.astype(o_ref.dtype)
    s_scr[:] = state

    @pl.when(c == pl.num_programs(1) - 1)
    def _done():
        s_ref[:] = s_scr[:]


def _prefill_pallas(q, k, v, slopes, n_valid, scale, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, t, hd = q.shape
    if max(chunk, hd) > 128:
        raise ValueError(f"chunk={chunk}, head_dim={hd}: the kernel takes up to 128 of each")
    exact = q.dtype == jnp.float32
    seq = pl.BlockSpec((None, chunk, hd), lambda i, c, *_: (i, c, 0))
    # a head's slope over one float32 tile, so that it is a vector in the kernel
    tiled = jnp.broadcast_to(
        jnp.asarray(slopes, jnp.float32)[:, None, None], (h, 8, 128))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, t // chunk),
        in_specs=[seq, seq, seq,
                  pl.BlockSpec((None, 8, 128), lambda i, c, *_: (i, 0, 0))],
        out_specs=[seq, pl.BlockSpec((None, hd, hd), lambda i, c, *_: (i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, chunk=chunk, exact=exact),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((h, t, hd), q.dtype),
                   jax.ShapeDtypeStruct((h, hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lightning_prefill",
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), q, k, v, tiled)


def _prefill_lax(q, k, v, slopes, n_valid, scale, chunk):
    h, t, hd = q.shape
    exact = q.dtype == jnp.float32
    n = t // chunk
    cut = lambda a: a.reshape(h, n, chunk, hd).swapaxes(0, 1)  # [n, H, C, hd]
    slope = jnp.broadcast_to(
        jnp.asarray(slopes, jnp.float32)[:, None, None], (h, 1, max(chunk, hd)))
    one = jax.vmap(functools.partial(_chunk_terms, scale=scale, exact=exact),
                   in_axes=(0, 0, 0, 0, 0, None))

    def step(state, chunk_in):
        qc, kc, vc, c = chunk_in
        m = jnp.clip(n_valid - c * chunk, 0, chunk).astype(jnp.float32)
        o, state = one(qc, kc, vc, state, slope, m)
        return state, o.astype(q.dtype)

    state, o = jax.lax.scan(
        step, jnp.zeros((h, hd, hd), jnp.float32),
        (cut(q), cut(k), cut(v), jnp.arange(n, dtype=jnp.int32)))
    return o.swapaxes(0, 1).reshape(h, t, hd), state


def lightning_prefill(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, slopes, n_valid=None,
    *, scale: Optional[float] = None, chunk: int = CHUNK,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q, k, v: [H, T, hd] of one sequence, positions 0..T-1 (rope applied by
    the caller); slopes: [H]; ``n_valid``: int32 scalar, how many leading
    positions feed and decay the state (None: all T). Returns (o [H, T, hd]
    in q's type, the state after position ``n_valid - 1``, [H, hd, hd]
    float32). ``kernel`` None: the Pallas kernel where it is native. T that
    is no multiple of ``chunk`` is padded with zeros, which feed nothing."""
    h, t, hd = q.shape
    scale = float(hd) ** -0.5 if scale is None else float(scale)
    n_valid = jnp.asarray(t if n_valid is None else n_valid, jnp.int32)
    chunk = min(chunk, -(-t // 8) * 8)
    pad = -t % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (q, k, v))
        n_valid = jnp.minimum(n_valid, t)
    if interpret is None:
        interpret = _interpret_default()
    if kernel is None:
        kernel = not interpret
    if kernel:
        o, state = _prefill_pallas(q, k, v, slopes, n_valid, scale, chunk, interpret)
    else:
        o, state = _prefill_lax(q, k, v, slopes, n_valid, scale, chunk)
    return (o[:, :t] if pad else o), state


# --------------------------------------------------------------------- #
# decode: one position, the state in place
# --------------------------------------------------------------------- #
def _decode_kernel(lam_ref, q_ref, k_ref, v_ref, s_in, o_ref, s_out,
                   *, scale, heads, exact):
    from jax.experimental import pallas as pl

    first = pl.program_id(1) * heads
    for i in range(heads):
        # rows 1.. of k are zero, so the product over them is k^T v
        fed = _dot(k_ref[i], v_ref[i], _TN, exact)
        state = lam_ref[first + i] * s_in[i] + fed
        s_out[i] = state
        o_ref[i] = _dot(q_ref[i], state, _NN, exact) * scale


def _decode_pallas(q, k, v, states, first_row, lam, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    exact = q.dtype == jnp.float32
    heads = max(d for d in range(1, min(_HEADS_PER_STEP, h) + 1) if h % d == 0)
    # one row of q, k, v a head, padded with zeros to a sublane tile of the
    # inputs' type (8 rows of float32, 16 of bfloat16)
    rows = 32 // jnp.dtype(q.dtype).itemsize
    tile = lambda a: jnp.pad(
        a[:, :, None, :], ((0, 0), (0, 0), (0, rows - 1), (0, 0)))
    row = pl.BlockSpec((None, heads, rows, hd), lambda r, j, *_: (r, j, 0, 0))
    state = pl.BlockSpec(
        (None, heads, hd, hd), lambda r, j, *_: (first_row + r, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // heads),
        in_specs=[row, row, row, state],
        out_specs=[row, state],
    )
    o, states = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, heads=heads, exact=exact),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, rows, hd), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 4 (behind the prefetched scalars): the stack of states,
        # written where it is read
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="lightning_decode",
    )(jnp.asarray(lam, jnp.float32), tile(q), tile(k), tile(v), states)
    return o[:, :, 0], states


def _decode_lax(q, k, v, states, first_row, lam, scale):
    b = q.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    rows = slice(first_row, first_row + b)
    new = (jnp.asarray(lam, jnp.float32)[None, :, None, None] * states[rows]
           + f32(k)[..., :, None] * f32(v)[..., None, :])
    o = jnp.einsum("bhd,bhde->bhe", f32(q), new,
                   precision=jax.lax.Precision.HIGHEST) * scale
    return o, states.at[rows].set(new)


def lightning_decode(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, states: jnp.ndarray,
    slopes, first_row: int = 0, *, scale: Optional[float] = None,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position of every row. q, k, v: [B, H, hd]; ``states``: [R, H, hd,
    hd] float32, rows ``[first_row, first_row + B)`` of which are these rows'
    (a stack of several layers' states, this layer's first row a Python
    int). Returns (o [B, H, hd] float32, ``states`` with those rows moved on
    one position: the same buffer where the caller donated it)."""
    hd = q.shape[-1]
    scale = float(hd) ** -0.5 if scale is None else float(scale)
    lam = np.exp(-np.asarray(slopes, np.float64)).astype(np.float32)
    if interpret is None:
        interpret = _interpret_default()
    if kernel is None:
        kernel = not interpret
    if kernel:
        return _decode_pallas(q, k, v, states, int(first_row), lam, scale, interpret)
    return _decode_lax(q, k, v, states, int(first_row), lam, scale)
