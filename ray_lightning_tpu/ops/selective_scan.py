"""The selective scan of a Mamba-1 state-space layer: the causal depthwise
convolution in front of it, the scan over a prompt, and the one-position
update of every slot's carried state.

A channel ``c`` of a layer keeps a state of ``N`` float32 numbers that does
not grow with the sequence. With ``x_t`` the channel's input after the
convolution and its ``silu``, ``dt_t > 0`` its step, ``B_t`` and ``C_t`` the
position's ``N`` input and output coefficients (shared by the channels) and
``A < 0`` the channel's ``N`` decay rates::

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) * B_t        (N numbers)
    y_t = sum_n S_t[n] * C_t[n] + D * x_t

Everything here keeps the CHANNELS ON THE LAST AXIS: a state is ``[N, C]``, a
convolution tail ``[K - 1, C]`` (flat in the pool: ``[(K - 1) * C]``), ``A`` is
``[N, C]``. The chip lays an array
out in tiles of 128 of its last axis, so ``[C, N]`` with ``N = 16`` would be
an eighth full, and a Pallas operand is taken in the order it is declared.

- :func:`causal_conv`, :func:`conv_step`: ``x_t = silu(b + sum_j w[j] *
  in_{t - (K - 1) + j})`` over a prompt, and for one position of every slot
  from the slot's TAIL, its last ``K - 1`` inputs, which it shifts. Plain
  ``jax.numpy``: elementwise work XLA fuses, on a leaf it updates in place.
  :func:`causal_conv` also runs without the bias and without the ``silu``
  (the gated short convolution of ``models/lfm2.py``).
- :func:`mamba_scan` (``name="mamba_scan"``): the scan over one prompt. A grid
  over channel tiles and chunks of positions; a tile's state stays in VMEM
  from chunk to chunk and only ``y`` and the last state leave: no ``[L, C,
  N]`` array exists. ``n_valid`` (scalar prefetch) counts the positions that
  are real: a position at or behind it neither decays nor feeds the state
  (its ``dt`` is taken as 0, which is exactly that), whole groups of 8
  positions behind it are not computed and their ``y`` is 0.
- :func:`mamba_decode` (``name="mamba_decode"``): one position of every row,
  over the rows' states inside the stack of all layers' (``[layers, rows, N,
  C]``, the layer a traced scalar), aliased in to out: under the caller's
  donation the pool is updated in place. Bound by the state's bytes, read
  once and written once.

Each kernel has the same arithmetic in ``jax.numpy`` (``kernel=False``; what
runs off the TPU unless the interpreter is asked for). All of it is float32:
``exp``, three multiplies and an add a state element, an ``N``-wide sum a
channel. That is the vector unit's work; no matrix product is here.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "conv_step", "mamba_decode", "mamba_scan"]

LANES = 128
GROUP = 8  # positions (scan) or rows (decode) a step of the inner loop: one sublane tile
CHUNK = 128  # positions a grid step of the scan
SCAN_TILE = 1024  # channels a grid step of the scan: 8 lane tiles of state in registers
DECODE_TILE = 1280  # channels a grid step of the decode update


def _interpret_default() -> bool:
    return jax.devices()[0].platform != "tpu"


def _tile(channels: int, most: int) -> int:
    """The most channels up to ``most`` that are whole lane tiles and divide
    ``channels``."""
    return max(t for t in range(LANES, max(most, LANES) + 1, LANES) if channels % t == 0)


def _resolve(kernel: Optional[bool], interpret: Optional[bool]) -> Tuple[bool, bool]:
    """(kernel, interpret) with None resolved: the interpreter off the TPU,
    and the kernel where it is native."""
    if interpret is None:
        interpret = _interpret_default()
    return (not interpret if kernel is None else bool(kernel)), interpret


def _f32(*arrays):
    return tuple(t.astype(jnp.float32) for t in arrays)


def _lanes(coef):
    """[..., N] -> [..., N, LANES]: each coefficient across a lane tile, so
    that the kernels multiply it with any lane tile of a state as it is."""
    return jnp.broadcast_to(coef.astype(jnp.float32)[..., None], coef.shape + (LANES,))


# --------------------------------------------------------------------- #
# the convolution in front of the scan
# --------------------------------------------------------------------- #
def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray], n_valid=None,
                activation: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [L, C] of one sequence; w: [K, C] (``w[K - 1]`` meets the
    position's own input); b: [C], or None for a convolution published
    without a bias. Returns (``silu(conv)`` [L, C] float32, or ``conv``
    itself with ``activation=False``: a gated short convolution has neither
    bias nor activation; the tail [K - 1, C] in x's type: the inputs of
    positions ``n_valid - K + 1 .. n_valid - 1`` (None: the last), zeros
    before the sequence's start)."""
    n, k = x.shape[0], w.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    acc = 0.0 if b is None else b.astype(jnp.float32)[None, :]
    for j in range(k):
        acc = acc + w[j].astype(jnp.float32)[None, :] * padded[j: j + n].astype(jnp.float32)
    n_valid = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
    tail = jax.lax.dynamic_slice_in_dim(padded, jnp.clip(n_valid, 0, n), k - 1, axis=0)
    return (jax.nn.silu(acc) if activation else acc), tail


def conv_step(x: jnp.ndarray, tails: jnp.ndarray, layer, w: jnp.ndarray, b: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position of every row. x: [B, C]; ``tails``: [layers, B, (K - 1) *
    C] (row b is slot b's: its last K - 1 inputs one after the other, the
    oldest first), ``layer`` a traced index into it. Returns (``silu(conv)``
    [B, C] float32, ``tails`` with that layer's rows shifted by one input:
    where the caller donated it, the same buffer). A tail is kept FLAT: as
    ``[K - 1, C]`` the compiler lays the stack out one way where a program
    takes it and another inside a layer loop, and copies it whole between
    the two."""
    k, c = w.shape
    f32 = lambda t: t.astype(jnp.float32)
    tail = jax.lax.dynamic_index_in_dim(tails, layer, axis=0, keepdims=False)
    acc = f32(b)[None, :] + f32(w[k - 1])[None, :] * f32(x)
    for j in range(k - 1):
        acc = acc + f32(w[j])[None, :] * f32(tail[:, j * c: (j + 1) * c])
    shifted = jnp.concatenate([tail[:, c:], x.astype(tails.dtype)], axis=1)
    tails = jax.lax.dynamic_update_index_in_dim(tails, shifted, layer, axis=0)
    return jax.nn.silu(acc), tails


# --------------------------------------------------------------------- #
# the recurrence on one lane tile: what both kernels run
# --------------------------------------------------------------------- #
def _advance(state, dt, x, b, c, a):
    """One position of one lane tile. state, a, b, c: [N, LANES]; dt, x: [1,
    LANES] (a row, broadcast down the state's rows). Returns (the state
    after it, ``sum_n state * c`` [1, LANES])."""
    state = jnp.exp(dt * a) * state + (dt * x) * b
    return state, jnp.sum(state * c, axis=0, keepdims=True)


def _place(rows, i, row, into):
    """``into`` [GROUP, LANES] with its row ``i`` set to ``row`` [1, LANES]."""
    return jnp.where(rows == i, row, into)


# --------------------------------------------------------------------- #
# prefill: the scan over a prompt
# --------------------------------------------------------------------- #
def _scan_kernel(nv_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, s_ref, s_scr,
                 *, chunk, group):
    from jax.experimental import pallas as pl

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    live = jnp.clip(nv_ref[0] - k * chunk, 0, chunk)  # the chunk's real positions
    groups = (live + group - 1) // group

    @pl.when(groups < chunk // group)
    def _blank():
        y_ref[...] = jnp.zeros_like(y_ref)

    tiles = x_ref.shape[1] // LANES
    cut = lambda j: slice(j * LANES, (j + 1) * LANES)
    rows = jax.lax.broadcasted_iota(jnp.int32, (group, LANES), 0)

    def step(g, states):
        t0 = pl.multiple_of(g * group, group)
        at = pl.ds(t0, group)
        b8, c8 = b_ref[at], c_ref[at]  # [group, N, LANES]
        states = list(states)
        for j in range(tiles):
            x8 = x_ref[at, cut(j)]
            # a position behind n_valid: dt 0 is decay 1 and nothing fed
            dt8 = jnp.where(rows + t0 < live, dt_ref[at, cut(j)], 0.0)
            a = a_ref[:, cut(j)]
            y8 = jnp.zeros((group, LANES), jnp.float32)
            for i in range(group):
                states[j], y = _advance(
                    states[j], dt8[i: i + 1], x8[i: i + 1], b8[i], c8[i], a)
                y8 = _place(rows, i, y, y8)
            y_ref[at, cut(j)] = y8 + d_ref[:, cut(j)] * x8
        return tuple(states)

    states = jax.lax.fori_loop(
        0, groups, step, tuple(s_scr[:, cut(j)] for j in range(tiles)))
    for j in range(tiles):
        s_scr[:, cut(j)] = states[j]

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        s_ref[...] = s_scr[...]


def _scan_pallas(x, dt, b, c, a, d, n_valid, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pos, channels = x.shape
    n = a.shape[0]
    tile = _tile(channels, SCAN_TILE)
    group = min(GROUP, chunk)
    seq = pl.BlockSpec((chunk, tile), lambda i, k, *_: (k, i))
    coef = pl.BlockSpec((chunk, n, LANES), lambda i, k, *_: (k, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(channels // tile, n_pos // chunk),
        in_specs=[seq, seq, coef, coef,
                  pl.BlockSpec((n, tile), lambda i, k, *_: (0, i)),
                  pl.BlockSpec((1, tile), lambda i, k, *_: (0, i))],
        out_specs=[seq, pl.BlockSpec((n, tile), lambda i, k, *_: (0, i))],
        scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
    )
    # the barrier keeps the call an instruction of its own: handed straight
    # to a layer loop's stacked outputs, the compiler wraps it and their
    # dynamic-update-slice in one fusion, and a device trace then shows a
    # fusion where the readers look for the kernel by its name
    return jax.lax.optimization_barrier(pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_pos, channels), jnp.float32),
                   jax.ShapeDtypeStruct((n, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), x, dt, _lanes(b), _lanes(c), a,
      d.reshape(1, channels)))


def _scan_lax(x, dt, b, c, a, d, n_valid):
    real = jnp.arange(x.shape[0]) < n_valid
    dt = jnp.where(real[:, None], dt, 0.0)

    def step(state, at):
        xt, dtt, bt, ct = at
        state = jnp.exp(dtt[None, :] * a) * state + (dtt * xt)[None, :] * bt[:, None]
        return state, jnp.sum(state * ct[:, None], axis=0) + d * xt

    state, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32), (x, dt, b, c))
    return y, state


def mamba_scan(
    x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
    a: jnp.ndarray, d: jnp.ndarray, n_valid=None, *, chunk: int = CHUNK,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The selective scan over one sequence from a zero state. x: [L, C] (the
    convolution's output), dt: [L, C] (> 0), b, c: [L, N], a: [N, C] (< 0),
    d: [C]; ``n_valid``: int32 scalar, how many leading positions are real
    (None: all L). Returns (y [L, C] float32, the state after position
    ``n_valid - 1`` [N, C] float32). Behind ``n_valid`` y is finite and means
    nothing. ``kernel`` None: the Pallas kernel where it is native; it takes
    L in whole groups of 8, C in whole lane tiles and N in whole sublane
    tiles, and other shapes run the ``jax.numpy`` form."""
    n_pos, channels = x.shape
    n_valid = jnp.asarray(n_pos if n_valid is None else n_valid, jnp.int32)
    x, dt, b, c, a, d = _f32(x, dt, b, c, a, d)
    kernel, interpret = _resolve(kernel, interpret)
    chunk = min(chunk, n_pos)
    if kernel and not (n_pos % chunk or chunk % GROUP or channels % LANES or a.shape[0] % 8):
        return tuple(_scan_pallas(x, dt, b, c, a, d, n_valid, chunk, interpret))
    return _scan_lax(x, dt, b, c, a, d, n_valid)


# --------------------------------------------------------------------- #
# decode: one position of every row, the states in place
# --------------------------------------------------------------------- #
def _decode_kernel(layer_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_in,
                   y_ref, s_out, *, rows_per_step):
    del layer_ref  # the index maps' operand
    tiles = x_ref.shape[1] // LANES
    cut = lambda j: slice(j * LANES, (j + 1) * LANES)
    rows = jax.lax.broadcasted_iota(jnp.int32, (rows_per_step, LANES), 0)
    b8, c8 = b_ref[...], c_ref[...]
    for j in range(tiles):
        x8, dt8, a = x_ref[:, cut(j)], dt_ref[:, cut(j)], a_ref[:, cut(j)]
        y8 = jnp.zeros((rows_per_step, LANES), jnp.float32)
        for i in range(rows_per_step):
            state, y = _advance(
                s_in[i, :, cut(j)], dt8[i: i + 1], x8[i: i + 1], b8[i], c8[i], a)
            s_out[i, :, cut(j)] = state
            y8 = _place(rows, i, y, y8)
        y_ref[:, cut(j)] = y8 + d_ref[:, cut(j)] * x8


def _decode_pallas(x, dt, b, c, a, d, states, layer, rows_per_step, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_rows, channels = x.shape
    n = a.shape[0]
    tile = _tile(channels, DECODE_TILE)
    row = pl.BlockSpec((rows_per_step, tile), lambda r, j, *_: (r, j))
    coef = pl.BlockSpec((rows_per_step, n, LANES), lambda r, j, *_: (r, 0, 0))
    state = pl.BlockSpec((None, rows_per_step, n, tile),
                         lambda r, j, layer_ref: (layer_ref[0], r, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows // rows_per_step, channels // tile),
        in_specs=[row, row, coef, coef,
                  pl.BlockSpec((n, tile), lambda r, j, *_: (0, j)),
                  pl.BlockSpec((1, tile), lambda r, j, *_: (0, j)),
                  state],
        out_specs=[row, state],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows_per_step=rows_per_step),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, channels), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 7 (behind the prefetched layer): the stack of states,
        # written where it is read
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mamba_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, dt, _lanes(b), _lanes(c), a,
      d.reshape(1, channels), states)


def _decode_lax(x, dt, b, c, a, d, states, layer):
    old = jax.lax.dynamic_index_in_dim(states, layer, axis=0, keepdims=False)  # [B, N, C]
    new = (jnp.exp(dt[:, None, :] * a[None]) * old
           + (dt * x)[:, None, :] * b[:, :, None])
    y = jnp.sum(new * c[:, :, None], axis=1) + d[None, :] * x
    return y, jax.lax.dynamic_update_index_in_dim(states, new, layer, axis=0)


def mamba_decode(
    x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
    a: jnp.ndarray, d: jnp.ndarray, states: jnp.ndarray, layer, *,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One position of every row. x, dt: [B, C]; b, c: [B, N]; a: [N, C];
    d: [C]; ``states``: [layers, B, N, C] float32, row b of which is slot
    b's, and ``layer`` the (traced) layer these rows are of. Returns (y [B,
    C] float32, ``states`` with that layer's rows moved on one position: the
    same buffer where the caller donated it). A row that holds no request
    moves its own state on and no other's."""
    n_rows, channels = x.shape
    x, dt, b, c, a, d = _f32(x, dt, b, c, a, d)
    layer = jnp.asarray(layer, jnp.int32)
    kernel, interpret = _resolve(kernel, interpret)
    rows_per_step = GROUP if n_rows % GROUP == 0 else n_rows
    if kernel and rows_per_step <= GROUP and not (channels % LANES or a.shape[0] % 8):
        return tuple(_decode_pallas(x, dt, b, c, a, d, states, layer, rows_per_step, interpret))
    return _decode_lax(x, dt, b, c, a, d, states, layer)
