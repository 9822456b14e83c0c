"""The ``jamba`` family's plain reference: a decoder that mixes Mamba-1
selective-state-space layers with attention layers, in ``jax.numpy`` float32.

Written from the published ``jamba`` modelling code's equations, which the
configurations of this family state at their own widths (what the published
``config.json`` has no key for is in the configuration file's ``assumed``).
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``. ``h0 = E[token]``; a layer is
``h <- h + mixer(RMSNorm_in(h))`` then ``h <- h + W_down(silu(W_gate u) *
W_up u)``, ``u = RMSNorm_ff(h)``; logits ``= RMSNorm_f(h_L) E^T``, the head
tied. Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``, else Mamba. No positional encoding anywhere.

An attention layer: ``q = W_q u`` (``heads`` of ``hd``), ``k, v = W_k u, W_v
u`` (``kv_heads``); query head ``n`` reads key/value head ``n // (heads /
kv_heads)``; ``softmax(q k^T / sqrt(hd))`` over ``t <= i``, then ``W_o``.

A Mamba layer, the state in the published orientation ``S [C, N]``: ``[x, z] =
W_in u``; ``x_t <- silu(b_c + sum_{j<K} w_c[:, j] x_{t-(K-1)+j})``
(depthwise, zeros before the start); ``[d, B, C] = W_x x_t``; ``d, B, C <-
RMSNorm(d), RMSNorm(B), RMSNorm(C)`` (a weight each); ``dt = softplus(W_dt d
+ b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt (x) 1 * A) * S_{t-1} + (dt *
x_t) (x) B``; ``y_t = S_t C + D * x_t``; out ``= W_out (y_t * silu(z_t))``.

It imports nothing of ``ray_lightning_tpu`` and takes no array the program
has made: weights come from the family's ``weights.py`` by seed, a layer at
a time, in the configuration's own type and are cast to float32 here (the
leaves that keep their channels last are turned to the published
orientation). Every matmul runs under
``jax.default_matmul_precision("highest")``. No cache, no chunks, no kernels:
the Mamba layers are the recurrence itself, a ``lax.scan`` over positions.

``quant`` is the control's hook (``benchmarks/reference.py``): a function
applied to both operands of every matmul. A control may also carry a
``state`` attribute, a function applied to the scan's state after every
position: :class:`StateOnly` rounds nothing but that, which is the control
that a comparison of this family has to refuse beside the float8 one (the
state is float32 by the configuration; bfloat16 is the nearest precision
below).

Departures from the description, all for memory and none for the
arithmetic: one sequence at a time; attention one query head after another;
the MLP in blocks of rows. No training step: the program has none for this
family.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import Quant, mm

from . import weights
from .weights import ATTENTION, MAMBA, dims

MLP_ROWS = 4096


class StateOnly:
    """The control that leaves every matmul as it is and carries the scan's
    state in bfloat16."""

    def __call__(self, x):
        return x

    @staticmethod
    def state(s):
        """Round to bfloat16 and back, to nearest even, on the bits: the
        chip's compiler folds a convert to bfloat16 and back into nothing
        (as it does float8's, ``benchmarks/reference.py::fp8``)."""
        bits = jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.uint32)
        odd = (bits >> 16) & jnp.uint32(1)
        bits = (bits + jnp.uint32(0x7FFF) + odd) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rows(t: int, most: int) -> int:
    """The most rows up to ``most`` that divide ``t``."""
    return max(d for d in range(1, max(1, min(most, t)) + 1) if t % d == 0)


def _q(x, quant: Quant):
    return x if quant is None else quant(x)


def attention_mixer(u, lp, m: Dict[str, Any], quant: Quant):
    """u: [T, D], the normed input of one sequence."""
    t = u.shape[0]
    heads, hkv, hd = m["heads"], m["kv_heads"], m["hd"]
    q = mm(u, lp["wq"], quant).reshape(t, heads, hd)
    k = _q(mm(u, lp["wk"], quant).reshape(t, hkv, hd), quant)
    v = _q(mm(u, lp["wv"], quant).reshape(t, hkv, hd), quant)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def head(args):
        qh, n = args  # [T, hd], the query head's number
        kh, vh = k[:, n // (heads // hkv)], v[:, n // (heads // hkv)]
        s = jnp.matmul(_q(qh, quant), kh.T) * hd ** -0.5
        p = _q(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), quant)
        return jnp.matmul(p, vh)

    out = jax.lax.map(head, (q.swapaxes(0, 1), jnp.arange(heads)))  # [H, T, hd]
    return mm(out.swapaxes(0, 1).reshape(t, heads * hd), lp["wo"], quant)


def mamba_mixer(u, lp, m: Dict[str, Any], quant: Quant):
    """u: [T, D], the normed input of one sequence."""
    t = u.shape[0]
    ci, n, r, k, eps = m["ci"], m["n"], m["r"], m["k"], m["eps"]
    xz = mm(u, lp["w_in"], quant)
    x, z = xz[:, :ci], xz[:, ci:]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    w = lp["conv_w"]  # [K, C]: tap K - 1 meets the position's own input
    x = jax.nn.silu(lp["conv_b"][None, :] + sum(w[j][None, :] * padded[j: j + t] for j in range(k)))
    proj = mm(x, lp["w_x"], quant)
    d = rmsnorm(proj[:, :r], lp["dt_norm"], eps)
    b = rmsnorm(proj[:, r: r + n], lp["b_norm"], eps)
    c = rmsnorm(proj[:, r + n:], lp["c_norm"], eps)
    dt = jax.nn.softplus(mm(d, lp["w_dt"], quant) + lp["b_dt"][None, :])
    a = -jnp.exp(lp["a_log"].T)  # [C, N], the published orientation
    keep = getattr(quant, "state", None) or (lambda s: s)

    def step(state, at):
        xt, dtt, bt, ct = at
        state = keep(jnp.exp(dtt[:, None] * a) * state + (dtt * xt)[:, None] * bt[None, :])
        return state, state @ ct + lp["d"] * xt

    _, y = jax.lax.scan(step, jnp.zeros((ci, n), jnp.float32), (x, dt, b, c))
    return mm(y * jax.nn.silu(z), lp["w_out"], quant)


def mlp(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [T, D], the residual stream: normed here, in blocks of rows."""
    def rows(blk):
        u = rmsnorm(blk, lp["norm_ff"], m["eps"])
        return mm(jax.nn.silu(mm(u, lp["w_gate"], quant)) * mm(u, lp["w_up"], quant),
                  lp["w_down"], quant)

    t = x.shape[0]
    n = _rows(t, MLP_ROWS)
    return jax.lax.map(rows, x.reshape(t // n, n, -1)).reshape(x.shape)


def layer(x, lp, sizes: Dict[str, Any], kind: str, quant: Quant = None):
    """One layer on one sequence. x: [T, D] float32; lp: that layer's leaves
    in float32; ``kind``: its mixer."""
    m = dims(sizes)
    mixer = attention_mixer if kind == ATTENTION else mamba_mixer
    x = x + mixer(rmsnorm(x, lp["norm_in"], m["eps"]), lp, m, quant)
    return x + mlp(x, lp, m, quant)


def _f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


# ---------------------------------------------------------------------- #
# serving: teacher-forced logits
# ---------------------------------------------------------------------- #
def logits_fn(sizes: Dict[str, Any], seed: int, quant: Quant = None):
    """The jitted ``tokens [B, T] int32 -> logits [B, T, V] float32``, the
    weights made inside it from the seed one layer at a time, one sequence
    after another."""
    m = dims(sizes)
    assert set(m["kinds"]) <= {ATTENTION, MAMBA}

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = _f32(weights.top_leaves(sizes, keys))

            def one_row(row):
                x = top["embed"][row]
                for place, kind in enumerate(m["kinds"]):
                    # a barrier a layer, so that one layer's float32 weights
                    # are dropped before the next layer's are made
                    x = jax.lax.optimization_barrier(x)
                    lp = _f32(weights.layer_leaves(sizes, keys, place))
                    x = layer(x, lp, sizes, kind, quant)
                h = rmsnorm(x, top["final_norm"], m["eps"])
                return mm(h, top["embed"].T, quant)

            return jax.lax.map(one_row, tokens)

    keys = weights.seed_keys(sizes, seed)  # arguments, so every seed shares the program
    fn = jax.jit(run)
    return lambda tokens: fn(keys, tokens)


def teacher_forced_logits(sizes: Dict[str, Any], seed: int, tokens, quant: Quant = None):
    return logits_fn(sizes, seed, quant)(jnp.asarray(tokens, jnp.int32))


class TrainReference:
    """The program serves this family and does not train it, so there is no
    training step to hold a reference against."""

    def __init__(self, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any],
                 quant: Quant = None):
        raise NotImplementedError(
            "the jamba family is served, not trained: no training reference")
