"""The ``minicpm_sala`` family's plain reference: a decoder that mixes
block-sparse attention layers, which select the blocks they read, with
lightning (decayed linear) attention layers, in ``jax.numpy`` float32.

Written from the published description of the block, which the
configurations of this family state at their own widths (what the published
``config.json`` has no key for is in the configuration file's ``assumed``).
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``. ``x0 = scale_emb * E[token]``;
a layer is ``x <- x + r * mixer(RMSNorm(x))`` then ``x <- x + r *
W_down(silu(W_gate h') * W_up h')``, ``h' = RMSNorm(x)``, ``r = scale_depth /
sqrt(published depth)``; logits ``= W_head (RMSNorm(x_L) / (hidden /
dim_model_base))``, the head untied.

A ``minicpm4`` layer: ``q = RMSNorm_head(W_q h)``, ``k = RMSNorm_head(W_k h)``
(a weight a head dimension), ``v = W_v h``, NO position encoding; query head
``n`` reads key/value head ``n // (heads / kv_heads)``. Pooled keys ``Kp_j =
mean(k_t, stride j <= t < stride j + pool)``, complete once ``stride j + pool
- 1`` is written. The query at ``i`` of group ``g``: ``s_gj = sum_{h in g}
softmax_j(q_h . Kp_j / sqrt(hd))`` over the complete ``j`` with ``stride j +
pool - 1 <= i``; block ``b`` (positions ``block b .. block b + block - 1``)
scores the largest ``s_gj`` of the pooled keys that overlap it; the first
``init`` blocks, the blocks of the last ``window`` positions and the query's
own are always taken; the ``topk`` best (the forced among them, ties to the
lower number) are attended, causally: ``softmax(q_h . k_t / sqrt(hd))`` over
``t <= i`` in the chosen blocks. A query whose length ``i + 1`` is under
``dense_len`` attends every ``t <= i``. ``mixer = W_o (concat(heads) *
sigmoid(W_g h))``.

A ``lightning-attn`` layer: ``q, k = rope(RMSNorm_head(W h))`` (the halves
``(i, i + hd / 2)`` turned by ``pos * theta ** (-2 i / hd)``), ``v = W_v h``;
``S_t = lam_h S_{t-1} + k_t^T v_t`` (float32, ``lam_h = exp(-2 ** (-8 (h + 1)
/ H))``), ``o_t = q_t S_t / sqrt(hd)``; ``mixer = W_o (RMSNorm_head(o) *
sigmoid(W_g h))``.

It imports nothing of ``ray_lightning_tpu`` and takes no array the program
has made: weights come from the family's ``weights.py`` by seed, a layer at
a time, in the configuration's own type and are cast to float32 here. Every
matmul runs under ``jax.default_matmul_precision("highest")``. ``quant`` is
the control's hook (``benchmarks/reference.py``). No cache, no chunks, no
kernels: the lightning layers are the recurrence itself, a ``lax.scan`` over
positions; a sparse layer's pooled keys are recomputed from all keys and each
query's blocks chosen from scratch.

Departures from the description, all for memory and none for the
arithmetic: one sequence at a time; a sparse layer one key/value head's group
after another, its queries in blocks of rows against every key, masked; the
MLP in blocks of rows. No training step: the program has none for this family.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import Quant, mm

from . import weights
from .weights import LIGHTNING, SPARSE, dims

SCORE_BYTES = 2 ** 28  # one block of float32 scores, the heads of one group
MLP_ROWS = 4096


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: [T, H, hd], positions 0..T-1 on the first axis; column i of the
    first half turns with column i of the second."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :])[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _rows(t: int, most: int) -> int:
    """The most rows up to ``most`` that divide ``t``."""
    return max(d for d in range(1, max(1, min(most, t)) + 1) if t % d == 0)


def _q(x, quant: Quant):
    return x if quant is None else quant(x)


# ---------------------------------------------------------------------- #
# the sparse layer's indexer
# ---------------------------------------------------------------------- #
def pooled_keys(k, m: Dict[str, Any]):
    """k: [T, hd] of one key/value head -> [J, hd], J = T // stride + 1: the
    mean of positions ``[stride j, stride j + pool)``; one that reaches
    behind T is not complete and is masked by whoever reads it."""
    t = k.shape[0]
    j = jnp.arange(t // m["stride"] + 1)
    at = m["stride"] * j[:, None] + jnp.arange(m["pool"])[None, :]
    return jnp.mean(k[jnp.minimum(at, t - 1)], axis=1)


def chosen_blocks(q, kp, pos, m: Dict[str, Any], quant: Quant = None):
    """The blocks the queries of one key/value head's group attend. q: [Q,
    G, hd]; kp: [J, hd]; pos: [Q] -> [Q, topk] block numbers, best first
    (ties to the lower number). Meaningful where ``pos + 1 >= dense_len``."""
    hd, stride, pool, block = q.shape[-1], m["stride"], m["pool"], m["block"]
    s = jnp.einsum("qgd,jd->gqj", _q(q, quant), _q(kp, quant)) * hd ** -0.5
    j = jnp.arange(kp.shape[0])
    complete = (stride * j + pool - 1)[None, :] <= pos[:, None]  # [Q, J]
    p = jax.nn.softmax(jnp.where(complete, s, -1e30), axis=-1)
    s = jnp.where(complete, jnp.sum(p, axis=0), -jnp.inf)  # [Q, J]
    # block b is overlapped by the pooled keys that start in [block b - pool + 1, block b + block)
    n_blocks = -(-(kp.shape[0] * stride) // block)
    b = jnp.arange(n_blocks)
    first = -((pool - 1) // stride)  # the earliest, counted from the block's own first
    over = (block // stride) * b[:, None] + jnp.arange(first, block // stride)[None, :]
    inside = (over >= 0) & (over < kp.shape[0])
    score = jnp.max(jnp.where(inside[None], s[:, jnp.clip(over, 0, kp.shape[0] - 1)],
                              -jnp.inf), axis=-1)  # [Q, blocks]
    own = pos[:, None] // block
    forced = (b[None, :] < m["init"]) | (
        b[None, :] >= jnp.maximum(pos[:, None] - m["window"] + 1, 0) // block)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b[None, :] <= own, score, -jnp.inf)
    return jax.lax.top_k(score, m["topk"])[1]


def sparse_attention(q, k, v, m: Dict[str, Any], quant: Quant):
    """Attention of the query heads that read ONE key/value head, one
    sequence. q: [T, G, hd]; k, v: [T, hd] -> [T, G, hd]."""
    t, g, hd = q.shape
    block = m["block"]
    kp = pooled_keys(k, m)
    n_blocks = -(-(kp.shape[0] * m["stride"]) // block)
    qb = _rows(t, SCORE_BYTES // (4 * g * t))
    kq, vq = _q(k, quant), _q(v, quant)

    def rows(args):
        qblk, start = args  # [qb, G, hd]
        pos = start + jnp.arange(qb)
        picked = chosen_blocks(qblk, kp, pos, m, quant)  # [qb, topk]
        hit = jnp.any(picked[:, :, None] == jnp.arange(n_blocks)[None, None, :], axis=1)
        hit = hit | (pos + 1 < m["dense_len"])[:, None]
        cols = jnp.arange(t)
        seen = (cols[None, :] <= pos[:, None]) & jnp.take_along_axis(
            hit, jnp.broadcast_to(cols // block, (qb, t)), axis=1)
        s = jnp.einsum("qgd,kd->gqk", _q(qblk, quant), kq) * hd ** -0.5
        p = _q(jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1), quant)
        return jnp.einsum("gqk,kd->qgd", p, vq)

    out = jax.lax.map(rows, (q.reshape(t // qb, qb, g, hd), jnp.arange(t // qb) * qb))
    return out.reshape(t, g, hd)


def sparse_mixer(h, lp, m: Dict[str, Any], quant: Quant):
    """h: [T, D], the normed input of one sequence."""
    t = h.shape[0]
    hkv, hd = m["kv_heads"], m["hd"]
    g = m["heads"] // hkv
    q = rmsnorm(mm(h, lp["wq"], quant).reshape(t, hkv, g, hd), lp["q_norm"], m["eps"])
    k = rmsnorm(mm(h, lp["wk"], quant).reshape(t, hkv, hd), lp["k_norm"], m["eps"])
    v = mm(h, lp["wv"], quant).reshape(t, hkv, hd)
    heads = [sparse_attention(q[:, n], k[:, n], v[:, n], m, quant) for n in range(hkv)]
    out = jnp.stack(heads, axis=1).reshape(t, hkv * g * hd)
    return mm(out * jax.nn.sigmoid(mm(h, lp["wg"], quant)), lp["wo"], quant)


# ---------------------------------------------------------------------- #
# the lightning layer: the recurrence itself
# ---------------------------------------------------------------------- #
def slopes(n_heads: int):
    return 2.0 ** (-8.0 * (jnp.arange(n_heads, dtype=jnp.float32) + 1.0) / n_heads)


def lightning_mixer(h, lp, m: Dict[str, Any], quant: Quant):
    t = h.shape[0]
    n, hd = m["lheads"], m["lhd"]
    q = rope(rmsnorm(mm(h, lp["wq"], quant).reshape(t, n, hd), lp["q_norm"], m["eps"]), m["theta"])
    k = rope(rmsnorm(mm(h, lp["wk"], quant).reshape(t, n, hd), lp["k_norm"], m["eps"]), m["theta"])
    v = mm(h, lp["wv"], quant).reshape(t, n, hd)
    lam = jnp.exp(-slopes(n))[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv  # [H, hd]
        state = lam * state + _q(kt, quant)[:, :, None] * _q(vt, quant)[:, None, :]
        return state, jnp.einsum("hd,hde->he", _q(qt, quant), _q(state, quant)) * hd ** -0.5

    _, o = jax.lax.scan(step, jnp.zeros((n, hd, hd), jnp.float32), (q, k, v))
    o = rmsnorm(o, lp["o_norm"], m["eps"]).reshape(t, n * hd)
    return mm(o * jax.nn.sigmoid(mm(h, lp["wg"], quant)), lp["wo"], quant)


def mlp(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [T, D], the residual stream: normed here, in blocks of rows."""
    def rows(blk):
        h = rmsnorm(blk, lp["mlp_norm"], m["eps"])
        return mm(jax.nn.silu(mm(h, lp["w_gate"], quant)) * mm(h, lp["w_up"], quant),
                  lp["w_down"], quant)

    t = x.shape[0]
    n = _rows(t, MLP_ROWS)
    return jax.lax.map(rows, x.reshape(t // n, n, -1)).reshape(x.shape)


def layer(x, lp, sizes: Dict[str, Any], kind: str, quant: Quant = None):
    """One layer on one sequence. x: [T, D] float32; lp: that layer's leaves
    in float32; ``kind``: its mixer."""
    m = dims(sizes)
    r = m["scale_depth"] / m["depth"] ** 0.5
    mixer = sparse_mixer if kind == SPARSE else lightning_mixer
    x = x + r * mixer(rmsnorm(x, lp["attn_norm"], m["eps"]), lp, m, quant)
    return x + r * mlp(x, lp, m, quant)


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
    assert set(m["kinds"]) <= {SPARSE, LIGHTNING}

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = weights.top_leaves(sizes, keys)

            def one_row(row):
                x = m["scale_emb"] * top["embed"][row].astype(jnp.float32)
                for place, kind in enumerate(m["kinds"]):
                    # a barrier a layer, so that one layer's float32 weights
                    # are dropped before the next layer's are made
                    x = jax.lax.optimization_barrier(x)
                    lp = _f32(weights.layer_leaves(sizes, keys, place))
                    x = layer(x, lp, sizes, kind, quant)
                h = rmsnorm(x, top["final_norm"].astype(jnp.float32), m["eps"])
                return mm(h / (m["d"] / m["base"]), top["lm_head"].astype(jnp.float32), quant)

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
            "the minicpm_sala family is served, not trained: no training reference")
