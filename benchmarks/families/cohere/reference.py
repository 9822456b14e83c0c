"""The ``cohere2_moe`` family's plain reference: a decoder of parallel blocks
with window and full attention layers mixed and sigmoid-routed experts, in
``jax.numpy`` float32.

Written from the published description of the block, which the
configurations of this family state at their own widths. One LayerNorm a
layer (``(x - mean) / sqrt(var + eps) * w``, no bias) feeds both branches.
Attention: ``q = h Wq``, ``k = h Wk``, ``v = h Wv``, no bias, no q/k norm;
on a ``sliding_attention`` layer rope turns the ADJACENT pairs ``(2i, 2i +
1)`` of the whole head of ``q`` and ``k``, each pair where it stands
(``rope_gptj``, ``rotary_pct`` 1), and a key at ``j`` is seen from ``i`` only
if ``0 <= i - j < sliding_window``; on a ``full_attention`` layer nothing is
turned and every earlier key is seen. Scores times ``head_dim ** -0.5``;
query head ``n`` reads key/value head ``n // (heads / kv_heads)``. Experts:
``s = sigmoid(h Wr)`` over every expert the router scores, the ``top_k``
largest chosen (ties to the lower number), their scores over their sum
(``norm_topk_prob``) the weights, each expert a SwiGLU; the shared experts'
outputs are averaged and added. ``y = x + attn + ffn``. A final LayerNorm,
then ``logit_scale * h E^T`` with the embedding (tied).

A SHARE of the experts: a configuration that holds ``num_experts`` of
``published_num_experts`` is given the same share here. The router scores
and chooses among all of them; only the held experts' terms of the weighted
sum are added. What the others would have added is left out here as in the
program, and that partial result goes on to the next layer. Logits are over
the rows of the vocabulary the configuration holds.

It imports nothing of ``ray_lightning_tpu`` and takes no array the program
has made: weights come from the family's ``weights.py`` by seed, a layer at
a time, in the configuration's own type and are cast to float32 here. Every
matmul runs under ``jax.default_matmul_precision("highest")``. ``quant`` is
the control's hook (``benchmarks/reference.py``).

Departures from the description, all for memory and none for the
arithmetic: one sequence at a time, a scan over the layers (both kinds in
one body: a full_attention layer selects the unturned q and k and drops the
window from the mask); attention one key/value head's group of query heads
after another, over blocks of query rows against every key, masked; every held expert is evaluated on every token,
one expert after another, and weighted by the routing (zero where the token
was not routed to it); an expert's matrices are cast to float32 one expert
at a time. No training step: the program has none for this family.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import Quant, mm

from . import weights
from .weights import dims

SCORE_BYTES = 2 ** 28  # one block of float32 scores, the heads of one group


def layernorm(x, w, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w


def rope(x, theta: float):
    """x: [T, H, hd], positions 0..T-1 on the first axis; turns the adjacent
    pairs (x[2i], x[2i+1]) and leaves each where it stands."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :])[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (hd // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, full, window: int, quant: Quant):
    """Causal attention of the query heads that read ONE key/value head, one
    sequence. q: [T, G, hd]; k, v: [T, hd] -> [T, G, hd]. ``full`` (a
    boolean, may be traced): every earlier key; else a key at j is seen from
    i only if i - j < window."""
    t, g, hd = q.shape
    most = max(1, SCORE_BYTES // (4 * g * t))
    qb = max(d for d in range(1, min(most, t) + 1) if t % d == 0)
    starts = jnp.arange(t // qb) * qb
    kq = quant(k) if quant is not None else k
    vq = quant(v) if quant is not None else v
    scale = hd ** -0.5

    def block(args):
        qblk, start = args  # [qb, G, hd]
        if quant is not None:
            qblk = quant(qblk)
        s = jnp.einsum("qgd,kd->gqk", qblk, kq) * scale
        rows = start + jnp.arange(qb)[:, None]
        cols = jnp.arange(t)[None, :]
        seen = (cols <= rows) & (full | (rows - cols < window))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        if quant is not None:
            p = quant(p)
        return jnp.einsum("gqk,kd->qgd", p, vq)

    out = jax.lax.map(block, (q.reshape(t // qb, qb, g, hd), starts))
    return out.reshape(t, g, hd)


def attend(h, lp, m: Dict[str, Any], full, quant: Quant):
    """h: [T, D], the normed input of one sequence; ``full``: whether this
    is a full_attention layer (a boolean, may be traced). One key/value head
    after another: query head n reads key/value head n // (heads /
    kv_heads), so the heads of a group are the adjacent columns of ``wq``
    and rows of ``wo``, and the groups' outputs add up."""
    t, d = h.shape
    hkv, hd = m["kv_heads"], m["hd"]
    g = m["heads"] // hkv
    k = mm(h, lp["wk"], quant).reshape(t, hkv, hd)
    v = mm(h, lp["wv"], quant).reshape(t, hkv, hd)
    # a sliding_attention layer turns q and k; a full_attention layer has no positions
    k = jnp.where(full, k, rope(k, m["theta"]))

    def one_group(acc, group):
        wq, wo, kn, vn = group  # [D, G * hd], [G * hd, D], [T, hd], [T, hd]
        q = mm(h, wq, quant).reshape(t, g, hd)
        q = jnp.where(full, q, rope(q, m["theta"]))
        att = attention(q, kn, vn, full, m["window"], quant)
        return acc + mm(att.reshape(t, g * hd), wo, quant), None

    out, _ = jax.lax.scan(
        one_group, jnp.zeros_like(h),
        (lp["wq"].reshape(d, hkv, g * hd).swapaxes(0, 1), lp["wo"].reshape(hkv, g * hd, d),
         k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return out


def swiglu(x, w_gate, w_up, w_down, quant: Quant):
    return mm(jax.nn.silu(mm(x, w_gate, quant)) * mm(x, w_up, quant), w_down, quant)


def route(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [N, D] -> [N, E]: a token's weight on every expert the router
    scores, zero on those it is not routed to."""
    s = jax.nn.sigmoid(mm(x, lp["router"], quant))
    _, idx = jax.lax.top_k(s, m["top_k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if m["renorm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)  # [N, K, E]
    return jnp.sum(chosen * picked[..., None], axis=1)


def routed(x, lp, experts, m: Dict[str, Any], quant: Quant):
    """The held experts' part of the routed sum. x: [N, D]; ``experts``:
    {leaf: [held, ...]} in the configuration's own type, each cast as its
    turn comes."""
    w = route(x, lp, m, quant)[:, m["first"]: m["first"] + m["held"]]

    def one(acc, expert):
        wg, wu, wd, col = expert
        f32 = lambda a: a.astype(jnp.float32)
        return acc + col[:, None] * swiglu(x, f32(wg), f32(wu), f32(wd), quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (experts["w_gate"], experts["w_up"], experts["w_down"], w.T))
    return out


def shared(x, lp, m: Dict[str, Any], quant: Quant):
    """The mean of the shared experts, each a SwiGLU of the expert's width:
    expert j is columns [j F, (j + 1) F) of the wide gate and up and those
    rows of the wide down."""
    f, n = m["f"], m["shared"]
    total = jnp.zeros_like(x)
    for j in range(n):
        cut = slice(j * f, (j + 1) * f)
        total = total + swiglu(x, lp["shared/w_gate"][:, cut], lp["shared/w_up"][:, cut],
                               lp["shared/w_down"][cut, :], quant)
    return total / n


def layer(x, lp, experts, sizes: Dict[str, Any], full, quant: Quant = None):
    """One parallel block on one sequence. x: [T, D] float32; lp: that
    layer's leaves in float32; experts: its held experts' stacks; ``full``:
    a full_attention layer (else a sliding_attention one)."""
    m = dims(sizes)
    h = layernorm(x, lp["norm"], m["eps"])
    return (x + attend(h, lp, m, full, quant)
            + routed(h, lp, experts, m, quant) + shared(h, lp, m, quant))


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

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = _f32(weights.top_leaves(sizes, keys))

            def one_layer(x, place):
                # a scan, so that one layer's weights are made and dropped
                # before the next layer's are
                full = place % m["period"] == m["period"] - 1
                lp = _f32(weights.layer_leaves(sizes, keys, place))
                experts = weights.held_experts(sizes, keys, place)
                return layer(x, lp, experts, sizes, full, quant), None

            def one_row(row):
                x, _ = jax.lax.scan(
                    one_layer, top["embed"][row], jnp.arange(m["layers"], dtype=jnp.uint32))
                h = layernorm(x, top["final_norm"], m["eps"])
                return m["logit_scale"] * mm(h, top["embed"].T, quant)

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
            "the cohere2_moe family is served, not trained: no training reference")
