"""The ``lfm2_moe`` family's plain reference: a decoder of gated short
convolutions and a few grouped-query attention layers over sigmoid-routed
experts, in ``jax.numpy`` float32.

Written from the published description of the ``lfm2_moe`` model type
(``h`` the stream, every norm an RMSNorm with ``norm_eps``):

- block ``i``: ``h = h + mixer_i(norm1(h))``; ``h = h + ffn_i(norm2(h))``;
  after the last block a final norm, then logits on the tied embedding;
- a ``conv`` mixer: ``[B, C, x] = W_in u`` (three chunks of the hidden
  size); ``y = C * conv(B * x)``, ``conv`` a causal depthwise convolution of
  ``conv_L_cache`` taps a channel, the last tap on the position's own input,
  no bias, no activation; out ``W_out y``;
- a ``full_attention`` mixer: ``q, k, v`` by ``W_q``, ``W_k``, ``W_v`` (no
  bias); an RMSNorm over each head of ``q`` and ``k`` (a weight a position of
  the head), then rope on the two halves of the head; causal softmax
  attention, ``heads / kv_heads`` query heads a key/value head, scores scaled
  by ``head_dim ** -0.5``; out ``W_o``;
- ``ffn_i`` for ``i < num_dense_layers``: SwiGLU ``W_2(silu(W_1 u) * W_3
  u)``; else ``s = sigmoid(u W_r)`` over ALL the experts the router scores;
  the chosen ``top_k`` are the largest of ``s + expert_bias`` (ties to the
  lower number); their weights are ``s`` at the chosen, without the bias,
  over their sum (+ ``renorm_eps``), times ``routed_scaling_factor``; the
  output is the weighted sum of the chosen experts' SwiGLUs. No shared
  expert, no capacity, no drop;
- next-token cross-entropy over the rows of the vocabulary held here, the
  mean over every position that has a target.

A configuration that holds a SHARE ``[first_expert, first_expert +
num_experts)`` of the routed experts computes those experts' terms and leaves
the others' out, as the program does: the choice and the weights are over
all of them, and that partial sum goes on to the next layer.

It imports nothing of ``ray_lightning_tpu`` and takes no array the program
has made: weights come from the family's ``weights.py``, a layer at a time,
in bfloat16 as the configuration states and are cast to float32 here. Every
matmul runs under ``jax.default_matmul_precision("highest")``. ``quant`` is
the control's hook (``benchmarks/reference.py``): a function applied to both
operands of every matmul, or :class:`DropBeyond`, the second control, which
leaves every matmul as it is and DROPS the pairs an expert gets beyond a
capacity, as a capacity-bounded dispatch would.

Departures from the description, all for memory and none for the
arithmetic: attention runs over blocks of query rows (each against all keys,
masked), a held expert is evaluated on every token and weighted by its
(mostly zero) gate, one expert after another and a chunk of tokens at a
time (under ``jax.checkpoint``, so that the backward holds one chunk's
products and not sixteen experts' over the whole batch), and the training
step walks
the layers by hand (forward keeping each layer's input, backward by
``jax.vjp`` a layer at a time) so that only one layer's float32 weights and
gradients are alive.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import Quant, mm, schedule

from . import weights

Q_BLOCK = 1024  # query rows scored at a time
TOKEN_CHUNK = 2048  # tokens the held experts are evaluated on at a time
BUFFERS = ("expert_bias",)  # leaves no gradient and no weight decay reach


class DropBeyond:
    """The dropping control: of the pairs routed to one expert, in the order
    of the tokens (first choices before second ones), those beyond
    ``factor`` x the mean load of an expert are dropped and add nothing."""

    def __init__(self, factor: float = 1.25):
        self.factor = factor


def _drops(quant) -> bool:
    """By what it carries and not by its class: a cell's family is a module
    of its own root, and a control made from another root's is as good."""
    return hasattr(quant, "factor")


def _matmul_quant(quant):
    return None if _drops(quant) else quant


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: [T, H, hd], positions 0..T-1; rotates (x[i], x[i + hd/2])."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, quant: Quant):
    """Causal grouped-query attention of one sequence. q: [T, Hq, hd],
    k, v: [T, Hkv, hd] -> [T, Hq, hd]."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = max(d for d in range(1, min(Q_BLOCK, t) + 1) if t % d == 0)
    qg = q.reshape(t // qb, qb, hkv, g, hd)
    starts = jnp.arange(t // qb) * qb
    kq = quant(k) if quant is not None else k
    vq = quant(v) if quant is not None else v

    @jax.checkpoint
    def block(args):
        qblk, start = args
        if quant is not None:
            qblk = quant(qblk)
        s = jnp.einsum("qhgd,khd->hgqk", qblk, kq) / math.sqrt(hd)
        rows = start + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant is not None:
            p = quant(p)
        return jnp.einsum("hgqk,khd->qhgd", p, vq)

    out = jax.lax.map(block, (qg, starts))
    return out.reshape(t, hq, hd)


def short_conv(u, lp, quant: Quant):
    """The gated short convolution. u: [B, T, D] (normed)."""
    gate_in, gate_out, x = jnp.split(mm(u, lp["in_proj"], quant), 3, axis=-1)
    bx = gate_in * x
    taps, t = lp["conv_w"].shape[0], u.shape[1]
    padded = jnp.pad(bx, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(lp["conv_w"][j][None, None, :] * padded[:, j: j + t] for j in range(taps))
    return mm(gate_out * conv, lp["out_proj"], quant)


def attention_mixer(u, lp, m: Dict[str, Any], quant: Quant):
    b, t, _ = u.shape
    hd, eps = m["hd"], m["eps"]
    q = rmsnorm(mm(u, lp["wq"], quant).reshape(b, t, -1, hd), lp["q_norm"], eps)
    k = rmsnorm(mm(u, lp["wk"], quant).reshape(b, t, -1, hd), lp["k_norm"], eps)
    v = mm(u, lp["wv"], quant).reshape(b, t, -1, hd)

    def one_row(args):
        qr, kr, vr = args
        return attention(rope(qr, m["theta"]), rope(kr, m["theta"]), vr, quant)

    att = jax.lax.map(one_row, (q, k, v)).reshape(b, t, -1)
    return mm(att, lp["wo"], quant)


def route(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [N, D] -> (idx [N, K] among all the routed experts, weights [N, K])."""
    s = jax.nn.sigmoid(mm(x, lp["router"], quant))
    _, idx = jax.lax.top_k(s + lp["expert_bias"] if "expert_bias" in lp else s, m["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if m["renorm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + m["renorm_eps"])
    return idx, w * m["scale"]


def kept(idx, m: Dict[str, Any], factor: float):
    """[N, K] 0/1: the pairs a capacity of ``factor`` x the mean load keeps,
    an expert's pairs counted first choices first, then in token order."""
    n, k = idx.shape
    capacity = int(factor * n * k / m["routed"])
    flat = jax.nn.one_hot(idx.T.reshape(-1), m["routed"], dtype=jnp.float32)  # k-major
    before = jnp.cumsum(flat, axis=0) - flat
    mine = jnp.sum(before * flat, axis=-1)
    return (mine < capacity).astype(jnp.float32).reshape(k, n).T


def experts(x, lp, m: Dict[str, Any], quant):
    """x: [N, D]. Every held expert on every token, weighted by its gate (zero
    outside the token's choices), ``TOKEN_CHUNK`` tokens at a time. Returns
    (out, idx)."""
    mq = _matmul_quant(quant)
    idx, w = route(x, lp, m, mq)
    if _drops(quant):
        w = w * kept(idx, m, quant.factor)
    ids = m["first"] + jnp.arange(m["held"])
    # [N, held]: the weight of each held expert for each token
    gates = jnp.sum((idx[:, None, :] == ids[None, :, None]) * w[:, None, :], axis=-1)
    stacks = (lp["experts/w_gate"], lp["experts/w_up"], lp["experts/w_down"])

    @jax.checkpoint
    def chunk(args):
        xc, gc = args

        def one(acc, expert):
            wg, wu, wd, col = expert
            h = jax.nn.silu(mm(xc, wg, mq)) * mm(xc, wu, mq)
            return acc + col[:, None] * mm(h, wd, mq), None

        return jax.lax.scan(one, jnp.zeros_like(xc), (*stacks, gc.T))[0]

    n, d = x.shape
    c = max(t for t in range(1, min(TOKEN_CHUNK, n) + 1) if n % t == 0)
    out = jax.lax.map(chunk, (x.reshape(n // c, c, d), gates.reshape(n // c, c, -1)))
    return out.reshape(n, d), idx


def layer(x, lp, sizes: Dict[str, Any], where: int, quant=None, choices: bool = False):
    """One block. x: [B, T, D] float32; lp: that layer's float32 leaves, flat;
    ``where``: its place in the stack. With ``choices`` also the router's idx
    ([B * T, K], None for a dense layer)."""
    m = weights.dims(sizes)
    mq = _matmul_quant(quant)
    b, t, d = x.shape
    u = rmsnorm(x, lp["norm1"], m["eps"])
    if m["kinds"][where] == "conv":
        x = x + short_conv(u, lp, mq)
    else:
        x = x + attention_mixer(u, lp, m, mq)
    u = rmsnorm(x, lp["norm2"], m["eps"])
    if where < m["dense"]:
        out = mm(jax.nn.silu(mm(u, lp["w_gate"], mq)) * mm(u, lp["w_up"], mq), lp["w_down"], mq)
        idx = None
    else:
        out, idx = experts(u.reshape(b * t, d), lp, m, quant)
        out = out.reshape(b, t, d)
    return (x + out, idx) if choices else x + out


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layers(sizes) -> range:
    return range(weights.dims(sizes)["layers"])


# ---------------------------------------------------------------------- #
# teacher-forced logits (the tests), and the router's choices (the tools)
# ---------------------------------------------------------------------- #
def teacher_forced_logits(sizes: Dict[str, Any], seed: int, tokens, quant=None,
                          choices: bool = False):
    """``tokens [B, T] int32 -> logits [B, T, V] float32`` on the tied
    embedding; with ``choices`` also each expert layer's idx ``[B * T, K]``."""
    m = weights.dims(sizes)

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = _f32(weights.top_leaves(sizes, keys))
            x, picked = top["embed"][tokens], []
            for l in _layers(sizes):
                x, idx = layer(x, _f32(weights.whole_layer(sizes, keys, l)), sizes, l, quant,
                               choices=True)
                if idx is not None:
                    picked.append(idx)
            x = rmsnorm(x, top["final_norm"], m["eps"])
            return mm(x, top["embed"].T, _matmul_quant(quant)), picked

    logits, picked = jax.jit(run)(weights.seed_keys(sizes, seed), jnp.asarray(tokens, jnp.int32))
    return (logits, picked) if choices else logits


# ---------------------------------------------------------------------- #
# training: the first steps of the job
# ---------------------------------------------------------------------- #
class TrainReference:
    """AdamW on the next-token loss, float32 arithmetic on state kept in the
    configuration's own type (bfloat16 parameters and moments), a layer at a
    time. ``step`` returns the loss of the batch under the parameters before
    the update and the gradient's norm per leaf, named as the program's tree
    names them (``layers/01/experts/w_gate``). ``held_pairs`` holds, for
    every ``step`` and ``loss`` so far, the pairs that fell on held experts,
    an expert layer an entry."""

    def __init__(self, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any], quant=None):
        self.sizes, self.opt, self.quant = sizes, opt, quant
        self.m = weights.dims(sizes)
        self.count = 0
        self.keys = weights.seed_keys(sizes, seed)
        self.layers: List[Dict[str, Any]] = [
            jax.jit(lambda keys, l=l: weights.whole_layer(sizes, keys, l))(self.keys)
            for l in _layers(sizes)]
        self.top = jax.jit(lambda keys: weights.top_leaves(sizes, keys))(self.keys)
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
        self.m_layers = [zeros(lp) for lp in self.layers]
        self.v_layers = [zeros(lp) for lp in self.layers]
        self.m_top, self.v_top = zeros(self.top), zeros(self.top)
        self.held_pairs: List[List[int]] = []
        self._build()

    def _build(self):
        sizes, quant, opt, m = self.sizes, self.quant, self.opt, self.m
        hi = jax.default_matmul_precision
        held = lambda idx: jnp.sum((idx >= m["first"]) & (idx < m["first"] + m["held"]))

        def fwd(lp, x, where):
            with hi("highest"):
                y, idx = layer(x, _f32(lp), sizes, where, quant, choices=True)
                return y, (jnp.int32(0) if idx is None else held(idx))

        def bwd(lp, x, dy, where):
            with hi("highest"):
                _, pull = jax.vjp(lambda p, a: layer(a, p, sizes, where, quant), _f32(lp), x)
                return pull(dy)

        def head(top, x, tokens):
            with hi("highest"):
                def loss_of(norm_w, embed, x):
                    def row(args):
                        xr, tr = args
                        h = rmsnorm(xr, norm_w, m["eps"])
                        logits = mm(h, embed.T, _matmul_quant(quant))
                        tgt = jnp.roll(tr, -1)
                        lse = jax.nn.logsumexp(logits, axis=-1)
                        nll = lse - jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
                        return jnp.sum(nll[:-1])  # the last position has no target
                    total = jnp.sum(jax.lax.map(jax.checkpoint(row), (x, tokens)))
                    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
                t32 = _f32(top)
                return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
                    t32["final_norm"], t32["embed"], x)

        def embed_grad(head_grad, dx, tokens):
            return head_grad.at[tokens].add(dx)  # tied: the head's and the gather's

        def update(p, m_, v, g, lr, c1, c2):
            b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

            def one(name, p, m_, v, g):
                if name in BUFFERS:
                    return p, m_, v
                p32, m32, v32 = (a.astype(jnp.float32) for a in (p, m_, v))
                m32 = b1 * m32 + (1 - b1) * g
                v32 = b2 * v32 + (1 - b2) * g * g
                u = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps) + wd * p32
                return ((p32 - lr * u).astype(p.dtype), m32.astype(m_.dtype),
                        v32.astype(v.dtype))
            out = {n: one(n, p[n], m_[n], v[n], g[n]) for n in p}
            return tuple({n: t[i] for n, t in out.items()} for i in range(3))

        norms = lambda tree: jax.tree_util.tree_map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))), tree)
        self._fwd = jax.jit(fwd, static_argnums=2)
        self._bwd = jax.jit(bwd, static_argnums=3)
        self._head = jax.jit(head)
        self._embed_grad = jax.jit(embed_grad)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._norms = jax.jit(norms)

    def _forward(self, tokens):
        xs = [self.top["embed"].astype(jnp.float32)[tokens]]
        held = []
        for l, lp in enumerate(self.layers):
            y, n = self._fwd(lp, xs[-1], l)
            xs.append(y)
            if l >= self.m["dense"]:
                held.append(int(n))
        self.held_pairs.append(held)
        return xs

    def loss(self, tokens) -> float:
        """Forward only."""
        tokens = jnp.asarray(tokens, jnp.int32)
        return float(self._head(self.top, self._forward(tokens)[-1], tokens)[0])

    def step(self, tokens) -> Tuple[float, Dict[str, float]]:
        tokens = jnp.asarray(tokens, jnp.int32)
        self.count += 1
        lr = schedule(self.opt, self.count - 1)
        c1 = 1.0 - self.opt["b1"] ** self.count
        c2 = 1.0 - self.opt["b2"] ** self.count
        xs = self._forward(tokens)
        loss, (g_norm, g_head, dx) = self._head(self.top, xs.pop(), tokens)
        gnorms: Dict[str, float] = {}
        for l in reversed(range(len(self.layers))):
            g_l, dx = self._bwd(self.layers[l], xs.pop(), dx, l)
            for name, val in self._norms(g_l).items():
                gnorms[f"layers/{weights.place(l)}/{name}"] = float(val)
            self.layers[l], self.m_layers[l], self.v_layers[l] = self._update(
                self.layers[l], self.m_layers[l], self.v_layers[l], g_l, lr, c1, c2)
            del g_l
        g_top = {"embed": self._embed_grad(g_head, dx, tokens), "final_norm": g_norm}
        gnorms.update({n: float(v) for n, v in self._norms(g_top).items()})
        self.top, self.m_top, self.v_top = self._update(
            self.top, self.m_top, self.v_top, g_top, lr, c1, c2)
        return float(loss), gnorms

    def change_norms(self) -> Dict[str, float]:
        """Norm per leaf of (parameters now - parameters from the seed)."""
        sizes = self.sizes
        gap = lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        out: Dict[str, float] = {}
        for l, lp in enumerate(self.layers):
            diff = jax.jit(lambda now, keys, l=l: jax.tree_util.tree_map(
                gap, now, weights.whole_layer(sizes, keys, l)))(lp, self.keys)
            out.update({f"layers/{weights.place(l)}/{n}": float(v) for n, v in diff.items()})
        top = jax.jit(lambda now, keys: jax.tree_util.tree_map(
            gap, now, weights.top_leaves(sizes, keys)))(self.top, self.keys)
        out.update({n: float(v) for n, v in top.items()})
        return out
