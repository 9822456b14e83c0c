"""The Llama family's plain reference: a decoder-only transformer in
``jax.numpy`` float32.

Written from the published description of the Mistral / Mixtral family
(pre-norm blocks; RMSNorm; rotary embedding on the two halves of each head,
as the published checkpoints lay it out; grouped-query causal attention by
einsum; SwiGLU; for Mixtral a router that takes the softmax over all experts,
keeps the top two and renormalises them; untied head; next-token
cross-entropy), with no kernel, no cache and no batching tricks. It imports
nothing of ``ray_lightning_tpu`` and takes no array the program has made:
weights come from the family's ``weights.py`` by seed, a layer at a time, in
bfloat16 as the configuration states and are cast to float32 here. Every
matmul runs under ``jax.default_matmul_precision("highest")``; on a TPU a
float32 matmul is otherwise a bfloat16 one. ``quant`` is the control's hook
(``benchmarks/reference.py``).

Departures from the description, all for memory and none for the
arithmetic: attention runs over blocks of query rows (each against all keys,
masked), experts are evaluated one after another on every token and weighted
by the (mostly zero) gate, and the training step walks the layers by hand
(forward keeping each layer's input, backward by ``jax.vjp`` a layer at a
time) so that only one layer's float32 weights and gradients are alive.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import Quant, mm, schedule

from . import weights

Q_BLOCK = 1024  # query rows scored at a time


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


def mlp(x, lp, quant: Quant):
    gate = jax.nn.silu(mm(x, lp["w_gate"], quant)) * mm(x, lp["w_up"], quant)
    return mm(gate, lp["w_down"], quant)


def moe(x, lp, top_k: int, quant: Quant):
    """x: [N, D]. Softmax over all experts, top-k, renormalise; every expert
    on every token, weighted by its gate (zero outside the top-k)."""
    gates = jax.nn.softmax(mm(x, lp["moe/router"], quant), axis=-1)
    vals, idx = jax.lax.top_k(gates, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    n_e = gates.shape[-1]
    w = jnp.sum(jax.nn.one_hot(idx, n_e, dtype=jnp.float32) * vals[..., None], axis=1)

    def one(acc, expert):
        wg, wu, wd, col = expert
        h = jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant)
        return acc + col[:, None] * mm(h, wd, quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lp["moe/w_gate"], lp["moe/w_up"], lp["moe/w_down"], w.T))
    return out


def layer(x, lp, sizes: Dict[str, Any], quant: Quant = None):
    """One block. x: [B, T, D] float32; lp: that layer's float32 leaves."""
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    hd = sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"]
    b, t, d = x.shape
    h = rmsnorm(x, lp["attn_norm"], eps)
    q = mm(h, lp["wq"], quant).reshape(b, t, -1, hd)
    k = mm(h, lp["wk"], quant).reshape(b, t, -1, hd)
    v = mm(h, lp["wv"], quant).reshape(b, t, -1, hd)

    def one_row(args):
        qr, kr, vr = args
        return attention(rope(qr, theta), rope(kr, theta), vr, quant)

    att = jax.lax.map(one_row, (q, k, v)).reshape(b, t, -1)
    x = x + mm(att, lp["wo"], quant)
    h = rmsnorm(x, lp["mlp_norm"], eps)
    if "moe/router" in lp:
        out = moe(h.reshape(b * t, d), lp, sizes["num_experts_per_tok"], quant)
        return x + out.reshape(b, t, d)
    return x + mlp(h, lp, quant)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------- #
# serving: teacher-forced logits
# ---------------------------------------------------------------------- #
def logits_fn(sizes: Dict[str, Any], seed: int, quant: Quant = None):
    """The jitted ``tokens [B, T] int32 -> logits [B, T, V] float32``, the
    weights made inside it from the seed one layer at a time: one program
    whatever the depth."""

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = _f32(weights.top_leaves(sizes, keys))
            x = top["embed"][tokens]

            def step(x, l):
                lp = _f32(weights.layer_leaves(sizes, keys, l))
                return layer(x, lp, sizes, quant), None

            x, _ = jax.lax.scan(
                step, x, jnp.arange(sizes["num_hidden_layers"], dtype=jnp.uint32))
            x = rmsnorm(x, top["final_norm"], sizes["rms_norm_eps"])
            return mm(x, top["lm_head"], quant)

    keys = weights.seed_keys(sizes, seed)  # arguments, so every seed shares the program
    fn = jax.jit(run)
    return lambda tokens: fn(keys, tokens)


def teacher_forced_logits(sizes: Dict[str, Any], seed: int, tokens, quant: Quant = None):
    return logits_fn(sizes, seed, quant)(jnp.asarray(tokens, jnp.int32))


# ---------------------------------------------------------------------- #
# training: the first steps of the job
# ---------------------------------------------------------------------- #
class TrainReference:
    """AdamW on the next-token loss, float32 arithmetic on state kept in the
    configuration's own type (bfloat16 parameters and moments), a layer at a
    time. ``step`` returns the loss of the batch under the parameters before
    the update and the gradient's norm per leaf."""

    def __init__(self, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any],
                 quant: Quant = None):
        self.sizes, self.opt, self.quant = sizes, opt, quant
        self.count = 0
        self.n_layers = sizes["num_hidden_layers"]
        self.dtype = jnp.dtype(sizes.get("dtype", "bfloat16"))
        self.keys = weights.seed_keys(sizes, seed)
        gen = jax.jit(lambda keys, l: weights.layer_leaves(sizes, keys, l))
        self.layers: List[Dict[str, Any]] = [
            gen(self.keys, jnp.uint32(l)) for l in range(self.n_layers)]
        self.top = jax.jit(lambda keys: weights.top_leaves(sizes, keys))(self.keys)
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
        self.m_layers = [zeros(lp) for lp in self.layers]
        self.v_layers = [zeros(lp) for lp in self.layers]
        self.m_top, self.v_top = zeros(self.top), zeros(self.top)
        self._build()

    def _build(self):
        sizes, quant, opt = self.sizes, self.quant, self.opt
        hi = jax.default_matmul_precision

        def fwd(lp, x):
            with hi("highest"):
                return layer(x, _f32(lp), sizes, quant)

        def bwd(lp, x, dy):
            with hi("highest"):
                _, pull = jax.vjp(lambda p, a: layer(a, p, sizes, quant), _f32(lp), x)
                return pull(dy)

        def head(top, x, tokens):
            with hi("highest"):
                def loss_of(norm_w, head_w, x):
                    def row(args):
                        xr, tr = args
                        h = rmsnorm(xr, norm_w, sizes["rms_norm_eps"])
                        logits = mm(h, head_w, quant)
                        tgt = jnp.roll(tr, -1)
                        lse = jax.nn.logsumexp(logits, axis=-1)
                        nll = lse - jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
                        return jnp.sum(nll[:-1])  # the last position has no target
                    total = jnp.sum(jax.lax.map(jax.checkpoint(row), (x, tokens)))
                    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
                t32 = _f32(top)
                loss, grads = jax.value_and_grad(loss_of, argnums=(0, 1, 2))(
                    t32["final_norm"], t32["lm_head"], x)
                return loss, grads

        def embed_grad(dx, tokens, shape):
            return jnp.zeros(shape, jnp.float32).at[tokens].add(dx)

        def update(p, m, v, g, lr, c1, c2):
            b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
            def one(p, m, v, g):
                p32, m32, v32 = (a.astype(jnp.float32) for a in (p, m, v))
                m32 = b1 * m32 + (1 - b1) * g
                v32 = b2 * v32 + (1 - b2) * g * g
                u = (m32 / c1) / (jnp.sqrt(v32 / c2) + eps) + wd * p32
                return ((p32 - lr * u).astype(p.dtype), m32.astype(m.dtype),
                        v32.astype(v.dtype))
            out = jax.tree_util.tree_map(one, p, m, v, g)
            pick = lambda i: jax.tree_util.tree_map(
                lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
            return pick(0), pick(1), pick(2)

        norms = lambda tree: jax.tree_util.tree_map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))), tree)
        self._fwd, self._bwd = jax.jit(fwd), jax.jit(bwd)
        self._head = jax.jit(head)
        self._embed_grad = jax.jit(embed_grad, static_argnums=2)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))
        self._norms = jax.jit(norms)

    def loss(self, tokens) -> float:
        """Forward only."""
        tokens = jnp.asarray(tokens, jnp.int32)
        x = self.top["embed"].astype(jnp.float32)[tokens]
        for lp in self.layers:
            x = self._fwd(lp, x)
        return float(self._head(self.top, x, tokens)[0])

    def step(self, tokens) -> Tuple[float, Dict[str, float]]:
        tokens = jnp.asarray(tokens, jnp.int32)
        self.count += 1
        lr = schedule(self.opt, self.count - 1)
        c1 = 1.0 - self.opt["b1"] ** self.count
        c2 = 1.0 - self.opt["b2"] ** self.count
        xs = [self.top["embed"].astype(jnp.float32)[tokens]]
        for lp in self.layers:
            xs.append(self._fwd(lp, xs[-1]))
        loss, (g_norm, g_head, dx) = self._head(self.top, xs.pop(), tokens)
        gnorms: Dict[str, Any] = {}
        per_layer: List[Dict[str, Any]] = [None] * self.n_layers
        for l in reversed(range(self.n_layers)):
            g_l, dx = self._bwd(self.layers[l], xs.pop(), dx)
            per_layer[l] = self._norms(g_l)
            self.layers[l], self.m_layers[l], self.v_layers[l] = self._update(
                self.layers[l], self.m_layers[l], self.v_layers[l], g_l, lr, c1, c2)
            del g_l
        g_top = {
            "embed": self._embed_grad(dx, tokens, self.top["embed"].shape),
            "final_norm": g_norm, "lm_head": g_head,
        }
        top_norms = self._norms(g_top)
        self.top, self.m_top, self.v_top = self._update(
            self.top, self.m_top, self.v_top, g_top, lr, c1, c2)
        for name in per_layer[0]:
            # a stacked leaf's norm, as the program's tree holds it
            gnorms["layers/" + name] = float(np.sqrt(sum(
                float(per_layer[l][name]) ** 2 for l in range(self.n_layers))))
        for name, val in top_norms.items():
            gnorms[name] = float(val)
        return float(loss), gnorms

    def change_norms(self) -> Dict[str, float]:
        """Norm per leaf of (parameters now - parameters from the seed)."""
        sizes = self.sizes
        sq = lambda a, b: jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
        diff = jax.jit(lambda now, keys, l: jax.tree_util.tree_map(
            sq, now, weights.layer_leaves(sizes, keys, l)))
        out: Dict[str, float] = {}
        for l, lp in enumerate(self.layers):
            for name, val in diff(lp, self.keys, jnp.uint32(l)).items():
                out["layers/" + name] = out.get("layers/" + name, 0.0) + float(val)
        top = jax.jit(lambda now, keys: jax.tree_util.tree_map(
            sq, now, weights.top_leaves(sizes, keys)))(self.top, self.keys)
        out.update({k: float(v) for k, v in top.items()})
        return {k: math.sqrt(v) for k, v in out.items()}
