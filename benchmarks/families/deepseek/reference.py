"""The DeepSeek-V3-style family's plain reference: a decoder with latent
attention and sigmoid-routed experts in ``jax.numpy`` float32.

Written from the published modeling code of the DeepSeek-V3 block, which the
configurations of this family state at their own widths: pre-norm blocks;
RMSNorm; queries through a low-rank pair with a norm between; keys and
values from one compressed row a position (``kv_lora_rank`` normed columns
and ``qk_rope_head_dim`` roped ones, one key head shared by all query
heads); rope on the last ``qk_rope_head_dim`` columns of a query head, on
ADJACENT pairs ``(2i, 2i + 1)``, each pair turned where it stands
(``rope_interleave``); scores over ``nope + rope`` columns scaled by their
count to the -1/2, values ``v_head_dim`` wide; ``first_k_dense_replace``
leading blocks with a SwiGLU, the others with a router: sigmoid of the
float32 logits, the top-k of score + bias chosen (no groups), the scores of
the chosen ones (without the bias) renormalised and scaled, plus the shared
expert on every token; untied head; next-token cross-entropy.

Only the uncached, decompressed form is here: ``[k_nope | v] = c_kv W_kvb``
for every position, no cache, no absorbed product, no kernel. It imports
nothing of ``ray_lightning_tpu`` and takes no array the program has made:
weights come from the family's ``weights.py`` by seed, a layer at a time, in
the configuration's own type and are cast to float32 here. Every matmul runs
under ``jax.default_matmul_precision("highest")``. ``quant`` is the
control's hook (``benchmarks/reference.py``).

Departures from the description, all for memory and none for the
arithmetic: attention runs over blocks of query rows; every expert is
evaluated on every token, one expert after another, and weighted by the
routing (zero where the token was not routed to it); an expert's matrices
are cast to float32 one expert at a time. The training step keeps the whole
tree and differentiates it in one piece: it is for test sizes.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference import Quant, mm, schedule

from . import weights
from .weights import dims

Q_BLOCK = 1024  # query rows scored at a time
GROUPS = ("dense_layers", "moe_layers")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: [T, ..., hd], positions 0..T-1 on the first axis; turns the
    adjacent pairs (x[2i], x[2i+1]) and leaves each where it stands."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (hd // 2,))
    pairs = x.reshape(x.shape[:-1] + (hd // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, scale: float, quant: Quant):
    """Causal attention of one sequence. q, k: [T, H, dqk]; v: [T, H, dv]
    -> [T, H, dv]."""
    t, h, _ = q.shape
    qb = max(d for d in range(1, min(Q_BLOCK, t) + 1) if t % d == 0)
    starts = jnp.arange(t // qb) * qb
    kq = quant(k) if quant is not None else k
    vq = quant(v) if quant is not None else v

    @jax.checkpoint
    def block(args):
        qblk, start = args
        if quant is not None:
            qblk = quant(qblk)
        s = jnp.einsum("qhd,khd->hqk", qblk, kq) * scale
        rows = start + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant is not None:
            p = quant(p)
        return jnp.einsum("hqk,khd->qhd", p, vq)

    out = jax.lax.map(block, (q.reshape(t // qb, qb, h, -1), starts))
    return out.reshape(t, h, -1)


def latent_attention(h, lp, m: Dict[str, Any], quant: Quant):
    """h: [B, T, D], the normed input. The decompressed form."""
    b, t, _ = h.shape
    heads, nope, rp, dv = m["heads"], m["nope"], m["rope"], m["v"]
    c_q = rmsnorm(mm(h, lp["wq_a"], quant), lp["q_norm"], m["eps"])
    q = mm(c_q, lp["wq_b"], quant).reshape(b, t, heads, nope + rp)
    ckv = mm(h, lp["wkv_a"], quant)
    c_kv = rmsnorm(ckv[..., : m["rkv"]], lp["kv_norm"], m["eps"])
    kv = mm(c_kv, lp["wkv_b"], quant).reshape(b, t, heads, nope + dv)
    scale = (nope + rp) ** -0.5

    def one_row(args):
        qr, kvr, krr = args  # [T, H, .], [T, H, .], [T, rope]
        k_r = jnp.broadcast_to(rope(krr, m["theta"])[:, None, :], (t, heads, rp))
        qq = jnp.concatenate([qr[..., :nope], rope(qr[..., nope:], m["theta"])], axis=-1)
        kk = jnp.concatenate([kvr[..., :nope], k_r], axis=-1)
        return attention(qq, kk, kvr[..., nope:], scale, quant)

    att = jax.lax.map(one_row, (q, kv, ckv[..., m["rkv"]:]))
    return mm(att.reshape(b, t, heads * dv), lp["wo"], quant)


def swiglu(x, w_gate, w_up, w_down, quant: Quant):
    return mm(jax.nn.silu(mm(x, w_gate, quant)) * mm(x, w_up, quant), w_down, quant)


def route(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [N, D] -> [N, E]: a token's weight on every expert, zero on those
    it is not routed to."""
    s = jax.nn.sigmoid(mm(x, lp["moe/router"], quant))
    _, idx = jax.lax.top_k(s + lp["moe/bias"], m["top_k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if m["renorm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)  # [N, K, E]
    return jnp.sum(chosen * (picked * m["scale"])[..., None], axis=1)


def moe(x, lp, m: Dict[str, Any], quant: Quant):
    """x: [N, D]; ``lp``'s expert stacks may be in the configuration's own
    type: each expert is cast as its turn comes."""
    w = route(x, lp, m, quant)

    def one(acc, expert):
        wg, wu, wd, col = expert
        f32 = lambda a: a.astype(jnp.float32)
        return acc + col[:, None] * swiglu(x, f32(wg), f32(wu), f32(wd), quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lp["moe/w_gate"], lp["moe/w_up"], lp["moe/w_down"], w.T))
    shared = swiglu(x, lp["moe/shared/w_gate"], lp["moe/shared/w_up"],
                    lp["moe/shared/w_down"], quant)
    return out + shared


def layer(x, lp, sizes: Dict[str, Any], quant: Quant = None):
    """One block. x: [B, T, D] float32; lp: that layer's leaves, float32 but
    for the expert stacks."""
    m = dims(sizes)
    b, t, d = x.shape
    x = x + latent_attention(rmsnorm(x, lp["attn_norm"], m["eps"]), lp, m, quant)
    h = rmsnorm(x, lp["mlp_norm"], m["eps"])
    if "moe/router" in lp:
        return x + moe(h.reshape(b * t, d), lp, m, quant).reshape(b, t, d)
    return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], quant)


_STACKS = ("moe/w_gate", "moe/w_up", "moe/w_down")


def _f32(tree, but=()):
    return {k: (v if k in but else v.astype(jnp.float32)) for k, v in tree.items()}


# ---------------------------------------------------------------------- #
# serving: teacher-forced logits
# ---------------------------------------------------------------------- #
def logits_fn(sizes: Dict[str, Any], seed: int, quant: Quant = None):
    """The jitted ``tokens [B, T] int32 -> logits [B, T, V] float32``, the
    weights made inside it from the seed one layer at a time, a scan a
    group of layers."""
    specs = weights.leaf_specs(sizes)

    def run(keys, tokens):
        with jax.default_matmul_precision("highest"):
            top = _f32(weights.top_leaves(sizes, keys))
            x = top["embed"][tokens]
            for group in GROUPS:
                count, first, _ = specs["groups"][group]
                if not count:
                    continue

                def step(x, l, group=group):
                    lp = _f32(weights.layer_leaves(sizes, keys, l, group), but=_STACKS)
                    return layer(x, lp, sizes, quant), None

                x, _ = jax.lax.scan(
                    step, x, jnp.arange(count, dtype=jnp.uint32) + jnp.uint32(first))
            x = rmsnorm(x, top["final_norm"], dims(sizes)["eps"])
            return mm(x, top["lm_head"], quant)

    keys = weights.seed_keys(sizes, seed)  # arguments, so every seed shares the program
    fn = jax.jit(run)
    return lambda tokens: fn(keys, tokens)


def teacher_forced_logits(sizes: Dict[str, Any], seed: int, tokens, quant: Quant = None):
    return logits_fn(sizes, seed, quant)(jnp.asarray(tokens, jnp.int32))


# ---------------------------------------------------------------------- #
# training: the first steps of a job, at test sizes
# ---------------------------------------------------------------------- #
class TrainReference:
    """AdamW on the next-token loss, float32 arithmetic on state kept in the
    configuration's own type, the whole tree at once. The router's selection
    bias is held fixed: no gradient reaches it (it picks and does not
    weigh) and no weight decay is applied to it. ``step`` returns the loss
    of the batch under the parameters before the update and the gradient's
    norm per leaf (``<group>/<leaf>`` as the program's tree names them)."""

    FIXED = "moe_layers/moe/bias"

    def __init__(self, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any],
                 quant: Quant = None):
        self.sizes, self.opt, self.quant = sizes, opt, quant
        self.count = 0
        self.keys = weights.seed_keys(sizes, seed)
        self.params = self._from_seed()
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self.m, self.v = zeros(), zeros()
        self._grad = jax.jit(jax.value_and_grad(self._loss))
        self._loss_only = jax.jit(self._loss)

    def _from_seed(self) -> Dict[str, Any]:
        """{"embed": a, "dense_layers/wq_a": [Ld, ...], ...}"""
        sizes, specs = self.sizes, weights.leaf_specs(self.sizes)

        def make(keys):
            flat = dict(weights.top_leaves(sizes, keys))
            for group, (count, first, _) in specs["groups"].items():
                stacked = jax.vmap(lambda l, g=group: weights.layer_leaves(sizes, keys, l, g))(
                    jnp.arange(count, dtype=jnp.uint32) + jnp.uint32(first))
                flat.update({f"{group}/{k}": v for k, v in stacked.items()})
            return flat

        return jax.jit(make)(self.keys)

    def _loss(self, params, tokens):
        sizes, quant = self.sizes, self.quant
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in params.items()}
            x = p["embed"][tokens]
            for group in GROUPS:
                leaves = {k.split("/", 1)[1]: v for k, v in p.items()
                          if k.startswith(group + "/")}
                for l in range(next(iter(leaves.values())).shape[0] if leaves else 0):
                    x = layer(x, {k: v[l] for k, v in leaves.items()}, sizes, quant)
            h = rmsnorm(x, p["final_norm"], dims(sizes)["eps"])
            logits = mm(h, p["lm_head"], quant)
            tgt = tokens[:, 1:]
            lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
            got = jnp.take_along_axis(logits[:, :-1], tgt[..., None], axis=-1)[..., 0]
            return jnp.mean(lse - got)

    def loss(self, tokens) -> float:
        return float(self._loss_only(self.params, jnp.asarray(tokens, jnp.int32)))

    def step(self, tokens) -> Tuple[float, Dict[str, float]]:
        tokens = jnp.asarray(tokens, jnp.int32)
        self.count += 1
        opt = self.opt
        lr = schedule(opt, self.count - 1)
        c1, c2 = 1.0 - opt["b1"] ** self.count, 1.0 - opt["b2"] ** self.count
        loss, grads = self._grad(self.params, tokens)
        norms = {k: float(jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))))
                 for k, g in grads.items()}
        for k, g in grads.items():
            if k == self.FIXED:
                continue
            p32, m32, v32 = (a[k].astype(jnp.float32) for a in (self.params, self.m, self.v))
            g = g.astype(jnp.float32)
            m32 = opt["b1"] * m32 + (1 - opt["b1"]) * g
            v32 = opt["b2"] * v32 + (1 - opt["b2"]) * g * g
            u = (m32 / c1) / (jnp.sqrt(v32 / c2) + opt["eps"]) + opt["weight_decay"] * p32
            self.params[k] = (p32 - lr * u).astype(self.params[k].dtype)
            self.m[k], self.v[k] = m32.astype(self.m[k].dtype), v32.astype(self.v[k].dtype)
        return float(loss), norms

    def change_norms(self) -> Dict[str, float]:
        """Norm per leaf of (parameters now - parameters from the seed)."""
        start = self._from_seed()
        return {k: math.sqrt(float(jnp.sum(jnp.square(
            self.params[k].astype(jnp.float32) - start[k].astype(jnp.float32)))))
            for k in self.params}
