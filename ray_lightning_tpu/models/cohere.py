"""A decoder of parallel blocks with window and full attention layers mixed
and a SHARE of sigmoid-routed experts (the published ``cohere2_moe`` block):
teacher-forced forward, prefill and a paged decode step over a pool of two
kinds of leaf. Inference only.

What differs from ``models/llama.py`` and ``models/deepseek.py``, and is why
this is a model of its own:

- the parallel block. One LayerNorm (mean and variance, a weight, no bias)
  feeds attention AND the expert layer, and both are added to the residual:
  ``y = x + attn(LN(x)) + ffn(LN(x))``;
- two kinds of attention layer in a fixed period: ``period - 1`` window
  layers (a key at ``j`` is seen from ``i`` only while ``i - j <
  sliding_window``; rope on the adjacent pairs of the whole head) and then
  one full layer (every earlier position, NO position encoding), repeated.
  GQA: query head ``n`` reads key/value head ``n // (n_heads / n_kv_heads)``;
- the cache follows the kinds: the pool has K and V leaves of a FULL kind
  (the full layers: a request's every position) and of a WINDOW kind (the
  window layers: the last ``sliding_window`` positions and no more;
  ``serving/paged_kv.py`` gives the blocks that fell out back). The decode
  kernel is the Llama family's, told each row's first live position on a
  window layer (``ops/paged_attention.py``);
- every layer is an expert layer, and this holder keeps ``experts_held`` of
  the ``n_experts`` the router scores, ``[first_expert, first_expert +
  experts_held)``: one of the chips that divide each layer by experts. The
  router has its published width; the top-k are chosen and weighed over all
  of them; only the pairs that fall on held experts are computed
  (``parallel/moe.py::moe_ffn_routed``, ``held=``). What the absent experts
  would have added is left out and nothing stands in for the exchange that
  would bring it. The ``n_shared_experts`` shared experts are averaged and
  held whole;
- tied, scaled logits over the rows of the vocabulary held here.

The shared experts are kept as ONE SwiGLU ``n_shared_experts`` times as wide
(expert ``j`` is columns ``[j F, (j + 1) F)``), its output times ``1 /
n_shared_experts``: the mean of their outputs, summed inside the product.
Rope keeps the turned halves apart (all ``2i``, then all ``2i + 1``) in
queries and cached keys alike, which leaves every score as it is. Precision:
weights and activations in ``dtype``, products accumulated in float32; the
norm in float32, and the router's scores from the float32 norm at the
highest precision (``_ffn_rows`` says why).

Not here: a mesh, a training step (the share of experts under ``jax.grad``),
speculation, leading dense layers (``first_k_dense_replace`` 0 only), q/k
norms, biases. ``CohereConfig`` refuses what it cannot run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generation import (
    cached_attention,
    flat_pages,
    gather_pages,
    write_rows,
)
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.rope import rope_adjacent, rope_angles
from ray_lightning_tpu.parallel.moe import moe_ffn_routed, route_sigmoid_bias

# counters the paged decode step returns, summed over its layers: distinct
# held experts that got a row, the choices that fell on held experts, the
# fullest held expert's rows, and the choices the router made
DECODE_COUNTERS = ("moe_expert_hits", "moe_routed_pairs", "moe_max_expert_rows",
                   "moe_choices")
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
KINDS = ("window", "full")
# tokens that go through the expert branch at once: a prompt of 16,384
# positions makes 131,072 routed pairs, whose rows and float32 products would
# be gigabytes, and as much again in the shared experts' 16,384-wide hidden
# rows; a chunk reads the shared and each held expert's weights once
MOE_CHUNK = 2048


@dataclass(frozen=True)
class CohereConfig:
    vocab_size: int = 32768  # the rows of the embedding held here
    dim: int = 4096
    n_layers: int = 4
    period: int = 4  # period - 1 window layers, then a full one
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    ffn_dim: int = 4096  # one expert's width; a shared expert is as wide
    n_experts: int = 128  # what the router scores
    experts_held: int = 16
    first_expert: int = 0
    n_shared_experts: int = 4
    expert_top_k: int = 8
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    max_seq: int = 17408
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.period < 2 or self.n_layers % self.period:
            raise ValueError(
                f"n_layers={self.n_layers} is no whole number of periods of "
                f"{self.period} (window layers, then a full one)")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even: rope turns pairs")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be >= 1")
        if self.expert_top_k > self.n_experts:
            raise ValueError("expert_top_k exceeds n_experts")
        if not (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts
                and self.experts_held >= 1):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held})"
                f" are not among the router's {self.n_experts}")

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    def layers_of(self, kind: str) -> int:
        return self.n_periods * (self.period - 1 if kind == "window" else 1)

    def serving(self):
        """What ``InferenceEngine`` and the paged pool ask of a model."""
        return CohereServing(self)


# --------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------- #
def _layer_shapes(cfg: CohereConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape of one layer, fan_in; 0 marks a norm weight). The
    held experts' stacks are not among them (``params["experts"]``)."""
    d, fs = cfg.dim, cfg.ffn_dim * cfg.n_shared_experts
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "norm": ((d,), 0),
        "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
        "wo": ((q, d), q),
        "router": ((d, cfg.n_experts), d),
        "shared/w_gate": ((d, fs), d), "shared/w_up": ((d, fs), d),
        "shared/w_down": ((fs, d), cfg.ffn_dim),
    }


def init_params(rng: jax.Array, cfg: CohereConfig) -> Dict[str, Any]:
    """Random parameters in the tree the forward takes: ``window_layers``
    and ``full_layers`` ({leaf: [layers of the kind, ...]}, in the stack's
    order), ``experts`` (the held experts of every layer, ``[n_layers,
    experts_held, ...]`` in the stack's order), ``embed``, ``final_norm``.
    Matrices normal with variance 1 / fan_in, norms 1, the router float32."""
    def make(key, shape, fan_in, dtype=cfg.dtype):
        if fan_in == 0:
            return jnp.ones(shape, dtype)
        return (jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5).astype(dtype)

    def group(key, n, shapes):
        keys = jax.random.split(key, len(shapes))
        out: Dict[str, Any] = {}
        for k, (name, (shape, fan)) in zip(keys, sorted(shapes.items())):
            dtype = jnp.float32 if name == "router" else cfg.dtype
            node, *rest = name.split("/")
            leaf = make(k, (n,) + shape, fan, dtype)
            if rest:
                out.setdefault(node, {})[rest[0]] = leaf
            else:
                out[node] = leaf
        return out

    ks = jax.random.split(rng, 6)
    d, f, held = cfg.dim, cfg.ffn_dim, cfg.experts_held
    lead = (cfg.n_layers, held)
    experts = {"w_gate": make(ks[2], lead + (d, f), d),
               "w_up": make(ks[3], lead + (d, f), d),
               "w_down": make(ks[4], lead + (f, d), f)}
    return {
        "embed": make(ks[5], (cfg.vocab_size, d), d),
        "window_layers": group(ks[0], cfg.layers_of("window"), _layer_shapes(cfg)),
        "full_layers": group(ks[1], cfg.layers_of("full"), _layer_shapes(cfg)),
        "experts": experts,
        "final_norm": jnp.ones((d,), cfg.dtype),
    }


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #
def rope_table(cfg: CohereConfig, length: int):
    return rope_angles(length, cfg.head_dim, cfg.rope_theta)


def layernorm(x, w, eps: float, dtype=None):
    """Mean and variance over the last axis in float32, a weight, no bias.
    Returned in ``dtype`` (None: x's)."""
    dtype = x.dtype if dtype is None else dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype)


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _qkv(h, lp, cfg: CohereConfig):
    """h: [..., D] -> q [..., H, hd], k, v [..., Hkv, hd], not roped."""
    lead = h.shape[:-1]
    q = (h @ lp["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _ffn_rows(x, lp, cfg: CohereConfig, experts, layer):
    """The expert branch for x: [T, D], rows of the residual stream, which
    it norms itself: the held experts' part of the routed sum and the shared
    experts' mean. The router's scores are the one product of the layer
    computed from the float32 normed rows at the highest precision: a
    choice among the 128 decides whether a held expert's whole term is in
    the row's sum, and one bfloat16 rounding of the rows or of the router
    changes the chosen eight of one row in fifty (the embedding through the
    first layer's norm and router at the published widths, against the same
    in float32). The product is 128 columns wide and costs nothing beside
    the experts'. Returns (out [T, D], sizes: the rows each held expert got,
    a bin an expert of every layer)."""
    h32 = layernorm(x, lp["norm"], cfg.norm_eps, jnp.float32)
    idx, w = route_sigmoid_bias(
        h32, lp["router"], None, cfg.expert_top_k,
        renormalize=cfg.norm_topk_prob, precision=jax.lax.Precision.HIGHEST)
    h = h32.astype(x.dtype)
    routed, sizes = moe_ffn_routed(
        experts, h, idx, w, held=(cfg.first_expert, cfg.experts_held, layer))
    shared = _swiglu(h, lp["shared"]) * (1.0 / cfg.n_shared_experts)
    return routed + shared.astype(h.dtype), sizes


def _ffn(x, lp, cfg: CohereConfig, experts, layer):
    """The block's expert branch on the residual stream x: [..., D] (not
    normed: the branch norms its rows a chunk at a time, since the float32
    rows of a whole 16,384-token prompt and the three bfloat16 parts the
    router's product takes them in are 0.8 GiB of temporaries; beside the
    attention branch's norm of the same rows the compiler keeps one).
    ``experts``: the held experts of ALL layers as one stack ``[L * held,
    ...]`` (``_expert_stack``), ``layer``: which of them this is. A long
    prompt goes through in chunks of ``MOE_CHUNK`` tokens. Returns (ffn,
    sizes)."""
    flat = x.reshape(-1, x.shape[-1])
    t = flat.shape[0]
    if t > MOE_CHUNK and t % MOE_CHUNK == 0:
        out, sizes = jax.lax.map(
            lambda chunk: _ffn_rows(chunk, lp, cfg, experts, layer),
            flat.reshape(t // MOE_CHUNK, MOE_CHUNK, -1))
        sizes = jnp.sum(sizes, axis=0)
    else:
        out, sizes = _ffn_rows(flat, lp, cfg, experts, layer)
    return out.reshape(x.shape), sizes


def _expert_stack(params):
    """The held experts of all layers as ONE stack ``[L * held, ...]`` (a
    reshape of the leading axes, no copy): a layer's slice handed to the
    grouped matmul would be a copy of it (``models/deepseek.py`` has the
    measurement); instead every layer sees the whole stack and its pairs
    are numbered from ``layer * held``."""
    return {k: params["experts"][k].reshape((-1,) + params["experts"][k].shape[2:])
            for k in EXPERT_STACKS}


def _stack(x, params, cfg: CohereConfig, block, pools=None):
    """Every layer in the stack's order: a period is a scan over its window
    layers and then its full layer, the periods unrolled. ``block(x, lp,
    kind, layer of the stack, layer of its kind, pool) -> (x, pool, out)``;
    ``pools``: ``{kind: the pool's leaves of that kind}``, which a period's
    scan carries beside x so that under the caller's donation the buffers
    that go in are the ones that come out. Returns (x, {kind: outs stacked
    in the kind's order}, pools)."""
    outs: Dict[str, list] = {k: [] for k in KINDS}
    pools = dict.fromkeys(KINDS) if pools is None else dict(pools)
    per = cfg.period - 1
    for p in range(cfg.n_periods):
        leaves = jax.tree_util.tree_map(
            lambda a: a[p * per:(p + 1) * per], params["window_layers"])

        def body(carry, a, p=p):
            lp, j = a
            x, pool, out = block(
                carry[0], lp, "window", p * cfg.period + j, p * per + j, carry[1])
            return (x, pool), out

        (x, pools["window"]), out = jax.lax.scan(
            body, (x, pools["window"]), (leaves, jnp.arange(per, dtype=jnp.int32)))
        outs["window"].append(out)
        lp = jax.tree_util.tree_map(lambda a: a[p], params["full_layers"])
        x, pools["full"], out = block(
            x, lp, "full", p * cfg.period + per, p, pools["full"])
        outs["full"].append(jax.tree_util.tree_map(lambda a: a[None], out))
    stacked = {
        k: jax.tree_util.tree_map(lambda *a: jnp.concatenate(a, axis=0), *v)
        for k, v in outs.items()}
    return x, stacked, pools


def _logits(x, params, cfg: CohereConfig):
    h = layernorm(x, params["final_norm"], cfg.norm_eps)
    return cfg.logit_scale * jnp.einsum(
        "...d,vd->...v", h, params["embed"], preferred_element_type=jnp.float32)


# float32 bytes of the heads rope turns at once over a whole prompt: all 128
# heads of 16,384 positions are a gigabyte in float32, twice over before the
# cast back (1.8 GiB of the prefill program's temporaries, compiled for the
# chip)
ROPE_BYTES = 2 ** 27


def _rope_heads(x, cos, sin):
    """Rope over whole sequences, some heads at a time. x: [B, H, T, hd];
    cos, sin: [T, hd / 2]."""
    b, h, t, hd = x.shape
    most = max(1, ROPE_BYTES // (4 * b * t * hd))
    n = max(d for d in range(1, min(most, h) + 1) if h % d == 0)
    if n == h:
        return rope_adjacent(x, cos, sin)
    turned = jax.lax.map(
        lambda heads: rope_adjacent(heads, cos, sin),
        jnp.moveaxis(x.reshape(b, h // n, n, t, hd), 1, 0))
    return jnp.moveaxis(turned, 0, 1).reshape(x.shape)


def _prefill_block(cfg: CohereConfig, cos, sin, experts):
    """The block over whole sequences. x: [B, T, D]; out: the roped K and V
    of the positions, ``[B, T, Hkv, hd]`` each."""
    def block(x, lp, kind, layer, _in_kind, _pool):
        b, t, _ = x.shape
        h = layernorm(x, lp["norm"], cfg.norm_eps)
        q, k, v = (a.swapaxes(1, 2) for a in _qkv(h, lp, cfg))  # [B, H, T, hd]
        if kind == "window":
            q, k = _rope_heads(q, cos, sin), _rope_heads(k, cos, sin)
        att = attention(
            q, k, v, causal=True,
            window=cfg.sliding_window if kind == "window" else None)
        att = att.swapaxes(1, 2).reshape(b, t, cfg.n_heads * cfg.head_dim)
        ffn, _ = _ffn(x, lp, cfg, experts, layer)
        return x + att @ lp["wo"] + ffn, None, (k.swapaxes(1, 2), v.swapaxes(1, 2))

    return block


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: CohereConfig,
            mesh=None) -> jnp.ndarray:
    """tokens [B, T] -> logits [B, T, V] float32. Teacher-forced, no cache."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("this model does not run under a mesh yet")
    cos, sin = rope_table(cfg, tokens.shape[1])
    block = _prefill_block(cfg, cos, sin, _expert_stack(params))
    x, _, _ = _stack(params["embed"][tokens], params, cfg, block)
    return _logits(x, params, cfg)


# --------------------------------------------------------------------- #
# serving: prefill and the paged decode step
# --------------------------------------------------------------------- #
def prefill(params, prompt: jnp.ndarray, cfg: CohereConfig, table):
    """One batched pass over prompts [B, P]. Returns (last-position logits
    [B, V] float32, {kind: (K, V)}: each kind's roped keys and values of
    positions [0, P), ``[layers of the kind, B, P, Hkv, hd]``)."""
    p = prompt.shape[1]
    block = _prefill_block(cfg, table[0][:p], table[1][:p], _expert_stack(params))
    x, kv, _ = _stack(params["embed"][prompt], params, cfg, block)
    return _logits(x[:, -1], params, cfg), kv


def decode_step_paged(
    params, cache: Dict[str, jnp.ndarray], token: jnp.ndarray, pos: jnp.ndarray,
    block_tables: Dict[str, jnp.ndarray], cfg: CohereConfig, table,
    kernel: Optional[bool] = None,
):
    """One decode step over the pool's two kinds of leaf. token, pos: [B]
    int32; ``block_tables``: ``{"full", "window"}``, each ``[B, max_blocks]``
    (a window kind's table names the trash block before the window's first
    block: those blocks were given back); ``cache``: ``k_full``, ``v_full``,
    ``k_window``, ``v_window``, each ``[layers of the kind, N of the kind,
    Hkv, bs, hd]``, carried through the layers as ``[layers * N * Hkv, bs,
    hd]`` (``models/generation.py::flat_pages``). Each row's new key and
    value are written at ``pos`` through the kind's table, then its heads
    attend ``[0, pos]`` on a full layer and ``[pos - W + 1, pos]`` on a
    window layer: the kernel where Pallas is native (``kernel`` None defers
    to ``paged_kernel_enabled()``), told the row's first live position on a
    window layer so that its walk starts there; else a gather of the row's
    pages under the same mask. Nothing is sliced out of the pool, so a
    caller that donates it gets it back updated in place.

    Returns (logits [B, V] float32, cache, counters [4] int32 in the order
    of ``DECODE_COUNTERS``, over all B rows of the step, free slots' dummy
    rows among them: what the step computed)."""
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    b = token.shape[0]
    nkv, bs, hd = cache["k_full"].shape[2:]
    n_pages = {kind: cache["k_" + kind].shape[1] for kind in KINDS}
    c, s = table[0][pos], table[1][pos]  # [B, hd/2]
    off = pos % bs
    phys = {kind: jnp.take_along_axis(
        block_tables[kind], (pos // bs)[:, None], axis=1)[:, 0] for kind in KINDS}
    first_live = jnp.maximum(pos - cfg.sliding_window + 1, 0)
    cols = jnp.arange(block_tables["full"].shape[1] * bs)[None, :]
    valid = {"full": cols <= pos[:, None],
             "window": (cols <= pos[:, None]) & (cols >= first_live[:, None])}
    experts = _expert_stack(params)

    def block(x, lp, kind, layer, in_kind, pool):
        first = in_kind * n_pages[kind]  # this layer's pages of the stack
        h = layernorm(x, lp["norm"], cfg.norm_eps)
        q, k, v = _qkv(h, lp, cfg)
        if kind == "window":
            q = rope_adjacent(q, c[:, None, :], s[:, None, :])
            k = rope_adjacent(k, c[:, None, :], s[:, None, :])
        # free slots all write the trash block: duplicates there are harmless
        k_flat, v_flat = (
            write_rows(flat, first + phys[kind], off, new)
            for flat, new in zip(pool, (k, v)))
        tables = block_tables[kind] + first
        qf = q.reshape(b, nkv, -1, hd)  # GQA: [B, Hkv, G, hd]
        if use_kernel:
            att = paged_decode_attention(
                qf.astype(jnp.float32), k_flat.reshape(-1, nkv, bs, hd),
                v_flat.reshape(-1, nkv, bs, hd), tables, pos,
                first=first_live if kind == "window" else None)
        else:
            att = cached_attention(
                qf, gather_pages(k_flat, tables, nkv),
                gather_pages(v_flat, tables, nkv),
                valid[kind][:, None, None, :])
        att = att.astype(x.dtype).reshape(b, cfg.n_heads * hd)
        ffn, sizes = _ffn(x, lp, cfg, experts, layer)
        counters = jnp.stack([
            jnp.sum(sizes > 0), jnp.sum(sizes), jnp.max(sizes),
            jnp.int32(b * cfg.expert_top_k)]).astype(jnp.int32)
        return x + att @ lp["wo"] + ffn, (k_flat, v_flat), counters

    x, counters, pools = _stack(
        params["embed"][token], params, cfg, block,
        pools={kind: tuple(flat_pages(cache[n + kind]) for n in ("k_", "v_"))
               for kind in KINDS})
    cache = {n + kind: flat.reshape(cache[n + kind].shape)
             for kind in KINDS for n, flat in zip(("k_", "v_"), pools[kind])}
    total = sum(jnp.sum(counters[kind], axis=0) for kind in KINDS)
    return _logits(x, params, cfg), cache, total


class CohereServing:
    """The model's side of the serving contract (see
    ``models/generation.py::LlamaServing`` for the contract): no
    speculation, no block shipments, and a pool whose leaves are of two
    kinds, which ``paged_block_leaves`` states as a fourth entry: 0 for the
    full kind, the window's width for the window kind."""

    name = "parallel-block window/full MoE decoder (models/cohere.py)"
    speculation = False
    counters = DECODE_COUNTERS

    def __init__(self, cfg: CohereConfig):
        self.cfg = cfg

    def rope_table(self, max_len: int):
        return rope_table(self.cfg, max_len)

    def paged_block_leaves(self, block_size: int):
        """leaf -> (layers, shape of one block in one layer, dtype, window:
        0 where the layers attend every position)."""
        cfg = self.cfg
        block = (cfg.n_kv_heads, block_size, cfg.head_dim)
        return {
            n + kind: (cfg.layers_of(kind), block, cfg.dtype,
                       cfg.sliding_window if kind == "window" else 0)
            for kind in ("full", "window") for n in ("k_", "v_")}

    def cache_bytes_per_position(self) -> int:
        """Through every layer, for a position every kind still holds (a
        window kind holds the last ``sliding_window`` only)."""
        cfg = self.cfg
        return (2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)

    def prefill_blocks(self, params, prompt_row, n_blocks, block_size, table):
        """prompt_row [1, P] (P <= n_blocks * block_size) -> the pool's
        leaves for those positions cut into blocks: [layers, n_blocks, Hkv,
        block_size, hd]. Every block of every kind: which of them a window
        kind keeps is its write table's to say."""
        cfg = self.cfg
        _, kv = prefill(params, prompt_row, cfg, table)
        grow = n_blocks * block_size - prompt_row.shape[1]

        def blocks(rows):  # [l, 1, P, Hkv, hd]
            rows = jnp.pad(rows[:, 0], ((0, 0), (0, grow), (0, 0), (0, 0)))
            return rows.reshape(
                rows.shape[0], n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim
            ).transpose(0, 1, 3, 2, 4)

        return {n + kind: blocks(rows)
                for kind in KINDS for n, rows in zip(("k_", "v_"), kv[kind])}

    def decode_paged(self, params, cache, token, pos, tables, table):
        return decode_step_paged(params, cache, token, pos, tables, self.cfg, table)
