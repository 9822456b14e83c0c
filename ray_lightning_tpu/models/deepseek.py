"""A decoder with multi-head latent attention and sigmoid-routed experts
(the published DeepSeek-V3-style block): teacher-forced forward, loss,
prefill and a paged decode step over a LATENT pool.

What differs from ``models/llama.py``, and is why this is a model of its own:

- two kinds of layer in one stack: ``n_dense_layers`` leading blocks with a
  plain SwiGLU, then expert blocks, each group scanned by itself
  (``params["dense_layers"]``, ``params["moe_layers"]``);
- latent attention. Queries go through a low-rank pair with an RMSNorm
  between (``wq_a``, ``q_norm``, ``wq_b``) to heads of ``qk_nope_head_dim +
  qk_rope_head_dim`` columns, rope on the last ``qk_rope_head_dim`` only.
  Keys and values come from ONE compressed row a position: ``x @ wkv_a``
  gives ``kv_lora_rank`` columns (normed: ``c_kv``) and ``qk_rope_head_dim``
  more (roped: ``k_r``, one key head shared by all query heads). That row is
  all the cache holds. The stated head sizes are the config's, not
  ``dim / n_heads``; scores are scaled by ``(nope + rope) ** -0.5``;
- two forms of the same attention. Prefill decompresses: ``[k_nope | v] =
  c_kv @ wkv_b`` a head, ``k = [k_nope | k_r]``, causal attention with keys
  wider than values (``ops/attention.py``). Decode absorbs ``wkv_b`` into
  the query and the output instead (``q' = q_nope @ W_kb^T``, scores
  ``q' . c_kv + q_rope . k_r``, head output ``(softmax . c_kv) @ W_vb``), so
  a tick reads the cached rows once for all heads and never rebuilds K or V
  (``ops/paged_attention.py::mla_paged_decode_attention``). Equal in exact
  arithmetic; ``tests/test_deepseek.py`` holds them together;
- the expert layer: sigmoid scores in float32, a selection bias that picks
  but does not weigh, top-k renormalised and scaled, plus shared experts
  every token takes (``parallel/moe.py::route_sigmoid_bias``,
  ``moe_ffn_routed``: only the routed pairs are computed, none dropped).

Rope pairs ADJACENT columns ``(2i, 2i + 1)`` of the rope part
(``rope_interleave``); the rotated halves are kept apart (all ``2i`` then
all ``2i + 1``) in queries and cached keys alike, which leaves every score
as it is.

Not here: groups in the router (``n_group`` 1 only), rope scaling, a
multi-token-prediction module, a mesh. ``DeepseekConfig`` refuses what it
cannot run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.losses import masked_softmax_cross_entropy
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.ops.rope import rope_adjacent as _rope, rope_angles
from ray_lightning_tpu.parallel.moe import moe_ffn_routed, route_sigmoid_bias

# counters the paged decode step returns, summed over its expert layers:
# distinct experts chosen, (row, expert) pairs, the fullest expert's rows
DECODE_COUNTERS = ("moe_expert_hits", "moe_routed_pairs", "moe_max_expert_rows")
LANES = 128
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 5  # all blocks, the leading dense ones among them
    n_dense_layers: int = 1
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 7168  # the dense blocks' SwiGLU
    moe_ffn_dim: int = 768  # one expert's; a shared expert is as wide
    n_experts: int = 256
    n_shared_experts: int = 1
    expert_top_k: int = 8
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    max_seq: int = 4096
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    sliding_window: int = 0  # none; the paged pool asks every config

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of n_layers={self.n_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even: rope turns pairs")
        if self.sliding_window:
            raise ValueError("latent attention here is dense causal: no window")
        if self.expert_top_k > self.n_experts:
            raise ValueError("expert_top_k exceeds n_experts")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What the model caches a position a layer: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The pool's row: ``latent_width`` rounded up to the chip's 128
        lanes with zero columns (the chip lays a 576-wide array out 640
        wide whatever it is told, and copies only whole lanes)."""
        return -(-self.latent_width // LANES) * LANES

    @property
    def sm_scale(self) -> float:
        return float(self.qk_head_dim) ** -0.5

    def serving(self):
        """What ``InferenceEngine`` and the paged pool ask of a model."""
        return DeepseekServing(self)


# --------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------- #
def _attn_shapes(cfg: DeepseekConfig) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape of one layer, fan_in; 0 marks a norm weight)."""
    d, h = cfg.dim, cfg.n_heads
    return {
        "attn_norm": ((d,), 0),
        "wq_a": ((d, cfg.q_lora_rank), d),
        "q_norm": ((cfg.q_lora_rank,), 0),
        "wq_b": ((cfg.q_lora_rank, h * cfg.qk_head_dim), cfg.q_lora_rank),
        "wkv_a": ((d, cfg.latent_width), d),
        "kv_norm": ((cfg.kv_lora_rank,), 0),
        "wkv_b": ((cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                  cfg.kv_lora_rank),
        "wo": ((h * cfg.v_head_dim, d), h * cfg.v_head_dim),
        "mlp_norm": ((d,), 0),
    }


def _mlp_shapes(d: int, f: int, lead: Tuple[int, ...] = ()):
    return {"w_gate": (lead + (d, f), d), "w_up": (lead + (d, f), d),
            "w_down": (lead + (f, d), f)}


def init_params(rng: jax.Array, cfg: DeepseekConfig) -> Dict[str, Any]:
    """Random parameters in the tree the forward takes. Matrices normal with
    variance 1 / fan_in, norms 1, the router and its selection bias float32
    (the bias small and not zero, so it moves some choices)."""
    def make(key, shape, fan_in, dtype=cfg.dtype):
        if fan_in == 0:
            return jnp.ones(shape, dtype)
        return (jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5).astype(dtype)

    def group(key, n, shapes, dtype=cfg.dtype):
        keys = jax.random.split(key, len(shapes))
        return {name: make(k, (n,) + shape, fan, dtype)
                for k, (name, (shape, fan)) in zip(keys, sorted(shapes.items()))}

    ks = jax.random.split(rng, 10)
    d, e = cfg.dim, cfg.n_experts
    dense = group(ks[0], cfg.n_dense_layers,
                  {**_attn_shapes(cfg), **_mlp_shapes(d, cfg.ffn_dim)})
    moe_layers = group(ks[1], cfg.n_moe_layers, _attn_shapes(cfg))
    moe = group(ks[2], cfg.n_moe_layers, _mlp_shapes(d, cfg.moe_ffn_dim, (e,)))
    moe["router"] = make(ks[3], (cfg.n_moe_layers, d, e), d, jnp.float32)
    moe["bias"] = 0.1 * jax.random.normal(ks[4], (cfg.n_moe_layers, e), jnp.float32)
    moe["shared"] = group(
        ks[5], cfg.n_moe_layers,
        _mlp_shapes(d, cfg.moe_ffn_dim * cfg.n_shared_experts))
    moe_layers["moe"] = moe
    return {
        "embed": make(ks[6], (cfg.vocab_size, d), d),
        "dense_layers": dense, "moe_layers": moe_layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": make(ks[7], (d, cfg.vocab_size), d),
    }


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #
def rope_table(cfg: DeepseekConfig, length: int):
    return rope_angles(length, cfg.qk_rope_head_dim, cfg.rope_theta)


def _queries(h, lp, cfg: DeepseekConfig):
    """h: [..., D] -> (q_nope [..., H, nope], q_rope [..., H, rope], not
    roped yet)."""
    c_q = rmsnorm(h @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    q = (c_q @ lp["wq_b"]).reshape(*h.shape[:-1], cfg.n_heads, cfg.qk_head_dim)
    return q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def _latent(h, lp, cfg: DeepseekConfig):
    """h: [..., D] -> (c_kv [..., rank] normed, k_r [..., rope] not roped)."""
    ckv = h @ lp["wkv_a"]
    c_kv = rmsnorm(ckv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg.norm_eps)
    return c_kv, ckv[..., cfg.kv_lora_rank:]


def _wkv_b(lp, cfg: DeepseekConfig):
    """``wkv_b`` a head: (W_kb [rank, H, nope], W_vb [rank, H, v])."""
    w = lp["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _swiglu(h, p):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _ffn(x, lp, cfg: DeepseekConfig, experts=None, layer=None):
    """The block's second half on x: [..., D]. ``experts`` and ``layer``:
    the expert stacks of ALL expert layers (``_layer_groups``) and which of
    them this is. Returns (x, sizes): the rows each expert got (int32, one
    bin an expert of every expert layer, this layer's alone not zero) or
    None for a dense block."""
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    if "moe" not in lp:
        return x + _swiglu(h, lp), None
    moe = lp["moe"]
    flat = h.reshape(-1, h.shape[-1])
    idx, w = route_sigmoid_bias(
        flat, moe["router"], moe["bias"], cfg.expert_top_k,
        scale=cfg.routed_scaling, renormalize=cfg.norm_topk_prob)
    routed, sizes = moe_ffn_routed(experts, flat, idx + layer * cfg.n_experts, w)
    return x + routed.reshape(x.shape) + _swiglu(h, moe["shared"]), sizes


def _attend_prefill(x, lp, cfg: DeepseekConfig, cos, sin):
    """The decompressed form over whole sequences. x: [B, T, D]. Returns
    (x, latent [B, T, latent_width]: what the cache holds of these
    positions)."""
    b, t, _ = x.shape
    h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q_nope, q_rope = _queries(h, lp, cfg)
    c_kv, k_r = _latent(h, lp, cfg)
    q_rope = _rope(q_rope, cos[:, None, :], sin[:, None, :])
    k_r = _rope(k_r, cos, sin)
    w_kb, w_vb = _wkv_b(lp, cfg)
    k_nope = jnp.einsum("btr,rhd->bthd", c_kv, w_kb)
    v = jnp.einsum("btr,rhd->bthd", c_kv, w_vb)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], q_rope.shape)], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    att = attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=True,
        sm_scale=cfg.sm_scale)  # [B, H, T, v]
    att = att.swapaxes(1, 2).reshape(b, t, cfg.n_heads * cfg.v_head_dim)
    return x + att @ lp["wo"], jnp.concatenate([c_kv, k_r], axis=-1)


def _layer_groups(params):
    """((name, the leaves a scan slices a layer at a time), ...) and the
    expert stacks of all expert layers as ONE stack ``[Lm * E, ...]`` (a
    reshape of the leading axes, no copy). The experts are not scanned
    over: a scan slices its operands, and a slice handed to a kernel is a
    copy, 2.4 GB a layer a tick at the published widths (45 ms of decode
    program where 15 are needed, on the chip). Instead every expert layer
    sees the whole stack and offsets its experts' numbers by ``layer * E``;
    the grouped matmul reads only the groups that hold rows."""
    moe_layers = params["moe_layers"]
    moe = moe_layers["moe"]
    experts = {k: moe[k].reshape((-1,) + moe[k].shape[2:]) for k in EXPERT_STACKS}
    rest = dict(moe_layers, moe={k: v for k, v in moe.items() if k not in EXPERT_STACKS})
    return (("dense", params["dense_layers"]), ("moe", rest)), experts


def _stack(x, params, cfg: DeepseekConfig, block, pools=None):
    """Both groups of layers, each scanned: ``block(x, lp, experts, layer,
    pool) -> (x, pool, out)``. ``pools``: a leaf a group, which rides that
    group's scan as its CARRY beside x: the block writes its rows into the
    leaf it is handed and hands it on, so under the caller's donation the
    buffer that goes in is the one that comes out (scanned over, a leaf is
    sliced a layer and stacked into a second buffer). Returns (x, {"dense":
    the group's stacked outs, "moe": ...}, the pools as the scans left them)."""
    groups, experts = _layer_groups(params)
    outs, carried = {}, {}
    for name, leaves in groups:
        count = jax.tree_util.tree_leaves(leaves)[0].shape[0]

        def body(carry, a):
            x, pool, out = block(carry[0], a[0], experts, a[1], carry[1])
            return (x, pool), out

        (x, carried[name]), outs[name] = jax.lax.scan(
            body, (x, None if pools is None else pools[name]),
            (leaves, jnp.arange(count, dtype=jnp.int32)))
    return x, outs, carried


def forward(
    params: Dict[str, Any], tokens: jnp.ndarray, cfg: DeepseekConfig,
    mesh=None,
) -> jnp.ndarray:
    """tokens [B, T] -> logits [B, T, V]. Teacher-forced, no cache."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("this model does not run under a mesh yet")
    cos, sin = rope_table(cfg, tokens.shape[1])

    def block(x, lp, experts, layer, _):
        x, _ = _attend_prefill(x, lp, cfg, cos, sin)
        x, _ = _ffn(x, lp, cfg, experts, layer)
        return x, None, None

    if cfg.remat:
        block = jax.checkpoint(block)
    x, _, _ = _stack(params["embed"][tokens], params, cfg, block)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]


def lm_loss(params, tokens, cfg: DeepseekConfig, mesh=None):
    """Next-token cross-entropy (no balancing term: the selection bias is
    held fixed, as the published recipe moves it outside the gradient)."""
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    total, count = masked_softmax_cross_entropy(
        forward(params, tokens, cfg, mesh), targets, mask)
    loss = total / count
    return loss, {"loss": loss, "ppl": jnp.exp(loss)}


# --------------------------------------------------------------------- #
# serving: prefill and the paged decode step
# --------------------------------------------------------------------- #
def prefill(params, prompt: jnp.ndarray, cfg: DeepseekConfig, table):
    """One batched pass over prompts [B, P]. Returns (last-position logits
    [B, V] float32, {"dense", "moe"}: each group's cached rows of positions
    [0, P), [layers of the group, B, P, latent_width])."""
    p = prompt.shape[1]
    cos, sin = table[0][:p], table[1][:p]

    def block(x, lp, experts, layer, _):
        x, latent = _attend_prefill(x, lp, cfg, cos, sin)
        x, _ = _ffn(x, lp, cfg, experts, layer)
        return x, None, latent

    x, latent, _ = _stack(params["embed"][prompt], params, cfg, block)
    h = rmsnorm(x[:, -1], params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    return logits, latent


def absorbed_attention(q_lat, q_rope, rows, valid, cfg: DeepseekConfig):
    """The absorbed form in plain ``jax.numpy``. q_lat: [B, H, rank],
    q_rope: [B, H, rope] (roped), rows: [B, C, >= latent_width] cached rows
    in position order, valid: [B, C] bool. Returns float32 [B, H, rank]:
    ``softmax(q_lat . c_kv + q_rope . k_r) . c_kv``."""
    rows = rows.astype(jnp.float32)
    r = cfg.kv_lora_rank
    s = (jnp.einsum("bhr,bcr->bhc", q_lat.astype(jnp.float32), rows[..., :r])
         + jnp.einsum("bhd,bcd->bhc", q_rope.astype(jnp.float32),
                      rows[..., r: cfg.latent_width])) * cfg.sm_scale
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    return jnp.einsum("bhc,bcr->bhr", jax.nn.softmax(s, axis=-1), rows[..., :r])


def decode_step_paged(
    params, cache: Dict[str, jnp.ndarray], token: jnp.ndarray, pos: jnp.ndarray,
    block_tables: jnp.ndarray, cfg: DeepseekConfig, table,
    kernel: Optional[bool] = None,
):
    """One decode step over the latent paged pool. token, pos: [B] int32;
    block_tables: [B, max_blocks]; ``cache["dense"]``, ``cache["moe"]``:
    [layers of the group, N, bs, latent_row], a leaf a group, which that
    group's scan carries (``_stack``) as ``[layers * N, bs, latent_row]``, a
    reshape of leading axes. Each row's new latent is written at ``pos``
    (through its table, into page ``layer * N + physical block`` of that
    stack), then its heads attend positions [0, pos] in the absorbed form
    over the WHOLE stack through tables offset by ``layer * N``: the kernel
    where Pallas is native (``kernel`` None defers to
    ``paged_kernel_enabled()``), else a gather of the row's pages. Nothing
    is sliced out of the pool, so a caller that donates it (the engine does)
    gets it back updated in place.

    Returns (logits [B, V] float32, cache, counters [3] int32 in the order
    of ``DECODE_COUNTERS``, over all B rows of the step, free slots' dummy
    rows among them: what the step computed)."""
    from ray_lightning_tpu.ops.paged_attention import (
        mla_paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    n_pages, bs, row = cache["moe"].shape[1:]
    n_cols = block_tables.shape[1]
    b = token.shape[0]
    c, s = table[0][pos], table[1][pos]  # [B, rope/2]
    phys = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    valid = jnp.arange(n_cols * bs)[None, :] <= pos[:, None]
    pad = row - cfg.latent_width

    def block(x, lp, experts, layer, pool):  # pool: [layers * N, bs, row]
        first = layer * n_pages  # this layer's pages are [first, first + N)
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q_nope, q_rope = _queries(h, lp, cfg)
        c_kv, k_r = _latent(h, lp, cfg)
        q_rope = _rope(q_rope, c[:, None, :], s[:, None, :])
        new = jnp.concatenate(
            [c_kv, _rope(k_r, c, s), jnp.zeros((b, pad), c_kv.dtype)], axis=-1)
        # free slots all write the trash block: duplicates there are harmless
        pool = pool.at[first + phys, off].set(new.astype(pool.dtype))
        tables = block_tables + first
        w_kb, w_vb = _wkv_b(lp, cfg)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_kb)
        if use_kernel:
            q_row = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros((b, cfg.n_heads, pad), q_lat.dtype)],
                axis=-1)
            u = mla_paged_decode_attention(
                q_row, pool, tables, pos,
                v_width=cfg.kv_lora_rank, sm_scale=cfg.sm_scale)
        else:
            rows = pool[tables].reshape(b, n_cols * bs, row)
            u = absorbed_attention(q_lat, q_rope, rows, valid, cfg)
        att = jnp.einsum("bhr,rhd->bhd", u.astype(x.dtype), w_vb)
        x = x + att.reshape(b, cfg.n_heads * cfg.v_head_dim) @ lp["wo"]
        x, sizes = _ffn(x, lp, cfg, experts, layer)
        if sizes is None:
            counters = jnp.zeros((3,), jnp.int32)
        else:
            counters = jnp.stack(
                [jnp.sum(sizes > 0), jnp.sum(sizes), jnp.max(sizes)]).astype(jnp.int32)
        return x, pool, counters

    x, counters, pools = _stack(
        params["embed"][token], params, cfg, block,
        pools={name: leaf.reshape((-1,) + leaf.shape[2:])
               for name, leaf in cache.items()})
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return (logits,
            {name: pool.reshape(cache[name].shape) for name, pool in pools.items()},
            jnp.sum(counters["moe"], axis=0))


class DeepseekServing:
    """The model's side of the serving contract (see
    ``models/generation.py::LlamaServing`` for the contract): no
    speculation, no block shipments, and a pool of one leaf a group of
    layers whose block is ``[block_size, latent_row]``."""

    name = "latent-attention MoE decoder (models/deepseek.py)"
    speculation = False
    counters = DECODE_COUNTERS

    def __init__(self, cfg: DeepseekConfig):
        self.cfg = cfg

    def rope_table(self, max_len: int):
        return rope_table(self.cfg, max_len)

    def paged_block_leaves(self, block_size: int):
        """leaf -> (layers, shape of one block in one layer, dtype)."""
        cfg = self.cfg
        block = (block_size, cfg.latent_row)
        return {"dense": (cfg.n_dense_layers, block, cfg.dtype),
                "moe": (cfg.n_moe_layers, block, cfg.dtype)}

    def cache_bytes_per_position(self) -> int:
        """Through every layer, as the pool lays it out."""
        cfg = self.cfg
        return cfg.n_layers * cfg.latent_row * jnp.dtype(cfg.dtype).itemsize

    def prefill_blocks(self, params, prompt_row, n_blocks, block_size, table):
        """prompt_row [1, P] (P <= n_blocks * block_size) -> the pool's
        leaves for those positions cut into blocks: [layers, n_blocks, ...]."""
        cfg = self.cfg
        _, latent = prefill(params, prompt_row, cfg, table)  # [l, 1, P, w]
        grow = n_blocks * block_size - prompt_row.shape[1]
        pad = ((0, 0), (0, grow), (0, cfg.latent_row - cfg.latent_width))
        return {
            name: jnp.pad(rows[:, 0], pad).reshape(
                rows.shape[0], n_blocks, block_size, cfg.latent_row)
            for name, rows in latent.items()}

    def decode_paged(self, params, cache, token, pos, tables, table):
        return decode_step_paged(
            params, cache, token, pos, tables["full"], self.cfg, table)


# --------------------------------------------------------------------- #
# LightningModule wrapper
# --------------------------------------------------------------------- #
class DeepseekModule(LightningModule):
    """Decoder-LM pretraining step on :func:`lm_loss`, the optimizer
    ``LlamaModule`` sets (AdamW b1 0.9 b2 0.95 under warm-up + cosine)."""

    def __init__(self, config: DeepseekConfig, lr: float = 3e-4,
                 warmup_steps: int = 100, total_steps: int = 10000,
                 weight_decay: float = 0.1):
        super().__init__()
        self.config = config
        self.lr, self.warmup_steps = lr, warmup_steps
        self.total_steps, self.weight_decay = total_steps, weight_decay
        self.mesh = None

    def init_params(self, rng):
        return init_params(rng, self.config)

    def _tokens_of(self, batch):
        return batch["input_ids"] if isinstance(batch, dict) else batch

    def training_step(self, params, batch, batch_idx):
        loss, logs = lm_loss(params, self._tokens_of(batch), self.config, self.mesh)
        self.log("train_loss", loss, on_step=True, on_epoch=True)
        self.log("train_ppl", logs["ppl"], on_step=True, on_epoch=False)
        return loss

    def validation_step(self, params, batch, batch_idx):
        loss, logs = lm_loss(params, self._tokens_of(batch), self.config, self.mesh)
        self.log("val_loss", loss)
        self.log("val_ppl", logs["ppl"])

    def predict_step(self, params, batch, batch_idx):
        return forward(params, self._tokens_of(batch), self.config, self.mesh)

    def configure_optimizers(self):
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup_steps,
            max(self.total_steps, self.warmup_steps + 1))
        # the router's selection bias is held fixed: no gradient reaches it
        # (it picks and does not weigh), and no decay may move it either
        decayed = lambda params: jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) != "bias", params)
        return optax.adamw(schedule, b1=0.9, b2=0.95,
                           weight_decay=self.weight_decay, mask=decayed)
