"""A decoder that mixes Mamba-1 selective-state-space layers with a few
attention layers (the published ``jamba`` model type at ``num_experts`` 1):
teacher-forced forward, prefill and a paged decode step. Inference only.

What differs from the other models here, and is why this is one of its own:

- layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset`` and a Mamba layer everywhere else; every layer's
  feed-forward is the dense SwiGLU. All norms are RMSNorm; the head is the
  embedding, tied. There is NO positional encoding of any kind: the
  recurrence orders the sequence.
- an attention layer is GQA (here 20 query heads over ONE key/value head),
  causal, no bias, no rope, no window.
- a Mamba layer: ``[x, z] = W_in u``; a causal depthwise convolution of 4
  inputs and a ``silu`` on ``x``; ``[d, B, C] = W_x x``, an RMSNorm on each
  of the three; ``dt = softplus(W_dt d + b_dt)``; the selective scan ``S_t =
  exp(dt A) S_{t-1} + (dt x_t) B_t``, ``y_t = S_t C_t + D x_t`` with ``A =
  -exp(A_log)`` (``ops/selective_scan.py``); out ``= W_out (y * silu(z))``.
- the cache follows the kinds. The attention layers' K and V are paged leaves
  of the full kind. A Mamba layer keeps, a request, its scan state ``[N,
  C]`` float32 and its convolution's TAIL, the last 3 inputs ``[3, C]`` (flat in the pool):
  two leaves of the STATE kind (``serving/paged_kv.py``), a slot beside the K
  and V. Both keep the channels on the last axis, as the chip lays arrays
  out (``ops/selective_scan.py`` says why): the published ``A_log [C, N]``
  and ``conv1d.weight [C, 1, 4]`` are held transposed.
- the Mamba layers run as ``lax.scan``s over their stacked weights, one a
  run of consecutive Mamba layers (7, 13 and 6 of them at the published
  pattern), with both state leaves as the loop's carry; the attention layers
  stand between the runs as they are.

Precision: weights and activations in ``dtype``, products accumulated in
float32; norms, the convolution, ``dt``, ``A``, the scan and its state in
float32.

Not here: a mesh, a training step, experts (``num_experts`` 1 only),
speculation, prefix sharing (a shared block says nothing of the state behind
it). ``JambaConfig`` and the engine refuse what cannot run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generation import (
    cached_attention,
    flat_pages,
    gather_pages,
    write_rows,
)
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.ops.selective_scan import (
    causal_conv,
    conv_step,
    mamba_decode,
    mamba_scan,
)

ATTENTION, MAMBA = "attention", "mamba"


@dataclass(frozen=True)
class JambaConfig:
    """The published ``config.json``'s keys under their own names, and
    beside them ``head_dim`` (which it leaves to ``hidden_size /
    num_attention_heads``), ``max_seq`` and ``dtype``."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    head_dim: Optional[int] = None
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    sliding_window: Optional[int] = None
    max_position_embeddings: int = 262144
    num_logits_to_keep: int = 1
    use_mamba_kernels: bool = True
    model_type: str = "jamba"
    max_seq: int = 3072
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for key, want in (("num_experts", 1), ("num_experts_per_tok", 1),
                          ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                          ("hidden_act", "silu"), ("tie_word_embeddings", True),
                          ("sliding_window", None), ("model_type", "jamba")):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: this model runs {want!r} only")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset must lie inside attn_layer_period")

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of each layer, as the model type computes it."""
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset else MAMBA
            for i in range(self.num_hidden_layers))

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def runs(self) -> List[Tuple[str, int, int]]:
        """The stack as runs of one kind: (kind, the run's first layer
        counted among its kind, how many). An attention layer is a run of
        one."""
        out: List[Tuple[str, int, int]] = []
        seen = {ATTENTION: 0, MAMBA: 0}
        for kind in self.kinds:
            if kind == MAMBA and out and out[-1][0] == MAMBA:
                out[-1] = (MAMBA, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, seen[kind], 1))
            seen[kind] += 1
        return out

    def serving(self):
        """What ``InferenceEngine`` and the paged pool ask of a model."""
        return JambaServing(self)


# --------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------- #
def layer_shapes(cfg: JambaConfig, kind: str) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """leaf -> (shape, how it is made: a fan-in for a matrix, or one of
    ``"norm"``, ``"a_log"``, ``"dt_bias"``, ``"one"``) of one
    layer."""
    d, f, ci = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    n, r, k = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    mlp = {"norm_in": ((d,), "norm"), "norm_ff": ((d,), "norm"),
           "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f)}
    if kind == ATTENTION:
        q, kv = cfg.num_attention_heads * cfg.hd, cfg.num_key_value_heads * cfg.hd
        return {**mlp, "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
                "wo": ((q, d), q)}
    return {**mlp, "w_in": ((d, 2 * ci), d), "conv_w": ((k, ci), k), "conv_b": ((ci,), k),
            "w_x": ((ci, r + 2 * n), ci), "dt_norm": ((r,), "norm"),
            "b_norm": ((n,), "norm"), "c_norm": ((n,), "norm"),
            "w_dt": ((r, ci), r), "b_dt": ((ci,), "dt_bias"),
            "a_log": ((n, ci), "a_log"), "d": ((ci,), "one"), "w_out": ((ci, d), ci)}


def init_params(rng: jax.Array, cfg: JambaConfig) -> Dict[str, Any]:
    """Random parameters in the tree the forward takes: ``embed`` (the head
    too), ``final_norm``, ``mamba`` (one dict, every leaf stacked over the
    Mamba layers in the stack's order) and ``attn`` (a tuple, one dict an
    attention layer). Matrices normal with variance 1 / fan_in, norms 1, and
    the Mamba initialisation: ``A_log = log(1..N)`` a channel, ``D`` 1,
    ``b_dt`` such that ``softplus(b_dt)`` is log-uniform in [1e-3, 1e-1]."""
    def make(key, shape, how):
        if how == "norm" or how == "one":
            return jnp.ones(shape, cfg.dtype)
        if how == "a_log":
            rates = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(rates)[:, None], shape).astype(cfg.dtype)
        if how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                         * (jnp.log(1e-1) - jnp.log(1e-3)) + jnp.log(1e-3))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype)  # softplus's inverse
        return (jax.random.normal(key, shape, jnp.float32) / how ** 0.5).astype(cfg.dtype)

    def layer(key, kind):
        shapes = layer_shapes(cfg, kind)
        return {name: make(k, *shapes[name])
                for k, name in zip(jax.random.split(key, len(shapes)), sorted(shapes))}

    keys = jax.random.split(rng, cfg.num_hidden_layers + 1)
    by_kind = {ATTENTION: [], MAMBA: []}
    for key, kind in zip(keys, cfg.kinds):
        by_kind[kind].append(layer(key, kind))
    tree = {
        "embed": make(keys[-1], (cfg.vocab_size, cfg.hidden_size), cfg.hidden_size),
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "attn": tuple(by_kind[ATTENTION]),
    }
    if by_kind[MAMBA]:
        tree["mamba"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *by_kind[MAMBA])
    return tree


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #
def _dot(a, w):
    """``a @ w`` in the weights' type, kept in float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _logits(x, params, cfg: JambaConfig):
    h = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", h, params["embed"],
                      preferred_element_type=jnp.float32)


def _mlp(x, lp, cfg: JambaConfig):
    """The SwiGLU branch on the residual stream, which it norms itself."""
    h = rmsnorm(x, lp["norm_ff"], cfg.rms_norm_eps)
    return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _selective(x, lp, cfg: JambaConfig):
    """What the scan takes, from the convolution's output x [..., C]
    float32: (dt [..., C], B, C [..., N], A [N, C], D [C]), all float32."""
    r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
    proj = _dot(x, lp["w_x"])
    eps = cfg.rms_norm_eps
    f32 = lambda w: w.astype(jnp.float32)
    dlt = rmsnorm(proj[..., :r], f32(lp["dt_norm"]), eps)
    b = rmsnorm(proj[..., r: r + n], f32(lp["b_norm"]), eps)
    c = rmsnorm(proj[..., r + n:], f32(lp["c_norm"]), eps)
    dt = jax.nn.softplus(_dot(dlt, lp["w_dt"]) + f32(lp["b_dt"]))
    return dt, b, c, -jnp.exp(f32(lp["a_log"])), f32(lp["d"])


def _gated_out(y, z, lp):
    """``W_out (y * silu(z))``; y float32, z in the model's type."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype) @ lp["w_out"]


def _mamba_prefill(h, lp, cfg: JambaConfig, n_valid, kernel):
    """A Mamba mixer over one sequence. h: [T, D] normed; ``n_valid``: the
    positions that are real. Returns (mixer out [T, D], the state [N, C]
    float32 and the tail [K - 1, C] after position ``n_valid - 1``)."""
    xz = h @ lp["w_in"]
    ci = cfg.d_inner
    x, tail = causal_conv(xz[:, :ci], lp["conv_w"], lp["conv_b"], n_valid)
    dt, b, c, a, d = _selective(x, lp, cfg)
    y, state = mamba_scan(x, dt, b, c, a, d, n_valid, kernel=kernel)
    return _gated_out(y, xz[:, ci:], lp), state, tail


def _attention_prefill(h, lp, cfg: JambaConfig):
    """An attention mixer over one sequence. h: [T, D] normed. Returns
    (mixer out [T, D], (k, v [T, Hkv, hd]))."""
    t, hd = h.shape[0], cfg.hd
    q = (h @ lp["wq"]).reshape(t, -1, hd)
    k = (h @ lp["wk"]).reshape(t, -1, hd)
    v = (h @ lp["wv"]).reshape(t, -1, hd)
    att = attention(q.swapaxes(0, 1)[None], k.swapaxes(0, 1)[None],
                    v.swapaxes(0, 1)[None], causal=True)[0]
    return att.swapaxes(0, 1).reshape(t, -1).astype(h.dtype) @ lp["wo"], (k, v)


def _layer_of(stack, i):
    """Layer ``i`` (traced) of leaves stacked over layers."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False), stack)


def _indices(first: int, count: int):
    return jnp.arange(first, first + count, dtype=jnp.int32)


def _prefill_row(params, tokens, cfg: JambaConfig, n_valid, kernel=None):
    """Every layer over one sequence. tokens: [T]. Returns (x [T, D], the
    attention layers' (k, v) as a list, the Mamba layers' states [layers, N,
    C] and tails [layers, K - 1, C], or None where there is no such layer)."""
    eps = cfg.rms_norm_eps
    x = params["embed"][tokens]
    kv, states, tails = [], [], []

    def mamba_layer(x, i):
        lp = _layer_of(params["mamba"], i)
        mixed, state, tail = _mamba_prefill(
            rmsnorm(x, lp["norm_in"], eps), lp, cfg, n_valid, kernel)
        x = x + mixed
        return x + _mlp(x, lp, cfg), (state, tail)

    for kind, first, count in cfg.runs():
        if kind == MAMBA:
            x, (state, tail) = jax.lax.scan(mamba_layer, x, _indices(first, count))
            states.append(state)
            tails.append(tail)
        else:
            lp = params["attn"][first]
            mixed, kept = _attention_prefill(rmsnorm(x, lp["norm_in"], eps), lp, cfg)
            kv.append(kept)
            x = x + mixed
            x = x + _mlp(x, lp, cfg)
    if not states:
        return x, kv, None, None
    return x, kv, jnp.concatenate(states), jnp.concatenate(tails)


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: JambaConfig,
            mesh=None) -> jnp.ndarray:
    """tokens [B, T] -> logits [B, T, V] float32. Teacher-forced, no cache;
    one sequence after another."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("this model does not run under a mesh yet")

    def row(toks):
        x = _prefill_row(params, toks, cfg, None)[0]
        return _logits(x, params, cfg)

    return jax.lax.map(row, tokens)


# --------------------------------------------------------------------- #
# serving: prefill and the paged decode step
# --------------------------------------------------------------------- #
def prefill(params, prompt: jnp.ndarray, cfg: JambaConfig, length=None,
            kernel: Optional[bool] = None):
    """One pass over one prompt [1, P], padded behind ``length`` real tokens
    (None: all P). Returns (the logits of position ``length - 1`` [1, V]
    float32, cache): ``k``, ``v`` ``[attention layers, P, Hkv, hd]`` of
    positions [0, P) (what lies at and behind ``length`` is the padding's
    and nothing may read it), ``ssm`` ``[Mamba layers, N, C]`` float32 and
    ``conv`` ``[Mamba layers, K - 1, C]`` AS OF position ``length - 1``: a
    padded position reaches neither."""
    if prompt.shape[0] != 1:
        raise ValueError("prefill takes one prompt a call")
    p = prompt.shape[1]
    length = jnp.asarray(p if length is None else length, jnp.int32)
    x, kv, states, tails = _prefill_row(params, prompt[0], cfg, length, kernel)
    last = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(length - 1, 0), 1, axis=0)
    cache = {}
    if kv:
        cache["k"] = jnp.stack([k for k, _ in kv])
        cache["v"] = jnp.stack([v for _, v in kv])
    if states is not None:
        cache.update(ssm=states, conv=tails)
    return _logits(last, params, cfg), cache


def decode_step_paged(
    params, cache: Dict[str, jnp.ndarray], token: jnp.ndarray, pos: jnp.ndarray,
    block_tables: Dict[str, jnp.ndarray], cfg: JambaConfig,
    kernel: Optional[bool] = None,
):
    """One decode step over the pool. token, pos: [B] int32;
    ``block_tables``: ``{"full": [B, max_blocks]}``; ``cache``: ``k_full``,
    ``v_full`` ``[attention layers, N, Hkv, bs, hd]``, ``ssm_state`` ``[Mamba
    layers, B, N, C]`` float32 and ``conv_state`` ``[Mamba layers, B, (K -
    1) * C]``, row b of which is slot b's. An attention layer writes each row's
    key and value at ``pos`` and attends positions ``[0, pos]``: the paged
    kernel (``kernel`` None defers to ``paged_kernel_enabled()``), else a
    gather of the row's pages. A Mamba layer shifts each row's tail by its
    input and moves its state on one position. Nothing is sliced out of the
    pool, so a caller that donates it gets it back updated in place.

    Returns (logits [B, V] float32, cache, no counters: None)."""
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_enabled,
    )

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    eps = cfg.rms_norm_eps
    b = token.shape[0]
    pools = {}
    if "k_full" in cache:
        tables = block_tables["full"]
        n_pages, nkv, bs, hd = cache["k_full"].shape[1:]
        pools = {n: flat_pages(cache[n]) for n in ("k_full", "v_full")}
        off = pos % bs
        phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]

    def attention_layer(h, lp, place):
        first = place * n_pages  # this layer's pages of the stack
        k = (h @ lp["wk"]).reshape(b, nkv, hd)
        v = (h @ lp["wv"]).reshape(b, nkv, hd)
        k_flat = write_rows(pools["k_full"], first + phys, off, k)
        v_flat = write_rows(pools["v_full"], first + phys, off, v)
        pools.update(k_full=k_flat, v_full=v_flat)
        qf = (h @ lp["wq"]).reshape(b, nkv, -1, hd)  # GQA: [B, Hkv, G, hd]
        if use_kernel:
            att = paged_decode_attention(
                qf.astype(jnp.float32), k_flat.reshape(-1, nkv, bs, hd),
                v_flat.reshape(-1, nkv, bs, hd), tables + first, pos)
        else:
            cols = jnp.arange(tables.shape[1] * bs)[None, :]
            att = cached_attention(
                qf, gather_pages(k_flat, tables + first, nkv),
                gather_pages(v_flat, tables + first, nkv),
                (cols <= pos[:, None])[:, None, None, :])
        return att.astype(h.dtype).reshape(b, -1) @ lp["wo"]

    def mamba_layer(carry, i):
        x, states, tails = carry
        lp = _layer_of(params["mamba"], i)
        xz = rmsnorm(x, lp["norm_in"], eps) @ lp["w_in"]
        ci = cfg.d_inner
        xc, tails = conv_step(xz[:, :ci], tails, i, lp["conv_w"], lp["conv_b"])
        dt, bm, cm, a, d = _selective(xc, lp, cfg)
        y, states = mamba_decode(xc, dt, bm, cm, a, d, states, i, kernel=use_kernel)
        x = x + _gated_out(y, xz[:, ci:], lp)
        return (x + _mlp(x, lp, cfg), states, tails), None

    x = params["embed"][token]
    states, tails = cache.get("ssm_state"), cache.get("conv_state")
    for kind, first, count in cfg.runs():
        if kind == MAMBA:
            (x, states, tails), _ = jax.lax.scan(
                mamba_layer, (x, states, tails), _indices(first, count))
        else:
            lp = params["attn"][first]
            x = x + attention_layer(rmsnorm(x, lp["norm_in"], eps), lp, first)
            x = x + _mlp(x, lp, cfg)

    out = {n: flat.reshape(cache[n].shape) for n, flat in pools.items()}
    if states is not None:
        out.update(ssm_state=states, conv_state=tails)
    return _logits(x, params, cfg), out, None


class JambaServing:
    """The model's side of the serving contract (see
    ``models/generation.py::LlamaServing`` for the contract): no speculation,
    no block shipments, and a pool with TWO leaves of the state kind (a
    leaf's fourth entry names its kind: 0 for the full kind, ``"state"`` for
    a leaf that holds ``[layers, slots, *shape]`` and takes no blocks) beside
    the K and V of the attention layers."""

    name = "state-space / attention decoder (models/jamba.py)"
    speculation = False
    counters = ()

    def __init__(self, cfg: JambaConfig):
        self.cfg = cfg

    def rope_table(self, max_len: int):
        """Nothing: no layer of this model encodes a position."""
        return ()

    def paged_block_leaves(self, block_size: int):
        """leaf -> (layers, shape of one block (a state kind: of one slot)
        in one layer, dtype, kind)."""
        cfg = self.cfg
        leaves = {}
        n_att, n_mamba = cfg.layers_of(ATTENTION), cfg.layers_of(MAMBA)
        if n_att:
            page = (cfg.num_key_value_heads, block_size, cfg.hd)
            leaves.update(k_full=(n_att, page, cfg.dtype, 0),
                          v_full=(n_att, page, cfg.dtype, 0))
        if n_mamba:
            leaves["ssm_state"] = (
                n_mamba, (cfg.mamba_d_state, cfg.d_inner), jnp.float32, "state")
            # the tail flat, its K - 1 inputs one after the other
            # (``ops/selective_scan.py::conv_step`` says why)
            leaves["conv_state"] = (
                n_mamba, ((cfg.mamba_d_conv - 1) * cfg.d_inner,), cfg.dtype, "state")
        return leaves

    def cache_bytes_per_position(self) -> int:
        """What a position adds through every layer: the attention layers' K
        and V. The Mamba layers add nothing."""
        cfg = self.cfg
        return (2 * cfg.layers_of(ATTENTION) * cfg.num_key_value_heads * cfg.hd
                * jnp.dtype(cfg.dtype).itemsize)

    def prefill_blocks(self, params, prompt_row, n_blocks, block_size, table,
                       length=None):
        """prompt_row [1, P] (P <= n_blocks * block_size), ``length`` of it
        real -> the pool's leaves: K and V of positions [0, P) cut into
        blocks ``[layers, n_blocks, Hkv, block, hd]``, and the scan state
        and the tail ``[layers, ...]`` as of position ``length - 2``: the
        engine's first decode step feeds the prompt's last token again
        (``serving/paged_kv.py::Slot``), which K and V take as it is (the
        step writes that position before it reads it) and a tail and a state
        would take twice. So the pass runs over the first ``length - 1``
        tokens as the real ones."""
        cfg = self.cfg
        p = prompt_row.shape[1]
        length = jnp.asarray(p if length is None else length, jnp.int32)
        _, cache = prefill(params, prompt_row, cfg, length - 1)
        out = {}
        if "ssm" in cache:
            out.update(ssm_state=cache["ssm"],
                       conv_state=cache["conv"].reshape(cache["conv"].shape[0], -1))
        for name in ("k", "v"):
            if name not in cache:
                continue
            leaf = jnp.pad(cache[name],
                           ((0, 0), (0, n_blocks * block_size - p), (0, 0), (0, 0)))
            out[name + "_full"] = leaf.reshape(
                leaf.shape[0], n_blocks, block_size, cfg.num_key_value_heads, cfg.hd
            ).transpose(0, 1, 3, 2, 4)
        return out

    def decode_paged(self, params, cache, token, pos, tables, table):
        return decode_step_paged(params, cache, token, pos, tables, self.cfg)
