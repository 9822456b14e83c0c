"""A decoder that mixes block-sparse attention layers, which select the pages
they read, with lightning (decayed linear) attention layers, whose state does
not grow with the request (the published ``minicpm_sala`` block):
teacher-forced forward, prefill and a paged decode step. Inference only.

What differs from the other models here, and is why this is one of its own:

- two kinds of mixer in the order ``mixer_types`` states (the published list;
  the first ``n_layers`` of it run). A ``minicpm4`` layer is GQA attention
  with NO position encoding, a norm on each query and key head, and an
  INDEXER: one pooled key a 16 positions a key/value head, which a query
  scores to choose the 64 blocks of 64 positions it attends (block 0, the
  blocks of the last 2,048 positions and its own always among them); a
  request under ``dense_len`` attends everything (``ops/sparse_attention.py``
  has the rule). A ``lightning-attn`` layer keeps ``S <- lam_h S + k^T v``
  a head, ``o = q S / sqrt(hd)``, rope on q and k, a norm on each output
  head (``ops/lightning_attention.py``). Both gate the heads' outputs by
  ``sigmoid(W_g h)``;
- the cache follows the kinds. The sparse layers' K and V are paged leaves of
  the full kind, and beside them under the same tables the POOLED keys, a
  leaf with a smaller block (``block_size / stride`` a block). The lightning
  layers' state is a leaf of the STATE kind: ``[H, hd, hd]`` float32 a layer
  a slot, no blocks (``serving/paged_kv.py``). Decode appends K and V,
  completes a pooled key when one is due, scores the row's complete pooled
  keys, reads the chosen blocks through the paged kernel by a composed table,
  and moves every state on in place;
- muP scalings: the embedding times ``scale_emb``, every residual branch
  times ``scale_depth / sqrt(published_layers)`` (the PUBLISHED depth,
  whatever the cut), the final norm's output over ``dim / dim_model_base``
  before the untied head.

Precision: weights and activations in ``dtype``, products accumulated in
float32, norms and rope in float32; the indexer's scores and its ``top_k``
in float32 at the highest matmul precision; the lightning state float32.

Not here: a mesh, a training step, speculation, prefix sharing (a shared
block says nothing of the state behind it). ``MiniCPMSALAConfig`` and the
engine refuse what cannot run.
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
from ray_lightning_tpu.ops.lightning_attention import (
    lightning_decode,
    lightning_prefill,
    lightning_slopes,
)
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.ops.rope import apply_rope, rope_angles
from ray_lightning_tpu.ops.sparse_attention import (
    SparseSpec,
    choose_blocks,
    compose_tables,
    pooled_keys,
    prompt_block_mask,
    selected_attention,
    selected_positions,
)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# counters the paged decode step returns, over the rows that hold a request,
# of ONE sparse layer (every one chooses as many): positions in the blocks
# the rows chose (the same in every key/value head), and the complete pooled
# keys the selecting rows scored
DECODE_COUNTERS = ("kv_positions_selected", "indexer_keys_scanned")
# tokens that go through the MLP at once: the 16,384-wide hidden rows of a
# whole 16,384-token prompt would be a gigabyte, twice over
MLP_CHUNK = 2048


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    dim: int = 4096
    n_layers: int = 4
    mixer_types: Tuple[str, ...] = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_kv_heads: int = 32
    lightning_head_dim: int = 128
    ffn_dim: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    published_layers: int = 32  # the depth the residual scale is made of
    dim_model_base: int = 256
    sparse: SparseSpec = SparseSpec()
    max_seq: int = 20480
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = tuple(self.mixer_types[: self.n_layers])
        if len(kinds) != self.n_layers or set(kinds) - {SPARSE, LIGHTNING}:
            raise ValueError(
                f"mixer_types={self.mixer_types!r} names no {self.n_layers} "
                f"layers of {SPARSE!r} and {LIGHTNING!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.lightning_kv_heads != self.lightning_heads:
            raise ValueError(
                "lightning_nkv != lightning_nh: the lightning layers keep one "
                "state a head, with a key and a value head of its own")
        if self.lightning_head_dim % 2:
            raise ValueError("lightning_head_dim must be even: rope turns pairs")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of each layer that runs."""
        return tuple(self.mixer_types[: self.n_layers])

    def layers_of(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.published_layers ** 0.5

    @property
    def slopes(self):
        return lightning_slopes(self.lightning_heads)

    def serving(self):
        """What ``InferenceEngine`` and the paged pool ask of a model."""
        return MiniCPMSALAServing(self)


# --------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------- #
def layer_shapes(cfg: MiniCPMSALAConfig, kind: str) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape, fan_in; 0 marks a norm weight) of one layer."""
    d, f = cfg.dim, cfg.ffn_dim
    if kind == SPARSE:
        q, kv, hd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, cfg.head_dim
        extra = {}
    else:
        q = kv = cfg.lightning_heads * cfg.lightning_head_dim
        hd = cfg.lightning_head_dim
        extra = {"o_norm": ((hd,), 0)}
    return {
        "attn_norm": ((d,), 0), "mlp_norm": ((d,), 0),
        "wq": ((d, q), d), "wk": ((d, kv), d), "wv": ((d, kv), d),
        "wg": ((d, q), d), "wo": ((q, d), q),
        "q_norm": ((hd,), 0), "k_norm": ((hd,), 0),
        "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f),
        **extra,
    }


def init_params(rng: jax.Array, cfg: MiniCPMSALAConfig) -> Dict[str, Any]:
    """Random parameters in the tree the forward takes: ``embed``,
    ``lm_head`` (untied), ``final_norm`` and ``layers``, a tuple with one
    dict a layer in the stack's order (the two kinds have different leaves).
    Matrices normal with variance 1 / fan_in, norms 1."""
    def make(key, shape, fan_in):
        if fan_in == 0:
            return jnp.ones(shape, cfg.dtype)
        return (jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5).astype(cfg.dtype)

    keys = jax.random.split(rng, cfg.n_layers + 2)
    layers = []
    for key, kind in zip(keys, cfg.kinds):
        shapes = layer_shapes(cfg, kind)
        layers.append({
            name: make(k, *shapes[name])
            for k, name in zip(jax.random.split(key, len(shapes)), sorted(shapes))})
    return {
        "embed": make(keys[-2], (cfg.vocab_size, cfg.dim), cfg.dim),
        "lm_head": make(keys[-1], (cfg.dim, cfg.vocab_size), cfg.dim),
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "layers": tuple(layers),
    }


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #
def rope_table(cfg: MiniCPMSALAConfig, length: int):
    return rope_angles(length, cfg.lightning_head_dim, cfg.rope_theta)


def _embed(params, tokens, cfg: MiniCPMSALAConfig):
    return (params["embed"][tokens].astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)


def _logits(x, params, cfg: MiniCPMSALAConfig):
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    h = (h.astype(jnp.float32) / (cfg.dim / cfg.dim_model_base)).astype(x.dtype)
    return jnp.einsum("...d,dv->...v", h, params["lm_head"],
                      preferred_element_type=jnp.float32)


def _mlp_rows(x, lp, cfg: MiniCPMSALAConfig):
    h = rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
    return (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


def _mlp(x, lp, cfg: MiniCPMSALAConfig):
    """The SwiGLU branch on the residual stream x: [..., D], which it norms
    itself; a long prompt goes through in chunks of ``MLP_CHUNK`` tokens."""
    flat = x.reshape(-1, x.shape[-1])
    t = flat.shape[0]
    if t > MLP_CHUNK and t % MLP_CHUNK == 0:
        out = jax.lax.map(lambda rows: _mlp_rows(rows, lp, cfg),
                          flat.reshape(t // MLP_CHUNK, MLP_CHUNK, -1))
    else:
        out = _mlp_rows(flat, lp, cfg)
    return out.reshape(x.shape)


def _residual(x, branch, cfg: MiniCPMSALAConfig):
    return (x.astype(jnp.float32)
            + cfg.residual_scale * branch.astype(jnp.float32)).astype(x.dtype)


def _heads(h, w, n, hd):
    return (h @ w).reshape(h.shape[:-1] + (n, hd))


def _qkv(h, lp, cfg: MiniCPMSALAConfig, kind: str):
    """h: [..., D] -> q [..., H, hd], k, v [..., Hkv, hd] (q and k normed a
    head, not roped) and the gate [..., H * hd]."""
    if kind == SPARSE:
        nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    else:
        nq = nkv = cfg.lightning_heads
        hd = cfg.lightning_head_dim
    q = rmsnorm(_heads(h, lp["wq"], nq, hd), lp["q_norm"], cfg.norm_eps)
    k = rmsnorm(_heads(h, lp["wk"], nkv, hd), lp["k_norm"], cfg.norm_eps)
    return q, k, _heads(h, lp["wv"], nkv, hd), jax.nn.sigmoid(h @ lp["wg"])


def _sparse_prefill(h, lp, cfg: MiniCPMSALAConfig):
    """A sparse layer over one sequence. h: [T, D] normed. Returns (mixer
    out [T, D], (k, v [T, Hkv, hd], pooled keys [T / stride, Hkv, hd]))."""
    t = h.shape[0]
    spec = cfg.sparse
    q, k, v, gate = _qkv(h, lp, cfg, SPARSE)
    pad = -t % spec.stride
    kp = pooled_keys(jnp.pad(k, ((0, pad), (0, 0), (0, 0))), spec)
    qh, kh, vh = (a.swapaxes(0, 1) for a in (q, k, v))  # [H, T, hd]
    if t < spec.dense_len:  # no query of it selects
        att = attention(qh[None], kh[None], vh[None], causal=True)[0]
    else:
        mask = prompt_block_mask(
            qh.reshape(cfg.n_kv_heads, -1, t, cfg.head_dim), kp.swapaxes(0, 1), spec)
        # off a block's edge (a teacher-forced sequence of the tests, never a
        # prefill rung) the kernel has no tiles
        att = selected_attention(
            qh, kh, vh, mask, spec, kernel=None if t % spec.block == 0 else False)
    att = att.swapaxes(0, 1).reshape(t, -1) * gate
    return att @ lp["wo"], (k, v, kp)


def _lightning_prefill(h, lp, cfg: MiniCPMSALAConfig, cos, sin, n_valid):
    """A lightning layer over one sequence. h: [T, D] normed; ``n_valid``:
    the positions that feed the state. Returns (mixer out [T, D], the state
    after position ``n_valid - 1`` [H, hd, hd] float32)."""
    t = h.shape[0]
    q, k, v, gate = _qkv(h, lp, cfg, LIGHTNING)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    o, state = lightning_prefill(
        q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), cfg.slopes, n_valid)
    o = rmsnorm(o.swapaxes(0, 1), lp["o_norm"], cfg.norm_eps).reshape(t, -1) * gate
    return o @ lp["wo"], state


def _prefill_row(params, tokens, cfg: MiniCPMSALAConfig, table, n_valid):
    """Every layer over one sequence. tokens: [T]. Returns (x [T, D], the
    sparse layers' (k, v, pooled) and the lightning layers' states, each a
    list in the kind's order)."""
    t = tokens.shape[0]
    cos, sin = table[0][:t], table[1][:t]
    x = _embed(params, tokens, cfg)
    kv, states = [], []
    for kind, lp in zip(cfg.kinds, params["layers"]):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == SPARSE:
            mixed, kept = _sparse_prefill(h, lp, cfg)
            kv.append(kept)
        else:
            mixed, kept = _lightning_prefill(h, lp, cfg, cos, sin, n_valid)
            states.append(kept)
        x = _residual(x, mixed, cfg)
        x = _residual(x, _mlp(x, lp, cfg), cfg)
    return x, kv, states


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: MiniCPMSALAConfig,
            mesh=None) -> jnp.ndarray:
    """tokens [B, T] -> logits [B, T, V] float32. Teacher-forced, no cache;
    one sequence after another."""
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError("this model does not run under a mesh yet")
    table = rope_table(cfg, tokens.shape[1])

    def row(toks):
        x, _, _ = _prefill_row(params, toks, cfg, table, None)
        return _logits(x, params, cfg)

    return jax.lax.map(row, tokens)


# --------------------------------------------------------------------- #
# serving: prefill and the paged decode step
# --------------------------------------------------------------------- #
def prefill(params, prompt: jnp.ndarray, cfg: MiniCPMSALAConfig, table,
            length=None, state_upto=None):
    """One pass over one prompt [1, P], padded behind ``length`` real tokens
    (None: all P). Returns (the logits of position ``length - 1`` [1, V]
    float32, cache): ``k``, ``v`` ``[sparse layers, P, Hkv, hd]`` and ``kp``
    ``[sparse layers, P / stride, Hkv, hd]`` of positions [0, P) (what lies
    behind ``length`` is the padding's and nothing may read it), and
    ``state`` ``[lightning layers, H, hd, hd]`` float32 AS OF position
    ``state_upto - 1`` (None: ``length``): a padded position neither decays
    nor feeds it."""
    if prompt.shape[0] != 1:
        raise ValueError("prefill takes one prompt a call")
    p = prompt.shape[1]
    length = jnp.asarray(p if length is None else length, jnp.int32)
    upto = length if state_upto is None else jnp.asarray(state_upto, jnp.int32)
    x, kv, states = _prefill_row(params, prompt[0], cfg, table, upto)
    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=0)
    cache = {}
    if kv:
        cache.update({n: jnp.stack([kept[i] for kept in kv])
                      for i, n in enumerate(("k", "v", "kp"))})
    if states:
        cache["state"] = jnp.stack(states)
    return _logits(last, params, cfg), cache


def decode_step_paged(
    params, cache: Dict[str, jnp.ndarray], token: jnp.ndarray, pos: jnp.ndarray,
    block_tables: Dict[str, jnp.ndarray], cfg: MiniCPMSALAConfig, table,
    kernel: Optional[bool] = None,
):
    """One decode step over the pool. token, pos: [B] int32;
    ``block_tables``: ``{"full": [B, max_blocks]}``; ``cache``: ``k_full``,
    ``v_full`` ``[sparse layers, N, Hkv, bs, hd]``, ``kp_full`` ``[sparse
    layers, N, Hkv, bs / stride, hd]`` (pooled keys, under the same table)
    and ``s_state`` ``[lightning layers, B, H, hd, hd]`` float32, row b of
    which is slot b's. A sparse layer writes each row's key and value at
    ``pos``, writes the pooled key that ``pos`` completes (the mean of the
    row's last ``kernel`` keys, read back from the pages; the trash block
    when none is due), chooses the row's blocks from its complete pooled keys
    and attends them: the paged kernel over ``[N * Hkv, 1, bs, hd]`` pages
    by a table composed a row a key/value head (``kernel`` None defers to
    ``paged_kernel_enabled()``), else a gather of the same pages. A
    lightning layer moves each row's state on one position. Nothing is
    sliced out of the pool, so a caller that donates it gets it back updated
    in place.

    Returns (logits [B, V] float32, cache, counters [2] int32 in the order of
    ``DECODE_COUNTERS``, over the rows whose table names a block of their
    own: a free slot's names the trash block and counts nothing)."""
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_enabled,
    )
    from ray_lightning_tpu.serving.paged_kv import TRASH_BLOCK

    use_kernel = paged_kernel_enabled() if kernel is None else bool(kernel)
    spec = cfg.sparse
    b = token.shape[0]
    tables = block_tables["full"]
    pools, states = {}, cache.get("s_state")
    if "k_full" in cache:
        nkv, bs, hd = cache["k_full"].shape[2:]
        n_pages = cache["k_full"].shape[1]
        per = cache["kp_full"].shape[3]
        pools = {n: flat_pages(cache[n]) for n in ("k_full", "v_full", "kp_full")}
        off = pos % bs
        phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        # the pooled key this position completes, if any
        done = pos + 1 - spec.kernel
        due = (done >= 0) & (done % spec.stride == 0)
        j = jnp.maximum(done, 0) // spec.stride
        kp_phys = jnp.where(due, jnp.take_along_axis(
            tables, (j // per)[:, None], axis=1)[:, 0], TRASH_BLOCK)
        back = jnp.maximum(pos[:, None] - jnp.arange(spec.kernel - 1, -1, -1), 0)  # [B, kernel]
        back_page = jnp.take_along_axis(tables, back // bs, axis=1)
    if states is not None:
        c, s = table[0][pos], table[1][pos]  # [B, hd / 2]
        n_rows = states.shape[1]
        states = states.reshape((-1,) + states.shape[2:])

    def sparse_layer(h, lp, place):
        first = place * n_pages  # this layer's pages of the stack
        q, k, v, gate = _qkv(h, lp, cfg, SPARSE)
        k_flat = write_rows(pools["k_full"], first + phys, off, k)
        v_flat = write_rows(pools["v_full"], first + phys, off, v)
        # the row's last `kernel` keys, the new one among them: [B, kernel, Hkv, hd]
        rows = ((first + back_page) * nkv)[..., None] + jnp.arange(nkv)
        last = k_flat[rows, (back % bs)[..., None]].astype(jnp.float32)
        kp_flat = write_rows(pools["kp_full"], first + kp_phys, j % per,
                             last.mean(axis=1).astype(k.dtype))
        pools.update(k_full=k_flat, v_full=v_flat, kp_full=kp_flat)
        qf = q.reshape(b, nkv, -1, hd)  # GQA: [B, Hkv, G, hd]
        kp_rows = gather_pages(kp_flat, tables + first, nkv)  # [B, Hkv, J, hd]
        chosen = jax.vmap(
            lambda qr, kr, p: choose_blocks(qr[:, :, None], kr, p[None], spec)[:, 0]
        )(qf, kp_rows, pos)  # [B, Hkv, topk]
        composed, last_pos = compose_tables(tables, chosen, pos, spec)
        # a page of ONE key/value head: [N * Hkv, 1, bs, hd], and a row a
        # (row, head) pair, so that each walks its own chosen blocks
        head_pages = ((composed + first) * nkv + jnp.arange(nkv)[None, :, None]
                      ).reshape(b * nkv, -1)
        qr = qf.reshape(b * nkv, 1, -1, hd)
        last_pos = jnp.repeat(last_pos, nkv)
        if use_kernel:
            att = paged_decode_attention(
                qr.astype(jnp.float32), k_flat[:, None], v_flat[:, None],
                head_pages, last_pos)
        else:
            cols = jnp.arange(head_pages.shape[1] * bs)[None, :]
            att = cached_attention(
                qr, gather_pages(k_flat, head_pages, 1), gather_pages(v_flat, head_pages, 1),
                (cols <= last_pos[:, None])[:, None, None, :])
        att = att.astype(h.dtype).reshape(b, -1) * gate
        return att @ lp["wo"]

    def lightning_layer(h, lp, place):
        nonlocal states
        q, k, v, gate = _qkv(h, lp, cfg, LIGHTNING)
        # a row its own angles: the rows stand where apply_rope has positions
        q, k = apply_rope(q, c, s), apply_rope(k, c, s)
        o, states = lightning_decode(
            q, k, v, states, cfg.slopes, first_row=place * n_rows,
            kernel=use_kernel)
        o = rmsnorm(o.astype(h.dtype), lp["o_norm"], cfg.norm_eps).reshape(b, -1) * gate
        return o @ lp["wo"]

    x = _embed(params, token, cfg)
    place = {SPARSE: 0, LIGHTNING: 0}
    for kind, lp in zip(cfg.kinds, params["layers"]):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        mix = sparse_layer if kind == SPARSE else lightning_layer
        x = _residual(x, mix(h, lp, place[kind]), cfg)
        place[kind] += 1
        x = _residual(x, _mlp(x, lp, cfg), cfg)

    out = {n: flat.reshape(cache[n].shape) for n, flat in pools.items()}
    if states is not None:
        out["s_state"] = states.reshape(cache["s_state"].shape)
    live = tables[:, 0] != TRASH_BLOCK
    sparse_rows = live & (pos + 1 >= spec.dense_len)
    counters = jnp.stack([
        jnp.sum(jnp.where(live, selected_positions(pos, spec), 0)),
        jnp.sum(jnp.where(sparse_rows, spec.complete(pos + 1), 0)),
    ]).astype(jnp.int32) * min(cfg.layers_of(SPARSE), 1)
    return _logits(x, params, cfg), out, counters


class MiniCPMSALAServing:
    """The model's side of the serving contract (see
    ``models/generation.py::LlamaServing`` for the contract): no speculation,
    no block shipments, and a pool with a STATE kind beside the full one. A
    leaf's fourth entry names its kind: 0 for the full kind (K, V, and the
    pooled keys with a block shape of their own), ``"state"`` for a leaf
    that holds ``[layers, slots, *shape]`` and takes no blocks."""

    name = "sparse / lightning attention decoder (models/minicpm_sala.py)"
    speculation = False
    counters = DECODE_COUNTERS

    def __init__(self, cfg: MiniCPMSALAConfig):
        self.cfg = cfg

    def rope_table(self, max_len: int):
        return rope_table(self.cfg, max_len)

    def paged_block_leaves(self, block_size: int):
        """leaf -> (layers, shape of one block (a state kind: of one slot)
        in one layer, dtype, kind)."""
        cfg, spec = self.cfg, self.cfg.sparse
        leaves = {}
        if cfg.layers_of(SPARSE):
            if block_size != spec.block:
                raise ValueError(
                    f"block_size={block_size}: a page has to be one selectable "
                    f"block of {spec.block} positions, since a row's chosen "
                    "blocks are read through its block table")
            n = cfg.layers_of(SPARSE)
            page = (cfg.n_kv_heads, block_size, cfg.head_dim)
            pooled = (cfg.n_kv_heads, block_size // spec.stride, cfg.head_dim)
            leaves.update(k_full=(n, page, cfg.dtype, 0), v_full=(n, page, cfg.dtype, 0),
                          kp_full=(n, pooled, cfg.dtype, 0))
        if cfg.layers_of(LIGHTNING):
            hd = cfg.lightning_head_dim
            leaves["s_state"] = (cfg.layers_of(LIGHTNING),
                                 (cfg.lightning_heads, hd, hd), jnp.float32, "state")
        return leaves

    def cache_bytes_per_position(self) -> int:
        """What a position adds through every layer: the sparse layers' K, V
        and share of a pooled key. The lightning layers add nothing."""
        cfg = self.cfg
        width = cfg.n_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
        return cfg.layers_of(SPARSE) * (2 * width + width // cfg.sparse.stride)

    def prefill_blocks(self, params, prompt_row, n_blocks, block_size, table,
                       length=None):
        """prompt_row [1, P] (P <= n_blocks * block_size), ``length`` of it
        real -> the pool's leaves: K, V and pooled keys of positions [0, P)
        cut into blocks ``[layers, n_blocks, Hkv, block, hd]``, and the
        state ``[layers, H, hd, hd]`` as of position ``length - 2``: the
        engine's first decode step feeds the prompt's last token again
        (``serving/paged_kv.py::Slot``), which K and V take as it is and a
        state would take twice."""
        cfg = self.cfg
        p = prompt_row.shape[1]
        length = jnp.asarray(p if length is None else length, jnp.int32)
        _, cache = prefill(params, prompt_row, cfg, table, length, length - 1)
        out = {}
        if "state" in cache:
            out["s_state"] = cache["state"]
        for name, rows, size in (("k", p, block_size), ("v", p, block_size),
                                 ("kp", -(-p // cfg.sparse.stride),
                                  block_size // cfg.sparse.stride)):
            if name not in cache:
                continue
            leaf = jnp.pad(cache[name], ((0, 0), (0, n_blocks * size - rows), (0, 0), (0, 0)))
            out[name + "_full"] = leaf.reshape(
                leaf.shape[0], n_blocks, size, cfg.n_kv_heads, cfg.head_dim
            ).transpose(0, 1, 3, 2, 4)
        return out

    def decode_paged(self, params, cache, token, pos, tables, table):
        return decode_step_paged(params, cache, token, pos, tables, self.cfg, table)
