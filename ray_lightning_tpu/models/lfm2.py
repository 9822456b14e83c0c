"""A decoder whose blocks mix GATED SHORT CONVOLUTIONS with a few grouped-query
attention layers over sigmoid-routed experts (the published ``lfm2_moe``
block), trained: teacher-forced forward, the language-model loss and a
``LightningModule``. A holder keeps a SHARE of every layer's experts.

What differs from ``models/llama.py`` and is why this is a model of its own:

- the mixer of most layers is no attention. A ``conv`` layer projects the
  normed stream to three chunks ``B, C, x`` (``in_proj``, ``[D, 3 D]``),
  runs a causal depthwise convolution of ``conv_L_cache`` taps a channel
  over ``B * x`` (no bias, no activation:
  ``ops/selective_scan.py::causal_conv``), gates it with ``C`` and projects
  back (``out_proj``). ``layer_types`` says which layers are ``conv`` and
  which ``full_attention``;
- a ``full_attention`` layer is grouped-query causal attention whose queries
  and keys go through an RMSNorm over each head's ``head_dim`` (a weight a
  position of the head) BEFORE the rotary embedding (halves rotated, as the
  Llama family's);
- the first ``num_dense_layers`` blocks have a dense SwiGLU of
  ``intermediate_size``; every later block routes: ``s = sigmoid(u W_r)`` in
  float32 over all ``num_experts``, the ``num_experts_per_tok`` largest of
  ``s + expert_bias`` are chosen, their weights are ``s`` at the chosen
  (without the bias) over their sum times ``routed_scaling_factor``, and the
  block adds the weighted sum of the chosen experts' SwiGLUs of
  ``moe_intermediate_size``. No shared expert, no capacity: no pair is ever
  dropped (``parallel/moe.py::route_sigmoid_bias`` and ``moe_ffn_routed``,
  whose grouped products differentiate on the chip). ``expert_bias`` is a
  buffer the published training moves by its own rule (the balancing update
  of the bias), which is not part of this step: the loss is the
  language-model loss alone, the bias gets no gradient and no weight decay;
- this holder keeps ``experts_held`` of the ``num_experts`` the router
  scores, ``[first_expert, first_expert + experts_held)``: one of the chips
  that divide each layer by experts. The router has its published width; a
  pair whose expert is not held adds nothing here, and nothing stands in for
  the other holders or for the exchange that would bring their part;
- the embedding is tied: logits are the final norm's rows times the rows of
  the vocabulary held here.

Layers differ in kind from one to the next, so nothing is stacked and
nothing is scanned: ``params["layers"]`` maps a block's two-digit place to
its own leaves, and the blocks run one after another, each under
``jax.checkpoint`` when ``remat`` is set. Precision: weights and the stream
in ``dtype``, products accumulated in float32, norms and the convolution in
float32, the router's product in float32 at the highest precision (a default
float32 product on the chip is one bfloat16 pass, enough to change which
experts are chosen among near-ties).

Not here: serving (a pool that keeps a convolution's tail beside K and V), a
mesh of several devices, the auxiliary-loss-free update of ``expert_bias``.
``Lfm2Config`` and ``Lfm2Module`` refuse what they cannot run.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu.core.module import LightningModule
from ray_lightning_tpu.observability import phase_span
from ray_lightning_tpu.ops.attention import attention
from ray_lightning_tpu.ops.losses import (
    chunked_softmax_cross_entropy,
    masked_softmax_cross_entropy,
)
from ray_lightning_tpu.ops.rmsnorm import rmsnorm
from ray_lightning_tpu.ops.rope import apply_rope, rope_angles
from ray_lightning_tpu.ops.selective_scan import causal_conv
from ray_lightning_tpu.parallel.moe import moe_ffn_routed, route_sigmoid_bias

KINDS = ("conv", "full_attention")


@dataclass(frozen=True)
class Lfm2Config:
    """The published ``config.json``'s keys under their own names, and what
    a holder of a share adds (``first_expert``, ``experts_held``)."""
    vocab_size: int = 65536  # the rows of the tied embedding held here
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = ()  # at least num_hidden_layers entries; () = all conv
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    max_seq: int = 8192  # the sequences trained on
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32  # what the router scores
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    first_expert: int = 0
    experts_held: Optional[int] = None  # None: all of them
    tie_embedding: bool = True
    dtype: Any = jnp.bfloat16
    remat: bool = True
    loss_chunks: int = 0  # ops/losses.py: the loss over this many sequence chunks
    attn_impl: Optional[str] = None  # None=auto, "flash", "reference"

    def __post_init__(self):
        kinds = tuple(self.layer_types) or ("conv",) * self.num_hidden_layers
        object.__setattr__(self, "layer_types", kinds)
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        if len(kinds) < self.num_hidden_layers or set(kinds) - set(KINDS):
            raise ValueError(
                f"layer_types={kinds!r}: {self.num_hidden_layers} layers need as many "
                f"entries, each one of {KINDS}")
        if self.conv_bias:
            raise ValueError("conv_bias=True: the gated short convolution here has no bias")
        if not self.tie_embedding:
            raise ValueError("tie_embedding=False: the logits here are on the embedding")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("hidden_size must divide into heads of an even size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers lies outside the stack")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")
        if not (0 <= self.first_expert and self.experts_held >= 1
                and self.first_expert + self.experts_held <= self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.experts_held})"
                f" are not among the router's {self.num_experts}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixers of the layers that are run."""
        return self.layer_types[: self.num_hidden_layers]

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["dtype"] = jnp.dtype(self.dtype).name
        d["layer_types"] = list(self.layer_types)
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Lfm2Config":
        d = dict(d)
        d["dtype"] = jnp.dtype(d.get("dtype", "bfloat16")).type
        d["layer_types"] = tuple(d.get("layer_types", ()))
        return Lfm2Config(**d)

    def num_params(self) -> int:
        return sum(int(np.prod(shape)) for shapes in layer_shapes(self).values()
                   for shape, _, _ in shapes.values()) + sum(
            int(np.prod(shape)) for shape, _, _ in top_shapes(self).values())

    def flops_per_token(self) -> float:
        """Forward and backward, 6 a matrix weight a token uses (of the
        experts: its ``num_experts_per_tok`` choices' share that is held
        here, in expectation) plus causal attention at ``max_seq``."""
        d, f = self.hidden_size, self.moe_intermediate_size
        held = self.num_experts_per_tok * self.experts_held / self.num_experts
        used = 0
        for i, kind in enumerate(self.kinds):
            used += 4 * d * d if kind == "conv" else (
                2 * d * d + 2 * d * self.num_key_value_heads * self.head_dim)
            used += 3 * d * self.intermediate_size if i < self.num_dense_layers else (
                d * self.num_experts + held * 3 * d * f)
        return 6.0 * (used + d * self.vocab_size
                      + self.kinds.count("full_attention") * self.max_seq * d)

    @staticmethod
    def tiny() -> "Lfm2Config":
        """The cut's pattern at a size for the CPU tests."""
        return Lfm2Config(
            vocab_size=97, hidden_size=64, num_hidden_layers=5,
            layer_types=("conv", "conv", "full_attention", "conv", "conv"),
            num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
            intermediate_size=96, moe_intermediate_size=32, num_experts=8,
            num_experts_per_tok=2, max_position_embeddings=64, max_seq=64,
            dtype=jnp.float32, remat=False)


# --------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------- #
def place(i: int) -> str:
    """A block's key in ``params["layers"]``."""
    return f"{i:02d}"


def top_shapes(cfg: Lfm2Config) -> Dict[str, Tuple[Tuple[int, ...], int, Any]]:
    d = cfg.hidden_size
    return {"embed": ((cfg.vocab_size, d), d, cfg.dtype), "final_norm": ((d,), 0, cfg.dtype)}


def layer_shapes(cfg: Lfm2Config) -> Dict[str, Dict[str, Tuple[Tuple[int, ...], int, Any]]]:
    """``{place: {leaf: (shape, fan_in, dtype)}}``; fan_in 0 marks a norm's
    weight, -1 the selection bias (zeros)."""
    d, hd, dt = cfg.hidden_size, cfg.head_dim, cfg.dtype
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    out: Dict[str, Dict[str, Any]] = {}
    for i, kind in enumerate(cfg.kinds):
        lp: Dict[str, Any] = {"norm1": ((d,), 0, dt), "norm2": ((d,), 0, dt)}
        if kind == "conv":
            lp.update({"in_proj": ((d, 3 * d), d, dt), "conv_w": ((cfg.conv_L_cache, d), cfg.conv_L_cache, dt),
                       "out_proj": ((d, d), d, dt)})
        else:
            lp.update({"wq": ((d, q), d, dt), "wk": ((d, kv), d, dt), "wv": ((d, kv), d, dt),
                       "q_norm": ((hd,), 0, dt), "k_norm": ((hd,), 0, dt), "wo": ((q, d), q, dt)})
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            lp.update({"w_gate": ((d, f), d, dt), "w_up": ((d, f), d, dt), "w_down": ((f, d), f, dt)})
        else:
            f, e = cfg.moe_intermediate_size, cfg.experts_held
            lp.update({"router": ((d, cfg.num_experts), d, jnp.float32),
                       "experts/w_gate": ((e, d, f), d, dt), "experts/w_up": ((e, d, f), d, dt),
                       "experts/w_down": ((e, f, d), f, dt)})
            if cfg.use_expert_bias:
                lp["expert_bias"] = ((cfg.num_experts,), -1, jnp.float32)
        out[place(i)] = lp
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, value in flat.items():
        group, _, leaf = name.rpartition("/")
        (out.setdefault(group, {}) if group else out)[leaf] = value
    return out


def init_params(rng: jax.Array, cfg: Lfm2Config) -> Dict[str, Any]:
    """``{"embed", "final_norm", "layers": {place: {leaf: array, "experts":
    {stack: [held, ...]}}}}``: normal with variance 1/fan_in, norms one, the
    selection bias zero."""
    def one(key, spec):
        shape, fan_in, dt = spec
        if fan_in == 0:
            return jnp.ones(shape, dt)
        if fan_in < 0:
            return jnp.zeros(shape, dt)
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dt)

    shapes = layer_shapes(cfg)
    keys = iter(jax.random.split(rng, 2 + sum(len(lp) for lp in shapes.values())))
    tree = {name: one(next(keys), spec) for name, spec in top_shapes(cfg).items()}
    tree["layers"] = {
        where: _nest({name: one(next(keys), spec) for name, spec in lp.items()})
        for where, lp in shapes.items()}
    return tree


# --------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------- #
def _short_conv(u, lp, cfg: Lfm2Config):
    """The gated short convolution on the normed stream u: [B, S, D]."""
    with jax.named_scope("rlt.lfm2.short_conv"):
        gate_in, gate_out, x = jnp.split(u @ lp["in_proj"], 3, axis=-1)
        conv = jax.vmap(lambda row: causal_conv(row, lp["conv_w"], None, activation=False)[0])(
            gate_in * x)
        return (gate_out * conv).astype(u.dtype) @ lp["out_proj"]


def _attention(u, lp, cfg: Lfm2Config, cos, sin):
    """Grouped-query causal attention with a norm a head before rope."""
    b, s, _ = u.shape
    hd = cfg.head_dim
    q = rmsnorm((u @ lp["wq"]).reshape(b, s, -1, hd), lp["q_norm"], cfg.norm_eps)
    k = rmsnorm((u @ lp["wk"]).reshape(b, s, -1, hd), lp["k_norm"], cfg.norm_eps)
    v = (u @ lp["wv"]).reshape(b, s, -1, hd)
    q = apply_rope(q, cos, sin).swapaxes(1, 2)  # [B, H, S, hd]
    k = apply_rope(k, cos, sin).swapaxes(1, 2)
    att = attention(q, k, v.swapaxes(1, 2), causal=True, impl=cfg.attn_impl)
    return att.swapaxes(1, 2).reshape(b, s, -1) @ lp["wo"]


def _experts(u, lp, cfg: Lfm2Config):
    """The routed branch on the normed stream u: [B, S, D]. Returns (out,
    the rows each held expert got [experts_held])."""
    b, s, d = u.shape
    flat = u.reshape(b * s, d)
    with jax.named_scope("rlt.moe.route"):
        idx, w = route_sigmoid_bias(
            flat, lp["router"], lp.get("expert_bias"), cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
            precision=jax.lax.Precision.HIGHEST)
    with jax.named_scope("rlt.moe.experts"):
        out, sizes = moe_ffn_routed(
            lp["experts"], flat, idx, w, held=(cfg.first_expert, cfg.experts_held),
            differentiable=True)
    return out.reshape(b, s, d), sizes


def _block(x, lp, cos, sin, cfg: Lfm2Config, kind: str, dense: bool):
    u = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    x = x + (_short_conv(u, lp, cfg) if kind == "conv" else _attention(u, lp, cfg, cos, sin))
    u = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    if dense:
        out = (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]
        return x + out, jnp.zeros((cfg.experts_held,), jnp.int32)
    out, sizes = _experts(u, lp, cfg)
    return x + out, sizes


def forward(
    params: Dict[str, Any], tokens: jnp.ndarray, cfg: Lfm2Config, return_hidden: bool = False
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [B, S] -> (logits [B, S, V] on the tied embedding, or with
    ``return_hidden`` the final norm's rows [B, S, D]; the rows each held
    expert got, an expert layer a row: [expert layers, experts_held])."""
    x = params["embed"][tokens]
    cos, sin = rope_angles(tokens.shape[1], cfg.head_dim, cfg.rope_theta)
    sizes = []
    for i, kind in enumerate(cfg.kinds):
        dense = i < cfg.num_dense_layers
        block = functools.partial(_block, cfg=cfg, kind=kind, dense=dense)
        x, got = (jax.checkpoint(block) if cfg.remat else block)(
            x, params["layers"][place(i)], cos, sin)
        if not dense:
            sizes.append(got)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    sizes = jnp.stack(sizes) if sizes else jnp.zeros((0, cfg.experts_held), jnp.int32)
    return (x if return_hidden else x @ params["embed"].T), sizes


def lm_loss(params, tokens, cfg: Lfm2Config) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross entropy over the rows of the vocabulary held here:
    the whole sequence is fed and the last position masked. The logs carry
    the routing's ``moe_sizes`` beside the loss."""
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    if cfg.loss_chunks > 1:
        h, sizes = forward(params, tokens, cfg, return_hidden=True)
        total, count = chunked_softmax_cross_entropy(
            h, params["embed"].T, targets, mask, cfg.loss_chunks)
    else:
        logits, sizes = forward(params, tokens, cfg)
        total, count = masked_softmax_cross_entropy(logits, targets, mask)
    loss = total / count
    return loss, {"loss": loss, "ppl": jnp.exp(loss), "moe_sizes": sizes}


# --------------------------------------------------------------------- #
# LightningModule wrapper
# --------------------------------------------------------------------- #
class Lfm2Module(LightningModule):
    """Decoder-LM pretraining step of the ``lfm2_moe`` family, built as
    ``LlamaModule`` is: AdamW(b1 0.9, b2 0.95) under warm-up and cosine
    decay, its moments in the parameters' type."""

    def __init__(self, config: Optional[Lfm2Config] = None, lr: float = 3e-4,
                 warmup_steps: int = 100, total_steps: int = 10000,
                 weight_decay: float = 0.1):
        super().__init__()
        if isinstance(config, dict):  # rebuilt from checkpoint hparams
            config = Lfm2Config.from_dict(config)
        self.config = config or Lfm2Config.tiny()
        self.lr, self.warmup_steps = lr, warmup_steps
        self.total_steps, self.weight_decay = total_steps, weight_decay
        self.hparams.update(
            config=self.config.to_dict(), lr=lr, warmup_steps=warmup_steps,
            total_steps=total_steps, weight_decay=weight_decay)

    def init_params(self, rng):
        return init_params(rng, self.config)

    def param_shardings(self, mesh):
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                f"Lfm2Module on a mesh of {mesh.size} devices: its kernels are not "
                "wrapped for a partitioned step; train it on one device")
        return None

    def _tokens_of(self, batch):
        return batch["input_ids"] if isinstance(batch, dict) else batch

    def training_step(self, params, batch, batch_idx):
        loss, logs = lm_loss(params, self._tokens_of(batch), self.config)
        self.log("train_loss", loss, on_step=True, on_epoch=True)
        self.log("train_ppl", logs["ppl"], on_step=True, on_epoch=False)
        if self.config.n_expert_layers:
            self.log("moe_sizes", logs["moe_sizes"], on_step=True, on_epoch=False)
        return loss

    def on_train_batch_end(self, outputs, batch, batch_idx):
        """``rlt.train.moe_routing``: what the step's routing sent to the held
        experts, on the profiler's clock. Opened only where the step's
        outputs have arrived (a callback has read the loss): it never waits
        for the device."""
        sizes = outputs.get("moe_sizes") if isinstance(outputs, dict) else None
        if sizes is None or not getattr(sizes, "is_ready", lambda: True)():
            return
        sizes, cfg = np.asarray(sizes), self.config
        tokens = int(np.prod(np.shape(self._tokens_of(batch))))
        with phase_span(
                "rlt.train.moe_routing",
                routed_pairs=tokens * cfg.num_experts_per_tok * cfg.n_expert_layers,
                held_pairs=int(sizes.sum()), max_expert_rows=int(sizes.max(axis=1).sum()),
                experts_held=cfg.experts_held, expert_layers=cfg.n_expert_layers):
            pass

    def validation_step(self, params, batch, batch_idx):
        loss, logs = lm_loss(params, self._tokens_of(batch), self.config)
        self.log("val_loss", loss)
        self.log("val_ppl", logs["ppl"])

    def predict_step(self, params, batch, batch_idx):
        return forward(params, self._tokens_of(batch), self.config)[0]

    def configure_optimizers(self):
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup_steps, max(self.total_steps, self.warmup_steps + 1))
        # the selection bias is a buffer: no gradient reaches it, and it is
        # not decayed either
        decayed = lambda params: jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) != "expert_bias", params)
        return optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=self.weight_decay,
                           mask=decayed)

    def flops_per_sample(self) -> float:
        return self.config.flops_per_token() * self.tokens_per_sample()

    def tokens_per_sample(self) -> int:
        return self.config.max_seq
