"""Import HuggingFace Llama checkpoints into the native param pytree.

The flagship family is bit-compatible with the HF Llama architecture
(half-split "rotate_half" rope, RMSNorm, SwiGLU MLP, GQA), so a change
of weight layout is all an import needs: torch ``[out, in]`` projections
transpose to our ``[in, out]``, per-layer tensors stack into the
``[L, ...]`` scanned leaves, and the config fields map one-to-one.
Logit parity against ``transformers``' own forward is tested
(tests/test_llama.py::test_hf_llama_import_logit_parity).

This is the "bring your pretrained model" path the reference gets for
free by wrapping torch modules: fine-tune or serve a real Llama
checkpoint on any mesh layout (the imported pytree carries the same
megatron/fsdp PartitionSpecs as a natively-initialized one).

torch is CPU-side import tooling here, never the compute path.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.llama import LlamaConfig


def config_from_hf(hf_config, dtype=jnp.bfloat16, **overrides) -> LlamaConfig:
    """Map a ``transformers.LlamaConfig`` onto :class:`LlamaConfig`."""
    from ray_lightning_tpu.ops.rope import normalize_rope_scaling

    # refuses unsupported kinds (importing with plain rope_theta would
    # silently change every position's angles)
    raw_scaling = getattr(hf_config, "rope_scaling", None)
    if raw_scaling:
        raw_scaling = dict(raw_scaling)
        kind = raw_scaling.get("rope_type", raw_scaling.get("type"))
        if (
            kind == "yarn"
            and not raw_scaling.get("original_max_position_embeddings")
        ):
            # HF semantics: absent/None means the config's
            # max_position_embeddings (transformers' _compute_yarn_parameters)
            raw_scaling["original_max_position_embeddings"] = int(
                hf_config.max_position_embeddings
            )
        if kind == "longrope":
            # Phi-3 semantics (transformers' _compute_longrope_parameters):
            # the pretrain length lives on the CONFIG
            # (original_max_position_embeddings); when present, the
            # attention-factor ratio is max_position / original,
            # overriding any 'factor' in the scaling dict. Absent, HF
            # treats max_position as the pretrain length (short factors
            # always) with the dict's own factor.
            attr_orig = getattr(
                hf_config, "original_max_position_embeddings", None
            )
            orig = attr_orig or hf_config.max_position_embeddings
            raw_scaling["original_max_position_embeddings"] = int(orig)
            if attr_orig:
                raw_scaling["factor"] = (
                    float(hf_config.max_position_embeddings) / int(orig)
                )
    scaling = normalize_rope_scaling(raw_scaling)
    if float(getattr(hf_config, "partial_rotary_factor", 1.0) or 1.0) != 1.0:
        # e.g. Phi-4-mini (0.75): the native rope rotates the full head
        # dim; importing anyway would silently diverge
        raise NotImplementedError(
            "partial_rotary_factor != 1.0 is not mapped (the native rope "
            "rotates the whole head dim)"
        )
    if getattr(hf_config, "mlp_bias", False):
        raise NotImplementedError(
            "mlp_bias checkpoints are not mapped (the native MLP is "
            "bias-free, matching the whole Llama/Mistral/Qwen2 family)"
        )
    if getattr(hf_config, "attention_bias", False):
        # HF's attention_bias puts a bias on o_proj TOO, which the native
        # family cannot represent — mapping only qkv would silently
        # diverge. Qwen2-style qkv-only bias has no config attr; it is
        # detected from the state_dict by import_hf_llama (attn_bias
        # override).
        raise NotImplementedError(
            "attention_bias checkpoints carry an o_proj bias the native "
            "attention does not have; only qkv-only bias (Qwen2 family) "
            "is mapped"
        )
    # Mistral/Mixtral-style windowed attention maps onto the native band
    # kernels; Qwen2-style configs gate it behind use_sliding_window.
    # The native band is UNIFORM across layers, so per-layer gating
    # (Qwen2's max_window_layers, newer configs' mixed layer_types)
    # refuses rather than silently applying the band everywhere
    window = getattr(hf_config, "sliding_window", None)
    if window and not getattr(hf_config, "use_sliding_window", True):
        window = None
    if window:
        n_layers = hf_config.num_hidden_layers
        layer_types = getattr(hf_config, "layer_types", None)
        if layer_types and len(set(layer_types)) > 1:
            raise NotImplementedError(
                f"mixed per-layer attention types {sorted(set(layer_types))}"
                ": the native sliding window is uniform across layers"
            )
        if layer_types and set(layer_types) == {"full_attention"}:
            window = None
        # Qwen2 semantics: layers with idx >= max_window_layers slide,
        # earlier ones are dense
        mwl = getattr(hf_config, "max_window_layers", None)
        if window and mwl is not None and 0 < mwl < n_layers:
            raise NotImplementedError(
                f"max_window_layers={mwl} of {n_layers}: mixed dense/"
                "windowed layers; the native sliding window is uniform"
            )
        if window and mwl is not None and mwl >= n_layers:
            window = None  # no layer actually slides
    fields = dict(
        vocab_size=hf_config.vocab_size,
        dim=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(
            hf_config, "num_key_value_heads", hf_config.num_attention_heads
        ),
        ffn_dim=hf_config.intermediate_size,
        max_seq=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rope_scaling=scaling,
        norm_eps=float(hf_config.rms_norm_eps),
        sliding_window=int(window or 0),
        dtype=dtype,
    )
    fields.update(overrides)
    return LlamaConfig(**fields)


def _np(t) -> np.ndarray:
    return t.detach().to("cpu").to_dense().float().numpy()


def _make_take(sd, dt):
    def take(name, transpose=False, target_dtype=None):
        # per-tensor to the TARGET dtype immediately: only one fp32 copy
        # is ever transient, so an 8B-scale import peaks near
        # torch-model + imported-pytree instead of 2x more
        arr = _np(sd[name])
        return jnp.asarray(arr.T if transpose else arr, target_dtype or dt)

    return take


def _check_uniform_heads(cfg: LlamaConfig) -> None:
    if cfg.n_heads * cfg.head_dim != cfg.dim:
        raise ValueError(
            f"hidden_size {cfg.dim} != num_attention_heads {cfg.n_heads} x "
            f"head_dim {cfg.head_dim}: non-uniform head dims are not "
            "supported"
        )


def _attn_layer_leaves(take, p, layers, attn_bias: bool = False) -> None:
    """The attention + norm leaves shared by every family member.
    torch Linear stores [out, in]; the native layout is [in, out]."""
    layers["attn_norm"].append(take(p + "input_layernorm.weight"))
    layers["wq"].append(take(p + "self_attn.q_proj.weight", True))
    layers["wk"].append(take(p + "self_attn.k_proj.weight", True))
    layers["wv"].append(take(p + "self_attn.v_proj.weight", True))
    layers["wo"].append(take(p + "self_attn.o_proj.weight", True))
    layers["mlp_norm"].append(take(p + "post_attention_layernorm.weight"))
    if attn_bias:  # Qwen2-family qkv bias (o_proj stays bias-free)
        layers["bq"].append(take(p + "self_attn.q_proj.bias"))
        layers["bk"].append(take(p + "self_attn.k_proj.bias"))
        layers["bv"].append(take(p + "self_attn.v_proj.bias"))


def _assemble(take, hf_config, layer_tree) -> Dict[str, Any]:
    embed = take("model.embed_tokens.weight")  # [V, D]
    if getattr(hf_config, "tie_word_embeddings", False):
        # tied checkpoints alias lm_head to the embedding; materialize the
        # native layout explicitly (torch state_dicts often still carry
        # the aliased lm_head.weight key — the config flag is the truth)
        lm_head = embed.T
    else:
        lm_head = take("lm_head.weight", True)  # [D, V]
    return {
        "embed": embed,
        "layers": layer_tree,
        "final_norm": take("model.norm.weight"),
        "lm_head": lm_head,
    }


def import_hf_llama(
    model_or_path, dtype=jnp.bfloat16, **config_overrides
) -> Tuple[Dict[str, Any], LlamaConfig]:
    """Build ``(params, cfg)`` from a ``transformers`` Llama-family model.

    ``model_or_path``: a ``LlamaForCausalLM``-shaped instance (Llama,
    Mistral incl. ``sliding_window``, Qwen2 incl. qkv bias — anything
    with the ``model.layers.N.self_attn/mlp`` state_dict layout), or a
    name/path for ``from_pretrained``. Tied word embeddings
    (``tie_word_embeddings``) materialize as an explicit ``lm_head``.
    ``config_overrides`` go to :class:`LlamaConfig` (e.g. a shorter
    ``max_seq`` for fine-tuning, ``remat_policy=...``).
    """
    if isinstance(model_or_path, str):
        # Auto, not LlamaForCausalLM: a Qwen2/Mistral checkpoint loaded
        # through the Llama class coerces the config with only a warning
        # and DROPS the qkv biases as unexpected keys — the exact silent
        # divergence this importer refuses everywhere else
        from transformers import AutoModelForCausalLM

        model_or_path = AutoModelForCausalLM.from_pretrained(model_or_path)
    model = model_or_path
    sd = dict(model.state_dict())
    # the state_dict is the ground truth on biases: Qwen2's qkv bias is
    # architectural (its config has no attention_bias attr)
    has_qkv_bias = "model.layers.0.self_attn.q_proj.bias" in sd
    if "model.layers.0.self_attn.o_proj.bias" in sd:
        raise NotImplementedError(
            "o_proj bias is not mapped (no family member ships one; the "
            "native out-projection is bias-free)"
        )
    if "model.layers.0.mlp.gate_proj.bias" in sd:
        raise NotImplementedError(
            "mlp bias is not mapped (the native MLP is bias-free)"
        )
    config_overrides.setdefault("attn_bias", has_qkv_bias)
    cfg = config_from_hf(model.config, dtype=dtype, **config_overrides)
    _check_uniform_heads(cfg)

    take = _make_take(sd, cfg.dtype)
    layers: Dict[str, Any] = {
        "attn_norm": [], "wq": [], "wk": [], "wv": [], "wo": [],
        "mlp_norm": [], "w_gate": [], "w_up": [], "w_down": [],
        **({"bq": [], "bk": [], "bv": []} if cfg.attn_bias else {}),
    }
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        _attn_layer_leaves(take, p, layers, attn_bias=cfg.attn_bias)
        layers["w_gate"].append(take(p + "mlp.gate_proj.weight", True))
        layers["w_up"].append(take(p + "mlp.up_proj.weight", True))
        layers["w_down"].append(take(p + "mlp.down_proj.weight", True))

    layer_tree = {k: jnp.stack(v) for k, v in layers.items()}
    return _assemble(take, model.config, layer_tree), cfg


def import_hf_mixtral(
    model_or_path, dtype=jnp.bfloat16, **config_overrides
) -> Tuple[Dict[str, Any], LlamaConfig]:
    """Build ``(params, cfg)`` from a ``transformers`` Mixtral model — the
    MoE member of the family. The expert layout maps onto the native MoE
    leaves (gate/up/down stacks over an expert dim, router in fp32), and
    the routing math is algebraically identical: Mixtral's
    softmax-over-top-k-logits equals our softmax-over-all followed by
    top-k renormalization (e^l_i / sum_topk e^l_j either way).

    Semantics notes:
    - Mixtral routes without expert capacity (token choice). The imported
      config sets ``capacity_factor`` to cover the worst case so training
      matches; generation routes without capacity (only the routed pairs
      are computed, none dropped: ``parallel/moe.py::moe_ffn_routed``).
    - ``sliding_window`` checkpoints map onto the native band kernels
      (cfg.sliding_window; ops/attention.py ``window=``), so sequences
      longer than the window import and run with HF-matching masks.
    """
    if isinstance(model_or_path, str):
        from transformers import MixtralForCausalLM

        model_or_path = MixtralForCausalLM.from_pretrained(model_or_path)
    model = model_or_path
    hf_cfg = model.config
    overrides = dict(
        n_experts=hf_cfg.num_local_experts,
        expert_top_k=hf_cfg.num_experts_per_tok,
        # no-capacity (token-choice) routing: capacity = cf * top_k * T/E,
        # worst-case per-expert load is T, so cf = E/top_k never binds
        # without over-allocating the [T, E, C] dispatch tensors
        capacity_factor=(
            float(hf_cfg.num_local_experts) / hf_cfg.num_experts_per_tok
        ),
        moe_aux_weight=float(
            getattr(hf_cfg, "router_aux_loss_coef", 0.001)
        ),
    )
    overrides.update(config_overrides)
    cfg = config_from_hf(hf_cfg, dtype=dtype, **overrides)
    _check_uniform_heads(cfg)
    if cfg.attn_bias:
        # no Mixtral checkpoint ships qkv biases; accepting the override
        # here would produce params with no bias leaves while the config
        # (and param_specs) claim them
        raise NotImplementedError(
            "attn_bias is not supported on the Mixtral import (the family "
            "ships no qkv bias)"
        )

    take = _make_take(dict(model.state_dict()), cfg.dtype)
    layers: Dict[str, Any] = {
        "attn_norm": [], "wq": [], "wk": [], "wv": [], "wo": [],
        "mlp_norm": [],
    }
    moe: Dict[str, Any] = {
        "router": [], "w_gate": [], "w_up": [], "w_down": [],
    }
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        _attn_layer_leaves(take, p, layers)
        # the native router runs in fp32 (routing decisions are precision
        # sensitive); experts: w1 = gate, w3 = up, w2 = down, torch
        # [out, in] transposed to [in, out]
        moe["router"].append(
            take(p + "block_sparse_moe.gate.weight", True,
                 target_dtype=jnp.float32)
        )
        for leaf, key in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
            moe[leaf].append(
                jnp.stack([
                    take(p + f"block_sparse_moe.experts.{e}.{key}.weight", True)
                    for e in range(cfg.n_experts)
                ])
            )

    layer_tree = {k: jnp.stack(v) for k, v in layers.items()}
    layer_tree["moe"] = {k: jnp.stack(v) for k, v in moe.items()}
    return _assemble(take, hf_cfg, layer_tree), cfg


def import_hf_phi3(
    model_or_path, dtype=jnp.bfloat16, **config_overrides
) -> Tuple[Dict[str, Any], LlamaConfig]:
    """Build ``(params, cfg)`` from a ``transformers`` Phi-3 model.

    Architecturally a Llama-family member (rmsnorm, SwiGLU, GQA, no
    biases) with two deltas: the qkv and gate/up projections ship FUSED
    (``self_attn.qkv_proj``, ``mlp.gate_up_proj`` — split here along the
    torch OUT dim into the native separate leaves) and position scaling
    is 'longrope' (per-frequency long/short factor lists keyed on the
    pretrain context, ops/rope.py::_longrope_scale).

    Factor-regime note: each jit program picks long/short factors from
    its STATIC length (forward: the sequence; generate: prompt + new
    tokens). transformers switches factor sets mid-generation when the
    live length crosses the pretrain context — a generation whose length
    straddles the boundary will differ from HF at the crossing (HF's
    switch rewrites rope for the whole cache mid-stream; ours is
    consistent for the whole program)."""
    if isinstance(model_or_path, str):
        from transformers import AutoModelForCausalLM

        model_or_path = AutoModelForCausalLM.from_pretrained(model_or_path)
    model = model_or_path
    cfg = config_from_hf(model.config, dtype=dtype, **config_overrides)
    _check_uniform_heads(cfg)

    take = _make_take(dict(model.state_dict()), cfg.dtype)
    hd = cfg.head_dim
    q_rows = cfg.n_heads * hd
    kv_rows = cfg.n_kv_heads * hd
    layers: Dict[str, Any] = {
        "attn_norm": [], "wq": [], "wk": [], "wv": [], "wo": [],
        "mlp_norm": [], "w_gate": [], "w_up": [], "w_down": [],
    }
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layers["attn_norm"].append(take(p + "input_layernorm.weight"))
        # fused [q_rows + 2*kv_rows, D] torch layout; transpose AFTER the
        # row split so each piece lands [in, out] like the native leaves
        qkv = take(p + "self_attn.qkv_proj.weight")  # [out, in]
        layers["wq"].append(qkv[:q_rows].T)
        layers["wk"].append(qkv[q_rows:q_rows + kv_rows].T)
        layers["wv"].append(qkv[q_rows + kv_rows:].T)
        layers["wo"].append(take(p + "self_attn.o_proj.weight", True))
        layers["mlp_norm"].append(take(p + "post_attention_layernorm.weight"))
        gate_up = take(p + "mlp.gate_up_proj.weight")  # [2F, D]
        layers["w_gate"].append(gate_up[:cfg.ffn_dim].T)
        layers["w_up"].append(gate_up[cfg.ffn_dim:].T)
        layers["w_down"].append(take(p + "mlp.down_proj.weight", True))

    layer_tree = {k: jnp.stack(v) for k, v in layers.items()}
    return _assemble(take, model.config, layer_tree), cfg
