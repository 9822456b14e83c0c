"""The Llama family's side of the program under test: the one file of the
family that imports ``ray_lightning_tpu``. It builds the program's own
objects from a configuration file's sizes (HF key names); what every family
shares of the program (trainer, loader, engine, counters) is in
``benchmarks/program.py``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``LlamaConfig`` for a configuration file's sizes."""
    from ray_lightning_tpu.models.llama import LlamaConfig

    if sizes.get("sliding_window"):
        raise ValueError("the paged engine refuses a sliding window")
    hd = sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"]
    if hd * sizes["num_attention_heads"] != sizes["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim from hidden_size / heads")
    return LlamaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], ffn_dim=sizes["intermediate_size"],
        max_seq=max_seq, rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        dtype=jnp.dtype(sizes.get("dtype", "bfloat16")).type,
        n_experts=sizes.get("num_local_experts", 0),
        expert_top_k=sizes.get("num_experts_per_tok", 2),
        **model,
    )


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    """``LlamaModule`` whose weights come from the family's generator (so the
    reference can regenerate them) and whose optimizer is the one
    ``LlamaModule`` sets: AdamW(b1 0.9, b2 0.95) under warm-up + cosine."""
    from ray_lightning_tpu.models.llama import LlamaModule

    for key, want in (("b1", 0.9), ("b2", 0.95), ("eps", 1e-8)):
        if opt[key] != want:
            raise ValueError(f"LlamaModule fixes {key}={want}; the job states {opt[key]}")

    class SeededLlama(LlamaModule):
        def init_params(self, rng):
            return weights.make_params(sizes, weights.seed_keys(sizes, seed))

    return SeededLlama(
        cfg, lr=opt["lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
    )


def engine_params(sizes: Dict[str, Any], seed: int):
    """The parameters an ``InferenceEngine`` takes beside ``model_config``:
    the module's own tree, made on the device (dispatched, not waited for)."""
    return weights.make_params_on_device(sizes, seed)
