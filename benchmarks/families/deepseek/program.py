"""The DeepSeek-V3-style family's side of the program under test: the one
file of the family that imports ``ray_lightning_tpu``. It builds the
program's own objects from a configuration file's sizes (HF key names)."""
from __future__ import annotations

from typing import Any, Dict

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``DeepseekConfig`` for a configuration file's sizes."""
    from ray_lightning_tpu.models.deepseek import DeepseekConfig

    m = weights.dims(sizes)
    return DeepseekConfig(
        vocab_size=m["vocab"], dim=m["d"], n_layers=m["layers"],
        n_dense_layers=m["dense"], n_heads=m["heads"], q_lora_rank=m["rq"],
        kv_lora_rank=m["rkv"], qk_nope_head_dim=m["nope"], qk_rope_head_dim=m["rope"],
        v_head_dim=m["v"], ffn_dim=m["f"], moe_ffn_dim=m["fe"],
        n_experts=m["experts"], n_shared_experts=m["shared"], expert_top_k=m["top_k"],
        routed_scaling=m["scale"], norm_topk_prob=m["renorm"], max_seq=max_seq,
        rope_theta=m["theta"], norm_eps=m["eps"], dtype=m["dtype"].type, **model,
    )


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    """``DeepseekModule`` on the family's seeded weights, with the optimizer
    the module sets: AdamW(b1 0.9, b2 0.95) under warm-up + cosine."""
    from ray_lightning_tpu.models.deepseek import DeepseekModule

    for key, want in (("b1", 0.9), ("b2", 0.95), ("eps", 1e-8)):
        if opt[key] != want:
            raise ValueError(f"DeepseekModule fixes {key}={want}; the job states {opt[key]}")

    class SeededDeepseek(DeepseekModule):
        def init_params(self, rng):
            return weights.make_params(sizes, weights.seed_keys(sizes, seed))

    return SeededDeepseek(
        cfg, lr=opt["lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
    )


def engine_params(sizes: Dict[str, Any], seed: int):
    """The parameters an ``InferenceEngine`` takes beside ``model_config``:
    the module's own tree, made on the device (dispatched, not waited for)."""
    return weights.make_params_on_device(sizes, seed)
