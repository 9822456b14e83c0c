"""The ``cohere2_moe`` family's side of the program under test: the one file
of the family that imports ``ray_lightning_tpu``. It builds the program's own
objects from a configuration file's sizes (HF key names). The program serves
this family and does not train it (a share of the experts under ``jax.grad``
is not written): ``make_module`` says so."""
from __future__ import annotations

from typing import Any, Dict

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``CohereConfig`` for a configuration file's sizes.
    ``remat`` is the serve driver's word to every family; this model has no
    training step to rematerialise."""
    from ray_lightning_tpu.models.cohere import CohereConfig

    model.pop("remat", None)
    m = weights.dims(sizes)
    return CohereConfig(
        vocab_size=m["vocab"], dim=m["d"], n_layers=m["layers"], period=m["period"],
        n_heads=m["heads"], n_kv_heads=m["kv_heads"], head_dim=m["hd"],
        sliding_window=m["window"], ffn_dim=m["f"], n_experts=m["routed"],
        experts_held=m["held"], first_expert=m["first"], n_shared_experts=m["shared"],
        expert_top_k=m["top_k"], norm_topk_prob=m["renorm"], logit_scale=m["logit_scale"],
        max_seq=max_seq, rope_theta=m["theta"], norm_eps=m["eps"], dtype=m["dtype"].type,
        **model,
    )


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    raise NotImplementedError(
        "the cohere2_moe family is served, not trained: the program has no "
        "training step over a share of the experts")


def engine_params(sizes: Dict[str, Any], seed: int):
    """The parameters an ``InferenceEngine`` takes beside ``model_config``:
    the model's own tree, made on the device (dispatched, not waited for)."""
    return weights.make_params_on_device(sizes, seed)
