"""The ``lfm2_moe`` family's side of the program under test: the one file of
the family that imports ``ray_lightning_tpu``. It builds the program's own
objects from a configuration file's sizes (HF key names, which the program's
``Lfm2Config`` keeps). The program trains this family and does not serve it:
``engine_params`` says so."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``Lfm2Config`` for a configuration file's sizes: every
    key of the file that the config object has, at the file's value, but the
    count of experts, which the file gives as held here (``num_experts``)
    beside what the router scores (``published_num_experts``)."""
    from ray_lightning_tpu.models.lfm2 import Lfm2Config

    m = weights.dims(sizes)  # refuses what the family has no equations for
    stated = {f.name: sizes[f.name] for f in dataclasses.fields(Lfm2Config)
              if f.name in sizes and f.name not in ("dtype", "layer_types")}
    stated.update(num_experts=m["routed"], experts_held=m["held"], first_expert=m["first"],
                  layer_types=tuple(sizes.get("layer_types", ())), max_seq=max_seq,
                  dtype=m["dtype"].type)
    return Lfm2Config(**stated, **model)


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    """``Lfm2Module`` whose weights come from the family's generator (so the
    reference can regenerate them) and whose optimizer is the one
    ``Lfm2Module`` sets: AdamW(b1 0.9, b2 0.95) under warm-up + cosine, its
    moments in the parameters' type."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models.lfm2 import Lfm2Module

    for key, want in (("b1", 0.9), ("b2", 0.95), ("eps", 1e-8)):
        if opt[key] != want:
            raise ValueError(f"Lfm2Module fixes {key}={want}; the job states {opt[key]}")
    if jnp.dtype(opt.get("state_dtype", cfg.dtype)) != jnp.dtype(cfg.dtype):
        raise ValueError(f"Lfm2Module keeps Adam's moments in the parameters' type, "
                         f"{jnp.dtype(cfg.dtype).name}; the job states {opt['state_dtype']}")
    if weights.dims(sizes)["renorm_eps"] != 1e-20:
        raise ValueError("route_sigmoid_bias renormalises over the sum + 1e-20")

    class SeededLfm2(Lfm2Module):
        def init_params(self, rng):
            return weights.make_params(sizes, weights.seed_keys(sizes, seed))

    return SeededLfm2(
        cfg, lr=opt["lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"])


def engine_params(sizes: Dict[str, Any], seed: int):
    raise NotImplementedError(
        "the lfm2 family is trained, not served: the program has no pool that keeps a "
        "convolution's tail beside K and V")
