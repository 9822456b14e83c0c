"""The one place where the benchmark touches the program under test.

Everything else under ``benchmarks/`` is the yardstick and imports nothing
of ``ray_lightning_tpu``; this module builds the system under test from a
configuration file's sizes (HF key names) and reads its counters.
"""
from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights


def cache_dir(root: str) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory inside the checkout. The program's own
    resolution (``runtime/compile_cache.py``) is handed the same place, so
    it sets no other."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(root, ".xla_cache")
    if not placed:
        os.environ["RLT_XLA_CACHE_DIR"] = path
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def llama_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
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


def leaf_names(tree) -> Dict[str, Any]:
    """{"layers/wq": leaf, "layers/moe/w_up": leaf, "embed": leaf, ...}"""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path): leaf
        for path, leaf in flat
    }


def _norms(tree) -> Dict[str, float]:
    vals = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))(tree)
    return {k: float(v) for k, v in leaf_names(vals).items()}


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    """``LlamaModule`` whose weights come from the benchmark's generator
    (so the reference can regenerate them) and whose optimizer is the one
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


def first_gradient_norms(trainer, b1: float) -> Dict[str, float]:
    """After exactly one step Adam's first moment is (1 - b1) x the gradient
    the optimizer was given: its norm per leaf."""
    found = [
        s for s in jax.tree_util.tree_leaves(
            trainer._opt_state, is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return {k: v / (1.0 - b1) for k, v in _norms(found[0].mu).items()}


def change_norms(trainer, sizes: Dict[str, Any], seed: int) -> Dict[str, float]:
    """Norm per leaf of (the trainer's parameters now - those of the seed)."""
    vals = jax.jit(lambda now, keys: jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
        now, weights.make_params(sizes, keys)))(trainer._params, weights.seed_keys(sizes, seed))
    return {k: float(v) for k, v in leaf_names(vals).items()}


def make_trainer(chips: int, callbacks, root_dir: str, seed: int, max_steps: int):
    """``Trainer`` under ``XLAStrategy``, data parallel over the cell's chips,
    with nothing a benchmark run does not need: no validation program, no
    checkpoint, no logger, no progress bar."""
    import ray_lightning_tpu as rlt

    from ray_lightning_tpu.parallel.mesh import MeshSpec

    strat = rlt.XLAStrategy(devices=chips, mesh_spec=MeshSpec(axes={"dp": chips}))
    return rlt.Trainer(
        strategy=strat, max_epochs=1, max_steps=max_steps,
        check_val_every_n_epoch=2, num_sanity_val_steps=0, callbacks=callbacks,
        enable_checkpointing=False, logger=False, enable_progress_bar=False,
        default_root_dir=root_dir, seed=seed % (2 ** 31),
    )


def make_loader(rows: np.ndarray, batch_size: int):
    from ray_lightning_tpu.core.data import DataLoader, DictDataset

    return DataLoader(DictDataset(input_ids=rows), batch_size=batch_size,
                      shuffle=False, drop_last=True)


def callback_base():
    from ray_lightning_tpu.callbacks.base import Callback

    return Callback


def release_trainer(trainer, module) -> None:
    """Drop every device array the fit left, so the reference has the chip."""
    trainer._params = trainer._opt_state = None
    module.params = None


def make_engine(cfg, params, engine: Dict[str, Any]):
    from ray_lightning_tpu.serving import EngineConfig, InferenceEngine

    return InferenceEngine(params, cfg, EngineConfig(**engine))


def engine_counters(engine) -> Dict[str, Any]:
    out = {k: float(v) for k, v in engine.stats.items()}
    out.update({"pool." + k: v for k, v in engine.pool.stats().items()
                if isinstance(v, (int, float))})
    out["num_slots"] = engine.engine_config.num_slots
    out["max_prompt_len"] = engine.engine_config.max_prompt_len
    out.update(engine.compile_stats())
    return out
