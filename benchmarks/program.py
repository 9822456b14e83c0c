"""Where the benchmark touches the program under test, whatever the model.

Everything else under ``benchmarks/`` is the yardstick and imports nothing
of ``ray_lightning_tpu``, save each family's ``program.py``
(``benchmarks/families/<family>/``), which builds the program's config
object, its module with the seeded weights and its engine's parameters. Here:
the trainer, the loader, the engine, its counters, and the norms read from
the trainer's state.
"""
from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


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


def change_norms(trainer, weights, sizes: Dict[str, Any], seed: int) -> Dict[str, float]:
    """Norm per leaf of (the trainer's parameters now - those of the seed,
    made anew by the family's ``weights``)."""
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
