"""The ``minicpm_sala`` family's side of the program under test: the one file
of the family that imports ``ray_lightning_tpu``. It builds the program's own
objects from a configuration file's sizes (HF key names). The program serves
this family and does not train it: ``make_module`` says so."""
from __future__ import annotations

from typing import Any, Dict

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``MiniCPMSALAConfig`` for a configuration file's sizes.
    ``remat`` is the serve driver's word to every family; this model has no
    training step to rematerialise."""
    from ray_lightning_tpu.models.minicpm_sala import MiniCPMSALAConfig
    from ray_lightning_tpu.ops.sparse_attention import SparseSpec

    model.pop("remat", None)
    m = weights.dims(sizes)
    return MiniCPMSALAConfig(
        vocab_size=m["vocab"], dim=m["d"], n_layers=m["layers"], mixer_types=m["kinds"],
        n_heads=m["heads"], n_kv_heads=m["kv_heads"], head_dim=m["hd"],
        lightning_heads=m["lheads"], lightning_kv_heads=m["lheads"],
        lightning_head_dim=m["lhd"], ffn_dim=m["f"], scale_emb=m["scale_emb"],
        scale_depth=m["scale_depth"], published_layers=m["depth"],
        dim_model_base=m["base"],
        sparse=SparseSpec(kernel=m["pool"], stride=m["stride"], block=m["block"],
                          topk=m["topk"], window=m["window"], init_blocks=m["init"],
                          dense_len=m["dense_len"]),
        max_seq=max_seq, rope_theta=m["theta"], norm_eps=m["eps"], dtype=m["dtype"].type,
        **model,
    )


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    raise NotImplementedError(
        "the minicpm_sala family is served, not trained: the program has no "
        "training step for it")


def engine_params(sizes: Dict[str, Any], seed: int):
    """The parameters an ``InferenceEngine`` takes beside ``model_config``:
    the model's own tree, made on the device (dispatched, not waited for)."""
    return weights.make_params_on_device(sizes, seed)
