"""The ``jamba`` family's side of the program under test: the one file of the
family that imports ``ray_lightning_tpu``. It builds the program's own
objects from a configuration file's sizes (HF key names, which the program's
``JambaConfig`` keeps). The program serves this family and does not train
it: ``make_module`` says so."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from . import weights


def model_config(sizes: Dict[str, Any], max_seq: int, **model: Any):
    """The program's ``JambaConfig`` for a configuration file's sizes: every
    key of the file that the config object has, at the file's value.
    ``remat`` is the serve driver's word to every family; this model has no
    training step to rematerialise."""
    from ray_lightning_tpu.models.jamba import JambaConfig

    model.pop("remat", None)
    m = weights.dims(sizes)  # refuses what the family has no equations for
    stated = {f.name: sizes[f.name] for f in dataclasses.fields(JambaConfig)
              if f.name in sizes and f.name != "dtype"}
    return JambaConfig(**dict(stated, head_dim=m["hd"], max_seq=max_seq,
                              dtype=m["dtype"].type), **model)


def make_module(cfg, sizes: Dict[str, Any], seed: int, opt: Dict[str, Any]):
    raise NotImplementedError(
        "the jamba family is served, not trained: the program has no "
        "training step for it")


def engine_params(sizes: Dict[str, Any], seed: int):
    """The parameters an ``InferenceEngine`` takes beside ``model_config``:
    the model's own tree, made on the device (dispatched, not waited for)."""
    return weights.make_params_on_device(sizes, seed)
