"""Misc shared helpers: rank-zero logging and optional-dependency sentinel."""
from __future__ import annotations

import functools
import importlib
import logging
import os

logger = logging.getLogger("ray_lightning_tpu")


def _global_rank() -> int:
    return int(os.environ.get("RLT_GLOBAL_RANK", "0"))


def rank_zero_info(msg: str, *args) -> None:
    if _global_rank() == 0:
        logger.info(msg, *args)


def rank_zero_warn(msg: str, *args) -> None:
    if _global_rank() == 0:
        logger.warning(msg, *args)


@functools.lru_cache(maxsize=None)
def optional_import(name: str):
    """The module ``name``, or None where it cannot be imported.

    For a library that only an optional integration needs: it is imported
    at the first call that asks for it, when the integration is used, and
    not with the package (TensorBoard's writer and orbax took 30 s of every
    process's start on a TPU host). The answer is kept, so a library that
    fails to import is tried once."""
    try:
        return importlib.import_module(name)
    except Exception:
        return None


class Unavailable:
    """Placeholder for optional integrations that are not installed.

    Mirrors the reference's optional-dependency fallback
    (reference: ray_lightning/util.py:42-46, tune.py:13-27): importing the
    symbol succeeds, using it raises with a helpful message.
    """

    _reason = "this optional dependency is not available in this environment"

    def __init__(self, *args, **kwargs):
        raise RuntimeError(f"Cannot instantiate: {self._reason}")

    def __getattr__(self, item):
        raise RuntimeError(f"Cannot use attribute {item!r}: {self._reason}")
