"""Bytes a step of serving has to move, from the configuration's shapes."""
from __future__ import annotations

from typing import Any, Dict

from benchmarks.flops import head_dim, matmul_params

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(sizes: Dict[str, Any], active_only: bool = False) -> int:
    """Layer and head weights, each read once by a decode tick. The
    embedding table is gathered by row and is not counted."""
    return matmul_params(sizes, active_only) * _WIDTH[sizes.get("dtype", "bfloat16")]


def kv_bytes_per_token(sizes: Dict[str, Any]) -> int:
    """K and V of one position through every layer."""
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * head_dim(sizes) * _WIDTH[sizes.get("dtype", "bfloat16")])


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float) -> float:
    """What one decode tick must read: every weight once, and K and V of the
    live context of every occupied row (their lengths summed)."""
    return weight_bytes(sizes) + kv_bytes_per_token(sizes) * live_context_tokens
