"""Operations and bytes the Llama family's arithmetic requires, from a
configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis (which
misses the Mosaic kernels): a multiply-add is two operations; the embedding
is a gather and counts nothing; attention is causal, so half of the square;
recomputation under remat is not required work and is not counted; a routed
MoE layer requires only the experts a token is routed to.
"""
from __future__ import annotations

from typing import Any, Dict


def head_dim(sizes: Dict[str, Any]) -> int:
    return sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"]


def layer_matmul_params(sizes: Dict[str, Any], active_only: bool = True) -> int:
    """Weights of one layer that sit in a matmul; with ``active_only`` an MoE
    layer counts the router and the experts one token uses."""
    d, f, hd = sizes["hidden_size"], sizes["intermediate_size"], head_dim(sizes)
    nq, nkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    attn = d * nq + 2 * d * nkv + nq * d
    experts = sizes.get("num_local_experts", 0)
    if experts:
        used = sizes["num_experts_per_tok"] if active_only else experts
        return attn + d * experts + used * 3 * d * f
    return attn + 3 * d * f


def matmul_params(sizes: Dict[str, Any], active_only: bool = True) -> int:
    """Layers and ``lm_head``; not the embedding table."""
    return (sizes["num_hidden_layers"] * layer_matmul_params(sizes, active_only)
            + sizes["hidden_size"] * sizes["vocab_size"])


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight, plus causal attention
    (scores and values: 4 S d per token forward if it were the whole
    square, half of it causal, three times that with the backward)."""
    attn = 6.0 * sizes["num_hidden_layers"] * seq_len * sizes["hidden_size"]
    return 6.0 * matmul_params(sizes) + attn


def forward_flops(sizes: Dict[str, Any], tokens: int, active_only: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions."""
    attn = 2.0 * sizes["num_hidden_layers"] * tokens * tokens * sizes["hidden_size"]
    return 2.0 * matmul_params(sizes, active_only) * tokens + attn


_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(sizes: Dict[str, Any], active_only: bool = False) -> int:
    """Layer and head weights, each read once by a decode tick. The
    embedding table is gathered by row and is not counted."""
    return matmul_params(sizes, active_only) * _WIDTH[sizes.get("dtype", "bfloat16")]


def cache_bytes_per_token(sizes: Dict[str, Any]) -> int:
    """What one cached position costs through every layer: K and V of every
    KV head."""
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * head_dim(sizes) * _WIDTH[sizes.get("dtype", "bfloat16")])


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float) -> float:
    """What one decode tick must read: every weight once, and the cache of
    the live context of every occupied row (their lengths summed)."""
    return weight_bytes(sizes) + cache_bytes_per_token(sizes) * live_context_tokens
