"""Operations and bytes the DeepSeek-V3-style family's arithmetic requires,
from a configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis: a
multiply-add is two operations; the embedding is a gather and counts
nothing; attention is causal, so half of the square; an expert layer
requires the router, the shared expert and only the experts a token is
routed to. Bytes are what the arithmetic needs: a cached position is its
``kv_lora_rank + qk_rope_head_dim`` values a layer (the chip's layout pads
the row to whole lanes; that is the program's cost, not the model's).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .weights import dims

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(sizes: Dict[str, Any]) -> int:
    return _WIDTH[sizes.get("dtype", "bfloat16")]


def attention_params(sizes: Dict[str, Any]) -> int:
    """``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o`` of one layer."""
    m = dims(sizes)
    d, h = m["d"], m["heads"]
    return (d * m["rq"] + m["rq"] * h * (m["nope"] + m["rope"])
            + d * (m["rkv"] + m["rope"]) + m["rkv"] * h * (m["nope"] + m["v"])
            + h * m["v"] * d)


def expert_params(sizes: Dict[str, Any]) -> int:
    m = dims(sizes)
    return 3 * m["d"] * m["fe"]


def dense_layer_params(sizes: Dict[str, Any]) -> int:
    m = dims(sizes)
    return attention_params(sizes) + 3 * m["d"] * m["f"]


def expert_layer_params(sizes: Dict[str, Any], experts: Optional[float] = None) -> float:
    """Attention, router, shared experts and ``experts`` routed ones (None:
    every one the layer holds)."""
    m = dims(sizes)
    n = m["experts"] if experts is None else experts
    return (attention_params(sizes) + m["d"] * m["experts"]
            + (m["shared"] + n) * expert_params(sizes))


def matmul_params(sizes: Dict[str, Any], active_only: bool = True) -> float:
    """Layers and ``lm_head``; not the embedding table. ``active_only``: the
    experts one token uses."""
    m = dims(sizes)
    n = m["top_k"] if active_only else None
    return (m["dense"] * dense_layer_params(sizes)
            + (m["layers"] - m["dense"]) * expert_layer_params(sizes, n)
            + m["d"] * m["vocab"])


def total_params(sizes: Dict[str, Any]) -> int:
    """Everything held: all experts, the embedding and the head."""
    m = dims(sizes)
    return int(matmul_params(sizes, active_only=False)) + m["vocab"] * m["d"]


def _attention_flops_per_token(sizes: Dict[str, Any], context: float) -> float:
    """Scores over ``nope + rope`` columns and values over ``v`` columns of
    every head against ``context`` positions, in the decompressed form."""
    m = dims(sizes)
    return 2.0 * m["layers"] * m["heads"] * (m["nope"] + m["rope"] + m["v"]) * context


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight a token uses, plus causal
    attention (half the square, three times the forward)."""
    return 6.0 * matmul_params(sizes) + 3.0 * _attention_flops_per_token(sizes, seq_len / 2.0)


def forward_flops(sizes: Dict[str, Any], tokens: int, active_only: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions."""
    return (2.0 * matmul_params(sizes, active_only)
            + _attention_flops_per_token(sizes, tokens / 2.0)) * tokens


def weight_bytes(sizes: Dict[str, Any], active_only: bool = False) -> float:
    """Layer and head weights. The embedding table is gathered by row and is
    not counted."""
    return matmul_params(sizes, active_only) * _width(sizes)


def cache_bytes_per_token(sizes: Dict[str, Any]) -> int:
    """What one cached position costs through every layer: the compressed
    K/V and the one roped key."""
    m = dims(sizes)
    return m["layers"] * (m["rkv"] + m["rope"]) * _width(sizes)


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float,
                      expert_hits: Optional[float] = None) -> float:
    """The least one decode tick must read: the latent cache of the live
    context (the rows' lengths summed), every weight outside the routed
    experts once, and ``expert_hits`` routed experts, the distinct experts
    the tick's rows chose summed over its expert layers. Never an expert no
    row chose. ``None``: every expert of every layer (an upper bound, for a
    caller that does not know the routing)."""
    m = dims(sizes)
    layers = m["layers"] - m["dense"]
    if expert_hits is None:
        expert_hits = layers * m["experts"]
    other = matmul_params(sizes, active_only=False) - layers * m["experts"] * expert_params(sizes)
    return ((other + expert_hits * expert_params(sizes)) * _width(sizes)
            + cache_bytes_per_token(sizes) * live_context_tokens)


def mla_decode_attention_bytes(sizes: Dict[str, Any], live_context_tokens: float,
                               rows: float) -> float:
    """What the latent paged decode kernel must move in one decode tick (a
    call a layer): the live context's cached rows once for all heads, the
    rows' absorbed queries in and their float32 sums out."""
    m = dims(sizes)
    latent = m["rkv"] + m["rope"]
    return m["layers"] * (
        live_context_tokens * latent * _width(sizes)
        + rows * m["heads"] * (latent * _width(sizes) + m["rkv"] * 4))


def mla_decode_attention_flops(sizes: Dict[str, Any], live_context_tokens: float) -> float:
    """One decode tick, absorbed form: in every layer every head scores
    ``rkv + rope`` columns and sums ``rkv`` value columns at each live
    position."""
    m = dims(sizes)
    return 2.0 * m["layers"] * m["heads"] * (2 * m["rkv"] + m["rope"]) * live_context_tokens


def routed_experts(sizes: Dict[str, Any]):
    """(experts a layer routes over, expert layers)."""
    m = dims(sizes)
    return m["experts"], m["layers"] - m["dense"]
