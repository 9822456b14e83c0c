"""Operations and bytes the ``cohere2_moe`` family's arithmetic requires, from
a configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis: a
multiply-add is two operations; the embedding's gather counts nothing, its
use as the tied output head counts as a matmul; attention is causal, and on
a window layer a query sees no more than ``sliding_window`` keys. An expert
layer requires the router, the shared experts and only the HELD experts a
token is routed to: a configuration that holds ``num_experts`` of
``published_num_experts`` computes, of a token's ``top_k`` choices, those
that fall on its share (``top_k * held / routed`` of them if routing is
even). Bytes are what the arithmetic needs: a cached position is its K and V
in the layers that still hold it, every layer's for a position inside the
window, the full layers' alone for one that fell out.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .weights import dims

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(sizes: Dict[str, Any]) -> int:
    return _WIDTH[sizes.get("dtype", "bfloat16")]


def layers_by_kind(sizes: Dict[str, Any]):
    """(window layers, full layers)."""
    m = dims(sizes)
    full = m["layers"] // m["period"]
    return m["layers"] - full, full


def attention_params(sizes: Dict[str, Any]) -> int:
    """``Wq``, ``Wk``, ``Wv``, ``Wo`` of one layer."""
    m = dims(sizes)
    q, kv = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    return m["d"] * (2 * q + 2 * kv)


def expert_params(sizes: Dict[str, Any]) -> int:
    m = dims(sizes)
    return 3 * m["d"] * m["f"]


def layer_params(sizes: Dict[str, Any], experts: Optional[float] = None) -> float:
    """Attention, router, shared experts and ``experts`` routed ones (None:
    every one the configuration holds of a layer)."""
    m = dims(sizes)
    n = m["held"] if experts is None else experts
    return (attention_params(sizes) + m["d"] * m["routed"]
            + (m["shared"] + n) * expert_params(sizes))


def experts_per_token_here(sizes: Dict[str, Any]) -> float:
    """Of a token's ``top_k`` choices, those that fall on the held experts
    if routing is even."""
    m = dims(sizes)
    return m["top_k"] * m["held"] / m["routed"]


def matmul_params(sizes: Dict[str, Any], active_only: bool = True) -> float:
    """Layers and the tied embedding as the output head. ``active_only``:
    the held experts one token uses."""
    m = dims(sizes)
    n = experts_per_token_here(sizes) if active_only else None
    return m["layers"] * layer_params(sizes, n) + m["d"] * m["vocab"]


def total_params(sizes: Dict[str, Any]) -> int:
    """Everything held: the held experts of every layer and the embedding,
    which is the head too and counts once."""
    return int(matmul_params(sizes, active_only=False))


def _seen(tokens: float, window: int) -> float:
    """Keys seen by all the queries of a causal pass over ``tokens``
    positions (``window`` 0: no window), halved squares and all."""
    if not window or tokens <= window:
        return tokens * (tokens + 1) / 2.0
    return window * (window + 1) / 2.0 + (tokens - window) * window


def _attention_flops(sizes: Dict[str, Any], tokens: float) -> float:
    """Scores and values of every head over the keys each query sees, through
    every layer of both kinds, for one sequence of ``tokens`` positions."""
    m = dims(sizes)
    n_window, n_full = layers_by_kind(sizes)
    seen = n_window * _seen(tokens, m["window"]) + n_full * _seen(tokens, 0)
    return 4.0 * m["heads"] * m["hd"] * seen


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight a token uses, plus attention
    three times the forward. (The program does not train this family; the
    count is the arithmetic's all the same.)"""
    return 6.0 * matmul_params(sizes) + 3.0 * _attention_flops(sizes, seq_len) / seq_len


def forward_flops(sizes: Dict[str, Any], tokens: int, active_only: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions."""
    return 2.0 * matmul_params(sizes, active_only) * tokens + _attention_flops(sizes, tokens)


def weight_bytes(sizes: Dict[str, Any], active_only: bool = False) -> float:
    """Layer weights and the tied embedding (read whole as the head)."""
    return matmul_params(sizes, active_only) * _width(sizes)


def cache_bytes_per_layer(sizes: Dict[str, Any]) -> int:
    """K and V of one position in one layer."""
    m = dims(sizes)
    return 2 * m["kv_heads"] * m["hd"] * _width(sizes)


def cache_bytes_per_token(sizes: Dict[str, Any]) -> int:
    """What one cached position costs through every layer while every kind
    holds it (a position out of the window costs the full layers' alone)."""
    return dims(sizes)["layers"] * cache_bytes_per_layer(sizes)


def cache_bytes(sizes: Dict[str, Any], full_tokens: float, window_tokens: float) -> float:
    """The cache a tick reads: ``full_tokens`` live positions in each full
    layer, ``window_tokens`` in each window layer."""
    n_window, n_full = layers_by_kind(sizes)
    return cache_bytes_per_layer(sizes) * (n_full * full_tokens + n_window * window_tokens)


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float,
                      expert_hits: Optional[float] = None,
                      window_tokens: Optional[float] = None) -> float:
    """The least one decode tick must read: the K and V of the live context
    by kind (``live_context_tokens``: the rows' lengths summed, what a full
    layer reads; ``window_tokens``: each row's length or the window,
    whichever is less, summed, what a window layer reads; None: as many),
    every weight outside the routed experts once, the embedding as the head
    among them, and ``expert_hits`` held experts, the distinct held experts
    the tick's rows chose summed over the layers. Never an expert no row
    chose. ``None``: every held expert of every layer (an upper bound)."""
    m = dims(sizes)
    if expert_hits is None:
        expert_hits = m["layers"] * m["held"]
    if window_tokens is None:
        window_tokens = live_context_tokens
    other = matmul_params(sizes, active_only=False) - m["layers"] * m["held"] * expert_params(sizes)
    return ((other + expert_hits * expert_params(sizes)) * _width(sizes)
            + cache_bytes(sizes, live_context_tokens, window_tokens))


def paged_decode_attention_bytes(sizes: Dict[str, Any], full_tokens: float,
                                 window_tokens: float, rows: float) -> float:
    """What the paged decode kernel must move in one decode tick (a call a
    layer): the live positions' K and V by kind, the rows' queries in and
    their sums out, both float32."""
    m = dims(sizes)
    return (cache_bytes(sizes, full_tokens, window_tokens)
            + m["layers"] * rows * m["heads"] * m["hd"] * (4 + 4))


def paged_decode_attention_flops(sizes: Dict[str, Any], full_tokens: float,
                                 window_tokens: float) -> float:
    """One decode tick: in every layer every head scores and sums ``hd``
    columns at each live position of the layer's kind."""
    m = dims(sizes)
    n_window, n_full = layers_by_kind(sizes)
    return 4.0 * m["heads"] * m["hd"] * (n_full * full_tokens + n_window * window_tokens)


def routed_experts(sizes: Dict[str, Any]):
    """(experts held of a layer, expert layers)."""
    m = dims(sizes)
    return m["held"], m["layers"]
