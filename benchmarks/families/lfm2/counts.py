"""Operations and bytes the ``lfm2_moe`` family's arithmetic requires, from a
configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis (which
misses the Mosaic kernels): a multiply-add is two operations; the embedding's
gather counts nothing, its use as the tied output head counts as a matmul;
attention is causal, so half of the square; the short convolution's three
taps and its gates are elementwise and count nothing beside the projections;
recomputation under remat is not required work and is not counted. An expert
layer requires the router and only the HELD experts a token is routed to: a
configuration that holds ``num_experts`` of ``published_num_experts``
computes, of a token's ``top_k`` choices, those that fall on its share, in
EXPECTATION ``top_k * held / routed`` of them (2 of 4 where 16 of 32 are
held). The family is trained, not served: the serve-only counts raise by
name.
"""
from __future__ import annotations

from typing import Any, Dict

from .weights import dims

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def conv_mixer_params(sizes: Dict[str, Any]) -> int:
    """``in_proj``, ``out_proj`` and the taps."""
    m = dims(sizes)
    return m["d"] * 3 * m["d"] + m["d"] * m["d"] + m["taps"] * m["d"]


def attention_mixer_params(sizes: Dict[str, Any]) -> int:
    """``Wq``, ``Wk``, ``Wv``, ``Wo`` (the per-head norms' 2 x head_dim apart)."""
    m = dims(sizes)
    return m["d"] * (2 * m["heads"] * m["hd"] + 2 * m["kv_heads"] * m["hd"])


def expert_params(sizes: Dict[str, Any]) -> int:
    m = dims(sizes)
    return 3 * m["d"] * m["f"]


def experts_per_token_here(sizes: Dict[str, Any]) -> float:
    """Of a token's ``top_k`` choices, those that fall on the held experts
    in expectation."""
    m = dims(sizes)
    return m["top_k"] * m["held"] / m["routed"]


def layer_matmul_params(sizes: Dict[str, Any], layer: int, active_only: bool = True) -> float:
    """Weights of one layer that sit in a matmul (the taps do not); with
    ``active_only`` an expert layer counts the router and the held experts
    one token uses, else every expert it holds."""
    m = dims(sizes)
    mixer = (4 * m["d"] * m["d"] if m["kinds"][layer] == "conv"
             else attention_mixer_params(sizes))
    if layer < m["dense"]:
        return mixer + 3 * m["d"] * m["f_dense"]
    used = experts_per_token_here(sizes) if active_only else m["held"]
    return mixer + m["d"] * m["routed"] + used * expert_params(sizes)


def matmul_params(sizes: Dict[str, Any], active_only: bool = True) -> float:
    """Layers and the tied head."""
    m = dims(sizes)
    return (sum(layer_matmul_params(sizes, l, active_only) for l in range(m["layers"]))
            + m["d"] * m["vocab"])


def held_params(sizes: Dict[str, Any]) -> int:
    """Every parameter this configuration holds: what its training state is
    8 bytes each of."""
    m = dims(sizes)
    total = m["vocab"] * m["d"] + m["d"]
    for layer, kind in enumerate(m["kinds"]):
        total += 2 * m["d"] + (conv_mixer_params(sizes) if kind == "conv"
                               else attention_mixer_params(sizes) + 2 * m["hd"])
        if layer < m["dense"]:
            total += 3 * m["d"] * m["f_dense"]
        else:
            total += (m["d"] * m["routed"] + m["routed"] * m["bias"]
                      + m["held"] * expert_params(sizes))
    return total


def attention_layers(sizes: Dict[str, Any]) -> int:
    return dims(sizes)["kinds"].count("full_attention")


def expert_layers(sizes: Dict[str, Any]) -> int:
    m = dims(sizes)
    return m["layers"] - m["dense"]


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight a token uses, the expert
    term at the EXPECTED held choices, plus causal attention in the
    ``full_attention`` layers (scores and values: 4 S d per token forward if
    it were the whole square, half of it causal, three times that with the
    backward)."""
    attn = 6.0 * attention_layers(sizes) * seq_len * sizes["hidden_size"]
    return 6.0 * matmul_params(sizes) + attn


def forward_flops(sizes: Dict[str, Any], tokens: int, active_only: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions."""
    attn = 2.0 * attention_layers(sizes) * tokens * tokens * sizes["hidden_size"]
    return 2.0 * matmul_params(sizes, active_only) * tokens + attn


def weight_bytes(sizes: Dict[str, Any], active_only: bool = False) -> float:
    """Layer and head weights in the configuration's type."""
    return matmul_params(sizes, active_only) * _WIDTH[sizes.get("dtype", "bfloat16")]


def expert_gmm_flops(sizes: Dict[str, Any], rows: float) -> float:
    """ONE call of either grouped kernel over ``rows`` rows in groups: a
    ``[rows, hidden] x [hidden, expert width]`` product or one of its two
    gradients, whichever of the three stacks it is and whatever implements
    it: 2 x hidden x width a row."""
    m = dims(sizes)
    return 2.0 * m["d"] * m["f"] * rows


def cache_bytes_per_token(sizes: Dict[str, Any]) -> int:
    raise NotImplementedError(
        "cache_bytes_per_token: the lfm2 family is trained, not served; no pool holds "
        "a convolution's tail beside K and V yet")


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float) -> float:
    raise NotImplementedError(
        "decode_tick_bytes: the lfm2 family is trained, not served")
