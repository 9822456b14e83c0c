"""Operations and bytes the ``jamba`` family's arithmetic requires, from a
configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis: a
multiply-add is two operations; the embedding's gather counts nothing, the
tied head counts as a matmul (and its matrix once among the bytes). An
attention layer's query requires every position up to its own. A Mamba layer
requires, a position a channel a state element, ``SCAN_OPS``: the decay's
product and its ``exp``, the input's product, the update's multiply and add,
the read-out's multiply and add; its state is ``C x N`` float32 and its
convolution's tail ``C x (K - 1)`` in the configuration's type, a layer a
request, and neither grows.
"""
from __future__ import annotations

from typing import Any, Dict

from .weights import ATTENTION, MAMBA, dims

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}
SCAN_OPS = 7.0  # a state element a position: 3 multiplies, an exp, an add, and the read-out's 2


def _width(sizes: Dict[str, Any]) -> int:
    return _WIDTH[sizes.get("dtype", "bfloat16")]


def layers_by_kind(sizes: Dict[str, Any]):
    """(attention layers, Mamba layers)."""
    kinds = dims(sizes)["kinds"]
    return kinds.count(ATTENTION), kinds.count(MAMBA)


def mixer_params(sizes: Dict[str, Any], kind: str) -> int:
    """One mixer's parameters. Attention: ``W_q``, ``W_o``, ``W_k``, ``W_v``.
    Mamba: ``in_proj``, the convolution and its bias, ``x_proj``,
    ``dt_proj`` and its bias, ``A_log``, ``D``, ``out_proj`` and the three
    inner norms."""
    m = dims(sizes)
    d, ci, n, r, k = m["d"], m["ci"], m["n"], m["r"], m["k"]
    if kind == ATTENTION:
        return 2 * d * m["heads"] * m["hd"] + 2 * d * m["kv_heads"] * m["hd"]
    return (d * 2 * ci + ci * k + ci + ci * (r + 2 * n) + r * ci + ci + ci * n + ci
            + ci * d + r + 2 * n)


def layer_params(sizes: Dict[str, Any], kind: str) -> int:
    """A layer: its mixer, the SwiGLU's three matrices and the two norms."""
    m = dims(sizes)
    return mixer_params(sizes, kind) + 3 * m["d"] * m["f"] + 2 * m["d"]


def total_params(sizes: Dict[str, Any]) -> int:
    """Everything held: the layers, the tied embedding and the final norm."""
    m = dims(sizes)
    n_att, n_mamba = layers_by_kind(sizes)
    return (n_att * layer_params(sizes, ATTENTION) + n_mamba * layer_params(sizes, MAMBA)
            + m["vocab"] * m["d"] + m["d"])


def matmul_params(sizes: Dict[str, Any], head: bool = True) -> int:
    """The weights a token is multiplied with: every layer's matrices (a
    Mamba layer's ``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj``) and,
    with ``head``, the tied head."""
    m = dims(sizes)
    d, ci, n, r = m["d"], m["ci"], m["n"], m["r"]
    n_att, n_mamba = layers_by_kind(sizes)
    mamba = d * 2 * ci + ci * (r + 2 * n) + r * ci + ci * d
    return (n_att * mixer_params(sizes, ATTENTION) + n_mamba * mamba
            + (n_att + n_mamba) * 3 * d * m["f"] + (m["vocab"] * d if head else 0))


def _mixer_flops(sizes: Dict[str, Any], tokens: int) -> float:
    """Scores and values of the attention layers, and the Mamba layers'
    convolution and scan, for one sequence."""
    m = dims(sizes)
    n_att, n_mamba = layers_by_kind(sizes)
    attend = 4.0 * m["heads"] * m["hd"] * tokens * (tokens + 1) / 2.0
    scan = tokens * m["ci"] * (SCAN_OPS * m["n"] + 2.0 * m["k"])
    return n_att * attend + n_mamba * scan


def forward_flops(sizes: Dict[str, Any], tokens: int, head: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions.
    ``head`` False: a prefill, which computes no logits."""
    return 2.0 * matmul_params(sizes, head) * tokens + _mixer_flops(sizes, tokens)


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight a token, plus the mixers
    three times the forward. (The program does not train this family; the
    count is the arithmetic's all the same.)"""
    return 6.0 * matmul_params(sizes) + 3.0 * _mixer_flops(sizes, seq_len) / seq_len


def weight_bytes(sizes: Dict[str, Any]) -> float:
    """What a decode tick reads of the weights: every parameter but the
    embedding's rows, of which it reads the head's (all of them, once) and a
    row a token."""
    return total_params(sizes) * _width(sizes)


def cache_bytes_per_layer(sizes: Dict[str, Any]) -> int:
    """K and V of one position in one attention layer."""
    m = dims(sizes)
    return 2 * m["kv_heads"] * m["hd"] * _width(sizes)


def cache_bytes_per_token(sizes: Dict[str, Any]) -> float:
    """What one more position costs through every layer: the attention
    layers' K and V; the Mamba layers nothing."""
    return layers_by_kind(sizes)[0] * cache_bytes_per_layer(sizes)


def scan_state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """The Mamba layers' float32 scan state of one request."""
    m = dims(sizes)
    return layers_by_kind(sizes)[1] * m["ci"] * m["n"] * 4


def state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """Both state leaves of one request: the scan state, float32, and the
    convolution's tail in the configuration's type."""
    m = dims(sizes)
    tail = layers_by_kind(sizes)[1] * m["ci"] * (m["k"] - 1) * _width(sizes)
    return scan_state_bytes_per_slot(sizes) + tail


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float,
                      state_slots: float = 0.0) -> float:
    """The least one decode tick must read and write: every weight once, the
    K and V of the live context an attention layer, and both state leaves of
    ``state_slots`` slots read and written."""
    return (weight_bytes(sizes) + cache_bytes_per_token(sizes) * live_context_tokens
            + 2.0 * state_bytes_per_slot(sizes) * state_slots)


# ---------------------------------------------------------------------- #
# the kernels, each what it must move and compute for what it is handed
# ---------------------------------------------------------------------- #
def mamba_scan_bytes(sizes: Dict[str, Any], tokens: int) -> float:
    """``mamba_scan`` over one prompt (a call a Mamba layer): x and dt in and
    y out, float32 a position a channel; B and C a position; A, D and the
    last state a call."""
    m = dims(sizes)
    per_call = (3.0 * tokens * m["ci"] * 4 + 2.0 * tokens * m["n"] * 4
                + 2.0 * m["ci"] * m["n"] * 4 + m["ci"] * 4)
    return layers_by_kind(sizes)[1] * per_call


def mamba_scan_flops(sizes: Dict[str, Any], tokens: int) -> float:
    m = dims(sizes)
    return layers_by_kind(sizes)[1] * tokens * m["ci"] * (SCAN_OPS * m["n"] + 2.0)


def mamba_decode_bytes(sizes: Dict[str, Any], slots: float) -> float:
    """``mamba_decode`` in one decode tick (a call a Mamba layer): every
    slot's scan state read and written, x and dt in and y out a channel, B
    and C a slot; A and D a call. (The convolution's tail is shifted by XLA
    in front of the kernel and is not among its bytes.)"""
    m = dims(sizes)
    n_mamba = layers_by_kind(sizes)[1]
    rows = n_mamba * slots * (3.0 * m["ci"] * 4 + 2.0 * m["n"] * 4)
    return (2.0 * scan_state_bytes_per_slot(sizes) * slots + rows
            + n_mamba * (m["ci"] * m["n"] * 4 + m["ci"] * 4))


def mamba_decode_flops(sizes: Dict[str, Any], slots: float) -> float:
    m = dims(sizes)
    return layers_by_kind(sizes)[1] * slots * m["ci"] * (SCAN_OPS * m["n"] + 2.0)


def paged_decode_attention_bytes(sizes: Dict[str, Any], live_tokens: float, rows: float) -> float:
    """``paged_decode_attention`` in one decode tick (a call an attention
    layer): the live positions' K and V, the rows' queries in and their sums
    out, both float32."""
    m = dims(sizes)
    return layers_by_kind(sizes)[0] * (
        cache_bytes_per_layer(sizes) * live_tokens + rows * m["heads"] * m["hd"] * (4 + 4))
