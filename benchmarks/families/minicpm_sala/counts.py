"""Operations and bytes the ``minicpm_sala`` family's arithmetic requires,
from a configuration's shapes.

Counted here and not read from the program or from XLA's cost analysis: a
multiply-add is two operations; the embedding's gather counts nothing, the
untied head counts as a matmul. A sparse layer's query REQUIRES the positions
of the blocks it chose (all of them under ``dense_len``, ``topk`` blocks from
there on) and the complete pooled keys it scored; what a kernel computes
beyond that (the prefill kernel computes every causal tile and masks) is
counted by that kernel's own function and by no other. A lightning layer
requires, a position a head, the outer product into the state and the
read-out from it (``4 hd^2``), whatever form computes them; its state is ``H
x hd x hd`` float32 a layer a request and does not grow.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .weights import LIGHTNING, SPARSE, dims

_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(sizes: Dict[str, Any]) -> int:
    return _WIDTH[sizes.get("dtype", "bfloat16")]


def layers_by_kind(sizes: Dict[str, Any]):
    """(sparse layers, lightning layers)."""
    kinds = dims(sizes)["kinds"]
    return kinds.count(SPARSE), kinds.count(LIGHTNING)


def layer_params(sizes: Dict[str, Any], kind: str) -> int:
    """The matrices of one layer: ``W_q``, ``W_k``, ``W_v``, ``W_g``,
    ``W_o`` and the SwiGLU's three."""
    m = dims(sizes)
    if kind == SPARSE:
        q, kv = m["heads"] * m["hd"], m["kv_heads"] * m["hd"]
    else:
        q = kv = m["lheads"] * m["lhd"]
    return m["d"] * (3 * q + 2 * kv) + 3 * m["d"] * m["f"]


def matmul_params(sizes: Dict[str, Any], head: bool = True) -> int:
    """Every layer's matrices and, with ``head``, the untied output head."""
    m = dims(sizes)
    n_sparse, n_light = layers_by_kind(sizes)
    return (n_sparse * layer_params(sizes, SPARSE) + n_light * layer_params(sizes, LIGHTNING)
            + (m["d"] * m["vocab"] if head else 0))


def total_params(sizes: Dict[str, Any]) -> int:
    """Everything held: the layers, the head and the embedding."""
    m = dims(sizes)
    return matmul_params(sizes) + m["d"] * m["vocab"]


def selected_positions(sizes: Dict[str, Any], pos: int) -> int:
    """Positions the query at ``pos`` attends in a sparse layer."""
    m = dims(sizes)
    if pos + 1 < m["dense_len"]:
        return pos + 1
    return (m["topk"] - 1) * m["block"] + pos % m["block"] + 1


def _seen(sizes: Dict[str, Any], tokens: int, chosen_only: bool) -> float:
    """Keys seen by all the queries of a pass over ``tokens`` positions in a
    sparse layer: the chosen blocks', or every causal one."""
    if not chosen_only:
        return tokens * (tokens + 1) / 2.0
    m = dims(sizes)
    dense = min(tokens, m["dense_len"] - 1)
    seen = dense * (dense + 1) / 2.0
    rest = tokens - dense
    # from dense_len on: topk - 1 whole blocks and the own block's start,
    # which is (block + 1) / 2 positions on average
    return seen + rest * ((m["topk"] - 1) * m["block"] + (m["block"] + 1) / 2.0)


def _mixer_flops(sizes: Dict[str, Any], tokens: int, chosen_only: bool = True) -> float:
    """Scores and values of the sparse layers, the indexer's scores, and the
    lightning layers' state arithmetic, for one sequence."""
    m = dims(sizes)
    n_sparse, n_light = layers_by_kind(sizes)
    attend = 4.0 * m["heads"] * m["hd"] * _seen(sizes, tokens, chosen_only)
    rest = max(0, tokens - (m["dense_len"] - 1))  # the queries that select
    index = 2.0 * m["heads"] * m["hd"] * rest * (tokens + m["dense_len"]) / 2.0 / m["stride"]
    state = 4.0 * m["lheads"] * m["lhd"] ** 2 * tokens
    return n_sparse * (attend + index) + n_light * state


def forward_flops(sizes: Dict[str, Any], tokens: int, head: bool = True) -> float:
    """One causal forward pass over one sequence of ``tokens`` positions:
    what the arithmetic requires. ``head`` False: a prefill, which computes
    no logits."""
    return 2.0 * matmul_params(sizes, head) * tokens + _mixer_flops(sizes, tokens)


def train_flops_per_token(sizes: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 per matmul weight a token, plus the mixers
    three times the forward. (The program does not train this family; the
    count is the arithmetic's all the same.)"""
    return 6.0 * matmul_params(sizes) + 3.0 * _mixer_flops(sizes, seq_len) / seq_len


def weight_bytes(sizes: Dict[str, Any]) -> float:
    """Layer weights and the head: what a decode tick reads of the weights
    (of the embedding it reads a row a token)."""
    return matmul_params(sizes) * _width(sizes)


def cache_bytes_per_layer(sizes: Dict[str, Any]) -> int:
    """K and V of one position in one sparse layer."""
    m = dims(sizes)
    return 2 * m["kv_heads"] * m["hd"] * _width(sizes)


def pooled_key_bytes(sizes: Dict[str, Any]) -> int:
    """One pooled key, every key/value head, of one sparse layer."""
    m = dims(sizes)
    return m["kv_heads"] * m["hd"] * _width(sizes)


def cache_bytes_per_token(sizes: Dict[str, Any]) -> float:
    """What one more position costs through every layer: the sparse layers'
    K, V and share of a pooled key; the lightning layers nothing."""
    m = dims(sizes)
    n_sparse, _ = layers_by_kind(sizes)
    return n_sparse * (cache_bytes_per_layer(sizes) + pooled_key_bytes(sizes) / m["stride"])


def state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """The lightning layers' float32 state of one request."""
    m = dims(sizes)
    return layers_by_kind(sizes)[1] * m["lheads"] * m["lhd"] ** 2 * 4


def decode_tick_bytes(sizes: Dict[str, Any], live_context_tokens: float,
                      selected_tokens: Optional[float] = None,
                      pooled_keys: float = 0.0, state_slots: float = 0.0) -> float:
    """The least one decode tick must read and write: every layer weight and
    the head once, the K and V of ``selected_tokens`` positions a sparse
    layer (the positions in the blocks the rows chose, summed over the rows;
    None: the whole live context, what a dense layer would read),
    ``pooled_keys`` pooled keys a sparse layer scored, and the lightning
    state of ``state_slots`` slots read and written."""
    n_sparse, _ = layers_by_kind(sizes)
    if selected_tokens is None:
        selected_tokens = live_context_tokens
    return (weight_bytes(sizes)
            + n_sparse * (cache_bytes_per_layer(sizes) * selected_tokens
                          + pooled_key_bytes(sizes) * pooled_keys)
            + 2.0 * state_bytes_per_slot(sizes) * state_slots)


# ---------------------------------------------------------------------- #
# the kernels, each what it must move and compute for what it is handed
# ---------------------------------------------------------------------- #
def paged_decode_attention_bytes(sizes: Dict[str, Any], selected_tokens: float,
                                 rows: float) -> float:
    """``paged_decode_attention`` over composed tables in one decode tick (a
    call a sparse layer): the selected positions' K and V, the rows' queries
    in and their sums out, both float32. The pooled keys are not its to
    read: the selection is XLA's (``sparse_select``)."""
    m = dims(sizes)
    n_sparse, _ = layers_by_kind(sizes)
    return n_sparse * (cache_bytes_per_layer(sizes) * selected_tokens
                       + rows * m["heads"] * m["hd"] * (4 + 4))


def paged_decode_attention_flops(sizes: Dict[str, Any], selected_tokens: float) -> float:
    m = dims(sizes)
    return layers_by_kind(sizes)[0] * 4.0 * m["heads"] * m["hd"] * selected_tokens


def lightning_decode_bytes(sizes: Dict[str, Any], slots: float) -> float:
    """``lightning_decode`` in one decode tick (a call a lightning layer):
    every slot's state read and written, q, k, v in and o out."""
    m = dims(sizes)
    n_light = layers_by_kind(sizes)[1]
    rows = n_light * slots * m["lheads"] * m["lhd"] * (3 * _width(sizes) + 4)
    return 2.0 * state_bytes_per_slot(sizes) * slots + rows


def lightning_decode_flops(sizes: Dict[str, Any], slots: float) -> float:
    m = dims(sizes)
    return layers_by_kind(sizes)[1] * slots * 4.0 * m["lheads"] * m["lhd"] ** 2


def lightning_prefill_flops(sizes: Dict[str, Any], tokens: int, chunk: int = 128) -> float:
    """``lightning_prefill`` over one prompt (a call a lightning layer), the
    chunked form: a chunk a head, the in-chunk scores and their values and
    the carried state's read-out and update."""
    m = dims(sizes)
    per_position = 4.0 * chunk * m["lhd"] + 4.0 * m["lhd"] ** 2
    return layers_by_kind(sizes)[1] * m["lheads"] * tokens * per_position


def lightning_prefill_bytes(sizes: Dict[str, Any], tokens: int) -> float:
    """q, k, v in and o out, and a head's state out."""
    m = dims(sizes)
    n_light = layers_by_kind(sizes)[1]
    return n_light * (4.0 * m["lheads"] * m["lhd"] * tokens * _width(sizes)
                      + state_bytes_per_slot(sizes) / max(n_light, 1))


def flash_fwd_selected_flops(sizes: Dict[str, Any], tokens: int) -> float:
    """``flash_fwd_selected`` over one prompt (a call a sparse layer): it
    computes every causal tile and masks what the query did not choose."""
    m = dims(sizes)
    return layers_by_kind(sizes)[0] * 4.0 * m["heads"] * m["hd"] * _seen(sizes, tokens, False)
