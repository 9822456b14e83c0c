"""Arithmetic shared by the per-layer readers of a cell whose model keeps
window and full attention layers in a pool of two kinds of leaf and holds a
share of its routed experts (``layer_metrics/*.doc.py``).

The engine's counters carry what a reader needs by kind: ``kv_positions_full``
and ``kv_positions_window`` (the positions a decode tick's rows attend in a
full layer, ``pos + 1`` each, and in a window layer, no more than the window,
summed over decode ticks), the routing counters of the held experts
(``moe_expert_hits``, ``moe_routed_pairs``, ``moe_max_expert_rows``,
``moe_choices``) and the pool's ``layers`` / ``num_blocks`` /
``blocks_highwater``, the full kind's under those names and the window
kind's behind ``window.``. The family's counts are reached through the
function the serve driver hands over (``latent_readers.family_counts``), so
a reader names no family. A program without the counters, as the parent of
the PR that brought them, leaves nothing to read."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import program_trace
from benchmarks.latent_readers import DECODE_DISPATCH, family_counts

KERNEL = "paged_decode_attention"


def live_by_kind(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Per decode tick: live positions a full layer and a window layer
    attend, and the rows decoded."""
    c = facts.get("counters", {})
    steps = c.get("decode_steps")
    if not steps or not c.get("kv_positions_full") or "kv_positions_window" not in c:
        return None
    return {"full": c["kv_positions_full"] / steps,
            "window": c["kv_positions_window"] / steps,
            "rows": c.get("busy_slot_steps", 0) / steps}


def window_kv_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Positions the window leaves hold for the decoding rows over those a
    pool of one kind would hold for the same rows, a window layer."""
    live = live_by_kind(facts)
    return None if live is None else 100.0 * live["window"] / live["full"]


def local_choice_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Choices that fell on held experts over the choices the router made."""
    c = facts.get("counters", {})
    if not c.get("moe_choices"):
        return None
    return 100.0 * c.get("moe_routed_pairs", 0) / c["moe_choices"]


def kv_highwater_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Most blocks ever in use over the pool's blocks, each kind's blocks
    weighed by its layers (a block of a kind with three layers is three
    times the bytes)."""
    c = facts.get("counters", {})
    used = held = 0.0
    for kind in ("pool.", "pool.window."):  # the full kind, the window kind
        layers = c.get(kind + "layers")
        if not layers or not c.get(kind + "num_blocks"):
            return None
        used += layers * c[kind + "blocks_highwater"]
        held += layers * c[kind + "num_blocks"]
    return 100.0 * used / held


def attention_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The least time the chip could take for the paged decode kernel in the
    traced window's decode ticks (the larger of its bytes over the memory's
    speed and its operations over the peak, for the window's mean live
    positions by kind and rows a decode tick), over the kernel's own time in
    the trace."""
    trace, found, live = facts.get("trace"), family_counts(facts), live_by_kind(facts)
    if not trace or KERNEL not in trace.get("kernels", {}) or found is None or live is None:
        return None
    counts, sizes = found
    if not hasattr(counts, "paged_decode_attention_bytes"):
        return None
    ticks = len(program_trace.named(
        program_trace.spans(facts.get("trace_path")), DECODE_DISPATCH))
    seconds = trace["kernels"][KERNEL]
    if not ticks or not seconds:
        return None
    peaks = facts["peaks"]
    least = max(
        counts.paged_decode_attention_bytes(sizes, live["full"], live["window"], live["rows"])
        / (peaks["hbm_gbps"] * 1e9),
        counts.paged_decode_attention_flops(sizes, live["full"], live["window"])
        / (peaks["bf16_tflops"] * 1e12))
    return 100.0 * least * ticks / seconds
