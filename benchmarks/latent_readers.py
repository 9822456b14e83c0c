"""Arithmetic shared by the per-layer readers of a cell whose model has
latent attention and routed experts (``layer_metrics/*.reason.py``).

The serve driver hands a reader the family's ``counts.decode_tick_bytes``
bound to the cell's sizes (``facts["decode_tick_bytes"]``, a
``functools.partial``); the family's other counts are the functions beside
it in the same module, reached from there, so a reader names no family.
The routing counters (``moe_expert_hits``, ``moe_routed_pairs``,
``moe_max_expert_rows``: sums over expert layers and decode ticks) are the
engine's; a program without them leaves nothing to read."""
from __future__ import annotations

import sys
from typing import Any, Dict, Optional, Tuple

from benchmarks import program_trace

KERNEL = "mla_paged_decode_attention"
DECODE_DISPATCH = "rlt.serve.decode_dispatch"


def family_counts(facts: Dict[str, Any]) -> Optional[Tuple[Any, Dict[str, Any]]]:
    """(the family's ``counts`` module, the cell's sizes), or None."""
    bound = facts.get("decode_tick_bytes")
    if bound is None or not getattr(bound, "args", None):
        return None
    counts = sys.modules.get(bound.func.__module__)
    return None if counts is None else (counts, bound.args[0])


def routing(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Per decode tick: distinct experts hit and routed pairs, summed over
    the expert layers; and the fullest expert's rows over the mean."""
    c = facts.get("counters", {})
    if not c.get("decode_steps") or not c.get("moe_routed_pairs"):
        return None
    found = family_counts(facts)
    if found is None or not hasattr(found[0], "routed_experts"):
        return None
    experts, layers = found[0].routed_experts(found[1])
    steps = c["decode_steps"]
    return {
        "hits_per_tick": c["moe_expert_hits"] / steps,
        "hit_share": c["moe_expert_hits"] / (experts * layers * steps),
        "imbalance": c["moe_max_expert_rows"] * experts / c["moe_routed_pairs"],
    }


def live_rows_and_tokens(facts: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """Mean (rows decoded, live context tokens) of the ticks that decoded."""
    ticks = [(n, lv) for _, _, _, n, lv in facts.get("ticks", ()) if n > 0]
    if not ticks:
        return None
    return (sum(n for n, _ in ticks) / len(ticks), sum(lv for _, lv in ticks) / len(ticks))


def attention_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The least time the chip could take for the latent paged decode kernel
    in the traced window's decode ticks (the larger of its bytes over the
    memory's speed and its operations over the peak, for the mean live rows
    and context of the window's decode ticks), over the kernel's own time in
    the trace."""
    trace, found, live = facts.get("trace"), family_counts(facts), live_rows_and_tokens(facts)
    if not trace or KERNEL not in trace.get("kernels", {}) or found is None or live is None:
        return None
    counts, sizes = found
    if not hasattr(counts, "mla_decode_attention_bytes"):
        return None
    ticks = len(program_trace.named(
        program_trace.spans(facts.get("trace_path")), DECODE_DISPATCH))
    seconds = trace["kernels"][KERNEL]
    if not ticks or not seconds:
        return None
    rows, tokens = live
    peaks = facts["peaks"]
    least = max(counts.mla_decode_attention_bytes(sizes, tokens, rows) / (peaks["hbm_gbps"] * 1e9),
                counts.mla_decode_attention_flops(sizes, tokens) / (peaks["bf16_tflops"] * 1e12))
    return 100.0 * least * ticks / seconds
