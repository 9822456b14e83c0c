"""Arithmetic shared by the readers of what the engine says of its own ticks
(``layer_metrics/device_starved_share.*.py``, ``prefill_window_share.*.py``).

The engine sums, over every tick of the window and with no profiler running:
``starved_steps``, the calls whose first dispatch found the decode program in
flight already complete, so that the device had nothing queued; and the tick
cycles, from the end of one retire's sync to the end of the next, under
``prefill_cycles`` / ``prefill_cycle_s`` where the retired tick enqueued
prefills ahead of its decode program and under ``decode_cycles`` /
``decode_cycle_s`` where it did not. A program without the counters, as every
one before PR 36, leaves nothing to read: no reading, never 0."""
from __future__ import annotations

from typing import Any, Dict, Optional


def device_starved_share(facts: Dict[str, Any]) -> Optional[float]:
    """``starved_steps`` over ``decode_steps``, in per cent: the share of the
    window's decode programs before which the device ran dry."""
    c = facts.get("counters", {})
    if "starved_steps" not in c or not c.get("decode_steps"):
        return None
    return 100.0 * c["starved_steps"] / c["decode_steps"]


def prefill_window_share(facts: Dict[str, Any]) -> Optional[float]:
    """What the window's prefill programs cost over the decode programs they
    rode with, over all counted cycles, in per cent: the cycles with prefills
    less as many mean cycles without. No cycle without prefills leaves no
    decode program's time to take off: no reading."""
    c = facts.get("counters", {})
    if "prefill_cycle_s" not in c or not c.get("decode_cycles"):
        return None
    decode_s = c["decode_cycle_s"] / c["decode_cycles"]
    return (100.0 * (c["prefill_cycle_s"] - c["prefill_cycles"] * decode_s)
            / (c["prefill_cycle_s"] + c["decode_cycle_s"]))
