"""Arithmetic shared by the readers of how often the engine's decode ticks
overlap (``layer_metrics/tick_overlap_share.*.py``).

The engine counts, beside ``decode_steps``, the decode programs it dispatched
while an earlier one was still unread (``overlapped_steps``): for such a tick
the host's part (schedule, prepare, dispatch, deliver) ran under the device's.
A program without the counter, as every one before PR 35, leaves nothing to
read: no reading, never 0."""
from __future__ import annotations

from typing import Any, Dict, Optional


def tick_overlap_share(facts: Dict[str, Any]) -> Optional[float]:
    """``overlapped_steps`` over ``decode_steps``, in per cent."""
    c = facts.get("counters", {})
    if "overlapped_steps" not in c or not c.get("decode_steps"):
        return None
    return 100.0 * c["overlapped_steps"] / c["decode_steps"]
