"""Arithmetic the per-layer readers share. A reader takes the run's facts
(what the driver counted and timed, the reduced trace under ``trace``, the
chip's ``peaks``) and returns a number, or ``None`` where it finds nothing
to read."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks import stats


def idle_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    trace = facts.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]


def tick_ms(facts: Dict[str, Any], prefill: bool) -> Optional[float]:
    """Median wall time of the engine ticks that ran a prefill (or that ran
    none and decoded)."""
    picked = [t1 - t0 for t0, t1, n_prefill, n_decoded, _ in facts.get("ticks", ())
              if (n_prefill >= 1) == prefill and (prefill or n_decoded > 0)]
    return stats.median(picked) * 1e3 if picked else None


def decode_live_tokens(facts: Dict[str, Any]) -> Optional[float]:
    """Mean live context (tokens over all occupied rows) of decode-only ticks."""
    live = [lv for _, _, n_prefill, n_decoded, lv in facts.get("ticks", ())
            if n_prefill == 0 and n_decoded > 0]
    return sum(live) / len(live) if live else None
