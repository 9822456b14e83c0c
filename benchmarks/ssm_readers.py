"""Arithmetic shared by the per-layer readers of a cell whose model mixes
state-space layers, whose scan state and convolution tail are leaves of the
pool's state kind, with a few attention layers (``layer_metrics/*.ssm.py``).

The engine's counters carry what a reader needs: ``kv_positions_live`` (the
positions the decoding rows hold, ``pos + 1`` each, summed over decode
ticks: what the attention layers read), ``state_bytes_touched`` (both state
leaves read and written, every slot's, a decode tick) and the pool's
``state.bytes_per_slot``. The two kernels are found in the trace by the
``name=`` of their ``pl.pallas_call`` (``KERNELS``), their calls counted from
the device's events; a call is one state-space layer of one prefill
(``mamba_scan``) or of one decode tick (``mamba_decode``). The family's counts
are reached through the function the serve driver hands over
(``latent_readers.family_counts``), so a reader names no family. What the
readers of the sparse / linear cell already compute the same way is theirs
(``sparse_readers``): a kernel's calls and own time, a kernel's share of the
busy time, the roofline of a kernel's calls. The prefill's share of the peak
is not taken from there: it holds the flops of the window's MEAN rung against
the MEDIAN prefill tick, which agree in a cell of one rung and not here (seven
prompts in eight run at the first of four rungs, and the mean rung is a
quarter over it); this one holds the mean against the mean. A program without the counters or the kernels, as the parent of the PR that
brought them, leaves nothing to read: every function returns ``None`` and none
raises."""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.readers import tick_ms
from benchmarks.sparse_readers import _counts, _mean_rung, _roofline

KERNELS = {"scan": "mamba_scan", "decode": "mamba_decode"}


def per_decode_tick(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Per decode tick: live positions, rows decoded and slots whose state
    was touched."""
    c = facts.get("counters", {})
    steps = c.get("decode_steps")
    per_slot = c.get("pool.state.bytes_per_slot")
    if not steps or not c.get("kv_positions_live") or not per_slot \
            or not c.get("state_bytes_touched"):
        return None
    return {"live": c["kv_positions_live"] / steps,
            "rows": c.get("busy_slot_steps", 0) / steps,
            "slots": c["state_bytes_touched"] / steps / (2.0 * per_slot)}


def _layers(counts, sizes) -> int:
    """The state-space layers: the calls of either kernel a prefill or a tick."""
    return counts.layers_by_kind(sizes)[1]


def scan_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The prefill scan: x, dt and y of the window's mean rung, a call a
    state-space layer a prefill."""
    rung = _mean_rung(facts)
    found = _counts(facts, "mamba_scan_bytes", "mamba_scan_flops", "layers_by_kind")
    if rung is None or found is None:
        return None
    counts, sizes = found
    return _roofline(facts, KERNELS["scan"], _layers(counts, sizes),
                     counts.mamba_scan_bytes(sizes, rung), counts.mamba_scan_flops(sizes, rung))


def state_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The decode update: every slot's scan state read and written, a call a
    state-space layer a tick."""
    tick = per_decode_tick(facts)
    found = _counts(facts, "mamba_decode_bytes", "mamba_decode_flops", "layers_by_kind")
    if tick is None or found is None or not tick["slots"]:
        return None
    counts, sizes = found
    return _roofline(facts, KERNELS["decode"], _layers(counts, sizes),
                     counts.mamba_decode_bytes(sizes, tick["slots"]),
                     counts.mamba_decode_flops(sizes, tick["slots"]))


def decode_hbm_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The least a decode tick must move (every weight once, the live
    positions' K and V, both state leaves read and written) over the median
    decode tick, over the chip's HBM bandwidth."""
    ms, tick = tick_ms(facts, prefill=False), per_decode_tick(facts)
    if ms is None or tick is None or facts.get("decode_tick_bytes") is None:
        return None
    try:
        need = facts["decode_tick_bytes"](tick["live"], state_slots=tick["slots"])
    except TypeError:  # another family's decode_tick_bytes
        return None
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)


def prefill_mfu_percent(facts: Dict[str, Any]) -> Optional[float]:
    """What a prefill at the window's mean rung requires (no logits: the head
    is not computed) over what a tick cycle with a prefill costs beyond one
    without, both means over every tick of the window (the engine's cycle
    counters), over the chip's bf16 peak."""
    c = facts.get("counters", {})
    rung, found = _mean_rung(facts), _counts(facts, "forward_flops", "layers_by_kind")
    if rung is None or found is None or not c.get("prefill_cycles") \
            or not c.get("decode_cycles") or "prefill_cycle_s" not in c:
        return None
    extra = c["prefill_cycle_s"] / c["prefill_cycles"] - c["decode_cycle_s"] / c["decode_cycles"]
    # a cycle that retires a tick with prefills holds as many as the window's mean
    per_cycle = c["prefills"] / c["prefill_cycles"]
    if extra <= 0:
        return None
    counts, sizes = found
    try:
        need = counts.forward_flops(sizes, rung, head=False)
    except TypeError:  # another family's forward_flops
        return None
    return 100.0 * need * per_cycle / extra / (facts["peaks"]["bf16_tflops"] * 1e12)
