"""Arithmetic shared by the per-layer readers of a cell whose model mixes
block-sparse attention layers, which choose the pages a decoding row reads,
with linear-attention layers, whose state is a leaf of the pool's state kind
(``layer_metrics/*.long.py``).

The engine's counters carry what a reader needs: ``kv_positions_live`` (the
positions the decoding rows hold, ``pos + 1`` each, summed over decode
ticks: what a layer that reads every position would read),
``kv_positions_selected`` (the positions in the blocks the sparse layers'
rows chose), ``indexer_keys_scanned`` (the complete pooled keys the selecting
rows scored), ``state_bytes_touched`` (the lightning state read and written,
every slot's, a decode tick) and the pool's ``state.bytes_per_slot``. The
kernels are found in the trace by the ``name=`` of their ``pl.pallas_call``
(``KERNELS``), their calls counted from the device's events. The family's
counts are reached through the function the serve driver hands over
(``latent_readers.family_counts``), so a reader names no family. A program
without the counters or the kernels, as the parent of the PR that brought
them, leaves nothing to read: every function returns ``None`` and none
raises."""
from __future__ import annotations

import functools
import os
from collections import Counter
from typing import Any, Dict, Optional, Tuple

from benchmarks import trace_reduce
from benchmarks.latent_readers import family_counts
from benchmarks.readers import tick_ms

KERNELS = {
    "sparse_decode": "paged_decode_attention",  # over tables composed of the chosen blocks
    "linear_decode": "lightning_decode",
    "linear_prefill": "lightning_prefill",
}


def per_decode_tick(facts: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Per decode tick: live and selected positions, pooled keys scored,
    rows decoded and slots whose state was touched."""
    c = facts.get("counters", {})
    steps = c.get("decode_steps")
    per_slot = c.get("pool.state.bytes_per_slot")
    if not steps or not c.get("kv_positions_live") or not c.get("kv_positions_selected") \
            or "indexer_keys_scanned" not in c or not per_slot:
        return None
    return {"live": c["kv_positions_live"] / steps,
            "selected": c["kv_positions_selected"] / steps,
            "pooled": c["indexer_keys_scanned"] / steps,
            "rows": c.get("busy_slot_steps", 0) / steps,
            "slots": c.get("state_bytes_touched", 0) / steps / (2.0 * per_slot)}


def selected_kv_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Positions in the blocks the sparse layers' rows chose over the
    positions those rows hold."""
    tick = per_decode_tick(facts)
    return None if tick is None else 100.0 * tick["selected"] / tick["live"]


@functools.lru_cache(maxsize=2)
def _calls_by_kernel(path: str, _mtime: int, _size: int) -> Dict[str, float]:
    """{kernel: its events on the device planes, a device's mean}: the trace
    is parsed once for all the readers of a run."""
    per_device = trace_reduce.device_events(path)
    calls = Counter(trace_reduce.kernel_name(hlo)
                    for events in per_device.values() for _, _, hlo in events)
    return {k: n / len(per_device) for k, n in calls.items() if k is not None}


def kernel_calls(facts: Dict[str, Any], kernel: str) -> Optional[Tuple[float, float]]:
    """(calls, own seconds) of the Mosaic kernel named ``kernel`` in the
    traced window, a device's mean; None where the trace has none."""
    trace, path = facts.get("trace"), facts.get("trace_path")
    if not trace or not trace.get("kernels", {}).get(kernel) or not path \
            or not os.path.exists(path):
        return None
    st = os.stat(path)
    calls = _calls_by_kernel(path, st.st_mtime_ns, st.st_size).get(kernel)
    return (calls, trace["kernels"][kernel]) if calls else None


def kernels_share_percent(facts: Dict[str, Any], *kernels: str) -> Optional[float]:
    """Own time of the named kernels together over the device's busy time;
    None unless the trace holds at least one of them."""
    trace = facts.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    found = [trace["kernels"][k] for k in kernels if k in trace.get("kernels", {})]
    return 100.0 * sum(found) / trace["busy_s"] if found else None


def _roofline(facts, kernel, calls_per_unit, nbytes, flops) -> Optional[float]:
    """The least time the chip could take for the kernel's calls in the
    trace (``nbytes`` and ``flops`` a unit of ``calls_per_unit`` calls: the
    larger of bytes over the memory's speed and operations over the peak),
    over the kernel's own time."""
    found = kernel_calls(facts, kernel)
    if found is None or not calls_per_unit:
        return None
    calls, seconds = found
    peaks = facts["peaks"]
    least = max(nbytes / (peaks["hbm_gbps"] * 1e9), flops / (peaks["bf16_tflops"] * 1e12))
    return 100.0 * least * (calls / calls_per_unit) / seconds


def _counts(facts, *needs):
    found = family_counts(facts)
    if found is None or not all(hasattr(found[0], n) for n in needs):
        return None
    return found


def sparse_decode_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The paged decode kernel over the chosen blocks: the selected
    positions' K and V (the window's mean a decode tick), a call a sparse
    layer a tick."""
    tick = per_decode_tick(facts)
    found = _counts(facts, "paged_decode_attention_bytes", "layers_by_kind")
    if tick is None or found is None:
        return None
    counts, sizes = found
    return _roofline(
        facts, KERNELS["sparse_decode"], counts.layers_by_kind(sizes)[0],
        counts.paged_decode_attention_bytes(sizes, tick["selected"], tick["rows"]),
        counts.paged_decode_attention_flops(sizes, tick["selected"]))


def linear_state_roofline_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The lightning decode update: every slot's state read and written, a
    call a lightning layer a tick."""
    tick = per_decode_tick(facts)
    found = _counts(facts, "lightning_decode_bytes", "layers_by_kind")
    if tick is None or found is None or not tick["slots"]:
        return None
    counts, sizes = found
    return _roofline(
        facts, KERNELS["linear_decode"], counts.layers_by_kind(sizes)[1],
        counts.lightning_decode_bytes(sizes, tick["slots"]),
        counts.lightning_decode_flops(sizes, tick["slots"]))


def _mean_rung(facts: Dict[str, Any]) -> Optional[int]:
    c = facts.get("counters", {})
    if not c.get("prefills") or not c.get("prefill_positions"):
        return None
    return int(round(c["prefill_positions"] / c["prefills"]))


def decode_hbm_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """The least a decode tick must move (every weight once, the selected
    positions' K and V, the pooled keys scored, the state read and written)
    over the median decode tick, over the chip's HBM bandwidth."""
    ms, tick = tick_ms(facts, prefill=False), per_decode_tick(facts)
    if ms is None or tick is None or facts.get("decode_tick_bytes") is None:
        return None
    need = facts["decode_tick_bytes"](
        tick["live"], selected_tokens=tick["selected"], pooled_keys=tick["pooled"],
        state_slots=tick["slots"])
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)


def prefill_mfu_percent(facts: Dict[str, Any]) -> Optional[float]:
    """What a prefill at the window's mean rung requires (no logits: the
    head is not computed) over the prefill tick less the decode tick that
    shares it, over the chip's bf16 peak."""
    with_prefill, alone = tick_ms(facts, prefill=True), tick_ms(facts, prefill=False)
    rung, found = _mean_rung(facts), _counts(facts, "forward_flops", "layers_by_kind")
    if with_prefill is None or alone is None or rung is None or found is None \
            or with_prefill <= alone:
        return None
    counts, sizes = found
    try:
        need = counts.forward_flops(sizes, rung, head=False)
    except TypeError:  # another family's forward_flops
        return None
    return 100.0 * need / ((with_prefill - alone) * 1e-3) / (
        facts["peaks"]["bf16_tflops"] * 1e12)


def kv_highwater_share_percent(facts: Dict[str, Any]) -> Optional[float]:
    """Most KV blocks ever in use over the pool's blocks (the state kind
    holds no blocks: a slot's state is there whether or not it is used)."""
    c = facts.get("counters", {})
    if not c.get("pool.num_blocks") or "pool.blocks_highwater" not in c:
        return None
    return 100.0 * c["pool.blocks_highwater"] / c["pool.num_blocks"]
