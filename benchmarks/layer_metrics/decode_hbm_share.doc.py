"""The least a decode tick must read (the family's ``counts.decode_tick_bytes``:
the K and V of the live positions by kind, every weight outside the routed
experts once, and only the held experts the tick's rows chose, from the engine's
counters) over the median decode tick, over the chip's HBM bandwidth."""
from benchmarks.latent_readers import routing
from benchmarks.readers import tick_ms
from benchmarks.window_readers import live_by_kind


def read(facts):
    ms, live, r = tick_ms(facts, prefill=False), live_by_kind(facts), routing(facts)
    if ms is None or live is None or r is None:
        return None
    need = facts["decode_tick_bytes"](
        live["full"], expert_hits=r["hits_per_tick"], window_tokens=live["window"])
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)
