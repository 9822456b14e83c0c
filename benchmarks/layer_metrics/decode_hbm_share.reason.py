"""The least a decode tick must read (the family's ``counts.decode_tick_bytes``:
the latent cache of the live rows, every weight outside the routed experts
once, and only the experts the tick's rows chose, from the engine's routing
counters) over the median decode tick, over the chip's HBM bandwidth."""
from benchmarks.latent_readers import routing
from benchmarks.readers import decode_live_tokens, tick_ms


def read(facts):
    ms, live, r = tick_ms(facts, prefill=False), decode_live_tokens(facts), routing(facts)
    if ms is None or live is None or r is None:
        return None
    need = facts["decode_tick_bytes"](live, expert_hits=r["hits_per_tick"])
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)
