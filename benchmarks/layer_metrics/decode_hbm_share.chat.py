"""Bytes a decode tick must read (the family's ``counts.decode_tick_bytes``,
which the driver hands over as a function of the live context: every weight
once and the cache of the live rows) over the median decode tick, over the
chip's HBM bandwidth."""
from benchmarks.readers import decode_live_tokens, tick_ms


def read(facts):
    ms, live = tick_ms(facts, prefill=False), decode_live_tokens(facts)
    if ms is None or live is None:
        return None
    need = facts["decode_tick_bytes"](live)
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)
