"""Bytes a decode tick must read (the family's ``counts.decode_tick_bytes``,
which the driver hands over as a function of the live context: every weight
once, all eight experts of every layer among them as the configuration's
``stands_for`` says a tick of 32 rows reads them, and the cache of the live
rows) over the median decode tick, over the chip's HBM bandwidth.
``expert_hit_share.batch`` beside it says whether every expert was read."""
from benchmarks.readers import decode_live_tokens, tick_ms


def read(facts):
    ms, live = tick_ms(facts, prefill=False), decode_live_tokens(facts)
    if ms is None or live is None:
        return None
    need = facts["decode_tick_bytes"](live)
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)
