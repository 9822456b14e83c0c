"""Bytes a decode tick must read (benchmarks/bytes.py: every weight once, K
and V of the live context) over the median decode tick, over the chip's HBM
bandwidth."""
from benchmarks import bytes as hbm
from benchmarks.readers import decode_live_tokens, tick_ms


def read(facts):
    ms, live = tick_ms(facts, prefill=False), decode_live_tokens(facts)
    if ms is None or live is None:
        return None
    need = hbm.decode_tick_bytes(facts["sizes"], live)
    return 100.0 * need / (ms * 1e-3) / (facts["peaks"]["hbm_gbps"] * 1e9)
