"""Median wall time of engine ticks that only decoded."""
from benchmarks.readers import tick_ms


def read(facts):
    return tick_ms(facts, prefill=False)
