"""Median wall time of engine ticks that ran a prefill."""
from benchmarks.readers import tick_ms


def read(facts):
    return tick_ms(facts, prefill=True)
