"""Median length of the ``rlt.serve.schedule`` spans: the scheduler's tick
with the housekeeping before it, here admission by blocks over 256 slots and
the zeroing of an admitted slot's two state leaves."""
from benchmarks.program_trace import SCHEDULE, span_median_ms


def read(facts):
    return span_median_ms(facts, SCHEDULE)
