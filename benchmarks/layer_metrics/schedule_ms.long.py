"""Median length of the ``rlt.serve.schedule`` spans: the scheduler's tick
with the housekeeping before it, here admission by blocks and the zeroing of
an admitted slot's state."""
from benchmarks.program_trace import SCHEDULE, span_median_ms


def read(facts):
    return span_median_ms(facts, SCHEDULE)
