"""Median length of the ``rlt.serve.schedule`` spans: the scheduler's tick
with the housekeeping before it, here admission by two kinds of leaf."""
from benchmarks.program_trace import SCHEDULE, span_median_ms


def read(facts):
    return span_median_ms(facts, SCHEDULE)
