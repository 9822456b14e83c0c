"""Median length of the ``rlt.serve.schedule`` spans: the scheduler's tick
with the export/import/evict housekeeping before it."""
from benchmarks.program_trace import SCHEDULE, span_median_ms


def read(facts):
    return span_median_ms(facts, SCHEDULE)
