"""Median length of the ``rlt.serve.sample_sync`` spans of ticks that ran no
prefill: how long the host waited for the decode program after handing it
over."""
from benchmarks.program_trace import decode_sync_ms as read  # noqa: F401
