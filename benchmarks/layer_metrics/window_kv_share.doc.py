"""Positions the window leaves hold for the decoding rows over the positions a
pool of one kind would hold for the same rows: ``kv_positions_window`` /
``kv_positions_full`` of the engine's counters."""
from benchmarks.window_readers import window_kv_share_percent as read  # noqa: F401
