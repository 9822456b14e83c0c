"""Positions in the blocks the sparse layers' decoding rows chose over the
positions those rows hold: ``kv_positions_selected`` / ``kv_positions_live`` of
the engine's counters. Under 100 % the selection drops pages."""
from benchmarks.sparse_readers import selected_kv_share_percent as read  # noqa: F401
