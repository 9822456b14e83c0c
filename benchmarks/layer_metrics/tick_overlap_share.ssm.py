"""Decode programs dispatched before the one before them was read, over all
decode programs of the window: the share of decode ticks whose host part ran
under the device's."""
from benchmarks.overlap_readers import tick_overlap_share as read  # noqa: F401
