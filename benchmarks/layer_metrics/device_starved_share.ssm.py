"""Calls of the engine whose first dispatch found the decode program in flight
already complete, over all decode programs of the window: how often the host
let the device run dry, with no profiler running."""
from benchmarks.tick_readers import device_starved_share as read  # noqa: F401
