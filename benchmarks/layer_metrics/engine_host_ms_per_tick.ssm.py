"""The engine's own host time a tick, over the whole window:
1e3 x (``tick_s`` - ``sync_wait_s``) / ``ticks`` of ``engine.stats``. Host
work that overlaps the device counts here and not in the idle gaps."""
from benchmarks.program_trace import engine_host_ms_per_tick as read  # noqa: F401
