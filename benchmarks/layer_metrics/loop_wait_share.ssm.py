"""Share of the engine loop thread's wall time spent with no work:
100 x ``loop_wait_s`` / (``tick_s`` + ``loop_wait_s``) of ``engine.stats``
over the whole window. A saturated closed loop reads about 0."""
from benchmarks.program_trace import loop_wait_share_percent as read  # noqa: F401
