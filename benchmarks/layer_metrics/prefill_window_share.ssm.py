"""Share of the window's tick cycles that its prefill programs took, over the
decode programs they rode with: from the engine's cycle counters, over every
tick of the window."""
from benchmarks.tick_readers import prefill_window_share as read  # noqa: F401
