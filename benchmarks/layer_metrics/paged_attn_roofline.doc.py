"""The paged decode attention kernel against its roofline: the family's
``counts`` bytes and operations for the live positions by kind (a window layer
reads no more than the window of a row), the larger of bytes over the HBM
bandwidth and operations over the bf16 peak, over the kernel's own time in the
trace."""
from benchmarks.window_readers import attention_roofline_percent as read  # noqa: F401
