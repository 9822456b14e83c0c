"""The paged decode attention kernel over the chosen blocks against its
roofline: the family's ``counts`` bytes and operations for the selected
positions (``kv_positions_selected`` a decode tick), the larger of bytes over
the HBM bandwidth and operations over the bf16 peak, over the kernel's own
time in the trace. The pooled keys are scored by XLA (``sparse_select``), not
by this kernel, and are not among its bytes."""
from benchmarks.sparse_readers import sparse_decode_roofline_percent as read  # noqa: F401
