"""What a prefill requires at the window's mean rung (the family's
``counts.forward_flops`` without the head: the chosen blocks' attention, not
the masked tiles) over the median prefill tick less the median decode tick,
over the chip's bf16 peak."""
from benchmarks.sparse_readers import prefill_mfu_percent as read  # noqa: F401
