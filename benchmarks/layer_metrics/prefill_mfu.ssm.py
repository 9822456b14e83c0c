"""What a prefill requires at the window's mean rung (the family's
``counts.forward_flops`` without the head: the projections, the two attention
layers and the scan's elementwise operations) over what a tick cycle with a
prefill costs beyond one without (the engine's cycle counters, means over
every tick of the window), over the chip's bf16 peak."""
from benchmarks.ssm_readers import prefill_mfu_percent as read  # noqa: F401
