"""The least a decode tick must move (the family's ``counts.decode_tick_bytes``:
every weight once, the K and V of the live positions in the attention layers,
the scan state and the convolution tail of every slot read and written) over
the median decode tick, over the chip's HBM bandwidth."""
from benchmarks.ssm_readers import decode_hbm_share_percent as read  # noqa: F401
