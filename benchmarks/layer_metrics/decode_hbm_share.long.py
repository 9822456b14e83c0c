"""The least a decode tick must move (the family's ``counts.decode_tick_bytes``:
every layer weight and the head once, the K and V of the positions the rows
chose, the pooled keys they scored, the lightning state read and written) over
the median decode tick, over the chip's HBM bandwidth."""
from benchmarks.sparse_readers import decode_hbm_share_percent as read  # noqa: F401
