"""The lightning decode update against its roofline: every slot's float32
state read once and written once a lightning layer a decode tick (the
family's ``counts.lightning_decode_bytes``) over the HBM bandwidth, or its
operations over the bf16 peak if larger, over the kernel's own time in the
trace."""
from benchmarks.sparse_readers import linear_state_roofline_percent as read  # noqa: F401
