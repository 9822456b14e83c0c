"""The decode update against its roofline: every slot's float32 scan state
read once and written once a state-space layer a decode tick (the family's
``counts.mamba_decode_bytes``) over the HBM bandwidth, or its operations over
the bf16 peak if larger, over the kernel's own time in the trace."""
from benchmarks.ssm_readers import state_roofline_percent as read  # noqa: F401
