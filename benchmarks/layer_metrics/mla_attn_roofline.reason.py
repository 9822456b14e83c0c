"""The latent paged decode attention kernel against its roofline: the family's
``counts`` bytes and operations for the live rows, the larger of bytes over
the HBM bandwidth and operations over the bf16 peak, over the kernel's own
time in the trace."""
from benchmarks.latent_readers import attention_roofline_percent as read  # noqa: F401
