"""The prefill scan against its roofline: x and dt in and y out at the
window's mean rung (the family's ``counts.mamba_scan_bytes``) over the HBM
bandwidth, or its operations over the bf16 peak if larger, over the kernel's
own time in the trace. The operations are the vector unit's and
``peaks.json`` has no vector peak: a ceiling the kernel stands well under."""
from benchmarks.ssm_readers import scan_roofline_percent as read  # noqa: F401
