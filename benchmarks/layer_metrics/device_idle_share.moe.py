"""1 - union of device-operation intervals over the traced window."""
from benchmarks.readers import idle_share_percent as read  # noqa: F401
