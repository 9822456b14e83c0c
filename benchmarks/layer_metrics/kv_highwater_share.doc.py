"""Most KV blocks ever in use over the pool's blocks, both kinds of leaf, each
kind's blocks weighed by its layers."""
from benchmarks.window_readers import kv_highwater_share_percent as read  # noqa: F401
