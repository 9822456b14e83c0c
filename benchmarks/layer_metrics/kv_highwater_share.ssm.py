"""Most KV blocks ever in use over the pool's blocks (the attention layers' K
and V; the state kind holds none)."""
from benchmarks.sparse_readers import kv_highwater_share_percent as read  # noqa: F401
