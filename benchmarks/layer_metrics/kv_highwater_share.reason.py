"""Most KV blocks ever in use over the pool's blocks."""


def read(facts):
    c = facts.get("counters", {})
    if not c.get("pool.num_blocks"):
        return None
    return 100.0 * c["pool.blocks_highwater"] / c["pool.num_blocks"]
