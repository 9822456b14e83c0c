"""Share of the device's busy time inside the two lightning-attention
kernels (``lightning_decode``, the state update of a decode tick, and
``lightning_prefill``, the chunked scan over a prompt): their own time in the
trace over ``busy_s``."""
from benchmarks.sparse_readers import KERNELS, kernels_share_percent


def read(facts):
    return kernels_share_percent(facts, KERNELS["linear_decode"], KERNELS["linear_prefill"])
