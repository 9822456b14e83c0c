"""Share of the device's busy time inside the paged decode attention kernel,
which here walks tables composed of the blocks each row chose: the own time of
every ``paged_decode_attention`` custom call of the trace over ``busy_s``."""
from benchmarks.sparse_readers import KERNELS, kernels_share_percent


def read(facts):
    return kernels_share_percent(facts, KERNELS["sparse_decode"])
