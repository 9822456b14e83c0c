"""Share of the device's busy time inside the two selective-scan kernels
(``mamba_decode``, the state update of a decode tick, and ``mamba_scan``, the
scan over a prompt): their own time in the trace over ``busy_s``."""
from benchmarks.sparse_readers import kernels_share_percent
from benchmarks.ssm_readers import KERNELS


def read(facts):
    return kernels_share_percent(facts, KERNELS["decode"], KERNELS["scan"])
