"""Share of the device's busy time inside the paged decode attention kernel
(full and window layers alike): the own time of every ``paged_decode_attention``
custom call of the trace (the reduced trace's ``kernels``, all events), over
``busy_s``."""
from benchmarks.program_trace import kernel_share_percent


def read(facts):
    return kernel_share_percent(facts, "paged_decode_attention")
