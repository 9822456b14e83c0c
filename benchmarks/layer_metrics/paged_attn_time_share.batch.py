"""Share of the device's busy time inside the paged decode attention kernel:
the entries of the reduced trace's ``device_ops`` named ``custom-call
%paged_decode_attention[.N]``, over ``busy_s``. ``device_ops`` is the ten
largest operations: where none of the ten has the name the reading is 0.0."""
from benchmarks.program_trace import kernel_share_percent


def read(facts):
    return kernel_share_percent(facts, "paged_decode_attention")
