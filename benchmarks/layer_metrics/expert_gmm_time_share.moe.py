"""Own time of the two grouped kernels (``gmm``: the routed experts' products
forward, rematerialised and for the rows' gradient; ``tgmm``: the stacks'
gradients) over the device's busy time."""
from benchmarks.moe_train_readers import gmm_time_share_percent as read  # noqa: F401
