"""The fullest held expert's rows over the mean rows a held expert gets, over
all layers and decode ticks: ``moe_max_expert_rows`` x held experts /
``moe_routed_pairs``."""
from benchmarks.latent_readers import routing


def read(facts):
    r = routing(facts)
    return None if r is None else r["imbalance"]
