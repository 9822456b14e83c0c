"""The fullest expert's rows over the mean rows an expert gets, over all
expert layers and decode ticks: ``moe_max_expert_rows`` x experts /
``moe_routed_pairs``."""
from benchmarks.latent_readers import routing


def read(facts):
    r = routing(facts)
    return None if r is None else r["imbalance"]
