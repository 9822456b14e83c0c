"""Distinct held experts a decode tick's rows chose over the experts its layers
hold: ``moe_expert_hits`` / (held experts x layers x decode ticks). It is the
share of the held expert weights a tick has to read."""
from benchmarks.latent_readers import routing


def read(facts):
    r = routing(facts)
    return None if r is None else 100.0 * r["hit_share"]
