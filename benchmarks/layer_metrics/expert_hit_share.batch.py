"""Distinct experts a decode tick's rows chose over the experts its layers
hold: ``moe_expert_hits`` / (``num_local_experts`` x ``num_hidden_layers`` x
decode ticks), the engine's routing counter over the cell's sizes. It is the
share of the expert weights a tick has to read: 100 says
``decode_hbm_share.batch`` counts what was read. A program that returns no
routing counters leaves nothing to read."""
from benchmarks.latent_readers import family_counts


def read(facts):
    c = facts.get("counters", {})
    found = family_counts(facts)
    if found is None or not c.get("decode_steps") or not c.get("moe_expert_hits"):
        return None
    sizes = found[1]
    experts = sizes["num_local_experts"] * sizes["num_hidden_layers"]
    return 100.0 * c["moe_expert_hits"] / (experts * c["decode_steps"])
