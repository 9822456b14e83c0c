"""Occupied rows per decode tick over the engine's slots."""


def read(facts):
    c = facts.get("counters", {})
    if not c.get("decode_steps"):
        return None
    return 100.0 * c["busy_slot_steps"] / (c["decode_steps"] * c["num_slots"])
