"""Share of prefilled positions that were padding: 1 - prompt tokens over
prefills x max_prompt_len."""


def read(facts):
    c = facts.get("counters", {})
    if not c.get("prefills"):
        return None
    return 100.0 * (1.0 - sum(facts["prompt_lens"]) / (c["prefills"] * c["max_prompt_len"]))
