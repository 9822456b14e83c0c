"""Share of the positions prefill computed that were padding up to the
prompt's rung: 1 - ``prefill_tokens`` / ``prefill_positions`` of
``engine.stats`` (the prompts' own tokens over the sum of the rungs they ran
at). A program without ``prefill_tokens`` leaves nothing to read."""


def read(facts):
    c = facts.get("counters", {})
    if not c.get("prefill_tokens") or not c.get("prefill_positions"):
        return None
    return 100.0 * (1.0 - c["prefill_tokens"] / c["prefill_positions"])
