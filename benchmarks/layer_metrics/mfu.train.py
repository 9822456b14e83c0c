"""Model FLOP/s utilisation: operations the forward and backward require per
token (the family's ``counts.train_flops_per_token``, handed over by the
driver) x tokens/s, over chips x the chip's bf16 peak."""


def read(facts):
    if "flops_per_token" not in facts:
        return None
    peak = facts["chips"] * facts["peaks"]["bf16_tflops"] * 1e12
    return 100.0 * facts["flops_per_token"] * facts["train_tokens_per_s"] / peak
