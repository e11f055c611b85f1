"""Whole train step's share of the chip's peak: model FLOPs per token
(causal attention, embedding gather excluded, no recompute) x tokens/s/chip
over the published bf16 peak."""

from perfbench.lib import weights


def read(ctx):
    if not ctx.get("peaks") or "train_tok_s" not in ctx["e2e"]:
        return None
    per_token = weights.family_of(ctx["dims"]).train_flops_per_token(
        ctx["dims"], ctx["traffic"]["sequence_length"])
    return 100.0 * per_token * ctx["e2e"]["train_tok_s"] / ctx["peaks"][
        "bf16_flops"]
