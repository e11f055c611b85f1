"""Whole serving step's share of the chip's peak: (2 x matmul params x
tokens processed, prefill and decode, + attention over each token's
context) per second of window, over the published bf16 peak."""

from perfbench.lib import weights


def read(ctx):
    serve = ctx.get("serve")
    if not serve or not ctx.get("peaks"):
        return None
    c = serve["counters"]
    total = weights.family_of(ctx["dims"]).serve_flops(
        ctx["dims"], c["decode_tokens"] + c["prefill_tokens"],
        c["decode_ctx"] + c["prefill_ctx"])
    return 100.0 * total / serve["window_s"] / ctx["peaks"]["bf16_flops"]
