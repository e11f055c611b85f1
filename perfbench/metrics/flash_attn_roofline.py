"""Flash attention's share of its roofline in the traced training steps:
the least time the chip could take for causal attention forward + backward
of the traced steps (FLOPs and bytes from shapes, the model family's count;
at these shapes the FLOP bound is the larger) over the device time of the
flash custom calls."""

from perfbench.lib import flops, weights
from perfbench.metrics._kernels import kernel_seconds, FLASH


def read(ctx):
    trace, train = ctx.get("trace"), ctx.get("train")
    if not trace or not train or not ctx.get("peaks"):
        return None
    seconds, calls = kernel_seconds(trace, FLASH)
    if not seconds:
        return None
    d = ctx["dims"]
    seq = ctx["traffic"]["sequence_length"]
    rows = train["batch"] // ctx["chips"]
    # forward launches once per layer per step
    steps = train["window"].get("traced_steps")
    if not steps:
        return None
    family = weights.family_of(d)
    least = flops.roofline_seconds(
        family.flash_attn_flops(d, rows, seq) * steps,
        family.flash_attn_bytes(d, rows, seq) * steps, ctx["peaks"])
    return 100.0 * least / seconds
