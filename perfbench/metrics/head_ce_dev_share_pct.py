"""Share of the device's busy time in the traced training steps under the
program's ``loss_head`` scope (lm-head matmul of the training forward and
the cross-entropy, forward and backward): self time of the ops so named over
the busy union."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("train"):
        return None
    return pt.share_pct(pt.summary_of(ctx), "loss_head")
