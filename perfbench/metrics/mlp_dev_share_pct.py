"""Share of the device's busy time in the traced training steps under flax's
``feed_forward`` module scope (the MLP block, forward and backward): self
time of the ops so named over the busy union."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("train"):
        return None
    return pt.share_pct(pt.summary_of(ctx), "feed_forward")
