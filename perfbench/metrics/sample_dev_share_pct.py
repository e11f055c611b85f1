"""Share of the device's busy time in the traced window under the program's
``sample`` scope (the sampling epilogue over the logits): self time of the
ops so named over the busy union."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("serve"):
        return None
    return pt.share_pct(pt.summary_of(ctx), "sample")
