"""Share of the device's busy time in the traced training steps under the
program's ``optimizer`` and ``grad_clip`` scopes (global norm, clip, optax
update, parameter apply): self time of the ops so named over the busy union."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("train"):
        return None
    return pt.share_pct(pt.summary_of(ctx), "optimizer", "grad_clip")
