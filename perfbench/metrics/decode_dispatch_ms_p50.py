"""Median of the program's ``ftl:engine.decode.dispatch`` spans in the traced
window: the call into the compiled decode program until it returns (the
host's share of a round before it waits for the device)."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    summary = pt.summary_of(ctx) if ctx.get("serve") else None
    if not summary:
        return None
    return pt.median_ms(summary["spans"], "ftl:engine.decode.dispatch")
