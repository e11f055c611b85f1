"""Share of the device's busy time in the traced training steps in NO bucket of
the program's scope table: ops whose ``op_name`` the compiler dropped or
that sit outside every named part (``tools/program_trace_report.py`` names
them)."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("train"):
        return None
    return pt.share_pct(pt.summary_of(ctx), pt.UNSCOPED)
