"""Share of the device's busy time in the traced serving window in NO bucket of
the program's scope table: ops whose ``op_name`` the compiler dropped (the
copies it inserts carry none) or that sit outside every named part. The
run's notes do not name them; ``tools/program_trace_report.py`` does."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("serve"):
        return None
    return pt.share_pct(pt.summary_of(ctx), pt.UNSCOPED)
