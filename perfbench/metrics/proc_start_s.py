"""Process hand-over: exit of one training child reaped -> the next child's
``Device |`` line (interpreter, imports, TPU runtime release and acquire),
mean of the run's cycles. Host clock of the harness."""

from perfbench.lib.recovery import mean_of


def read(ctx):
    return mean_of((ctx.get("train") or {}).get("cycles") or [], "handover_s")
