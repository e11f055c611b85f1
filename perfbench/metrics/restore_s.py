"""Checkpoint restore incl. integrity verification, the flight recorder's
``ckpt_restore`` record, mean of the cycles."""

from perfbench.lib.recovery import mean_of


def read(ctx):
    return mean_of((ctx.get("train") or {}).get("cycles") or [], "restore_s")
