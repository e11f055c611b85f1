"""Blocking fault-path checkpoint write (Orbax commit), the flight
recorder's ``ckpt_save`` record, mean of the cycles."""

from perfbench.lib.recovery import mean_of


def read(ctx):
    return mean_of((ctx.get("train") or {}).get("cycles") or [], "save_s")
