"""Warm compile of the train step in the resumed children: the flight
recorder's ``compile`` record (persistent cache hit), mean of the cycles."""

from perfbench.lib.recovery import mean_of


def read(ctx):
    return mean_of((ctx.get("train") or {}).get("cycles") or [], "compile_s")
