"""Median time between completions of consecutive optimizer steps in the
window, stamped where the trainer syncs on each step's metrics."""

import statistics


def read(ctx):
    window = (ctx.get("train") or {}).get("window")
    if not window or len(window.get("done_t", ())) < 3:
        return None
    t = window["done_t"]
    return statistics.median(b - a for a, b in zip(t, t[1:])) * 1e3
