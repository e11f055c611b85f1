"""Percentiles and per-request latency arithmetic (after
``obs/reqtrace.py``'s ``percentile`` / ``derive``, kept here so that no PR
to the program can move the yardstick)."""

import math


def percentile(values, q: float):
    """Linear-interpolated percentile, q in [0, 100]; None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def tpot_seconds(token_times) -> float:
    """(last token time - first token time) / (output tokens - 1); None for
    a request with fewer than two tokens."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1)


def request_latencies(requests) -> dict:
    """``requests``: dicts with ``t_ref`` (due or submit time),
    ``token_times`` (commit time of each output token), ``done`` (bool).
    Only completed requests enter the tails; the others are counted as
    failed by the caller — never as fast."""
    ttft, tpot = [], []
    for r in requests:
        if not r["done"] or not r["token_times"]:
            continue
        ttft.append(r["token_times"][0] - r["t_ref"])
        t = tpot_seconds(r["token_times"])
        if t is not None:
            tpot.append(t)
    return {"ttft_s": ttft, "tpot_s": tpot}


def spread(values) -> float:
    """Interquartile distance over the median, as the bounds' rule has it
    (``statistics.quantiles(values, n=4)``)."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
