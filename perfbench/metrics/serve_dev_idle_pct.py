"""Device idle share of the traced serving window: 1 - busy union / window,
from the device trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not ctx.get("serve") or not trace or trace.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
