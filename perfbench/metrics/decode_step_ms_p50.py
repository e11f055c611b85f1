"""Median of the harness span around ``engine.decode_step``: dispatch of
the decode program to the token sync."""

import statistics


def read(ctx):
    serve = ctx.get("serve")
    spans = (serve or {}).get("spans", {}).get("decode")
    if not spans:
        return None
    return statistics.median(e - s for s, e, _ in spans) * 1e3
