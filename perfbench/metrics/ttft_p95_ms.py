"""95th percentile of time to first token over the requests that completed
in the window: from the due time in an open loop, from submission in a
closed one. The scheduler's own commit stamp ends it."""

from perfbench.lib import stats


def read(ctx):
    serve = ctx.get("serve")
    if not serve or not serve["ttft_s"]:
        return None
    return stats.percentile(serve["ttft_s"], 95) * 1e3
