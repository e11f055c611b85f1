"""Share of the device's busy time in the traced window under the program's
``index_select`` scope: the indexer's query projections, its scores over the
cached index keys and the top-k (decode) or k-th-largest mask (prefill)."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    if not ctx.get("serve"):
        return None
    return pt.share_pct(pt.summary_of(ctx), "index_select")
