"""Share of the device's busy time in the traced training steps under the
expert layers' scopes: ``moe_route`` (router matmul, sigmoid, top-k,
grouping of the pairs by held expert), ``moe_experts`` (the grouped
matmuls forward and backward — the compiler's own ragged-dot calls, told
by name as ``_latent_trace`` tells them — and the combine) and
``moe_shared`` (the shared experts); self time over the busy union. A
program that trains no expert layer opens none of them in a training
step, and the metric is left out."""

from perfbench.metrics import _latent_trace as lt
from perfbench.metrics import _program_trace as pt

BUCKETS = ("moe_route", "moe_experts", "moe_shared")


def read(ctx):
    if not ctx.get("train"):
        return None
    summary = pt.summary_of(ctx)
    if not summary or not any(summary["buckets"].get(b) for b in BUCKETS):
        return None
    if pt.share_pct(summary, *BUCKETS) is None:
        return None
    seconds = lt.scoped_seconds(ctx, BUCKETS)
    return None if seconds is None else 100.0 * seconds / summary["busy_s"]
