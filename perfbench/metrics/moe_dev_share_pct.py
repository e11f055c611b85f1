"""Share of the device's busy time in the traced window under the expert
layer's scopes: ``moe_route`` (router, top-k, grouping), ``moe_experts``
(the grouped matmuls — the compiler's own ragged-dot calls, told by name —
and the combine) and ``moe_shared``; self time over the busy union."""

from perfbench.metrics import _latent_trace as lt


def read(ctx):
    return lt.window_share_pct(ctx, ("moe_route", "moe_experts",
                                     "moe_shared"))
