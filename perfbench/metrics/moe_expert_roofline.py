"""The held experts' grouped matmuls' share of their roofline in the traced
rounds (decode and prefill): the least time the chip could take for the
rounds' work — the weights of each held expert a round touched read once
(``moe_touched`` of the stats spans x the family's ``moe_expert_bytes``)
and the three matmuls of each (token, held expert) pair (``moe_pairs`` x
``moe_expert_flops``), ``lib/flops.roofline_seconds`` — over the device
time under the ``moe_experts`` scope inside those rounds. The work is the
algorithm's, whatever implements it: a kernel that skips untouched experts
reads no less than this."""

from perfbench.lib import flops, weights
from perfbench.metrics import _latent_trace as lt


def read(ctx):
    got = lt.reading(ctx, ("moe_experts",), ("decode", "prefill"))
    if not got or not got["seconds"] or not ctx.get("peaks"):
        return None
    d = ctx["dims"]
    fam = weights.family_of(d)
    least = flops.roofline_seconds(
        fam.moe_expert_flops(d, got["moe_pairs"]),
        fam.moe_expert_bytes(d, got["moe_touched"]), ctx["peaks"])
    return 100.0 * least / got["seconds"]
