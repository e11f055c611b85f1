"""The held experts' grouped matmuls' share of their roofline in the traced
training steps: the least time the chip could take for the traced steps'
expert work — the three matmuls of each (token, held expert) pair, forward
and backward (the family's ``moe_train_expert_flops``), and the weights of
each held expert a step touched, read by the forward and the backward and
their gradient written (``moe_train_expert_bytes``),
``lib/flops.roofline_seconds`` — over the device time under the
``moe_experts`` scope (the ragged-dot calls by name) in the traced window.

The pairs and the touched experts are the program's own counts: the
window's change of ``moe_pairs_total{phase=train}`` and
``moe_experts_touched_total{phase=train}``, which the trainer adds from
the values it reads off the device each step, over the steps it consumed
in the window (``done_t``), times the traced steps. A program without
those counters gives None and the metric is left out."""

from perfbench.lib import flops, weights
from perfbench.metrics import _latent_trace as lt


def _train_count(counters: dict, name: str):
    hits = [v for k, v in counters.items()
            if k.startswith(name + "{") and "phase=train" in k]
    return sum(hits) if hits else None


def read(ctx):
    train = ctx.get("train")
    if not train or not ctx.get("peaks"):
        return None
    window = train["window"]
    consumed, traced = len(window.get("done_t") or ()), window.get(
        "traced_steps")
    counters = window.get("counters") or {}
    pairs = _train_count(counters, "moe_pairs_total")
    touched = _train_count(counters, "moe_experts_touched_total")
    if not consumed or not traced or not pairs or touched is None:
        return None
    seconds = lt.scoped_seconds(ctx, ("moe_experts",))
    if not seconds:
        return None
    d = ctx["dims"]
    fam = weights.family_of(d)
    per = traced / consumed
    least = flops.roofline_seconds(
        fam.moe_train_expert_flops(d, pairs * per),
        fam.moe_train_expert_bytes(d, touched * per), ctx["peaks"])
    return 100.0 * least / seconds
