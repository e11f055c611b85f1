"""The paged KV read's share of its roofline in the traced decode rounds: the
least time the chip could take to read the live KV once (sum of the
``live_tokens`` the program's ``ftl:engine.decode`` spans carry x KV bytes a
token in bfloat16, over the published HBM bandwidth) over the device time
under the ``kv_read`` scope inside those spans. Bound by bytes; the bytes
are the algorithm's, whatever implements the read."""

from perfbench.lib import weights
from perfbench.metrics import _program_trace as pt


def read(ctx):
    summary = pt.summary_of(ctx) if ctx.get("serve") else None
    rounds = (summary or {}).get("decode")
    if not rounds or not rounds["kv_read_s"] or not ctx.get("peaks"):
        return None
    d = ctx["dims"]
    least = (weights.family_of(d).paged_read_bytes(d, rounds["live_tokens"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / rounds["kv_read_s"]
