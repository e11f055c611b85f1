"""The latent caches' reads' share of their roofline in the traced decode
rounds: the least time the chip could take to read what the rounds needed —
each index key scanned, each selected latent row and each window row once
(the stats spans' ``index_keys``, ``latent_rows``, ``window_rows`` x the
family's ``latent_read_bytes``, over the published HBM bandwidth) — over
the device time under the ``kv_read`` and ``index_select`` scopes inside
those rounds. Bound by bytes; a read that gathers whole tables or sorts
every score takes longer than this, and that is what the share shows."""

from perfbench.lib import weights
from perfbench.metrics import _latent_trace as lt


def read(ctx):
    got = lt.reading(ctx, ("kv_read", "index_select"), ("decode",))
    if not got or not got["seconds"] or not ctx.get("peaks"):
        return None
    d = ctx["dims"]
    least = (weights.family_of(d).latent_read_bytes(
        d, got["index_keys"], got["latent_rows"], got["window_rows"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / got["seconds"]
