"""What the readers of the latent / expert layers share (not a metric: no
``read``): the device seconds under some of the program's scopes inside
intervals of a traced window — the whole window, or the program's round
spans — and the counts those rounds carry in their stats spans
(``ftl:engine.decode.stats`` / ``ftl:engine.prefill.stats``, args
``moe_pairs``, ``moe_touched``, ``index_keys``, ``latent_rows``,
``window_rows``: what each round did, read back from the device with its
tokens). Seconds and counts are of the same rounds, so a share of a roofline
built on them compares like with like. A program with no such spans (the
parent of the PR that brought them) gives None and the metric is left out.
"""

import os
import re

from perfbench.lib import program_records, trace_reduce
from perfbench.metrics import _program_trace as pt

STATS = ("moe_pairs", "moe_touched", "index_keys", "latent_rows",
         "window_rows")
ROUNDS = {"decode": "ftl:engine.decode", "prefill": "ftl:engine.prefill"}
# ``jax.lax.ragged_dot`` reaches the chip as the compiler's own Mosaic call,
# which carries no scope path: the grouped matmuls of the ``moe_experts``
# scope are told by their op name, as ``_kernels.py`` tells the flash calls
BY_NAME = {"moe_experts": re.compile(r"^pallas:ragged-dot")}
_RAW = {}


def _raw_of(path: str) -> dict:
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _RAW:
        _RAW.clear()
        _RAW[key] = pt.load_xplane(path)
    return _RAW[key]


def _bucket(name: str, scope: str, order) -> str:
    bucket = pt.bucket_of(scope, order)
    if bucket == pt.UNSCOPED:
        for b, pattern in BY_NAME.items():
            if pattern.search(name):
                return b
    return bucket


def scoped_seconds(ctx, buckets: tuple, intervals=None):
    """Device seconds (mean over devices, self time) of the ops in
    ``buckets`` inside ``intervals`` (sorted, disjoint; default: the traced
    window), or None where there is no trace to read."""
    try:
        work = ctx["cell"].work_dir()
        order = program_records.read_scopes(work)["scopes"]
        raw = _raw_of(trace_reduce.newest_xplane(os.path.join(work,
                                                              "trace")))
    except Exception:  # a reader never raises: the metric is left out
        return None
    devices = [evs for evs in raw["device_ops"].values() if evs]
    if intervals is None:
        win = pt.window_of(raw)
        intervals = [list(win)] if win else []
    if not devices or not intervals:
        return None
    ns = 0
    for evs in devices:
        segs = pt.self_segments([(s, s + d, _bucket(name, scope, order))
                                 for name, scope, s, d in evs])
        ns += pt.overlap([(s, e) for s, e, b in segs if b in buckets],
                         intervals)
    return ns / len(devices) / 1e9


def window_share_pct(ctx, buckets: tuple):
    """``scoped_seconds`` of the window over its busy seconds, in %; None
    where ``pt.share_pct`` gives None (no device time, no program scope)."""
    summary = pt.summary_of(ctx) if ctx.get("serve") else None
    if pt.share_pct(summary, *buckets) is None:
        return None
    seconds = scoped_seconds(ctx, buckets)
    return None if seconds is None else 100.0 * seconds / summary["busy_s"]


def reading(ctx, buckets: tuple, phases: tuple):
    """``{"seconds": scoped_seconds inside the rounds of ``phases`` that
    carry a stats span, **their counts}``, or None."""
    summary = pt.summary_of(ctx) if ctx.get("serve") else None
    if not summary or not summary.get("spans"):
        return None
    spans = summary["spans"]
    inside = lambda c, p: (c[3] == p[3] and p[1] <= c[1]      # noqa: E731
                           and c[2] <= p[2])
    rounds, counts = [], dict.fromkeys(STATS, 0)
    for phase in phases:
        stats = [sp for sp in spans if sp[0] == ROUNDS[phase] + ".stats"]
        for parent in (sp for sp in spans if sp[0] == ROUNDS[phase]):
            mine = [st for st in stats if inside(st, parent)]
            if mine:
                rounds.append((parent[1], parent[2]))
            for st in mine:
                for k in STATS:
                    counts[k] += int(st[4].get(k, 0))
    if not rounds:
        return None
    seconds = scoped_seconds(ctx, buckets, trace_reduce.merge(rounds))
    return None if seconds is None else dict(counts, seconds=seconds)
