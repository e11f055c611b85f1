"""What the program itself writes into a traced run: its ``ftl:`` host
spans (``obs/trace.py`` ``SPANS``) and the scope path of every device op
(``SCOPES``, kept by the TPU profiler as the ``tf_op`` stat of the op's
event metadata). Not a metric (no ``read``); shared by the readers that
give device time to a model part or host time to a part of the scheduler,
so that a dozen readers cost one read of the trace.

The ``.xplane.pb`` is decoded here with ``google.protobuf`` alone (the
field numbers of tsl's ``xplane.proto``, nothing else of it): JAX's
``ProfileData`` does not show an event's metadata stats, where ``tf_op``
lives, and a reader that touches no JAX can run in the training cell's
parent, which must stay off the chip. The summary is cached as JSON in the
cell's work directory, keyed by the trace file and the bucket list.

The bucket list is the program's own scope table, not a copy kept here: the
process that traced (it imports the program anyway) wrote the table's names
in their order, and which of them the program opens itself, beside its
trace (``lib/program_records.py``). A scope the program adds to its table
is a bucket from its next traced run on.

Run against a program that writes no ``ftl:`` span and no scope (the parent
commit of the PR that added them), every reader finds nothing and returns
None.
"""

import json
import os
import re
import statistics

from perfbench.lib import program_records, trace_reduce

UNSCOPED = "_unscoped_"
SPAN_PREFIX = "ftl:"
CACHE_NAME = "program_trace.json"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


# ------------------------------------------------------------ the xplane
def _xspace_class():
    """Message class for the part of tsl's XSpace this reader needs."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package="perfbench_xplane",
        syntax="proto3")

    def message(name, *fields, oneof=None):
        m = fd.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=T.LABEL_REPEATED if repeated
                            else T.LABEL_OPTIONAL)
            if oneof and fname.endswith("_value"):
                f.oneof_index = 0
            if isinstance(ftype, str):
                f.type, f.type_name = T.TYPE_MESSAGE, (
                    ".perfbench_xplane." + ftype)
            else:
                f.type = ftype

    i64, u64, s, dbl = T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_STRING, (
        T.TYPE_DOUBLE)
    message("XStat", ("metadata_id", 1, i64, 0), ("double_value", 2, dbl, 0),
            ("uint64_value", 3, u64, 0), ("int64_value", 4, i64, 0),
            ("str_value", 5, s, 0), ("ref_value", 7, u64, 0),
            oneof="value")
    message("XEvent", ("metadata_id", 1, i64, 0), ("offset_ps", 2, i64, 0),
            ("duration_ps", 3, i64, 0), ("stats", 4, "XStat", 1))
    message("XLine", ("id", 1, i64, 0), ("name", 2, s, 0),
            ("timestamp_ns", 3, i64, 0), ("events", 4, "XEvent", 1))
    message("XEventMetadata", ("id", 1, i64, 0), ("name", 2, s, 0),
            ("stats", 5, "XStat", 1))
    message("XStatMetadata", ("id", 1, i64, 0), ("name", 2, s, 0))
    # a map<int64, M> is on the wire a repeated {key = 1, value = 2}
    message("EventMetadataEntry", ("key", 1, i64, 0),
            ("value", 2, "XEventMetadata", 0))
    message("StatMetadataEntry", ("key", 1, i64, 0),
            ("value", 2, "XStatMetadata", 0))
    message("XPlane", ("name", 2, s, 0), ("lines", 3, "XLine", 1),
            ("event_metadata", 4, "EventMetadataEntry", 1),
            ("stat_metadata", 5, "StatMetadataEntry", 1))
    message("XSpace", ("planes", 1, "XPlane", 1))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def _stat_value(stat, stat_names):
    which = stat.WhichOneof("value")
    if which == "ref_value":          # a string kept once, as a stat name
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


def load_xplane(path: str) -> dict:
    """``{"device_ops": {plane: [[short name, scope path, start_ns,
    dur_ns], ...]}, "spans": [[name, start_ns, end_ns, thread, args], ...]}``
    of one ``.xplane.pb``. Device ops are the ``XLA Ops`` line of each TPU
    plane, as ``trace_reduce.load_xplane`` takes them; spans are the ``ftl:``
    and ``pb:`` events of the host planes, ``thread`` the line's place."""
    space = _xspace_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    device_ops, spans = {}, []
    for pi, plane in enumerate(space.planes):
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            known = {}
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                out = device_ops.setdefault(plane.name, [])
                t0 = line.timestamp_ns * 1000
                for ev in line.events:
                    if ev.metadata_id not in known:
                        md = meta[ev.metadata_id]
                        scope = ""
                        for st in md.stats:
                            if stat_names.get(st.metadata_id) == "tf_op":
                                scope = str(_stat_value(st, stat_names))
                        known[ev.metadata_id] = (
                            trace_reduce.short_name(md.name), scope)
                    name, scope = known[ev.metadata_id]
                    out.append([name, scope, (t0 + ev.offset_ps) // 1000,
                                ev.duration_ps // 1000])
            continue
        for li, line in enumerate(plane.lines):
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                name = meta[ev.metadata_id].name if (
                    ev.metadata_id in meta) else ""
                if not name.startswith((SPAN_PREFIX,
                                        trace_reduce.SPAN_PREFIX)):
                    continue
                start = (t0 + ev.offset_ps) // 1000
                args = {stat_names.get(st.metadata_id, "?"):
                        _stat_value(st, stat_names) for st in ev.stats}
                spans.append([name, start, start + ev.duration_ps // 1000,
                              f"{pi}.{li}", args])
    return {"device_ops": device_ops, "spans": spans}


# ------------------------------------------------------------- arithmetic
def bucket_of(scope: str, buckets) -> str:
    """The first of ``buckets`` (the program's scope table, in its order)
    that is a component of the scope path
    (``jit(f)/transpose(jvp(Transformer))/layers_0/attention/kv_read/…``:
    a backward or rematerialised op keeps its forward scope inside the
    wrapper), else ``UNSCOPED``."""
    words = set(_WORD.findall(scope))
    for b in buckets:
        if b in words:
            return b
    return UNSCOPED


def self_segments(events):
    """``events``: [(start, end, key)], possibly nested (a ``while`` op
    spans the ops of its body). Returns disjoint [(start, end, key)]: each
    instant goes to the innermost event that covers it."""
    out, stack = [], []           # stack entries: [end, key, cursor]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, key, cur = stack.pop()
            if end > cur:
                out.append((cur, end, key))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s, e, key in sorted(events, key=lambda x: (x[0], -x[1])):
        close(s)
        if stack:
            if s > stack[-1][2]:
                out.append((stack[-1][2], s, stack[-1][1]))
            stack[-1][2] = max(stack[-1][2], s)
            e = min(e, stack[-1][0])      # a child never outlasts its parent
        if e > s:
            stack.append([e, key, s])
    close(float("inf"))
    return out


def window_of(raw: dict):
    """The window ``trace_reduce.reduce_events`` reduces over: the
    harness's ``pb:window`` span if it wrote one, else first device op to
    last; with no device op (a CPU rehearsal), first ``ftl:`` span to last."""
    for name, s, e, *_ in raw["spans"]:
        if name == trace_reduce.WINDOW_SPAN:
            return s, e
    ops = [op for evs in raw["device_ops"].values() for op in evs]
    if ops:
        return (min(op[2] for op in ops), max(op[2] + op[3] for op in ops))
    own = [sp for sp in raw["spans"] if sp[0].startswith(SPAN_PREFIX)]
    if own:
        return min(sp[1] for sp in own), max(sp[2] for sp in own)
    return None


def overlap(segments, intervals) -> int:
    """Nanoseconds of the disjoint ``segments`` inside the disjoint sorted
    ``intervals``."""
    total, i = 0, 0
    for s, e in sorted(segments):
        while i < len(intervals) and intervals[i][1] <= s:
            i += 1
        j = i
        while j < len(intervals) and intervals[j][0] < e:
            total += max(0, min(e, intervals[j][1])
                         - max(s, intervals[j][0]))
            j += 1
    return total


def reduce(raw: dict, scopes: dict) -> dict:
    """Summary of one traced window: device seconds by bucket (mean over
    devices, self time of nested ops), the ops in no bucket, the ``ftl:``
    spans inside the window, and for the decode rounds their
    ``live_tokens`` and the ``kv_read`` device seconds inside them.
    ``scopes`` is what the tracing process wrote of the program's table
    (``program_records.scopes``): ``scopes`` the bucket list in its order,
    ``opened`` those the program opens itself. The rest are flax's module
    names, which a program from before the scopes (or an executable read
    back from a compile cache that older code filled) carries too, so
    ``share_pct`` reads only a summary that holds an opened one."""
    win = window_of(raw)
    order, opened = scopes["scopes"], list(scopes["opened"])
    if win is None:
        return {"window_s": None, "busy_s": None, "buckets": {},
                "unscoped_ops": [], "idle_gaps": [], "spans": [],
                "decode": None, "devices": 0, "opened": opened}
    lo, hi = win
    spans = [sp for sp in raw["spans"] if sp[0].startswith(SPAN_PREFIX)
             and sp[1] >= lo and sp[2] <= hi]
    decode = [sp for sp in spans if sp[0] == "ftl:engine.decode"]
    rounds = trace_reduce.merge([(sp[1], sp[2]) for sp in decode])
    devices = {k: v for k, v in raw["device_ops"].items() if v}
    buckets, unscoped, busy, kv_read_ns = {}, {}, 0.0, 0.0
    for evs in devices.values():
        segs = self_segments([(s, s + d, (name, bucket_of(scope, order)))
                              for name, scope, s, d in evs])
        kv = []
        for s, e, (name, bucket) in segs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            buckets[bucket] = buckets.get(bucket, 0.0) + (e - s) / 1e9
            if bucket == UNSCOPED:
                unscoped[name] = unscoped.get(name, 0.0) + (e - s) / 1e9
            elif bucket == "kv_read":
                kv.append((s, e))
        kv_read_ns += overlap(kv, rounds)
        busy += trace_reduce.busy_seconds(
            [(s, s + d) for _, _, s, d in evs], lo, hi)
    n = max(len(devices), 1)
    # idle gaps of the first device by the innermost ftl: span of the
    # serving / training thread that covers them, as trace_reduce does by
    # pb: span (the prefetcher's thread works beside, not in the way)
    gaps_by = {}
    if devices:
        host = [sp[:3] for sp in spans if not sp[0].startswith("ftl:data.")]
        first = devices[sorted(devices)[0]]
        for s, e in trace_reduce.gaps([(s, s + d) for _, _, s, d in first],
                                      lo, hi):
            name = trace_reduce.span_at(host, (s + e) // 2) or (
                "_no_ftl_span_")
            gaps_by[name] = gaps_by.get(name, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n if devices else None,
        "buckets": {k: v / n for k, v in buckets.items()},
        "unscoped_ops": sorted(([k, v / n] for k, v in unscoped.items()),
                               key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by.items()),
                            key=lambda kv: -kv[1]),
        "spans": spans,
        "decode": {"rounds": len(decode),
                   "live_tokens": sum(int(sp[4].get("live_tokens", 0))
                                      for sp in decode),
                   "kv_read_s": kv_read_ns / n / 1e9} if decode else None,
        "devices": len(devices),
        "opened": opened,
    }


def children(spans, parent):
    """Spans directly or indirectly inside ``parent`` on its thread."""
    return [sp for sp in spans if sp is not parent and sp[3] == parent[3]
            and parent[1] <= sp[1] and sp[2] <= parent[2]]


def self_ms(spans, name: str, child_prefixes: tuple) -> list:
    """For every ``name`` span: its duration less the time its children
    whose names start with one of ``child_prefixes`` cover, in ms."""
    out = []
    for parent in (sp for sp in spans if sp[0] == name):
        covered = trace_reduce.merge(
            [(sp[1], sp[2]) for sp in children(spans, parent)
             if sp[0].startswith(child_prefixes)])
        out.append(((parent[2] - parent[1])
                    - sum(e - s for s, e in covered)) / 1e6)
    return out


def by_child_ms(spans, name: str) -> dict:
    """Mean ms per ``name`` span of each DIRECT child name and of
    ``_self_`` (what no child covers): they add up to the span."""
    parents = [sp for sp in spans if sp[0] == name]
    if not parents:
        return {}
    total = {}
    for parent in parents:
        inner = children(spans, parent)
        direct = [sp for sp in inner
                  if not any(o is not sp and o[1] <= sp[1] and sp[2] <= o[2]
                             for o in inner)]
        covered = 0
        for sp in direct:
            total[sp[0]] = total.get(sp[0], 0.0) + (sp[2] - sp[1]) / 1e6
            covered += sp[2] - sp[1]
        total["_self_"] = total.get("_self_", 0.0) + (
            parent[2] - parent[1] - covered) / 1e6
        total["_span_"] = total.get("_span_", 0.0) + (
            parent[2] - parent[1]) / 1e6
    return {k: v / len(parents) for k, v in total.items()}


def median_ms(spans, name: str):
    durs = [(sp[2] - sp[1]) / 1e6 for sp in spans if sp[0] == name]
    return statistics.median(durs) if durs else None


def share_pct(summary, *buckets):
    """Device seconds under ``buckets`` over the busy seconds, in %; None
    where there is no device time, or where no op carries a scope the
    program opens: the ops then ran under another program's names, and a
    share of 0 (or an unscoped share of everything) would be a wrong
    number, not a reading."""
    if not summary or not summary.get("busy_s"):
        return None
    named = summary["buckets"]
    if not any(k in summary["opened"] for k in named):
        return None
    return 100.0 * sum(named.get(b, 0.0) for b in buckets) / summary[
        "busy_s"]


# ------------------------------------------------------------ the readers' door
def summary_of(ctx):
    """The cached summary of the cell's newest trace, or None (no trace,
    or no scope table beside it: nothing of the program's to sort by)."""
    work = ctx["cell"].work_dir()
    scopes = program_records.read_scopes(work)
    try:
        path = trace_reduce.newest_xplane(os.path.join(work, "trace"))
    except (FileNotFoundError, OSError):
        return None
    if scopes is None:
        return None
    cache = os.path.join(work, CACHE_NAME)
    stamp = [path, os.path.getmtime(path), os.path.getsize(path), scopes]
    try:
        with open(cache) as fh:
            cached = json.load(fh)
        if cached.get("stamp") == stamp:
            return cached["summary"]
    except (OSError, ValueError):
        pass
    try:
        summary = reduce(load_xplane(path), scopes)
    except Exception as e:  # a reader never raises: the metric is left out
        import sys

        print(f"perfbench: program trace unreadable ({type(e).__name__}: "
              f"{e})", file=sys.stderr)
        return None
    try:
        with open(cache, "w") as fh:
            json.dump({"stamp": stamp, "summary": summary}, fh)
    except OSError:
        pass
    return summary


def events_of(ctx, job: str) -> list:
    """The flight recorder's records of one job of the training chain."""
    from perfbench.lib import recovery

    return recovery.read_events(os.path.join(
        ctx["cell"].work_dir(), "ckpts", "events", f"events_{job}.jsonl"))


def mean_over_resumed(ctx, value_of):
    """Mean over the chain's resumed children (``ctx["train"]["cycles"]``
    names them) of ``value_of(events)``; None if any child has none."""
    cycles = (ctx.get("train") or {}).get("cycles") or []
    vals = [value_of(events_of(ctx, c["to_job"])) for c in cycles]
    if not vals or any(v is None for v in vals):
        return None
    return sum(vals) / len(vals)
