#!/usr/bin/env python3
"""Print what the program's own spans and scopes say about a traced run:
device time by bucket, the ops in no bucket, the idle gaps by ``ftl:`` span,
and each ``ftl:`` parent span's time by child (they add up to the span).

    python3 perfbench/tools/program_trace_report.py <trace dir | summary.json>
        [--record out.json --rounds N]

A trace directory is reduced afresh, by the scope table the tracing process
left beside it (``program_scopes.json``, ``lib/program_records.py``); a
``program_trace.json`` (the readers' cache in a cell's work directory) is
read as it is. ``--record`` (trace
directory only) also writes the raw device ops and spans of the first N
``ftl:sched.step`` (or ``ftl:train.step``) spans, for the reduction's own
test beside ``tests/perfbench/recorded_trace.json``.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench.lib import program_records, trace_reduce  # noqa: E402
from perfbench.metrics import _program_trace as pt  # noqa: E402

PARENTS = ("ftl:sched.step", "ftl:engine.decode", "ftl:engine.prefill",
           "ftl:train.step")


def record(raw: dict, rounds: int) -> dict:
    """The raw records of the first ``rounds`` step spans that lie inside
    the window, device ops and spans alike, cut at their edges."""
    lo, hi = pt.window_of(raw)
    steps = sorted(sp for sp in raw["spans"]
                   if sp[0] in ("ftl:sched.step", "ftl:train.step")
                   and sp[1] >= lo and sp[2] <= hi)[:rounds]
    if not steps:
        return {"device_ops": {}, "spans": []}
    lo, hi = steps[0][1], steps[-1][2]
    return {
        "device_ops": {k: [op for op in v if op[2] >= lo
                           and op[2] + op[3] <= hi]
                       for k, v in raw["device_ops"].items()},
        # the cut is the slice's window, said the way the harness says it
        "spans": [[trace_reduce.WINDOW_SPAN, lo, hi, "cut", {}]] + [
            sp for sp in raw["spans"] if sp[1] >= lo and sp[2] <= hi]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("source", help="trace directory, or program_trace.json")
    ap.add_argument("--record", default="",
                    help="write the first rounds' raw records here")
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()
    if os.path.isdir(args.source):
        beside = os.path.dirname(os.path.abspath(args.source))
        scopes = program_records.read_scopes(beside)
        if scopes is None:
            raise SystemExit(f"no {program_records.SCOPES_NAME} in {beside}: "
                             f"the traced run writes it beside its trace")
        raw = pt.load_xplane(trace_reduce.newest_xplane(args.source))
        summary = pt.reduce(raw, scopes)
        outer = sorted((sp[2] - sp[1]) / 1e6 for sp in raw["spans"]
                       if sp[0] == "pb:decode")
        if outer:  # the harness's own span around engine.decode_step
            print(f"pb:decode in the traced part: {len(outer)} spans, "
                  f"median {outer[len(outer) // 2]:.3f} ms")
        if args.record:
            with open(args.record, "w") as fh:
                json.dump(record(raw, args.rounds), fh)
    else:
        with open(args.source) as fh:
            summary = json.load(fh)
        summary = summary.get("summary", summary)
    busy = summary.get("busy_s")
    print(f"window {summary['window_s']} s | busy {busy} s | devices "
          f"{summary['devices']}")
    if busy:
        print("| bucket | s | % of busy |\n| --- | --- | --- |")
        for k, v in sorted(summary["buckets"].items(), key=lambda kv: -kv[1]):
            print(f"| {k} | {v:.4f} | {100 * v / busy:.2f} |")
        print("ops in no bucket:")
        for name, v in summary["unscoped_ops"]:
            print(f"  {v:.4f} s  {100 * v / busy:5.2f} %  {name}")
        print("idle gaps by ftl: span:", json.dumps(summary["idle_gaps"]))
    if summary.get("decode"):
        print("decode rounds:", json.dumps(summary["decode"]))
    for parent in PARENTS:
        parts = pt.by_child_ms(summary["spans"], parent)
        if parts:
            n = sum(1 for sp in summary["spans"] if sp[0] == parent)
            whole = parts.pop("_span_")
            print(f"{parent}: {n} spans, mean {whole:.3f} ms = " + " + ".join(
                f"{k} {v:.3f}" for k, v in sorted(parts.items(),
                                                  key=lambda kv: -kv[1])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
