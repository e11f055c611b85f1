"""From a profiler trace to numbers: busy union, idle share, time by op,
idle gaps by what the host was doing.

The yardstick's own reduction (ROADMAP S2 had none): every PR's trace is
reduced by this file, and no PR that claims a gain can change it. It works
on plain tuples so that the tests can feed it a small recorded trace;
:func:`load_xplane` is the only part that needs JAX (``ProfileData`` reads
the ``.xplane.pb`` with nothing else).

Run as a script it reduces the newest trace under a directory and writes
the summary as JSON: ``python trace_reduce.py <trace_dir> <out.json>``. The
training cell's parent, which must stay off JAX, calls it that way.
"""

import glob
import json
import os
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "pb:"  # the harness's own TraceAnnotation names
WINDOW_SPAN = "pb:window"


def merge(intervals):
    """Union of [start, end) intervals -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(intervals, lo, hi) -> float:
    return sum(e - s for s, e in merge(clip(intervals, lo, hi))) / 1e9


def gaps(intervals, lo, hi):
    """Idle [start, end) stretches of [lo, hi) not covered by intervals."""
    out, cur = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def span_at(spans, t):
    """Name of the innermost harness span covering time t, else None."""
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN:
            if best is None or (e - s) < best[1]:
                best = (name, e - s)
    return best[0] if best else None


def reduce_events(device_ops: dict, host_spans: list) -> dict:
    """``device_ops``: {device name: [(op name, start_ns, dur_ns), ...]};
    ``host_spans``: [(span name, start_ns, end_ns), ...] on the same clock.

    Returns window_s, busy_s (mean over devices), idle share, seconds by op
    (mean over devices), and the idle gaps of the first device by host
    span. With no device op the result's ``busy_s`` is None: the readers
    then return nothing rather than a share of 0.
    """
    devices = {k: v for k, v in device_ops.items() if v}
    if not devices:
        return {"window_s": None, "busy_s": None, "ops": {}, "idle_gaps": [],
                "devices": 0}
    window = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(s + d for evs in devices.values() for _, s, d in evs)
    n_dev = len(devices)
    busy = 0.0
    ops = {}
    for evs in devices.values():
        iv = [(s, s + d) for _, s, d in evs]
        busy += busy_seconds(iv, lo, hi)
        for name, s, d in evs:
            if s + d <= lo or s >= hi:
                continue
            o = ops.setdefault(name, {"s": 0.0, "n": 0})
            o["s"] += (min(s + d, hi) - max(s, lo)) / 1e9
            o["n"] += 1
    for o in ops.values():
        o["s"] /= n_dev
        o["n"] = o["n"] / n_dev
    first = devices[sorted(devices)[0]]
    by_span = {}
    for s, e in gaps([(s, s + d) for _, s, d in first], lo, hi):
        name = span_at(host_spans, (s + e) // 2) or "_no_host_span_"
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n_dev,
        "ops": ops,
        "idle_gaps": sorted(([k, v] for k, v in by_span.items()),
                            key=lambda kv: -kv[1])[:10],
        "devices": n_dev,
    }


def breakdown(reduced: dict) -> dict:
    top = sorted(((k, v["s"]) for k, v in reduced["ops"].items()),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": reduced["idle_gaps"]}


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


_LAYOUT = re.compile(r"\{[^}]*\}")


def short_name(hlo: str) -> str:
    """The profiler names a device op by its whole HLO instruction
    (``%fusion.111 = (f32[3,4096]{...}, ...) fusion(...)``). Keep the
    instruction's name and its result type without layouts; a Mosaic
    (Pallas) custom call is marked ``pallas:`` so that the kernel readers
    can tell kernels from XLA's own fusions."""
    if " = " not in hlo:
        return hlo[:96]
    name, rest = hlo.split(" = ", 1)
    name = name.lstrip("%")
    if "custom-call(" in rest and "tpu_custom_call" in rest:
        name = "pallas:" + name
    rtype = _LAYOUT.sub("", rest.split(" fusion(")[0].split(
        " custom-call(")[0])
    if len(rtype) > 64 or "(" in rtype[1:]:
        rtype = rtype.split(")")[0][:64] + (")" if rtype.startswith("(")
                                            else "")
    return f"{name} {rtype}".strip()[:120]


def load_xplane(path: str, describe: bool = False):
    """(device_ops, host_spans[, description]) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, host_spans, desc = {}, [], []
    for plane in pd.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if describe:
                evs = list(line.events)
                desc.append({
                    "plane": plane.name, "line": line.name, "events": len(evs),
                    "sample": [[e.name, e.start_ns, e.duration_ns,
                                {str(k): str(v)[:120] for k, v in
                                 list(e.stats)[:8]}] for e in evs[:3]]})
            if is_dev and line.name == OPS_LINE:
                out = device_ops.setdefault(plane.name, [])
                names = {}
                for e in line.events:
                    short = names.get(e.name)
                    if short is None:
                        short = names[e.name] = short_name(e.name)
                    out.append((short, int(e.start_ns), int(e.duration_ns)))
            elif not is_dev:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = int(e.start_ns)
                        host_spans.append((e.name, s,
                                           s + int(e.duration_ns)))
    if describe:
        return device_ops, host_spans, desc
    return device_ops, host_spans


def reduce_dir(trace_dir: str, describe: bool = False) -> dict:
    path = newest_xplane(trace_dir)
    if describe:
        dev, spans, desc = load_xplane(path, describe=True)
    else:
        dev, spans = load_xplane(path)
    out = reduce_events(dev, spans)
    if describe:
        out["describe"] = desc
        # a small recorded slice for the reduction's own test
        first = sorted(dev)[0] if dev else None
        head = sorted(dev[first], key=lambda e: e[1])[:400] if first else []
        hi = max((s + d for _, s, d in head), default=0)
        out["raw_head"] = {
            "device_ops": {first: head} if first else {},
            "spans": [sp for sp in spans if sp[1] < hi][:200]}
    return out


if __name__ == "__main__":
    result = reduce_dir(sys.argv[1], describe="--describe" in sys.argv)
    with open(sys.argv[2], "w") as fh:
        json.dump(result, fh)
