"""The program's span tallies over the untraced part of a serving window:
the change of ``ftl_span_seconds_total{span=...}`` and
``ftl_spans_total{span=...}`` (``obs/trace.py``: every ``ftl:`` span timed
on the host's clock on every run, traced or not), through the counters
door, ``ctx["serve"]["program_counters"]``. Not a metric (no ``read``).

A traced run hands its readers the counters of the part of the window
before the profiler starts, so what these readers see ran with no profiler
at all. A program without the tallies (the commit before they were added)
has a count of 0 for every span, and every reader returns None."""

SECONDS = "ftl_span_seconds_total"
COUNT = "ftl_spans_total"


def serve_tally(ctx):
    """(seconds, count): two functions of a span name, its time in s and
    its number over the window; 0 for a span the window did not see."""
    counters = (ctx.get("serve") or {}).get("program_counters") or {}

    def seconds(name: str) -> float:
        return counters.get(f"{SECONDS}{{span={name}}}", 0.0)

    def count(name: str) -> float:
        return counters.get(f"{COUNT}{{span={name}}}", 0.0)

    return seconds, count
