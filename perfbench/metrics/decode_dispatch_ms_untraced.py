"""Mean host time of the program's ``ftl:engine.decode.dispatch`` spans in
the untraced part of the window, from its span tallies (``_span_tally``):
the call into the compiled decode program until it returns. Its traced
twin is ``decode_dispatch_ms_p50``, the median of the same spans under the
profiler. None where the window has no decode dispatch."""

from perfbench.metrics import _span_tally

SPAN = "ftl:engine.decode.dispatch"


def read(ctx):
    seconds, count = _span_tally.serve_tally(ctx)
    calls = count(SPAN)
    return seconds(SPAN) / calls * 1e3 if calls else None
