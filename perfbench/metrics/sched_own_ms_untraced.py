"""The scheduler's own host time a step, in the untraced part of the window:
(time of ``ftl:sched.step`` - time of ``ftl:engine.decode`` - time of
``ftl:engine.prefill``) / number of ``ftl:sched.step``, from the program's
span tallies (``_span_tally``): admission bookkeeping, hashing, packing the
slot arrays, gauges, banking. Its traced twin is ``sched_host_ms_per_step``,
read from the profiler's spans, under the profiler. None where the window
has no step."""

from perfbench.metrics import _span_tally


def read(ctx):
    seconds, count = _span_tally.serve_tally(ctx)
    steps = count("ftl:sched.step")
    if not steps:
        return None
    own = (seconds("ftl:sched.step") - seconds("ftl:engine.decode")
           - seconds("ftl:engine.prefill"))
    return own / steps * 1e3
