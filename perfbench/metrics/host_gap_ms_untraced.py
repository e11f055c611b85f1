"""Host time a decode round during which the host was not waiting on the
device, in the untraced part of the window: (time of ``ftl:sched.step`` -
time of ``ftl:engine.decode.sync`` - time of ``ftl:engine.prefill.sync``) /
number of ``ftl:engine.decode``, from the program's span tallies
(``_span_tally``). The scheduler reads each round back before it
dispatches the next, so this bounds from above the device's idle time a
round; time between steps (an open loop waiting for arrivals) is outside
``ftl:sched.step`` and not counted. Its traced twin, as a share, is
``serve_dev_idle_pct``. None where the window has no step or no round."""

from perfbench.metrics import _span_tally


def read(ctx):
    seconds, count = _span_tally.serve_tally(ctx)
    rounds = count("ftl:engine.decode")
    if not rounds or not count("ftl:sched.step"):
        return None
    gap = (seconds("ftl:sched.step") - seconds("ftl:engine.decode.sync")
           - seconds("ftl:engine.prefill.sync"))
    return gap / rounds * 1e3
