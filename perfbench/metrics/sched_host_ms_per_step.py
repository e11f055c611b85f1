"""Host time the scheduler itself takes a step: mean self time of the
program's ``ftl:sched.step`` spans in the traced window, that is their
duration less the ``ftl:engine.*`` spans inside them (admission, packing the
slot arrays, gauges, banking tokens: what holds the chip between rounds)."""

from perfbench.metrics import _program_trace as pt


def read(ctx):
    summary = pt.summary_of(ctx) if ctx.get("serve") else None
    if not summary:
        return None
    own = pt.self_ms(summary["spans"], "ftl:sched.step", ("ftl:engine.",))
    return sum(own) / len(own) if own else None
