"""The resume as the program itself stamps it: the flight recorder's
``backend_ready`` (where ``Device |`` is logged) to ``first_step_done`` (the
first step whose metrics were read back) in each resumed child's event
file, mean of the cycles."""

from perfbench.lib import recovery
from perfbench.metrics import _program_trace as pt


def inside(events):
    ready = recovery.first_of(events, "backend_ready")
    done = recovery.first_of(events, "first_step_done")
    return done["t"] - ready["t"] if ready and done else None


def read(ctx):
    return pt.mean_over_resumed(ctx, inside)
