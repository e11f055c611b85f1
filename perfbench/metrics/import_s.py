"""What a process pays before it can touch the backend: the flight recorder's
``imports_done`` record (interpreter start to the entry module imported) of
the resumed children, mean."""

from perfbench.lib import recovery
from perfbench.metrics import _program_trace as pt


def read(ctx):
    return pt.mean_over_resumed(
        ctx, lambda events: (recovery.first_of(events, "imports_done")
                             or {}).get("dur"))
