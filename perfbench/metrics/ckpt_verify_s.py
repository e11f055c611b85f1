"""The integrity gate's part of a restore: the flight recorder's
``ckpt_verify`` record (CRC scan of the step directory before Orbax reads
it) of the resumed children, mean. ``restore_s`` holds it."""

from perfbench.lib import recovery
from perfbench.metrics import _program_trace as pt


def read(ctx):
    return pt.mean_over_resumed(
        ctx, lambda events: (recovery.first_of(events, "ckpt_verify")
                             or {}).get("dur"))
