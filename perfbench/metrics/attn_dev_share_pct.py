"""Share of the device's busy time in the traced training steps that the
flash attention custom calls (forward and backward) take."""

from perfbench.metrics._kernels import kernel_seconds, FLASH


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("train") or not trace.get("busy_s"):
        return None
    seconds, _ = kernel_seconds(trace, FLASH)
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
