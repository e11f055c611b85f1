"""How the readers tell kernels apart in a device trace: by op name, as the
profiler writes it. Not a metric (no ``read``); shared by the kernel
readers so that one PR's rename is repaired in one place."""

import re

# Mosaic custom calls under the model's ``attention`` scope: in a train step
# these are the flash kernels of ops/flash_attention.py, forward and backward
# (``lib/trace_reduce.short_name`` marks Mosaic calls ``pallas:``)
FLASH = re.compile(r"^pallas:attention")


def kernel_seconds(trace: dict, pattern) -> tuple:
    """(device seconds, launches) of the ops whose name matches."""
    s = n = 0.0
    for name, o in trace.get("ops", {}).items():
        if pattern.search(name):
            s += o["s"]
            n += o["n"]
    return s, n
