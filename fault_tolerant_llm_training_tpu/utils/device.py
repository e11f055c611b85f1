"""Device-property queries backing the kernel/dispatch budgets.

The dispatch budgets were calibrated on the 16 GB v5e and derive from the
runtime's device properties, so a v5p/v6e engages the fused head+CE at its
own footprint:

- ``device_hbm_bytes`` — per-device accelerator memory, from
  ``Device.memory_stats()['bytes_limit']`` (consumers: ops/fused_ce.py
  ``auto_min_bytes``).
- VMEM: ops/flash_attention.py ``vmem_capacity_bytes`` reads jax's chip
  table (``pltpu.get_tpu_info()``, keyed on the device kind) and sizes the
  fused backward's ``vmem_limit_bytes`` request from it, so XLA's default
  scoped limit (``--xla_tpu_scoped_vmem_limit_kib``) does not bound which
  backward runs and no environment variable steers it.
- ``describe_device`` — the ``platform | kind | count`` triple every entry
  point logs at start-up, so a run that landed on the wrong backend says so
  in its own log.
"""

import functools


@functools.lru_cache(maxsize=None)
def device_hbm_bytes(default: int = 16 * 2**30) -> int:
    """Per-device accelerator memory in bytes.

    Reads ``bytes_limit`` from the first local device's ``memory_stats()``
    (the allocator's usable budget — slightly under the marketing HBM
    size, which is the number that matters for OOM dispatch decisions).
    A TPU backend that reports no limit is an error — guessing 16 GB there
    would silently mis-size every budget on another generation. ``default``
    (v5e's 16 GB, the calibration platform) is for backends that expose no
    stats at all: the CPU the tests run on."""
    import jax

    device = jax.local_devices()[0]
    limit = int((device.memory_stats() or {}).get("bytes_limit", 0))
    if limit > 0:
        return limit
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} reports no memory_stats()['bytes_limit']; "
            f"refusing to assume {default} bytes of HBM")
    return default


def describe_device() -> str:
    """``platform P | kind K | count N`` as JAX reports the backend."""
    import jax

    devices = jax.devices()
    return (f"platform {devices[0].platform} | kind "
            f"{devices[0].device_kind} | count {len(devices)}")
