"""Throughput / MFU meters (reference has none — SURVEY.md §5.5 notes the gap;
the only reference metric is the loss line at train.py:115-116)."""

import time
from typing import List, Optional, Tuple


class Throughput:
    """Steady-state tokens/sec and step-time tracker (excludes warmup steps).

    ``reset(tag=...)`` restarts the warmup-exclusion window and tags the
    next measured window; the trainer calls it on ``ckpt_restore`` so the
    first post-resume tokens/s figure (a) excludes the restore/recompile
    wall from its denominator instead of mixing it into "steady state", and
    (b) carries a ``window='post_resume'`` label in the emitted metric so
    dashboards don't read the transient as a regression.
    """

    def __init__(self, tokens_per_step: int, warmup_steps: int = 2):
        self.tokens_per_step = tokens_per_step
        self.warmup_steps = warmup_steps
        self.window_tag: Optional[str] = None
        self._seen = 0
        self._t0 = None
        self._steps = 0

    def reset(self, tag: Optional[str] = None) -> None:
        """Restart the meter (fresh warmup window); ``tag`` labels the new
        window until :meth:`clear_tag`."""
        self._seen = 0
        self._t0 = None
        self._steps = 0
        self.window_tag = tag

    def clear_tag(self) -> None:
        self.window_tag = None

    def step(self) -> None:
        self._seen += 1
        if self._seen == self.warmup_steps:
            self._t0 = time.perf_counter()
        elif self._seen > self.warmup_steps:
            self._steps += 1

    @property
    def steps_per_sec(self) -> float:
        if not self._steps or self._t0 is None:
            return 0.0
        return self._steps / (time.perf_counter() - self._t0)

    @property
    def tokens_per_sec(self) -> float:
        return self.steps_per_sec * self.tokens_per_step


def transformer_flops_per_token(n_params: int, seq_len: int, dim: int,
                                n_layers: int, causal: bool = False) -> float:
    """Model FLOPs per token, fwd+bwd: 6N matmul FLOPs plus attention
    score/value FLOPs — 12*L*S*d per token dense, halved under a causal
    mask (the kernels only compute the lower triangle). ``n_params``
    should EXCLUDE the input-embedding table when the embedding is a
    gather (no matmul FLOPs); the LM head does real matmuls and counts.
    This causal-masked, embed-excluded convention is the one behind every
    MFU figure in BASELINE.md."""
    attn = 12.0 * n_layers * dim * seq_len
    return 6.0 * n_params + (attn / 2.0 if causal else attn)


def mfu(tokens_per_sec: float, flops_per_token: float, peak_flops: float) -> float:
    return tokens_per_sec * flops_per_token / peak_flops


# Peak dense bf16 FLOP/s of ONE chip, keyed by ``Device.device_kind`` as
# JAX reports it (every listed generation is one JAX device per chip).
# Source: Google Cloud TPU documentation, the "System architecture" page of
# each version ("TPU v4", "TPU v5p", "TPU v5e", "TPU v6e"). A TPU that is
# not listed is an error where MFU is asked for — never a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5": 459e12,       # v5p
    "TPU v5 lite": 197e12,  # v5e
    "TPU v6 lite": 918e12,  # v6e
}


def device_peak_flops() -> Optional[float]:
    """Per-chip peak bf16 FLOP/s for MFU, from :data:`PEAK_BF16_FLOPS` by
    the first device's ``device_kind``. None off-TPU (a CPU 'MFU' against
    a TPU peak is noise); an unlisted TPU kind raises."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind "
            f"{device.device_kind!r}; add it to PEAK_BF16_FLOPS "
            f"(utils/metrics.py) with its source") from None


def per_device_memory_stats() -> List[Tuple[str, Optional[int], Optional[int]]]:
    """``(device id string, bytes_in_use, bytes_limit)`` for every LOCAL
    device; empty where the backend exposes no memory_stats (CPU). Feeds
    the per-device HBM gauges in the metric
    registry — under pipeline/tensor sharding the devices are NOT
    symmetric (stage 0 holds the embedding, the last stage the LM head),
    and the loudest device is the one that OOMs."""
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return []
    out = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            continue
        used = stats.get("bytes_in_use")
        limit = (stats.get("bytes_limit")
                 or stats.get("bytes_reservable_limit"))
        if used is None:
            continue
        out.append((str(getattr(d, "id", len(out))), used, limit))
    return out


def device_memory_report() -> str:
    """One line of every local device's ``bytes_in_use`` and
    ``peak_bytes_in_use`` (the allocator's high-water mark for the whole
    process), or '' without backend memory stats. Logged at teardown with
    the train state still resident, so it shows whether the state is
    spread over the mesh. On the v5e runtime the peak counts buffers only,
    not an executable's scratch (PERF.md §7), so it is NOT the step's peak
    HBM."""
    import jax

    parts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            parts.append(
                f"dev {d.id}: in use {stats['bytes_in_use'] / 1e9:.2f} GB, "
                f"peak {stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")
    return " | ".join(parts)


def device_memory_stats():
    """(bytes_in_use, bytes_limit) of the most-loaded local device —
    max-over-devices, the binding constraint under pipeline/tensor sharding
    where per-device footprints differ (device 0 alone underestimates the
    OOM risk by up to a stage's worth of params). (None, None) where the
    backend exposes no memory_stats."""
    stats = per_device_memory_stats()
    if not stats:
        return None, None
    _, used, limit = max(stats, key=lambda s: s[1])
    return used, limit


def hbm_usage_str() -> str:
    """'x.x/y.y GB' for the most-loaded device, or '' without backend
    memory stats."""
    used, limit = device_memory_stats()
    if used is None:
        return ""
    s = f"{used / 1e9:.1f}"
    return f"{s}/{limit / 1e9:.1f} GB" if limit else f"{s} GB"
