"""The roofline: the least time the chip could take for a count of
operations and bytes. The counts themselves are the model family's
(``perfbench/families/<family>.py``: FLOPs and bytes the *algorithm*
needs, from shapes alone)."""


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"],
               bytes_ / peaks["hbm_bytes_per_s"])
