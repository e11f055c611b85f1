"""Where a serving window's wall time went, for a run's notes (the driver
ignores them; the builder who asks why two runs of one cell differ reads
them): the scheduler's steps by what they held, the few longest waits with
their place in the window, and the garbage collector's pauses. Nothing here
enters a metric; everything is worked out after the window has closed from
the harness's own spans, but ``GcWatch``, a callback of two clock readings
a collection.
"""

import gc
import time

from . import stats


class GcWatch:
    """(start, seconds, generation) of every collection between ``start``
    and ``stop``, by ``gc.callbacks``."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info["generation"]))
            self._t = None

    def start(self):
        gc.callbacks.append(self)

    def stop(self):
        if self in gc.callbacks:
            gc.callbacks.remove(self)


def _ms(x):
    return None if x is None else round(x * 1e3, 3)


def _longest(rows, t_open, n=5):
    """[[seconds after the open, ms], ...] of the ``n`` longest."""
    top = sorted(rows, key=lambda r: r[1] - r[0], reverse=True)[:n]
    return [[round(r[0] - t_open, 2), _ms(r[1] - r[0])] for r in top]


def of(records: dict, t_open: float, t_close: float, pauses=()) -> dict:
    """``records``: the harness's spans, name -> [(start, end, extra)]."""
    def inside(name):
        return [r for r in records.get(name, ()) if t_open <= r[0] < t_close]

    steps, decodes, prefills = (inside(n) for n in
                                ("sched_step", "decode", "prefill"))

    def dur(rows):
        return [r[1] - r[0] for r in rows]

    inner = sorted(decodes + prefills, key=lambda r: r[0])
    own, j = [], 0
    for s in steps:
        held = 0.0
        while j < len(inner) and inner[j][0] < s[1]:
            if inner[j][0] >= s[0]:
                held += inner[j][1] - inner[j][0]
            j += 1
        own.append((s[0], s[1] - held))
    d = dur(decodes)
    in_window = [p for p in pauses if t_open <= p[0] <= t_close]
    return {
        "steps": len(steps),
        "steps_s": sum(dur(steps)),
        "outside_steps_s": (t_close - t_open) - sum(dur(steps)),
        "decode_rounds": len(d),
        "decode_s": sum(d),
        "decode_ms_p50": _ms(stats.percentile(d, 50)),
        "decode_ms_p99": _ms(stats.percentile(d, 99)),
        "decode_ms_mean": _ms(sum(d) / len(d)) if d else None,
        "decode_longest": _longest(decodes, t_open),
        "prefills": len(prefills),
        "prefill_s": sum(dur(prefills)),
        "prefill_longest": _longest(prefills, t_open, 3),
        "sched_own_s": sum(dur(own)),
        "sched_own_ms_p50": _ms(stats.percentile(dur(own), 50)),
        "sched_own_longest": _longest(own, t_open),
        "gc_collections": len(in_window),
        "gc_s": sum(p[1] for p in in_window),
        "gc_longest": [[round(p[0] - t_open, 2), _ms(p[1]), p[2]]
                       for p in sorted(in_window, key=lambda p: -p[1])[:5]],
    }
