"""Metric registry: counters, gauges, histograms → Prometheus text format.

Stdlib-only (the container must not need prometheus_client): a
:class:`MetricRegistry` holds named metric families, each family holds
labeled children, and :meth:`MetricRegistry.render` emits the Prometheus
text exposition format (version 0.0.4) that ``obs/prometheus.py`` serves at
``/metrics``. The ad-hoc meters in utils/metrics.py (Throughput, HBM
queries) remain the *measurement* layer; this module is the *export* layer
the training loop and the serving scheduler publish into.

Thread safety: one lock per registry guards family creation; each metric's
mutations are single-writer in practice (the training/serve driver thread)
but use atomic ops cheap enough to leave safe anyway.
"""

import bisect
import math
import threading
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

# Default duration buckets: spans 5 ms decode iterations to the 120 s USR1
# checkpoint lead the whole framework is built around.
DURATION_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

# Integer-count buckets for the speculative-decoding tokens-per-verify-round
# histogram (scheduler.py ftl_spec_tokens_per_round): a round emits between
# 1 (first proposal rejected) and spec_k + 1 (full accept + bonus) tokens,
# and spec_k rarely exceeds 8 — 1..16 covers it with exact per-count bins.
SPEC_TOKEN_BUCKETS = tuple(float(i) for i in range(1, 17))

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, str]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def escape_label_value(v: str) -> str:
    """Prometheus text-format label-value escaping (exposition format
    0.0.4): backslash, double-quote and newline must be escaped or a
    value like ``reason="bad \"token\""`` corrupts the whole scrape."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def escape_help(text: str) -> str:
    """HELP-line escaping: backslash and newline (quotes are legal)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(key: _LabelKey, extra: str = "") -> str:
    parts = [f'{k}="{escape_label_value(v)}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt_value(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class Counter:
    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter increment must be >= 0, got {amount}")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class ReadCounter(Counter):
    """A counter that keeps no value of its own: ``.value`` calls ``read``,
    which sums what a hot path accumulates by itself, with no lock and no
    call into the registry (``obs/trace.py``'s span tallies). That path is
    the only writer: ``inc`` is refused."""

    def __init__(self, read: Callable[[], float]):
        self._read = read

    def inc(self, amount: float = 1.0) -> None:
        raise TypeError("a ReadCounter is advanced by its source only")

    @property
    def value(self) -> float:
        return float(self._read())


class Gauge:
    def __init__(self):
        self._value = 0.0

    def set(self, value: float) -> None:
        self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self._value -= amount

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    def __init__(self, buckets: Sequence[float] = DURATION_BUCKETS):
        self.bounds = tuple(sorted(float(b) for b in buckets))
        self.counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self.counts[bisect.bisect_left(self.bounds, v)] += 1
            self.sum += v
            self.count += 1

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile (upper bound of the bucket holding the
        q-th observation) — coarse but dependency-free, for log lines."""
        if not self.count:
            return 0.0
        rank = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank and c:
                return (self.bounds[i] if i < len(self.bounds)
                        else self.bounds[-1])
        return self.bounds[-1]


class _Family:
    """One named metric family; labeled children created on demand. The
    family itself doubles as the unlabeled child (``registry.counter(n)
    .inc()`` and ``registry.counter(n).labels(x='y').inc()`` both work)."""

    def __init__(self, kind: str, name: str, help_text: str,
                 buckets: Optional[Sequence[float]] = None):
        self.kind = kind
        self.name = name
        self.help_text = help_text
        self.buckets = buckets
        self._children: Dict[_LabelKey, object] = {}
        self._lock = threading.Lock()

    def _make(self):
        if self.kind == "counter":
            return Counter()
        if self.kind == "gauge":
            return Gauge()
        return Histogram(self.buckets or DURATION_BUCKETS)

    def labels(self, **labels):
        key = _label_key(labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make()
            return child

    def adopt(self, child, **labels):
        """Install ``child`` (a :class:`ReadCounter`, say) as the series of
        ``labels``; a series that exists already is kept and returned."""
        key = _label_key(labels)
        with self._lock:
            return self._children.setdefault(key, child)

    # -- unlabeled convenience (delegates to the () child) --
    def inc(self, amount: float = 1.0) -> None:
        self.labels().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self.labels().dec(amount)

    def set(self, value: float) -> None:
        self.labels().set(value)

    def observe(self, value: float) -> None:
        self.labels().observe(value)

    @property
    def value(self) -> float:
        return self.labels().value

    def children(self) -> Iterable[Tuple[_LabelKey, object]]:
        with self._lock:
            return list(self._children.items())

    def render(self) -> str:
        lines = [f"# HELP {self.name} {escape_help(self.help_text)}",
                 f"# TYPE {self.name} {self.kind}"]
        for key, child in sorted(self.children()):
            if self.kind == "histogram":
                acc = 0
                for bound, c in zip(child.bounds, child.counts):
                    acc += c
                    le = 'le="%s"' % _fmt_value(bound)
                    lines.append(f"{self.name}_bucket"
                                 f"{_fmt_labels(key, le)} {acc}")
                inf = 'le="+Inf"'
                lines.append(f"{self.name}_bucket"
                             f"{_fmt_labels(key, inf)} {child.count}")
                lines.append(f"{self.name}_sum{_fmt_labels(key)}"
                             f" {_fmt_value(child.sum)}")
                lines.append(f"{self.name}_count{_fmt_labels(key)}"
                             f" {child.count}")
                # Summary-style quantile snapshots next to the buckets:
                # operators read p50/p95/p99 off one scrape instead of
                # integrating _bucket lines by hand. Bucket-resolution
                # (Histogram.quantile), good enough for SLO eyeballing.
                for q in (0.5, 0.95, 0.99):
                    quant = 'quantile="%s"' % _fmt_value(q)
                    lines.append(
                        f"{self.name}{_fmt_labels(key, quant)}"
                        f" {_fmt_value(child.quantile(q))}")
            else:
                lines.append(f"{self.name}{_fmt_labels(key)}"
                             f" {_fmt_value(child.value)}")
        return "\n".join(lines)


class MetricRegistry:
    def __init__(self):
        self._families: Dict[str, _Family] = {}
        self._lock = threading.Lock()

    def _family(self, kind: str, name: str, help_text: str,
                buckets: Optional[Sequence[float]] = None) -> _Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(kind, name, help_text,
                                                     buckets)
            elif fam.kind != kind:
                raise ValueError(
                    f"metric {name} already registered as {fam.kind}, "
                    f"requested {kind}")
            return fam

    def counter(self, name: str, help_text: str = "") -> _Family:
        return self._family("counter", name, help_text)

    def gauge(self, name: str, help_text: str = "") -> _Family:
        return self._family("gauge", name, help_text)

    def histogram(self, name: str, help_text: str = "",
                  buckets: Optional[Sequence[float]] = None) -> _Family:
        return self._family("histogram", name, help_text, buckets)

    def render(self) -> str:
        """Prometheus text exposition format, trailing newline included."""
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        return "\n".join(f.render() for f in fams) + "\n"

    def snapshot(self) -> Dict[str, Dict]:
        """Plain-dict view for tests and log lines."""
        out: Dict[str, Dict] = {}
        with self._lock:
            fams = list(self._families.values())
        for fam in fams:
            entry: Dict = {"kind": fam.kind, "series": {}}
            for key, child in fam.children():
                label = ",".join(f"{k}={v}" for k, v in key)
                if fam.kind == "histogram":
                    entry["series"][label] = {"sum": child.sum,
                                              "count": child.count,
                                              "p50": child.quantile(0.5),
                                              "p95": child.quantile(0.95),
                                              "p99": child.quantile(0.99)}
                else:
                    entry["series"][label] = child.value
            out[fam.name] = entry
        return out


# Default registry: the one the training loop, the serving scheduler, and
# the /metrics endpoint share within a process.
REGISTRY = MetricRegistry()


def default_registry() -> MetricRegistry:
    return REGISTRY
