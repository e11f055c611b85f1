"""Profiler capture and the program's one vocabulary of trace names.

**Names.** Every host span and every device scope the program opens is
named here, once: :data:`SPANS` (``ftl:`` host spans, written into the
profiler's own trace by :func:`span`, so they share a clock with the device
ops) and :data:`SCOPES` (``jax.named_scope`` names on device work, opened
by :func:`scope`; they land in every op's ``op_name``, which the TPU
profiler keeps as the ``tf_op`` stat of the op). Both refuse a name that is
not in their table, and tests/test_trace_names.py holds the tables, the
call sites and PERF.md §3 to each other. Per-request spans are another
record (``obs/reqtrace.py``), lifecycle events a third (``obs/events.py``).

**Span tallies.** Every :func:`span` is also timed on the host's monotonic
clock on every run, traced or not, into two registry counters labelled by
span name: ``ftl_span_seconds_total{span=...}`` and
``ftl_spans_total{span=...}``. They are how the host's time is read where
no profiler runs (``/metrics``; the benchmark's untraced readers). Inside
a capture they describe the same spans as the ``TraceAnnotation`` events;
only those place a span on the device trace's timeline.

**Capture.** ``--profile-dir`` alone traces the whole run — fine for a
5-step probe, useless for "step 400 regressed": a multi-hour trace is
unloadably large. The window form (``--trace-steps A:B``) arms the profiler
at step A and disarms it after step B (inclusive), each captured step
wrapped in a ``StepTraceAnnotation`` so XenseCope/TensorBoard group device
ops per step. scripts/profile_step.py used to do this ad hoc with its own
start/stop + parser; both now live here (:func:`capture`,
:func:`parse_trace`) so the CLI window, the script, and the tests share one
implementation.

:class:`AutoTraceWindow` (``--auto-trace``) is the reactive form: instead
of a pre-chosen window it arms itself, once per run, when a step's wall
time regresses past a multiple of the rolling median — capturing the
slowdown the operator didn't know to schedule a window for.

Every capture the program starts passes :func:`profile_options`: the host
tracer at level 2 (the ``ftl:`` spans and the runtime's own events land)
and the Python tracer OFF. Left at this jax's default (level 1) it records
every Python call, and the host code under it runs several times slower: a
loop of 20,000 small Python calls and 2,000 NumPy item writes took 4.4x
its untraced time under a default capture on a CPU host, and no more than
its untraced time with the Python tracer off. The scheduler's step is such
code. Nothing reads the Python tracer's call events; the spans carry the
host's structure.
"""

import collections
import contextlib
import glob
import gzip
import json
import re
import statistics
import threading
import time
from typing import Callable, Optional, Tuple

import jax

from .registry import REGISTRY, ReadCounter

# ------------------------------------------------------------------- names
# name -> (layer as PERF.md §3 lists it, what the span covers). A span's
# children are the spans opened inside it on the same thread; what no child
# covers is its self time.
SPANS = {
    "ftl:sched.step": (
        "scheduler", "one Scheduler.step iteration (args active, queued); "
        "self time = gauges, adapter sync"),
    "ftl:sched.admit": (
        "scheduler", "admission: queue -> slots, block allocation, prefix "
        "cache, sequential prefill calls"),
    "ftl:sched.prefill_round": (
        "scheduler", "one packed prefill round of the pending prompts"),
    "ftl:sched.pack": (
        "scheduler", "building the per-slot host arrays of a decode round"),
    "ftl:sched.bank": (
        "scheduler", "committing a round's tokens to requests, finishing "
        "requests"),
    "ftl:engine.decode": (
        "engine", "decode_step / decode_burst / spec_round / "
        "spec_tree_round (args slots_active, live_tokens, n)"),
    "ftl:engine.decode.dispatch": (
        "engine", "the call into the compiled decode program(s) returns"),
    "ftl:engine.decode.sync": (
        "engine", "host blocked on the device for the round's tokens"),
    "ftl:engine.decode.stats": (
        "engine", "opened after the read-back, inside the round's span, by "
        "a model whose programs return counts beside the tokens (args "
        "moe_pairs, moe_touched, index_keys, latent_rows, window_rows)"),
    "ftl:engine.prefill": (
        "engine", "prefill / prefill_packed (args new_tokens, start_pos, "
        "bucket; prefill also held = 1 when it resumed over the slot's "
        "held window rings, rebuilt_rows = the rows before start_pos it "
        "recomputed to rebuild them)"),
    "ftl:engine.prefill.dispatch": (
        "engine", "the calls into the compiled prefill chunk programs "
        "return"),
    "ftl:engine.prefill.sync": (
        "engine", "host blocked on the device for the first token"),
    "ftl:engine.prefill.stats": (
        "engine", "as ftl:engine.decode.stats, summed over the call's "
        "chunks (same args)"),
    "ftl:train.step": (
        "trainer", "one iteration of Trainer.run's step loop (arg step)"),
    "ftl:train.signal_check": (
        "trainer", "signal flag / cluster agreement at the step boundary"),
    "ftl:train.fetch": (
        "trainer", "waiting for the prefetcher's next batch (what "
        "ftl_data_stall_seconds_total counts)"),
    "ftl:train.dispatch": (
        "trainer", "the call into the compiled train step returns"),
    "ftl:train.consume": (
        "trainer", "blocking read of an older step's packed metrics"),
    "ftl:data.prefetch": (
        "data", "one batch's host work on the prefetcher's thread: loader, "
        "collate, device_put"),
}

# name -> (layer, what runs under it), in the ORDER a reader gives an op to
# a bucket: the first name that is a component of the op's ``op_name``
# wins, so the narrower scopes (opened by this program with :func:`scope`)
# come before the flax module names they sit inside. The last seven are
# flax's own module names, written into ``op_name`` by flax: they are
# listed, never opened here, and never renamed (parameter tree, every
# checkpoint).
SCOPES = {
    "kv_write": ("kernels, serve", "new K/V rows scattered into the cache "
                 "(paged pool or slot ring, int8 quantize included)"),
    "kv_read": ("kernels, serve", "attention over cached K/V: block "
                "gather + masked attention, or the in-place Pallas "
                "kernels"),
    "rope": ("kernels", "rotary embedding of q and k outside a fused "
             "kernel"),
    "sample": ("kernels, serve", "token sampling epilogue over the "
               "logits"),
    "loss_head": ("kernels, train", "lm-head matmul of the training "
                  "forward and the cross-entropy"),
    "grad_clip": ("kernels, train", "global gradient norm and clip"),
    "optimizer": ("kernels, train", "optax update and parameter apply"),
    "moe_route": ("kernels", "expert layer: router matmul, sigmoid, "
                  "top-k, grouping of (token, expert) pairs by held expert"),
    "moe_experts": ("kernels", "expert layer: the grouped matmuls "
                    "over the held experts and the weighted combine (the "
                    "compiler's ragged-dot calls carry no scope: readers "
                    "tell them by name)"),
    "moe_shared": ("kernels", "expert layer: the shared expert"),
    "index_select": ("kernels, serve", "indexer of a full latent layer: "
                     "its query projections, scores over the cached index "
                     "keys, top-k (decode) or k-th-largest mask (chunk)"),
    "feed_forward": ("kernels", "flax module: the MLP block"),
    "attention": ("kernels", "flax module: projections + attention "
                  "(flash kernels are named attention.N by it)"),
    "tok_embeddings": ("kernels", "flax module: token embedding"),
    "output": ("kernels, serve", "flax module: lm-head matmul outside "
               "loss_head (serving)"),
    "attention_norm": ("kernels", "flax module: RMSNorm before attention"),
    "ffn_norm": ("kernels", "flax module: RMSNorm before the MLP"),
    "norm": ("kernels", "flax module: final RMSNorm"),
}
# the scopes this program opens itself (the rest of SCOPES are flax's)
OPENED_SCOPES = ("kv_write", "kv_read", "rope", "sample", "loss_head",
                 "grad_clip", "optimizer", "moe_route", "moe_experts",
                 "moe_shared", "index_select")
_OPENED_HERE = OPENED_SCOPES  # the name the accepted benchmark imports


def tracing() -> bool:
    """True while a profiler session is collecting host spans."""
    return jax.profiler.TraceAnnotation.is_enabled()


# ------------------------------------------------------------ span tallies
# Each thread sums its spans into a table of its own, {name: [ns, spans]},
# written by that thread alone, so the hot path takes no lock; a counter's
# value sums the tables. A thread's table outlives it: a counter never goes
# back.
SPAN_SECONDS = "ftl_span_seconds_total"
SPAN_COUNT = "ftl_spans_total"
_now = time.perf_counter_ns
_local = threading.local()
_tables = []


def _own_table() -> dict:
    table = _local.table = {name: [0, 0] for name in SPANS}
    _tables.append(table)
    return table


def _tally(name: str, field: int, scale: float):
    return lambda: scale * sum(t[name][field] for t in list(_tables))


_seconds = REGISTRY.counter(
    SPAN_SECONDS, "host seconds inside each ftl: span (obs/trace.py SPANS), "
    "on every run, traced or not")
_count = REGISTRY.counter(SPAN_COUNT, "ftl: spans closed, by span name")
for _name in SPANS:
    _seconds.adopt(ReadCounter(_tally(_name, 0, 1e-9)), span=_name)
    _count.adopt(ReadCounter(_tally(_name, 1, 1)), span=_name)
del _name


class _Span:
    """What :func:`span` returns: timed on every run from ``t0`` to its
    exit, and counted on any exit, exceptions included. ``ann`` is its
    ``TraceAnnotation`` where a profiler runs, else None."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name: str, ann, t0: int):
        self._name = name
        self._ann = ann
        self._t0 = t0

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        elapsed = _now() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        try:
            cell = _local.table[self._name]
        except AttributeError:
            cell = _own_table()[self._name]
        cell[0] += elapsed
        cell[1] += 1
        return False


def span(name: str, **args):
    """Host span named from :data:`SPANS`: timed into
    ``ftl_span_seconds_total{span=name}`` and ``ftl_spans_total{span=name}``
    on every run, and written into the profiler's own trace as a
    ``TraceAnnotation`` while a profiler runs. ``args`` land as stats on
    that event; they are values the caller already holds. One that costs
    something to produce is passed as a zero-argument callable, called only
    while a profiler runs."""
    if name not in SPANS:
        raise ValueError(f"span {name!r} is not in obs.trace.SPANS")
    if not tracing():
        return _Span(name, None, _now())
    args = {k: v() if callable(v) else v for k, v in args.items()}
    # the clock is read before the annotation is made and when the block
    # exits, before the annotation stops: the profiler reads its own clock
    # late in both calls, so the two records of a span agree
    t0 = _now()
    return _Span(name, jax.profiler.TraceAnnotation(name, **args), t0)


def scope(name: str):
    """``jax.named_scope`` with a name from :data:`SCOPES` (only those this
    program opens itself; flax writes its module names)."""
    # the alias is what the accepted benchmark's checks extend when they
    # rehearse a program whose table gained a scope: read it, not a copy
    if name not in _OPENED_HERE:
        raise ValueError(f"scope {name!r} is not one obs.trace.SCOPES "
                         f"lets the program open")
    return jax.named_scope(name)



def profile_options():
    """Options for every profiler capture the program starts (the module
    docstring says why the Python tracer is off)."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    return opts


def parse_window(spec: str) -> Tuple[int, int]:
    """``"A:B"`` → (A, B) inclusive; ``"N"`` → (N, N). Raises ValueError on
    malformed or empty windows — a silently-ignored trace flag is worse
    than a failed launch."""
    parts = spec.split(":")
    try:
        if len(parts) == 1:
            a = b = int(parts[0])
        elif len(parts) == 2:
            a, b = int(parts[0]), int(parts[1])
        else:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"--trace-steps expects 'A:B' or 'N', got {spec!r}") from None
    if a < 0 or b < a:
        raise ValueError(f"--trace-steps window {spec!r} is empty "
                         f"(need 0 <= A <= B)")
    return a, b


class TraceWindow:
    """Arms ``jax.profiler`` for steps in [start, stop] (inclusive).

    The loop calls :meth:`on_step_start` before dispatching each step and
    :meth:`on_step_end` after the step counter advances; :meth:`annotate`
    wraps the dispatch in a ``StepTraceAnnotation``. ``drain`` (passed by
    the trainer) runs before ``stop_trace`` so the asynchronously
    dispatched device work of the window's final steps lands inside the
    capture instead of after it.
    """

    def __init__(self, spec: str, trace_dir: str,
                 drain: Optional[callable] = None):
        self.start_step, self.stop_step = parse_window(spec)
        self.trace_dir = trace_dir
        self.drain = drain
        self.active = False
        self.done = False

    def on_step_start(self, step: int) -> None:
        if (not self.active and not self.done
                and self.start_step <= step <= self.stop_step):
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
            self.active = True

    def annotate(self, step: int):
        if not self.active:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation("train", step_num=step)

    def on_step_end(self, step: int) -> None:
        if self.active and step >= self.stop_step:
            if self.drain is not None:
                self.drain()
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def close(self) -> None:
        """Stop a still-armed trace (loop exited inside the window)."""
        if self.active:
            try:
                if self.drain is not None:
                    self.drain()
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.active = False
            self.done = True


class AutoTraceWindow:
    """Self-arming profiler window on step-time regression (``--auto-trace``).

    ``--trace-steps`` needs the operator to know WHICH steps regressed —
    useless for the transient cliffs (a thermal-throttled chip, a slow
    storage burst, a noisy neighbor) that make long runs mysteriously
    slow after the fact. This watcher keeps a rolling window of recent
    step wall times and, when one step exceeds ``threshold`` times the
    rolling MEDIAN (robust against the very outliers it hunts), arms a
    bounded ``jax.profiler`` capture for the next ``capture_steps`` steps.
    It fires at most ONCE per run — the point is a post-mortem artifact,
    not a profiler left hot — and the trainer audits the arm
    (``[TRACE]``) so the receipt says exactly which step tripped it and
    where the trace landed.

    ``profiler_start``/``profiler_stop`` are injectable for tests; the
    defaults call ``jax.profiler`` lazily like :class:`TraceWindow`.
    """

    def __init__(self, trace_dir: str, threshold: float = 2.0,
                 history: int = 32, min_samples: int = 8,
                 capture_steps: int = 4,
                 profiler_start: Optional[Callable[[str], None]] = None,
                 profiler_stop: Optional[Callable[[], None]] = None):
        if threshold <= 1.0:
            raise ValueError(f"threshold must be > 1, got {threshold}")
        self.trace_dir = trace_dir
        self.threshold = float(threshold)
        self.min_samples = int(min_samples)
        self.capture_steps = int(capture_steps)
        self._times = collections.deque(maxlen=int(history))
        self._start = profiler_start
        self._stop = profiler_stop
        self.active = False
        self.done = False
        self.trigger_step: Optional[int] = None
        self.ratio = 0.0
        self._captured = 0

    def _profiler_start(self) -> None:
        if self._start is not None:
            self._start(self.trace_dir)
            return
        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=profile_options())

    def _profiler_stop(self) -> None:
        if self._stop is not None:
            self._stop()
            return
        jax.profiler.stop_trace()

    def observe(self, step: int, seconds: float) -> Optional[float]:
        """Feed one finished step's wall time. Returns the regression
        ratio when THIS sample arms the capture, else None (the trainer
        audits on a non-None return)."""
        if self.active:
            self._captured += 1
            if self._captured >= self.capture_steps:
                self._profiler_stop()
                self.active = False
                self.done = True
            return None
        if self.done:
            return None
        if len(self._times) >= self.min_samples:
            med = statistics.median(self._times)
            if med > 0 and seconds > self.threshold * med:
                self.ratio = seconds / med
                self.trigger_step = int(step)
                self._profiler_start()
                self.active = True
                return self.ratio
        self._times.append(float(seconds))
        return None

    def close(self) -> None:
        """Stop a still-armed capture (loop exited inside the window)."""
        if self.active:
            try:
                self._profiler_stop()
            except Exception:
                pass
            self.active = False
            self.done = True


@contextlib.contextmanager
def capture(trace_dir: str):
    """Whole-scope trace (scripts/profile_step.py's form)."""
    jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def parse_trace(trace_dir: str, steps: int):
    """Aggregate device-side op durations from the newest Chrome-trace JSON
    under ``trace_dir``. Returns (per-category ms/step dict, total
    ms/step). This is how the kernel/copy/fusion breakdown in BASELINE.md
    was measured."""
    files = sorted(glob.glob(f"{trace_dir}/**/*.trace.json.gz",
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    with gzip.open(files[-1]) as fh:
        data = json.load(fh)
    pids = {e["pid"]: e["args"].get("name", "")
            for e in data["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    cat = collections.Counter()
    for e in data["traceEvents"]:
        if e.get("ph") != "X":
            continue
        pname = pids.get(e["pid"], "")
        if "TPU" not in pname and "device" not in pname.lower():
            continue
        n = e["name"]
        # skip the whole-program span and the per-execution lane aggregates
        if n.startswith("jit_") or n.isdigit():
            continue
        cat[re.sub(r"\.\d+$", "", n)] += e.get("dur", 0)
    total = sum(cat.values())
    return ({k: v / steps / 1000 for k, v in cat.items()},
            total / steps / 1000)
