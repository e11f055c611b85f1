"""Every ``ftl:`` span is timed on every run, traced or not: its time and its
number reach ``ftl_span_seconds_total{span}`` and ``ftl_spans_total{span}``
in the registry (``obs/trace.py``), on any thread and on any exit, with no
profiler running. And every capture the program starts keeps the Python
tracer off (``obs/trace.py`` ``profile_options``).

Inside a capture the counters and the profiler's ``ftl:`` events are one
record: here for spans of a millisecond, and in tests/test_trace_names.py
for a tiny scheduler's own spans."""

import os
import sys
import threading
import time
from pathlib import Path

import pytest

from fault_tolerant_llm_training_tpu.obs import trace
from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

# perfbench/, the readers' own xplane decoder
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def tally() -> dict:
    """{span name: (seconds, count)} as the registry has them now."""
    snap = REGISTRY.snapshot()
    secs = snap[trace.SPAN_SECONDS]["series"]
    count = snap[trace.SPAN_COUNT]["series"]
    return {name: (secs[f"span={name}"], count[f"span={name}"])
            for name in trace.SPANS}


def change(before: dict, after: dict) -> dict:
    """The spans that moved: {name: (seconds, count)}."""
    out = {}
    for name, (s, n) in after.items():
        ds, dn = s - before[name][0], n - before[name][1]
        if dn or ds:
            out[name] = (ds, dn)
    return out


def test_every_span_name_has_both_series_after_import():
    snap = REGISTRY.snapshot()
    for family in (trace.SPAN_SECONDS, trace.SPAN_COUNT):
        assert snap[family]["kind"] == "counter"
        assert set(snap[family]["series"]) == {
            f"span={name}" for name in trace.SPANS}
    # the operator's view: both families at /metrics, one series a name
    text = REGISTRY.render()
    for name in trace.SPANS:
        assert f'ftl_spans_total{{span="{name}"}}' in text
        assert f'ftl_span_seconds_total{{span="{name}"}}' in text


def test_nested_spans_on_two_threads_are_each_counted():
    """The main thread nests a step around a pack; a prefetch-like thread
    opens its own spans, one of them a name the main thread also opens, at
    the same time. Each thread's spans are counted once, with their time;
    a parent covers its child."""
    barrier = threading.Barrier(2)

    def prefetch_like():
        barrier.wait()
        for _ in range(3):
            with trace.span("ftl:data.prefetch", batch=1):
                with trace.span("ftl:sched.pack"):
                    time.sleep(0.002)

    before = tally()
    worker = threading.Thread(target=prefetch_like)
    worker.start()
    barrier.wait()
    for _ in range(2):
        with trace.span("ftl:sched.step", active=1, queued=0):
            with trace.span("ftl:sched.pack"):
                time.sleep(0.004)
            time.sleep(0.001)
    worker.join()
    moved = change(before, tally())
    assert set(moved) == {"ftl:data.prefetch", "ftl:sched.step",
                          "ftl:sched.pack"}
    assert moved["ftl:data.prefetch"][1] == 3
    assert moved["ftl:sched.step"][1] == 2
    assert moved["ftl:sched.pack"][1] == 3 + 2
    assert moved["ftl:data.prefetch"][0] >= 3 * 0.002
    assert moved["ftl:sched.step"][0] >= 2 * (0.004 + 0.001)
    assert moved["ftl:sched.pack"][0] >= 3 * 0.002 + 2 * 0.004
    assert all(s < 60 for s, _ in moved.values())


def test_no_span_is_lost_when_many_threads_open_the_same_name():
    """More threads than cores open one name at once, the interpreter
    switching between them as often as it can: every span is counted (a
    shared read-modify-write would lose some)."""
    threads, each = 4 * (os.cpu_count() or 1) + 1, 2000
    switch = sys.getswitchinterval()
    before = tally()
    sys.setswitchinterval(1e-6)
    try:
        def opener():
            for _ in range(each):
                with trace.span("ftl:engine.decode.sync"):
                    pass

        workers = [threading.Thread(target=opener) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(switch)
    moved = change(before, tally())
    assert moved["ftl:engine.decode.sync"][1] == threads * each

def test_a_span_left_by_an_exception_is_counted_once():
    before = tally()
    with pytest.raises(KeyError):
        with trace.span("ftl:sched.bank"):
            time.sleep(0.001)
            raise KeyError("a request the bank does not know")
    moved = change(before, tally())
    assert set(moved) == {"ftl:sched.bank"}
    seconds, count = moved["ftl:sched.bank"]
    assert count == 1 and 0.001 <= seconds < 60


def test_a_name_outside_the_table_is_refused_and_counts_nothing():
    before = tally()
    with pytest.raises(ValueError, match="not in obs.trace.SPANS"):
        trace.span("ftl:sched.nap")
    assert change(before, tally()) == {}
    series = REGISTRY.snapshot()[trace.SPAN_COUNT]["series"]
    assert "span=ftl:sched.nap" not in series


def test_the_counters_are_read_only_views():
    child = REGISTRY.counter(trace.SPAN_COUNT).labels(span="ftl:sched.step")
    with pytest.raises(TypeError):
        child.inc()


def busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_inside_a_capture_the_counters_and_the_events_are_one_record(
        tmp_path):
    """Every name of the table, three spans of a millisecond each under a
    capture with the program's own options: each name's count is its
    number of events in the xplane and its time their summed durations
    within 2 %."""
    import jax

    from perfbench.lib import trace_reduce
    from perfbench.metrics import _program_trace

    before = tally()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace.profile_options())
    try:
        for name in trace.SPANS:
            for _ in range(3):
                with trace.span(name, n=1):
                    busy(0.001)
    finally:
        jax.profiler.stop_trace()
    moved = change(before, tally())
    spans = _program_trace.load_xplane(trace_reduce.newest_xplane(
        str(tmp_path)))["spans"]
    for name in trace.SPANS:
        events = [s for s in spans if s[0] == name]
        assert moved[name][1] == len(events) == 3, name
        assert all(int(s[4]["n"]) == 1 for s in events)
        traced = sum(s[2] - s[1] for s in events) / 1e9
        assert moved[name][0] == pytest.approx(traced, rel=0.02), name


# ---------------------------------------------------------- capture options
@pytest.fixture
def start_calls(monkeypatch):
    """Every ``jax.profiler.start_trace`` call, recorded and not run."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: calls.append((log_dir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    return calls


def test_every_capture_of_the_program_keeps_the_python_tracer_off(
        start_calls, tmp_path):
    """The window, the self-arming window and the whole-scope capture (the
    trainer's ``--profile-dir`` capture: tests/test_trace_names.py, whose
    trace holds no Python-call event)."""
    window = trace.TraceWindow("2:3", str(tmp_path))
    for step in range(5):
        window.on_step_start(step)
        window.on_step_end(step)
    auto = trace.AutoTraceWindow(str(tmp_path), threshold=2.0,
                                 min_samples=2, capture_steps=1)
    for step, seconds in enumerate((1.0, 1.0, 1.0, 5.0, 1.0, 1.0)):
        auto.observe(step, seconds)
    with trace.capture(str(tmp_path)):
        pass
    assert [d for d, _ in start_calls] == [str(tmp_path)] * 3
    for _, kw in start_calls:
        opts = kw["profiler_options"]
        assert (opts.python_tracer_level, opts.host_tracer_level) == (0, 2)

