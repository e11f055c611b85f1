"""The three readers of the program's span tallies (``ftl_span_seconds_total``
and ``ftl_spans_total``, ``obs/trace.py``) over the untraced part of a
serving window: ``sched_own_ms_untraced``, ``decode_dispatch_ms_untraced``
and ``host_gap_ms_untraced``. Their arithmetic on a made-up window; no
reading, and no error, where a program has no tallies (the parent of the PR
that added them, under this benchmark) or the window lacks a span a reader
divides by; the real registry's series through the door under the names
the readers look for; and the tiny serving cells of a CPU rehearsal report
all three."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import perfbench_checks as C  # noqa: E402
import perfbench_rehearsal as R  # noqa: E402

sys.path.insert(0, R.ROOT)
from perfbench.lib import manifest, program_records  # noqa: E402

READERS = ("sched_own_ms_untraced", "decode_dispatch_ms_untraced",
           "host_gap_ms_untraced")
SERVING = ("internlm2-1.8b.longdecode", "internlm2-1.8b.chat",
           "dots3-d5-ep8.sessions16k")


def door(**spans) -> dict:
    """``ctx`` whose door holds, for each ``name=(seconds, count)``, the
    two series of span ``ftl:<name with _ for .>``."""
    counters = {"decode_dispatches_total": 7.0}
    for key, (seconds, count) in spans.items():
        name = "ftl:" + key.replace("__", ".")
        counters[f"ftl_span_seconds_total{{span={name}}}"] = seconds
        counters[f"ftl_spans_total{{span={name}}}"] = count
    return {"serve": {"program_counters": counters}}


def read(name, ctx):
    return manifest.load_reader(name)(ctx)


# a window of 100 steps: 90 decode rounds of 8 ms (5 of them waiting on the
# device, 1.5 dispatching), 4 prefill calls of 25 ms (20 waiting), and 0.3 ms
# a step of the scheduler's own
WINDOW = dict(
    sched__step=(100 * 0.3e-3 + 90 * 8e-3 + 4 * 25e-3, 100),
    engine__decode=(90 * 8e-3, 90),
    engine__decode__dispatch=(90 * 1.5e-3, 90),
    engine__decode__sync=(90 * 5e-3, 90),
    engine__prefill=(4 * 25e-3, 4),
    engine__prefill__sync=(4 * 20e-3, 4),
    sched__pack=(100 * 0.1e-3, 100))


@pytest.mark.parametrize("name,want", [
    ("sched_own_ms_untraced", 0.3),
    ("decode_dispatch_ms_untraced", 1.5),
    # (all the step's time - what it waited on the device) / rounds
    ("host_gap_ms_untraced",
     (100 * 0.3 + 90 * 3 + 4 * 5) / 90),
])
def test_each_reader_is_its_spans_arithmetic(name, want):
    assert read(name, door(**WINDOW)) == pytest.approx(want)


def test_a_window_without_prefill_subtracts_nothing_for_it():
    window = {k: v for k, v in WINDOW.items() if "prefill" not in k}
    window["sched__step"] = (100 * 0.3e-3 + 90 * 8e-3, 100)
    assert read("sched_own_ms_untraced", door(**window)) == pytest.approx(
        0.3)
    assert read("host_gap_ms_untraced", door(**window)) == pytest.approx(
        (100 * 0.3 + 90 * 3) / 90)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("ctx", [
    {}, {"serve": None}, {"serve": {}}, {"serve": {"program_counters": {}}},
    # a program without the tallies: its other counters only
    {"serve": {"program_counters": {"decode_dispatches_total": 7.0}}},
    # the tallies there, but no span in the window
    door(sched__step=(0.0, 0), engine__decode=(0.0, 0),
         engine__decode__dispatch=(0.0, 0)),
    {"train": {"window": {"counters": {}}}},
])
def test_no_tallies_or_no_span_is_no_reading(name, ctx):
    assert read(name, ctx) is None


def test_steps_with_no_decode_round_give_no_gap_and_no_dispatch():
    ctx = door(sched__step=(0.5, 10), engine__prefill=(0.4, 10),
               engine__prefill__sync=(0.3, 10))
    assert read("sched_own_ms_untraced", ctx) == pytest.approx(10.0)
    assert read("host_gap_ms_untraced", ctx) is None
    assert read("decode_dispatch_ms_untraced", ctx) is None


def test_the_programs_spans_come_through_the_door_under_these_names():
    """Spans opened by the program's own ``span()``, nested as the
    scheduler nests them, reach the readers through the door: every reading
    is positive and the parts stay inside the whole."""
    import time

    from fault_tolerant_llm_training_tpu.obs.trace import span

    before = program_records.counters()
    for _ in range(3):
        with span("ftl:sched.step", active=1, queued=0):
            with span("ftl:sched.pack"):
                time.sleep(0.001)
            with span("ftl:engine.decode", n=1, live_tokens=4,
                      slots_active=1):
                with span("ftl:engine.decode.dispatch"):
                    time.sleep(0.002)
                with span("ftl:engine.decode.sync"):
                    time.sleep(0.003)
    ctx = {"serve": {"program_counters": program_records.change(
        before, program_records.counters())}}
    own = read("sched_own_ms_untraced", ctx)
    dispatch = read("decode_dispatch_ms_untraced", ctx)
    gap = read("host_gap_ms_untraced", ctx)
    assert 1.0 <= own < gap
    assert 2.0 <= dispatch < gap
    assert 3.0 <= gap < 1000


def test_the_manifest_lists_the_three_for_the_serving_cells():
    names = [m["name"] for m in C.manifest_of(R.ROOT)["per_layer"]]
    by_name = {m["name"]: m for m in C.manifest_of(R.ROOT)["per_layer"]}
    # appended after the accepted metrics, in this order
    at = [names.index(name) for name in READERS]
    assert at == sorted(at) and at[0] > names.index("expert_gmm_roofline")
    for name in READERS:
        m = by_name[name]
        assert m["workloads"][:3] == list(SERVING)
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_counter", "tpot_p95_ms")
        assert callable(manifest.load_reader(name))


# ------------------------------------------------- the tiny serving cells
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return R.make_checkout(tmp_path_factory.mktemp("pb_span_tally"))


@pytest.mark.parametrize("tiny", [
    "tiny.tiny-chat", "tiny.tiny-longdecode", "tiny-dots3.tiny-sessions"])
def test_the_tiny_serving_cells_report_the_three(checkout, tiny):
    assert any(tiny in stand for cell, stand in R.stand_ins().items()
               if cell in SERVING)
    proc, line = R.run_cell(checkout, tiny, "--rehearsal", trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True
    m = line["metrics"]
    for name in READERS:
        assert m[name]["unit"] == "ms"
        assert 0 < m[name]["value"] < 1000, (name, m[name])
