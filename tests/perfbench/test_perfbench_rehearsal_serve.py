"""Tiny-size CPU rehearsal of the serving cells end to end, through the one
command, in a temporary checkout that ADDS a configuration, a traffic mix, a
cell and a per-layer metric by files and entries alone."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import perfbench_checks as C  # noqa: E402
import perfbench_rehearsal as R  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return R.make_checkout(tmp_path_factory.mktemp("pb_serve"))


def check_line(line, metrics):
    assert line is not None and R.KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"      # named for what it is
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_chat_open_loop_rehearsal(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-chat",  "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    # an open loop under its knee, as ``chat``: judged by its tail; its
    # throughput is the schedule's own and stays in the notes
    check_line(line, {"tpot_p95_ms", "setup_s"})
    notes = R.notes_of(proc)
    assert float(notes["out_tokens"]) > 0 and int(notes["offered"]) > 20
    assert float(notes["ttft_p95_ms"]) > 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert line["compared"]["logit_gap_max"]["value"] <= 0.01
    assert "perfbench compared | logit_gap_max" in proc.stderr


def test_int8_control_comes_out_not_correct(checkout):
    """The control — the reference computed in int8, put in the program's
    place — goes through the harness's own comparison, the same number
    against the same limit, and the run prints ``correct`` false."""
    proc, line = R.run_cell(checkout, "tiny.tiny-chat",  "--rehearsal",
                            "--control", "int8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    gap = line["compared"]["logit_gap_max"]
    assert gap["ok"] is False and gap["value"] > gap["limit"]
    assert line["compared"]["requests_failed"]["ok"] is True
    notes = R.notes_of(proc)
    assert notes["control"] == "int8"
    assert float(notes["program_logit_gap_max"]) <= gap["limit"]
    assert "perfbench compared | correct=False" in proc.stderr


def test_added_cell_config_traffic_and_metric_by_files_alone(checkout):
    proc, line = R.run_cell(checkout, "tiny2.tiny-chat2", "--rehearsal",
                            trace=1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True
    assert "decode_calls" in line["metrics"]          # the added reader
    assert line["metrics"]["decode_calls"]["value"] > 0
    assert {"ttft_p95_ms", "decode_step_ms_p50", "serve_mfu_pct"} - set(
        line["metrics"]) == {"serve_mfu_pct"}  # no peak for a CPU: left out
    # a CPU trace holds no device plane: the idle share is left out, not 0
    assert "serve_dev_idle_pct" not in line["metrics"]
    assert "busy_s" not in line["device"]


def test_rehearsal_manifest_follows_the_real_one(checkout):
    """Each tiny cell reports what the real cell it stands for reports, end
    to end and per layer, so a change to ``BENCHMARK.json`` is rehearsed;
    which tiny cells those are is read from the stand-in files."""
    C.check_rehearsal_follows(R.ROOT, checkout)
    tiny = C.manifest_of(checkout)

    def reported(cell):
        return [m["name"] for m in tiny["end_to_end"]
                if cell in m.get("workloads", [cell])]

    assert "serve_tok_s" not in reported("tiny.tiny-chat")
    assert "serve_tok_s" in reported("tiny.tiny-longdecode")
    assert R.ADDED_METRIC in [m["name"] for m in tiny["per_layer"]
                              if R.ADDED_CELL in m["workloads"]]


def test_longdecode_closed_loop_rehearsal(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-longdecode", "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    # a closed loop, as ``longdecode``: the server sets its own throughput
    check_line(line, {"serve_tok_s", "tpot_p95_ms", "setup_s"})
    assert line["correct"] is True and line["failed"] == 0


def test_altered_token_comes_out_not_correct(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-chat", "--rehearsal",
                            "--fault", "token_altered")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False


def test_no_tpu_fails_and_prints_no_result(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-chat")   # real path
    assert proc.returncode != 0
    assert line is None and "correct" not in proc.stdout


def test_no_program_fails_and_prints_no_result(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-chat", "--rehearsal",
                            program=False)
    assert proc.returncode != 0 and line is None
