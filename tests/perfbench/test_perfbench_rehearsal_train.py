"""Tiny-size CPU rehearsal of the training cell end to end (first child,
two preempt -> resume cycles, the plain reference in the last child), its
int8 control, and the faults a training cell can have."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import perfbench_rehearsal as R  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return R.make_checkout(tmp_path_factory.mktemp("pb_train"))


def test_preempt_rehearsal_two_cycles(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt", "--rehearsal",
                            trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line is not None and R.KEYS <= set(line)
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"
    want = {"resume_state_breaks", "resume_step_breaks",
            "resume_data_breaks", "requeue_breaks", "loss_gap",
            "grad_norm_gap", "change_norm_gap", "frozen_unexpected"}
    assert want <= set(line["compared"])
    for k in ("proc_start_s", "recover_cycle_s", "save_s", "restore_s",
              "compile_warm_s", "step_ms_p50", "data_stall_pct"):
        assert line["metrics"][k]["value"] >= 0, k
    # no TPU peak, no device plane: shares are left out, never 0
    for k in ("train_mfu_pct", "train_dev_idle_pct", "flash_attn_roofline"):
        assert k not in line["metrics"]
    cycles = json.loads(R.notes_of(proc)["cycles"])
    assert len(cycles) == 2
    assert all(c["drain_s"] > 0 and c["resume_s"] > 0 for c in cycles)


def test_end_to_end_line_in_an_untraced_run(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt1", "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 2 and line["failed"] == 0


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "change_norm_gap"),
    ("half_batch", "grad_norm_gap"),
])
def test_fault_in_the_timed_path_comes_out_not_correct(checkout, fault,
                                                       number):
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt1", "--rehearsal",
                            "--fault", fault)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"][number]["ok"] is False, line["compared"]


def test_int8_control_comes_out_not_correct(checkout):
    """The control — the reference with every projection in int8, put in
    the program's place — goes through the harness's own comparison and the
    run prints ``correct`` false; what is exact across the resume holds."""
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt1", "--rehearsal",
                            "--control", "int8")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is False
    cmp = line["compared"]
    failed = [k for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")
              if not cmp[k]["ok"]]
    assert failed and all(cmp[k]["value"] > cmp[k]["limit"] for k in failed)
    assert all(c["ok"] for k, c in cmp.items() if k.endswith("_breaks"))
    # the program itself read sound in the same run
    sound = json.loads(R.notes_of(proc)["gaps"])
    assert all(sound[k] <= cmp[k]["limit"] for k in failed)
    assert "perfbench compared | correct=False" in proc.stderr


def test_no_tpu_fails_and_prints_no_result(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt1")
    assert proc.returncode != 0
    assert line is None and "correct" not in proc.stdout
