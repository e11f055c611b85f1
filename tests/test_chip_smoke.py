"""chip_smoke.py's own contract, and the compile cache it relies on.

The chip run itself cannot happen here; what can be pinned is everything that
decides whether a chip run is judged right: the parent stays off JAX (a chip
belongs to one process), a phase is never passed on rc 0 alone, the last line
is the contract's, and without a TPU the script fails in its first phase.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

GOOD_FAULT_LOG = """\
INFO - Device | platform tpu | kind TPU v5 lite | count 1
INFO - Attention | requested auto | resolved pallas (compiled)
INFO - Starting training!
INFO - Training step: 20 | Loss: 3.21
INFO - [EXIT HANDLER] Error during training encountered, saving checkpoint.
INFO - [EXIT HANDLER] Checkpoint saved at step 21
"""
FAULT_AUDIT = [r"Starting training!",
               r"\[EXIT HANDLER\] Checkpoint saved at step \d+"]


def _python(code, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=120)


def test_parent_imports_no_jax():
    r = _python("import sys, chip_smoke; "
                "sys.exit('jax' in sys.modules or 'jaxlib' in sys.modules)")
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("returncode,log,why", [
    (0, "INFO - Starting training!\n", "missing"),        # rc 0, no audit
    (-11, GOOD_FAULT_LOG, "killed by signal 11"),         # segfault
    (-6, GOOD_FAULT_LOG, "killed by signal 6"),           # abort at teardown
    (134, GOOD_FAULT_LOG, "exit status 134"),             # ... seen via a shell
    (0, GOOD_FAULT_LOG + "FATAL: exception not rethrown\n", "FATAL"),
    (0, GOOD_FAULT_LOG + "ERROR - close() failed; exit code preserved\n",
     "close() failed"),                                   # swallowed teardown
    (None, GOOD_FAULT_LOG, "timed out"),
], ids=["rc0-no-audit", "sigsegv", "sigabrt", "rc134", "abort-mark",
        "swallowed-close", "timeout"])
def test_judge_fails_a_phase_that_only_looks_finished(returncode, log, why):
    problems = chip_smoke.judge(returncode, log, chip_smoke.COMPILED
                                + FAULT_AUDIT)
    assert problems and any(why in p for p in problems), problems


def test_judge_passes_audited_clean_exit_and_wants_compiled_pallas():
    required = chip_smoke.COMPILED + FAULT_AUDIT
    assert chip_smoke.judge(0, GOOD_FAULT_LOG, required) == []
    # the same job having quietly taken the XLA attention, or interpreted
    # kernels, or the CPU, is a failure even with every audit line present
    for quiet in (("pallas (compiled)", "xla"),
                  ("pallas (compiled)", "pallas (interpret)"),
                  ("platform tpu", "platform cpu")):
        assert chip_smoke.judge(0, GOOD_FAULT_LOG.replace(*quiet), required)


def test_last_line_matches_the_contract_exactly():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "bytes_limit": 16909336576, "hostloader": "native"}
    assert chip_smoke.format_result(True, device) == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}')
    failed = json.loads(chip_smoke.format_result(False, phase="device"))
    assert failed["ok"] is False


def test_without_a_tpu_fails_in_the_device_phase_and_runs_nothing_else():
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    assert r.returncode != 0
    assert lines[-1] == {"ok": False, "phase": "device"}
    assert [l["phase"] for l in lines[:-1]] == ["device"]  # no later phase
    assert not (REPO / ".chip_smoke_work").exists()


_CACHE_PROBE = """
import jax
from fault_tolerant_llm_training_tpu.utils.compile_cache import (
    enable_compilation_cache)
before = jax.config.jax_compilation_cache_dir
print(enable_compilation_cache({arg}) or "off", before or "unset",
      jax.config.jax_compilation_cache_dir or "unset")
"""


@pytest.mark.parametrize("arg", ["None", "'/flag/dir'", "''"],
                         ids=["default", "flag", "flag-off"])
def test_cache_env_var_wins_and_code_sets_no_directory(arg, tmp_path):
    placed = str(tmp_path / "placed")
    r = _python(_CACHE_PROBE.format(arg=arg),
                JAX_COMPILATION_CACHE_DIR=placed)
    assert r.returncode == 0, r.stderr
    # in effect, what JAX took from the environment, what JAX holds after
    assert r.stdout.split() == [placed, placed, placed]


def test_cache_default_is_one_fixed_path_in_the_checkout():
    runs = [_python(_CACHE_PROBE.format(arg="None")) for _ in range(2)]
    assert all(r.returncode == 0 for r in runs), runs[0].stderr
    in_effect, before, after = runs[0].stdout.split()
    assert runs[1].stdout.split()[0] == in_effect  # same in every process
    assert (before, after) == ("unset", in_effect)
    assert Path(in_effect) == REPO / ".jax_compile_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_compile_cache/" in ignored


def test_cache_flag_places_it_and_empty_flag_turns_it_off():
    r = _python(_CACHE_PROBE.format(arg="'/flag/dir'"))
    assert r.stdout.split() == ["/flag/dir", "unset", "/flag/dir"], r.stderr
    r = _python(_CACHE_PROBE.format(arg="''"))
    assert r.stdout.split() == ["off", "unset", "unset"], r.stderr
