#!/usr/bin/env python3
"""Standing proof that the main path starts and runs on the chip.

Drives train -> fault -> save -> resume -> preempt -> serve -> kernel checks
through the normal CLIs (``train.py``, ``inference/serve.py``,
``scripts/kernel_checks.py``) at the full width and depth of ``gpt2-125m``
(dim 768, 12 layers, vocab 50257, seq 2048, batch 8), on random weights
from ``--seed`` and a seeded synthetic corpus — no network, no git.

This parent process NEVER imports JAX: a chip belongs to one process at a
time, so every phase is a child, run one at a time. ``train.py`` and
``serve.py`` exit 0 by contract whatever happened, so a phase is judged by
its audit lines and parsed values AND by its exit status (0, no signal) —
never by rc 0 alone. The children share one persistent compile cache: the
one ``JAX_COMPILATION_CACHE_DIR`` names, else the program's fixed
in-checkout default (utils/compile_cache.py); this script sets none.

    python chip_smoke.py             # one chip, all phases (the driver's run)
    python chip_smoke.py --chips 4   # only the cross-chip path: dp=4 and
                                     # fsdp=4 fault+resume vs a one-chip run

One JSON object per phase on its own line; the LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Any failed phase (or no TPU) ends the run: ``"ok": false``, exit status 1.
"""

import argparse
import json
import math
import os
import re
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")       # big files; removed
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")  # child logs; small
BUDGET_SECONDS = 1140.0  # the contract allows 1200 with compilation
# a child's log must hold none of these, whatever its exit status: glibc's
# abort of a thread left in native code at interpreter exit, and the two
# places train.py swallows a failure to keep its exit-0 contract
FORBIDDEN = ("FATAL: exception not rethrown", "close() failed",
             "Exit handler failed")

MODEL_ARGS = ["--model", "gpt2-125m", "--vocab-size", "50257"]
SHAPE_ARGS = ["--sequence-length", "2048", "--batch-size", "8"]
# |first post-resume loss - last pre-fault loss|: consecutive steps of one
# healthy run. A resume that lost its weights would read ~ln(50257) = 10.8.
RESUME_BAND = 1.0
# per-step |loss - one-chip loss| on four chips: same data, same seed; bf16
# matmuls with another reduction order and per-device batch
MULTICHIP_BAND = 0.05

_DEVICE_CHILD = """
import json, jax
d = jax.devices()
assert d[0].platform == "tpu", f"no TPU: platform {d[0].platform!r}"
from fault_tolerant_llm_training_tpu.data.native import have_native
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d),
                  "bytes_limit": d[0].memory_stats()["bytes_limit"],
                  "hostloader": "native" if have_native() else "numpy"}))
"""


class PhaseFailed(Exception):
    def __init__(self, phase, problems):
        super().__init__(f"{phase}: {problems}")
        self.phase, self.problems = phase, problems


def format_result(ok, device=None, phase=None):
    """The contract's last line. ``device`` is the device phase's report."""
    if ok:
        return json.dumps({"ok": True, "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}})
    return json.dumps({"ok": False, "phase": phase})


def judge(returncode, log, required=()):
    """Problems with one finished child; [] = the phase passed.

    rc 0 alone proves nothing (the CLIs exit 0 on every handled path), and
    a job that did its work and then died at teardown still failed: the
    scheduler would see a failed job."""
    problems = []
    if returncode is None:
        problems.append("timed out (killed)")
    elif returncode < 0:
        problems.append(f"killed by signal {-returncode}")
    elif returncode != 0:
        problems.append(f"exit status {returncode}")
    problems += [f"log has {mark!r}" for mark in FORBIDDEN if mark in log]
    for pattern in required:
        if not re.search(pattern, log):
            problems.append(f"missing /{pattern}/")
    return problems


_children = []


def _kill_children():
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def run_child(name, argv, deadline, env=None, on_line=None):
    """Run one child to its end (own process group, output tee'd to
    ``OUT/<name>.log``); ``on_line(line, proc)`` sees each line as it
    arrives. Returns (returncode or None on timeout, output)."""
    full_env = dict(os.environ, PYTHONUNBUFFERED="1")
    full_env.update(env or {})
    proc = subprocess.Popen(argv, cwd=HERE, env=full_env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            start_new_session=True)
    _children.append(proc)
    lines = []

    def pump():
        with open(os.path.join(OUT, f"{name}.log"), "w") as f:
            for line in proc.stdout:
                lines.append(line)
                f.write(line)
                f.flush()
                if on_line is not None:
                    on_line(line, proc)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        _kill_children()
        reader.join(timeout=10)
        return None, "".join(lines)
    reader.join(timeout=10)
    return proc.returncode, "".join(lines)


def report(phase, t0, **fields):
    print(json.dumps({"phase": phase, "ok": True,
                      "seconds": round(time.monotonic() - t0, 1), **fields}),
          flush=True)


def check(phase, returncode, log, required=(), extra=()):
    problems = judge(returncode, log, required) + list(extra)
    if problems:
        tail = "".join(log.splitlines(keepends=True)[-25:])
        print(json.dumps({"phase": phase, "ok": False, "problems": problems,
                          "log_tail": tail[-3000:]}), flush=True)
        raise PhaseFailed(phase, problems)


# ----------------------------------------------------------------- parsing
def events_of(job):
    path = os.path.join(WORK, "ckpts", "events", f"events_{job}.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def losses_of(job):
    """step -> full-precision loss, from the job's flight recorder."""
    return {e["step"]: e["loss"] for e in events_of(job)
            if e["kind"] == "step" and "loss" in e}


def first(pattern, log, cast=str, default=None):
    m = re.search(pattern, log)
    return cast(m.group(1)) if m else default


def train_facts(job, log):
    ev = events_of(job)
    durs = [e["dur"] / e["steps"] for e in ev
            if e["kind"] == "step" and "loss" in e][3:]  # past warm-up
    tps = re.findall(r"tokens/s ([\d,]+)", log)
    return {
        "attention": first(r"Attention \| .*resolved (.+)", log),
        "compile_seconds": first(r"Train step compiled in ([\d.]+)s", log,
                                 float),
        "compile_cache": first(r"Train step compiled in .*\(cache (.+)\)",
                               log),
        "step_seconds_median": (round(statistics.median(durs), 4)
                                if durs else None),
        "tokens_per_sec_logged": (int(tps[-1].replace(",", ""))
                                  if tps else None),
        "device_memory": first(r"Device memory \| (.+)", log),
    }


def in_use_gb(log):
    """Per-device resident GB from the trainer's teardown line."""
    line = first(r"Device memory \| (.+)", log, default="")
    return [float(x) for x in re.findall(r"in use ([\d.]+) GB", line)]


# ------------------------------------------------------------------ phases
def make_corpus(seed):
    """Seeded synthetic 'text' parquet: 256 documents long enough to fill a
    2048-byte sequence (the byte tokenizer's unit)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    words = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot",
             "golf", "hotel", "india", "juliet"]
    docs = [" ".join(rng.choice(words, size=int(rng.integers(420, 520))))
            for _ in range(256)]
    path = os.path.join(WORK, "train_data.parquet")
    pq.write_table(pa.table({"text": docs}), path)
    return path


def train_argv(corpus, seed, steps, *extra):
    return [sys.executable, "train.py", "--dataset", corpus,
            "--checkpoint-path", os.path.join(WORK, "ckpts"),
            "--tokenizer-name-or-path", "byte", *MODEL_ARGS,
            *SHAPE_ARGS, "--learning-rate", "1e-4", "--lr-warmup-steps", "5",
            "--logging-frequency", "1", "--seed", str(seed),
            "--training-steps", str(steps), *extra]


COMPILED = [r"Device \| platform tpu", r"resolved pallas \(compiled\)"]


def phase_device(deadline, want_count):
    t0 = time.monotonic()
    rc, log = run_child("device", [sys.executable, "-c", _DEVICE_CHILD],
                        deadline)
    check("device", rc, log, [r'"platform": "tpu"'])
    device = json.loads(log.strip().splitlines()[-1])
    check("device", rc, log, extra=(
        [] if device["count"] == want_count else
        [f"needs {want_count} chip(s), JAX reports {device['count']}"]))
    report("device", t0, **device)
    return device


def fault_and_resume(tag, corpus, seed, deadline, steps, error_step,
                     min_after, mesh_args=(), resume_args=()):
    """One fault -> save -> resume chain; returns (facts, losses by step)."""
    t0 = time.monotonic()
    j1, j2 = f"{tag}1", f"{tag}2"
    rc, log = run_child(
        j1, train_argv(corpus, seed, steps, "--raise-error", "--error-step",
                       str(error_step), *mesh_args),
        deadline, env={"SLURM_JOB_ID": j1})
    saved = first(r"\[EXIT HANDLER\] Checkpoint saved at step (\d+)", log,
                  int)
    check(f"{tag}:train+fault", rc, log, COMPILED + [
        r"Starting training!", r"Training step: \d+ \| Loss: [\d.]+",
        r"\[EXIT HANDLER\] Error during training encountered, saving "
        r"checkpoint\.", r"\[EXIT HANDLER\] Checkpoint saved at step \d+"])
    before = losses_of(j1)
    nonfinite = [s for s, v in before.items() if not math.isfinite(v)]
    check(f"{tag}:train+fault", rc, log, extra=(
        ([f"non-finite loss at steps {nonfinite}"] if nonfinite else [])
        + ([] if before else ["no step events in the flight recorder"])))
    facts = train_facts(j1, log)
    report(f"{tag}:train+fault", t0, saved_step=saved,
           checkpoint_write=first(r"Checkpoint write \| (.+)", log),
           loss_first=before[min(before)], loss_last=before[max(before)],
           **facts)

    t0 = time.monotonic()
    rc, log2 = run_child(
        j2, train_argv(corpus, seed, steps, "--checkpoint-id", j1,
                       *mesh_args, *resume_args),
        deadline, env={"SLURM_JOB_ID": j2})
    check(f"{tag}:resume", rc, log2, COMPILED + [
        rf"Resuming training from training_step {saved}\b",
        r"Training completed"])
    after = losses_of(j2)
    check(f"{tag}:resume", rc, log2, extra=(
        [f"only {len(after)} post-resume steps, want {min_after}"]
        if len(after) < min_after else []))
    jump = abs(after[min(after)] - before[max(before)])
    check(f"{tag}:resume", rc, log2, extra=(
        ([f"first post-resume loss {after[min(after)]:.4f} is "
            f"{jump:.3f} from the last pre-fault {before[max(before)]:.4f} "
            f"(band {RESUME_BAND})"] if jump > RESUME_BAND else [])))
    restore = [e["dur"] for e in events_of(j2) if e["kind"] == "ckpt_restore"]
    facts2 = train_facts(j2, log2)
    report(f"{tag}:resume", t0, resumed_step=saved,
           restore_seconds=round(restore[0], 2) if restore else None,
           steps_after=len(after), loss_jump=round(jump, 4),
           loss_last=after[max(after)], **facts2)
    return {"fault": facts, "resume": facts2, "fault_log": log,
            "resume_log": log2}, {**before, **after}


def phase_preempt(corpus, seed, deadline, cold_compile_seconds):
    t0 = time.monotonic()
    marker = os.path.join(WORK, "resubmitted.txt")
    sent = []

    def usr1_after_first_step(line, proc):
        if not sent and "Training step:" in line:
            sent.append(time.monotonic())
            proc.send_signal(signal.SIGUSR1)  # the python child, directly

    rc, log = run_child(
        "smoke3", train_argv(corpus, seed, 100000, "--resubmit-command",
                             f"touch {marker}"),
        deadline, env={"SLURM_JOB_ID": "smoke3"},
        on_line=usr1_after_first_step)
    facts = train_facts("smoke3", log)
    warm, bound = facts["compile_seconds"], max(10.0,
                                                0.5 * cold_compile_seconds)
    check("preempt", rc, log, COMPILED + [
        r"\[EXIT HANDLER\] Job timed out, saving checkpoint\.",
        r"\[EXIT HANDLER\] Checkpoint saved at step \d+",
        r"sbatch requeued"], extra=(
        ([] if os.path.exists(marker) else ["no resubmit marker file"])
        + ([] if warm is not None and warm <= bound else
           [f"compile {warm}s is not warm (bound {bound:.1f}s; the first "
            f"run of this step took {cold_compile_seconds}s)"])))
    report("preempt", t0,
           saved_step=first(r"Checkpoint saved at step (\d+)", log, int),
           signal_to_exit_seconds=round(time.monotonic() - sent[0], 1),
           warm_compile_seconds=warm,
           first_compile_seconds=cold_compile_seconds,
           checkpoint_write=first(r"Checkpoint write \| (.+)", log))


PROMPTS = ["alpha", "alpha bravo charlie", "delta echo foxtrot golf hotel "
           "india juliet", "bravo " * 12 + "charlie", "echo delta " * 7]


def phase_serve(deadline):
    streams = {}
    for kernel, mode in (("gather", "gather"), ("pallas",
                                                r"pallas \(compiled\)")):
        t0 = time.monotonic()
        argv = [sys.executable, "-m",
                "fault_tolerant_llm_training_tpu.inference.serve",
                "--checkpoint-path", os.path.join(WORK, "ckpts"),
                "--checkpoint-job-id", "smoke2", *MODEL_ARGS,
                "--slots", "8", "--no-eos", "--max-new-tokens", "32",
                "--max-len", "128", "--prefill-buckets", "32,128",
                "--paged-kernel", kernel]
        for p in PROMPTS:
            argv += ["--prompt", p]
        rc, log = run_child(f"serve_{kernel}", argv, deadline)
        done = re.findall(r"Request (\S+) done \| (\w+) \| prompt (\d+) tok "
                          r"\| generated (\d+) tok", log)
        short = [d for d in done if d[3] != "32"]
        check(f"serve:{kernel}", rc, log, [
            r"Device \| platform tpu", rf"Paged kernel \| {mode}",
            r"Serving ready \|", r"Serving completed"], extra=(
            ([f"{len(done)}/{len(PROMPTS)} requests completed"]
             if len(done) != len(PROMPTS) else [])
            + ([f"short streams: {short}"] if short else [])
            + (["[KV LEAK] in the drain audit"] if "[KV LEAK]" in log
               else [])))
        streams[kernel] = dict(re.findall(r"Request (\S+) output: (.+)",
                                          log))
        report(f"serve:{kernel}", t0, requests=len(done),
               prompt_tokens=[int(d[2]) for d in done],
               serving_metrics=first(r"Serving metrics: (.+)", log),
               restored_step=first(r"checkpoint step (\d+)", log, int))
    diverged = {}
    for rid, text in streams["gather"].items():
        other = streams["pallas"].get(rid, "")
        if text != other:
            diverged[rid] = next((i for i, (a, b) in
                                  enumerate(zip(text, other)) if a != b),
                                 min(len(text), len(other)))
    print(json.dumps({
        "phase": "serve:agreement", "ok": True,
        "greedy_streams_agree": not diverged,
        "first_divergent_char_of_decoded_text": diverged}), flush=True)


def phase_kernels(deadline):
    t0 = time.monotonic()
    rc, log = run_child("kernels",
                        [sys.executable, "scripts/kernel_checks.py"],
                        deadline)
    lines = [json.loads(l) for l in log.splitlines()
             if l.startswith('{"check"')]
    bad = [l["check"] for l in lines if not l["ok"]]
    check("kernels", rc, log, extra=(
        ([f"failed: {bad}"] if bad else [])
        + ([f"{len(lines)} check lines, want 22"] if len(lines) != 22
           else [])))
    report("kernels", t0, checks=len(lines), all_ok=not bad)


def one_chip(args, deadline):
    device = phase_device(deadline, 1)
    corpus = make_corpus(args.seed)
    # the resumed job also saves periodically (first blocking, then async)
    # and leaves the checkpoint the serve phase restores
    chain, _ = fault_and_resume(
        "smoke", corpus, args.seed, deadline, steps=40, error_step=20,
        min_after=10, resume_args=("--checkpoint-frequency", "10"))
    phase_preempt(corpus, args.seed, deadline,
                  chain["fault"]["compile_seconds"])
    phase_serve(deadline)
    phase_kernels(deadline)
    return device


def four_chips(args, deadline):
    device = phase_device(deadline, 4)
    corpus = make_corpus(args.seed)
    t0 = time.monotonic()
    rc, log = run_child("ref1", train_argv(corpus, args.seed, 20,
                                           "--dp", "1"),
                        deadline, env={"SLURM_JOB_ID": "ref1"})
    check("one-chip reference", rc, log, COMPILED + [r"Training completed"])
    ref = losses_of("ref1")
    ref_state = max(in_use_gb(log))
    report("one-chip reference", t0, steps=len(ref),
           in_use_gb=in_use_gb(log), loss_last=ref[max(ref)],
           **train_facts("ref1", log))
    verdicts = []  # judged after BOTH meshes ran: four chips cost four times
    for tag, mesh_args in (("dp4x", ()), ("fsdp4x", ("--fsdp", "4"))):
        chain, losses = fault_and_resume(tag, corpus, args.seed, deadline,
                                         steps=20, error_step=10,
                                         min_after=9, mesh_args=mesh_args)
        worst = max(abs(losses[s] - ref[s]) for s in set(ref) & set(losses))
        spread = in_use_gb(chain["resume_log"])
        problems = []
        if set(losses) != set(ref):
            problems.append(f"steps {sorted(set(ref) ^ set(losses))} differ "
                            f"from the one-chip run")
        if worst > MULTICHIP_BAND:
            problems.append(f"max |loss - one-chip loss| {worst:.4f} > "
                            f"{MULTICHIP_BAND}")
        if len(spread) != 4 or min(spread) <= 0:
            problems.append(f"state not on four devices: {spread}")
        if tag == "fsdp4x" and not (0.15 * ref_state <= min(spread)
                                    and max(spread) <= 0.5 * ref_state):
            problems.append(f"fsdp=4 shards {spread} GB are not ~1/4 of the "
                            f"one-chip {ref_state} GB")
        print(json.dumps({
            "phase": f"{tag}:vs one chip", "ok": not problems,
            "max_abs_loss_diff": round(worst, 5), "band": MULTICHIP_BAND,
            "in_use_gb_per_device": spread,
            "one_chip_in_use_gb": ref_state, "problems": problems}),
            flush=True)
        verdicts.append((f"{tag}:vs one chip", problems))
    for phase, problems in verdicts:
        if problems:
            raise PhaseFailed(phase, problems)
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip training path and the "
                         "one-chip run it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the corpus and the weights")
    args = ap.parse_args(argv)
    deadline = time.monotonic() + BUDGET_SECONDS
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT, exist_ok=True)
    try:
        device = (one_chip if args.chips == 1 else four_chips)(args,
                                                               deadline)
    except PhaseFailed as e:
        print(format_result(False, phase=e.phase), flush=True)
        return 1
    finally:
        _kill_children()
        shutil.rmtree(WORK, ignore_errors=True)
    print(format_result(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
