"""The training cells' parent: drives a preempt -> resume chain of child
processes and never touches JAX itself (a chip belongs to one process).

One *cycle* is: SIGUSR1 to the training child (the ``train.sh
--signal=USR1@120`` path), the child finishes its step, saves and exits; the
next child restores and trains on. Its time is two intervals the program
owns:

- drain:  ``os.kill`` -> the child's exit is reaped;
- resume: the next child's ``Device |`` line -> ``block_until_ready`` of its
  first optimizer step.

The hand-over between them (exit reaped -> ``Device |``: interpreter,
imports, TPU runtime release/acquire) is reported apart (``proc_start_s``).
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import zlib

from . import recovery

FORBIDDEN = ("FATAL: exception not rethrown", "close() failed",
             "Exit handler failed")
CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "train_child.py")


# --------------------------------------------------------------- the corpus
def corpus_rows(seed: int, docs: int, seq_len: int):
    """(docs, seq_len + 16) printable-ASCII bytes drawn from the seed. Each
    document is longer than a row, so no row is padded and all differ."""
    import numpy as np

    rng = np.random.default_rng([seed, 0xC0FFEE])
    return rng.integers(32, 127, size=(docs, seq_len + 16), dtype=np.uint8)


def write_corpus(path: str, rows) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq

    texts = [bytes(r).decode("ascii") for r in rows]
    pq.write_table(pa.table({"text": texts}), path)


def expected_tokens(rows, step: int, batch: int, seq_len: int):
    """The (batch, seq_len + 1) int32 token rows of optimizer step ``step``
    (0-based) as the byte tokenizer makes them: BOS, then byte + 3. Inputs
    are ``[:, :-1]``, labels ``[:, 1:]`` (the CLM collator's shift)."""
    import numpy as np

    idx = [(step * batch + b) % len(rows) for b in range(batch)]
    return np.concatenate(
        [np.ones((batch, 1), np.int32),
         rows[idx, :seq_len].astype(np.int32) + 3], axis=1)


def expected_inputs(rows, step: int, batch: int, seq_len: int):
    return expected_tokens(rows, step, batch, seq_len)[:, :-1]


def expected_batch_crc(rows, step, batch, seq_len) -> str:
    return f"{zlib.crc32(expected_inputs(rows, step, batch, seq_len).tobytes()):08x}"


# --------------------------------------------------------------- one child
class Child:
    def __init__(self, spec: dict, log_path: str, env: dict):
        self.spec = spec
        self.events = []       # PERFBENCH records, with arrival time
        self.lines = []        # (arrival wall time, text) of audit lines
        self.t_spawn = time.time()
        self.t_device_line = None
        self.t_exit = None
        self.log_path = log_path
        self._cv = threading.Condition()
        spec_path = log_path + ".spec.json"
        with open(spec_path, "w") as fh:
            json.dump(spec, fh)
        self.proc = subprocess.Popen(
            [sys.executable, "-u", CHILD, spec_path], stdout=subprocess.PIPE,
            stdin=subprocess.PIPE if spec.get("wait_go") else
            subprocess.DEVNULL,
            stderr=subprocess.STDOUT, env=env, cwd=spec["root"], bufsize=0)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        with open(self.log_path, "wb") as log:
            for raw in iter(self.proc.stdout.readline, b""):
                now = time.time()
                log.write(raw)
                text = raw.decode("utf-8", "replace").rstrip("\n")
                with self._cv:
                    if text.startswith("PERFBENCH "):
                        try:
                            ev = json.loads(text[len("PERFBENCH "):])
                            ev["t_arrival"] = now
                            self.events.append(ev)
                        except ValueError:
                            pass
                    elif " - INFO - " in text or "EXIT HANDLER" in text:
                        if self.t_device_line is None and "Device | " in text:
                            self.t_device_line = now
                        if any(k in text for k in (
                                "EXIT HANDLER", "Device | ", "Checkpoint write",
                                "Train step compiled", "Resuming training",
                                "Training completed", "Starting training")):
                            self.lines.append((now, text))
                    self._cv.notify_all()

    def event(self, name: str):
        for ev in self.events:
            if ev["ev"] == name:
                return ev
        return None

    def wait_event(self, name: str, deadline: float):
        """The named record, or None if the child exits or time runs out."""
        with self._cv:
            while True:
                ev = self.event(name)
                if ev is not None:
                    return ev
                if self.proc.poll() is not None and not self._reader.is_alive():
                    return self.event(name)
                left = deadline - time.time()
                if left <= 0:
                    return None
                self._cv.wait(min(left, 0.5))

    def go(self, **word) -> None:
        """Tell a child started ahead of its turn that the chip is free."""
        self.t_go = time.time()
        try:
            self.proc.stdin.write((json.dumps(dict(word, go=True))
                                   + "\n").encode())
            self.proc.stdin.flush()
            self.proc.stdin.close()
        except OSError:
            pass

    def abandon(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def signal(self, signum=signal.SIGUSR1) -> float:
        t = time.time()
        os.kill(self.proc.pid, signum)
        return t

    def reap(self, deadline: float) -> int:
        try:
            rc = self.proc.wait(max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = self.proc.wait()
        self.t_exit = time.time()
        self._reader.join(10.0)
        return rc

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path, "rb") as fh:
                return b"".join(fh.readlines()[-n:]).decode("utf-8", "replace")
        except OSError:
            return ""

    def log_has(self, needle: str) -> bool:
        try:
            with open(self.log_path, "rb") as fh:
                return needle.encode() in fh.read()
        except OSError:
            return False


# --------------------------------------------------------------- the chain
def child_argv(cell, seed, work, job, resume_from, steps, batch) -> list:
    tr = cell.traffic
    argv = ["--dataset", os.path.join(work, "corpus.parquet"),
            "--checkpoint-path", os.path.join(work, "ckpts"),
            "--tokenizer-name-or-path", "byte",
            "--model", cell.config_name,
            "--vocab-size", str(cell.config["vocab_size"]),
            "--sequence-length", str(tr["sequence_length"]),
            "--batch-size", str(batch),
            "--training-steps", str(steps),
            "--model-dtype", tr.get("model_dtype", "bf16"),
            "--learning-rate", str(tr["learning_rate"]),
            "--lr-warmup-steps", str(tr["lr_warmup_steps"]),
            "--seed", str(seed),
            "--resubmit-command",
            f"touch {os.path.join(work, 'resubmitted_' + job)}"]
    argv += [str(a) for a in tr.get("mesh_args", [])]
    if resume_from:
        argv += ["--checkpoint-id", resume_from]
    return argv


def run_chain(cell, seed: int, seconds: float, trace: bool, t0: float,
              cycles: int = None, require_tpu: bool = True,
              compare: bool = True, fault: str = "", control: str = "",
              budget_s: float = 1150.0, log=print) -> dict:
    """Child A (window) -> ``cycles`` x (signal, drain, resume) -> the last
    child trains ``last_child_steps`` more and runs the comparison. Returns
    everything measured; judges nothing."""
    tr = cell.traffic
    cycles = tr["cycles"] if cycles is None else cycles
    work = cell.work_dir()
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "ckpts"))
    batch = tr["rows_per_chip"] * cell.chips
    seq = tr["sequence_length"]
    seed_eff = seed % (2 ** 31 - 1)
    rows = corpus_rows(seed_eff, tr["docs"], seq)
    write_corpus(os.path.join(work, "corpus.parquet"), rows)
    deadline = t0 + budget_s
    base_env = dict(os.environ)
    base_env.pop("BENCH_RUN", None)
    base_env["PYTHONUNBUFFERED"] = "1"
    out = {"children": [], "cycles": [], "problems": [], "rows": rows,
           "batch": batch, "work": work}

    def spawn(role, job, resume_from, steps, compare_here=False,
              wait_go=False):
        spec = {
            "wait_go": wait_go,
            "root": cell.program_root, "bench_dir": cell.bench_dir, "role": role,
            "config": cell.config, "traffic": tr,
            "preset_name": cell.config_name, "seed": seed_eff,
            "seconds": seconds, "trace": bool(trace) and role == "first",
            "trace_dir": os.path.join(work, "trace"),
            "trace_steps": tr.get("trace_steps", 6),
            "work_dir": work, "chips": cell.chips, "batch_size": batch,
            "require_tpu": require_tpu, "compare": compare and compare_here,
            "control": control, "fault": fault,
            "limits": cell.limits,
            "argv": child_argv(cell, seed_eff, work, job, resume_from, steps,
                               batch),
        }
        env = dict(base_env, SLURM_JOB_ID=job)
        c = Child(spec, os.path.join(work, f"{job}.log"), env)
        c.job, c.role = job, role
        out["children"].append(c)
        return c

    def fail(msg, child=None):
        out["problems"].append(msg)
        if child is not None:
            log(f"--- tail of {child.log_path} ---\n{child.log_tail()}")

    # cycles == 0 (the limits study): the first child follows its warm
    # steps, a short window, ends by itself and compares
    a = spawn("first", "pbA", "", 1000000 if cycles else
              tr["warm_steps"] + 3, compare_here=not cycles)
    # The later children are started with the first, ahead of their turn:
    # they import (27 s of host work that needs no chip) beside the first
    # child's own imports, inside set-up, then wait for the word that their
    # predecessor has exited. By the time the window opens they are asleep
    # on a pipe, so nothing of theirs runs inside the timed window or
    # inside a cycle's stamped intervals; the imports' cost stays visible
    # in ``setup_s`` (the first child pays its own).
    names = ["pbA"] + [f"pb{chr(ord('B') + i)}" for i in range(cycles)]
    ahead = [spawn("last" if i == cycles - 1 else "resumed",
                   names[i + 1], names[i], 1000000,
                   compare_here=i == cycles - 1, wait_go=True)
             for i in range(cycles)]
    prev = a
    try:
        if a.wait_event("backend", deadline) is None:
            a.reap(time.time() + 5)
            fail("child A found no backend (no chip?)", a)
            return out
        out["backend"] = a.event("backend")
        for nxt in ahead:
            if nxt.wait_event("imported", deadline) is None:
                fail(f"child {nxt.job} never imported", nxt)
                return out
        if a.wait_event("window_open", deadline) is None:
            a.reap(time.time() + 5)
            fail("child A never opened its window", a)
            return out
        if a.wait_event("window_closed", deadline) is None:
            a.reap(time.time() + 5)
            fail("child A never closed its window", a)
            return out
        for i in range(cycles):
            last = i == cycles - 1
            nxt = ahead[i]
            t_kill = prev.signal()
            rc = prev.reap(deadline)
            saved = prev.event("saved")
            if rc != 0 or saved is None:
                fail(f"cycle {i}: child {prev.job} rc {rc}, saved {saved}",
                     prev)
                return out
            steps = (saved["step"] + tr["last_child_steps"] if last
                     else 1000000)
            argv = list(nxt.spec["argv"])
            argv[argv.index("--training-steps") + 1] = str(steps)
            nxt.go(argv=argv)
            first = nxt.wait_event("first_step_done", deadline)
            if first is None:
                nxt.reap(time.time() + 5)
                fail(f"cycle {i}: child {nxt.job} never finished a step",
                     nxt)
                return out
            out["cycles"].append(recovery.cycle_record(
                i, prev, nxt, t_kill, work))
            log("perfbench cycle | " + json.dumps(
                {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in out["cycles"][-1].items()}))
            prev = nxt
    finally:
        for c in ahead:
            if c is not prev:
                c.abandon()
    rc = prev.reap(deadline)
    if rc != 0:
        fail(f"last child {prev.job} rc {rc}", prev)
    for c in out["children"]:
        for bad in FORBIDDEN:
            if c.log_has(bad):
                fail(f"child {c.job}: log holds {bad!r}", c)
    return out
