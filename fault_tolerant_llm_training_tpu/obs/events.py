"""Structured JSONL flight recorder.

The reference's verification API is its log text: the README greps the Slurm
``.out`` files for the ``[EXIT HANDLER]`` audit trail (utils/logging.py keeps
those strings byte-identical). That trail is human-greppable but not
machine-accountable — nothing records how much compute a preempt →
checkpoint → resubmit → resume chain actually cost. The flight recorder
closes the gap without touching the text contract: every audit emission goes
through :func:`emit_audit`, which logs the byte-identical string AND appends
one typed event (``step``, ``ckpt_save``, ``ckpt_restore``, ``signal``,
``resume``, ``eval``, ``drain``, ...) with wall-clock, step, and duration.

Events are written through to a JSONL file (one JSON object per line, append
mode — a resumed job under the same id extends the same file) and mirrored
into an in-memory ring buffer of the last N events. ``ft/handler.py``
flushes the recorder on every exit path, so a crash leaves forensics on disk
even when stdout is lost with the node.

Event schema (all numbers host-local):

    {"t": <unix wall clock>, "kind": "...", "job": "...", "host": 0,
     "step": <int|null>, "dur": <seconds|null>, ...payload}

``obs/goodput.py`` stitches these files across restarts into goodput %,
MTTR, and per-failure-class lost time.
"""

import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from . import hlc

# Event kinds with a fixed meaning across the chain (payloads are free-form):
#   start         AUDIT_START — fresh run entered the loop
#   resume        AUDIT_RESUME_FMT — resumed run entered the loop
#   step          one logged step window (payload: steps covered, loss, ...)
#   ckpt_save     checkpoint written (dur = blocking wall; payload: fault?)
#   ckpt_restore  checkpoint restored at setup (dur = restore wall)
#   signal        fault signal agreed/observed (payload: signum, class)
#   eval          held-out evaluation pass
#   drain         serving drain lifecycle (payload: phase=begin|end)
#   requeue       sbatch resubmission attempt (payload: ok)
#   exit          exit-handler verdict (payload: error_type, class, saved)
#   complete      AUDIT_COMPLETED / AUDIT_SERVE_COMPLETED
#   chaos_<fault> chaos injection fired (chaos/injector.py; one kind per
#                 fault class, e.g. chaos_sigusr1, chaos_ckpt_corrupt —
#                 the latter twice: phase=raise then phase=corrupted)
#   ckpt_verify_failed   a step dir failed its integrity manifest at
#                        restore (payload: step, detail)
#   ckpt_fallback        restore fell back to an older passing step
#                        (payload: step chosen, rejected steps)
#   ckpt_partial_skipped leftover non-finalized tmp dir seen (and never
#                        restored) during the finalize sweep
# Lifecycle of one process, in the order a resumed trainer emits them (the
# restart's boundaries, read where the work happens):
#   proc_start       t = the interpreter's start (:func:`emit_startup`)
#   imports_done     the entry module finished importing (dur = since
#                    proc_start: what every process pays before it can
#                    touch the backend)
#   backend_ready    the backend answered, where ``Device | `` is logged
#                    (Trainer.__init__, InferenceEngine.__init__;
#                    payload: device)
#   ckpt_verify      integrity gate before a restore (dur = the CRC scan,
#                    bytes = bytes checksummed; payload: step chosen)
#   ckpt_manifest    integrity manifests written after a commit (dur,
#                    bytes = bytes checksummed; payload: steps)
#   first_step_done  the first step of this process whose metrics were read
#                    back (payload: resumed true/false)


class FlightRecorder:
    """Append-only JSONL event log + ring buffer of the last ``capacity``."""

    def __init__(self, path: Optional[str] = None, capacity: int = 512,
                 job: str = "local", host: int = 0,
                 clock: Callable[[], float] = time.time):
        self.path = path
        self.job = job
        self.host = host
        self.clock = clock
        self.ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._fh = None
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            self._fh = open(path, "a", buffering=1)  # line-buffered

    def emit(self, kind: str, step: Optional[int] = None,
             dur: Optional[float] = None, **payload) -> Dict:
        ev = {"t": self.clock(), "hlc": hlc.tick(), "kind": kind,
              "job": self.job, "host": self.host}
        if step is not None:
            ev["step"] = int(step)
        if dur is not None:
            ev["dur"] = float(dur)
        ev.update(payload)
        with self._lock:
            self.ring.append(ev)
            if self._fh is not None:
                try:
                    self._fh.write(json.dumps(ev) + "\n")
                except (OSError, ValueError):
                    pass  # a full/dead disk must never take down training
        return ev

    def flush(self) -> None:
        """Push buffered lines to the OS and fsync — the exit-path call
        (ft/handler.py): after this, the events survive the process."""
        with self._lock:
            if self._fh is None:
                return
            try:
                self._fh.flush()
                os.fsync(self._fh.fileno())
            except (OSError, ValueError):
                pass

    def dump(self, path: str) -> None:
        """Write the ring buffer to ``path`` (forensics fallback for runs
        that never configured a write-through file)."""
        with self._lock:
            events = list(self.ring)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


# --------------------------------------------------------- module singleton
# Memory-only until configure() points it at a file; ft/handler.py and the
# serving loop emit through the module functions so a partially-constructed
# Trainer (signal during setup) still leaves a trail.
_RECORDER = FlightRecorder()


def configure(path: Optional[str], job: str = "local", host: int = 0,
              capacity: int = 512) -> FlightRecorder:
    """Swap in a configured recorder; prior ring contents carry over so
    events emitted before configuration are not lost. What the memory-only
    default recorder collected (the process's start-up events, a signal
    during setup) belongs to this process, so it takes this job and host:
    the stitcher must not see a job of its own in it."""
    global _RECORDER
    old = _RECORDER
    rec = FlightRecorder(path, capacity=capacity, job=job, host=host)
    rec.ring.extend(
        dict(ev, job=job, host=host) if old.path is None
        and ev.get("job") == "local" else ev for ev in old.ring)
    if rec._fh is not None:
        for ev in rec.ring:  # replay pre-configuration events into the file
            try:
                rec._fh.write(json.dumps(ev) + "\n")
            except (OSError, ValueError):
                break
    old.close()
    _RECORDER = rec
    return rec


def get() -> FlightRecorder:
    return _RECORDER


def emit(kind: str, step: Optional[int] = None,
         dur: Optional[float] = None, **payload) -> Dict:
    return _RECORDER.emit(kind, step=step, dur=dur, **payload)


def flush() -> None:
    _RECORDER.flush()


def process_start_time() -> float:
    """Unix time at which this process started. Linux: the kernel's own
    stamp (``/proc/self/stat`` field 22, ticks since boot), so it holds
    wherever in the program it is asked; elsewhere: now."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return (time.time() - since_boot
                + ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.time()


def emit_startup(imports_done_t: float) -> None:
    """``proc_start`` and ``imports_done`` of this process. An entry point
    stamps ``imports_done_t`` (``time.time()``) right below its imports
    and calls this first thing in its main function; the events carry the
    times they describe, not the time of the call."""
    t0 = min(process_start_time(), imports_done_t)
    emit("proc_start", t=t0)
    emit("imports_done", dur=imports_done_t - t0, t=imports_done_t)


def emit_audit(log, text: str, kind: str, step: Optional[int] = None,
               dur: Optional[float] = None, **payload) -> Dict:
    """Log a byte-identical audit string AND emit exactly one typed event.

    This is the only sanctioned way to emit an ``AUDIT_*`` string
    (tests/test_audit_contract.py greps the source tree for raw
    ``logger.info(AUDIT_*`` call sites): the text contract and the
    machine-readable record can never drift apart.
    """
    log.info(text)
    return emit(kind, step=step, dur=dur, audit=True, **payload)


def read_events(path: str) -> List[Dict]:
    """Load one JSONL event file; tolerates a truncated final line (the
    crash case the ring-buffer flush exists for)."""
    events = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail write from a killed process
    return events
