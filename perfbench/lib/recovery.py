"""Recovery segments: from the stamps of a preempt -> resume chain to the
numbers the cell reports.

The arithmetic follows ``obs/goodput.py``'s stitching (jobs ordered in a
chain, restart = fault instant -> first completed step) but splits the
restart where ownership changes: what the program does before its process
exits (*drain*), what the machine does between processes (*hand-over*) and
what the program does once the backend holds the chip (*resume*).

Where each boundary comes from:

- ``t_kill``: the parent's clock at ``os.kill``;
- ``t_exit``: the parent's clock when ``wait()`` returns;
- ``t_device``: arrival of the next child's ``Device |`` log line on an
  unbuffered pipe (the flight recorder has no event there);
- ``t_first_step``: the child's clock after ``block_until_ready`` of its
  first optimizer step (one host, one wall clock);
- inside them, the flight recorder's own ``signal``, ``ckpt_save``,
  ``ckpt_restore`` and ``compile`` records.

The harness's own work inside an interval — the digest of the state it
takes before the save and after the restore, for ``correct`` — is stamped by
the child and taken out of drain and of resume: the program does not pay it.
"""

import json
import os


def read_events(path: str) -> list:
    out = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        pass  # torn tail of a killed process
    except OSError:
        pass
    return out


def first_of(events: list, kind: str):
    for ev in events:
        if ev.get("kind") == kind:
            return ev
    return None


def segments(t_kill: float, t_exit: float, t_device: float,
             t_first_step: float, prev_events: list, next_events: list,
             drain_harness_s: float = 0.0, resume_harness_s: float = 0.0
             ) -> dict:
    """One cycle's segments in seconds. Sub-segments whose record is
    missing are None; drain, hand-over and resume never are.
    ``*_harness_s`` is the harness's own work inside the interval."""
    seg = {
        "drain_s": t_exit - t_kill - drain_harness_s,
        "handover_s": t_device - t_exit,
        "resume_s": t_first_step - t_device - resume_harness_s,
        "harness_s": drain_harness_s + resume_harness_s,
    }
    sig = first_of([e for e in prev_events if e.get("t", 0) >= t_kill - 1],
                   "signal")
    save = None
    for ev in prev_events:
        if ev.get("kind") == "ckpt_save" and ev.get("fault"):
            save = ev
    seg["notice_s"] = (sig["t"] - t_kill) if sig else None
    seg["save_s"] = save.get("dur") if save else None
    if sig and save and save.get("dur") is not None:
        # the ckpt_save record is emitted after write + manifest sweep
        seg["save_other_s"] = ((save["t"] - sig["t"]) - save["dur"]
                               - drain_harness_s)
        seg["exit_s"] = t_exit - save["t"]
    else:
        seg["save_other_s"] = seg["exit_s"] = None
    restore = first_of(next_events, "ckpt_restore")
    compile_ = first_of(next_events, "compile")
    seg["restore_s"] = restore.get("dur") if restore else None
    seg["compile_s"] = compile_.get("dur") if compile_ else None
    if restore and restore.get("dur") is not None:
        seg["pre_restore_s"] = (restore["t"] - restore["dur"]) - t_device
    else:
        seg["pre_restore_s"] = None
    known = [seg[k] for k in ("restore_s", "compile_s", "pre_restore_s")]
    seg["first_step_s"] = (seg["resume_s"] - sum(known)
                           if None not in known else None)
    seg["cycle_s"] = seg["drain_s"] + seg["resume_s"]
    return seg


def cycle_record(index: int, prev, nxt, t_kill: float, work: str) -> dict:
    """Segments of one cycle plus what ``correct`` compares about it."""
    ev_dir = os.path.join(work, "ckpts", "events")
    saved, restored = prev.event("saved"), nxt.event("restored")
    seg = segments(
        t_kill, prev.t_exit, nxt.t_device_line
        if nxt.t_device_line is not None else float("nan"),
        nxt.event("first_step_done")["t"],
        read_events(os.path.join(ev_dir, f"events_{prev.job}.jsonl")),
        read_events(os.path.join(ev_dir, f"events_{nxt.job}.jsonl")),
        drain_harness_s=saved.get("digest_s", 0.0),
        resume_harness_s=(restored or {}).get("digest_s", 0.0))
    batch = nxt.event("batch")
    seg.update(
        index=index, from_job=prev.job, to_job=nxt.job,
        saved_step=saved["step"], saved_digest=saved["digest"],
        saved_calls=saved.get("calls"),
        restored_step=restored["step"] if restored else None,
        restored_digest=restored["digest"] if restored else None,
        first_batch_step=batch["step"] if batch else None,
        first_batch_crc=batch["crc"] if batch else None,
        resubmitted=os.path.exists(
            os.path.join(work, "resubmitted_" + prev.job)))
    return seg


def recover_cycle_s(cycles: list) -> float:
    """All the recovery time over all the recoveries: no median, no
    best-of."""
    return sum(c["cycle_s"] for c in cycles) / len(cycles)


def mean_of(cycles: list, key: str):
    vals = [c[key] for c in cycles if c.get(key) is not None]
    return sum(vals) / len(vals) if vals else None
