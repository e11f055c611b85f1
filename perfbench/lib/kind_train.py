"""A training cell: steady window in the first child, then the traffic
file's preempt -> resume cycles (``train_parent.run_chain``)."""

import json
import os
import shutil
import subprocess
import sys

from . import recovery, result, train_parent, weights
from .peaks import peaks_of


def continuity(chain: dict, cell) -> dict:
    """What has to be exact across each cycle: the state a child trains
    from is the state its predecessor saved; the step counter continues;
    the first batch after the resume is the one the uninterrupted run would
    have taken next. Numbers are counts of breaks; the limit is 0."""
    tr = cell.traffic
    rows, batch = chain["rows"], chain["batch"]
    state_breaks = step_breaks = data_breaks = requeue_breaks = 0
    for c in chain["cycles"]:
        state_breaks += c["saved_digest"] != c["restored_digest"]
        step_breaks += not (c["saved_step"] == c["restored_step"]
                            == c["first_batch_step"])
        want = train_parent.expected_batch_crc(
            rows, c["saved_step"], batch, tr["sequence_length"])
        data_breaks += c["first_batch_crc"] != want
        requeue_breaks += not c["resubmitted"]
    first = chain["children"][0]
    for ev in first.events:
        if ev["ev"] == "batch":
            want = train_parent.expected_batch_crc(
                rows, ev["step"], batch, tr["sequence_length"])
            data_breaks += ev["crc"] != want
    saved_a = first.event("saved")
    if saved_a is not None:
        # zero lost, zero repeated: every step A dispatched is in its save
        step_breaks += saved_a["step"] != saved_a["calls"]
    return {
        "resume_state_breaks": result.compared_entry(state_breaks, 0,
                                                     exact=True),
        "resume_step_breaks": result.compared_entry(step_breaks, 0,
                                                    exact=True),
        "resume_data_breaks": result.compared_entry(data_breaks, 0,
                                                    exact=True),
        "requeue_breaks": result.compared_entry(requeue_breaks, 0,
                                                exact=True),
    }


def reduce_trace(cell, work: str):
    """Reduce the child's trace in a process of its own, held to the CPU:
    this parent stays off JAX."""
    trace_dir = os.path.join(work, "trace")
    out = os.path.join(work, "trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = os.path.join(cell.bench_dir, "lib", "trace_reduce.py")
    rc = subprocess.call([sys.executable, script, trace_dir, out], env=env)
    if rc != 0 or not os.path.exists(out):
        return None
    with open(out) as fh:
        return json.load(fh)


def run(cell, args, t0: float) -> int:
    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    chain = train_parent.run_chain(
        cell, args.seed, args.seconds, bool(args.trace), t0,
        require_tpu=not args.rehearsal, fault=args.fault,
        control=getattr(args, "control", ""), log=log)
    for child in chain["children"]:
        child.abandon()  # nothing this run started outlives it
    work = chain["work"]
    for p in chain["problems"]:
        log("perfbench: " + p)
    window_path = os.path.join(work, "window.json")
    if not chain.get("backend") or not os.path.exists(window_path):
        return 1  # no chip, or nothing measured: no result
    with open(window_path) as fh:
        window = json.load(fh)
    first = chain["children"][0]
    backend = chain["backend"]
    tr = cell.traffic
    d = weights.dims_of(cell.config)
    cycles = chain["cycles"]

    e2e = {
        "train_tok_s": window["tokens"] / window["window_s"] / cell.chips,
        "setup_s": first.event("window_open")["t"] - t0,
    }
    if cycles:
        e2e["recover_cycle_s"] = recovery.recover_cycle_s(cycles)
    wanted = {m["name"]: m["unit"] for m in cell.end_to_end()}
    metrics = {k: (v, wanted[k]) for k, v in e2e.items() if k in wanted}

    notes = {}
    compared = continuity(chain, cell)
    compared["chain_problems"] = result.compared_entry(
        len(chain["problems"]), 0, exact=True)
    cmp_path = os.path.join(work, "compare.json")
    if os.path.exists(cmp_path):
        with open(cmp_path) as fh:
            cmp = json.load(fh)
        compared.update(cmp["compared"])
        notes["gaps"] = json.dumps(cmp["gaps"])
        notes["reference_s"] = cmp.get("reference_s")
        notes["loss_program_vs_reference"] = json.dumps(
            [cmp["program"]["loss"], cmp["reference"]["loss"]])
    else:
        compared["reference_ran"] = result.compared_entry(0, 1, ok=False)
    correct = all(c["ok"] for c in compared.values())

    device = {"platform": backend["platform"], "kind": backend["kind"],
              "count": backend["count"],
              "memory_peak_bytes": window["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        trace = reduce_trace(cell, work)
        try:
            peaks = peaks_of(backend["kind"])
        except KeyError:
            peaks = None
        ctx = {"cell": cell, "dims": d, "traffic": tr, "chips": cell.chips,
               "peaks": peaks, "e2e": e2e, "trace": trace,
               "train": {"window": window, "cycles": cycles,
                         "batch": chain["batch"]}}
        metrics = result.read_per_layer(cell, ctx)
        if trace and trace.get("busy_s") is not None:
            from .trace_reduce import breakdown as bd

            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            breakdown = bd(trace)
    steps = window["steps"]
    failed_cycles = sum(
        1 for c in cycles if c["saved_digest"] != c["restored_digest"])
    result.emit(correct, steps + len(cycles), failed_cycles, metrics, device,
                compared, breakdown,
                notes=dict(notes, cycles=json.dumps([
                    {k: v for k, v in c.items()
                     if k.endswith("_s")} for c in cycles])))
    if not os.environ.get("PERFBENCH_KEEP_WORK"):
        shutil.rmtree(os.path.join(work, "ckpts"), ignore_errors=True)
        shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)
    return 0
