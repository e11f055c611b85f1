#!/usr/bin/env python3
"""Segment study of the preempt -> resume chain: one chain of N cycles at
the cell's real size, every segment of every cycle printed, with spreads.
Not run by the driver; a later ``benchmark`` issue re-runs it with the same
code.

    python3 perfbench/tools/segment_study.py --workload mistral7b-d4.preempt \
        --cycles 12 --seed 7 [--trace 1] [--rehearsal]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

KEYS = ("notice_s", "save_other_s", "save_s", "exit_s", "drain_s",
        "handover_s", "pre_restore_s", "restore_s", "compile_s",
        "first_step_s", "resume_s", "cycle_s")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cycles", type=int, default=12)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/perfbench")
    args = ap.parse_args()
    from perfbench.lib import manifest, recovery, train_parent
    from perfbench import run as entry

    root = os.getcwd()
    cell = manifest.Cell(args.workload, root, bench_dir=BENCH)
    cell.program_root = entry.program_root(root)
    os.environ["PERFBENCH_KEEP_WORK"] = "1"
    chain = train_parent.run_chain(
        cell, args.seed, args.seconds, bool(args.trace), T0,
        cycles=args.cycles, require_tpu=not args.rehearsal, compare=False,
        budget_s=3300.0,
        log=lambda *a: print(*a, file=sys.stderr, flush=True))
    os.makedirs(args.out, exist_ok=True)
    cycles = chain["cycles"]
    print("problems:", chain["problems"], flush=True)
    print("| cycle | " + " | ".join(k[:-2] for k in KEYS) + " |")
    print("|" + " --- |" * (len(KEYS) + 1))
    for c in cycles:
        print(f"| {c['index']} | " + " | ".join(
            "-" if c[k] is None else f"{c[k]:.2f}" for k in KEYS) + " |")
    summary = {}
    for k in KEYS:
        vals = [c[k] for c in cycles if c[k] is not None]
        if len(vals) >= 2:
            q = statistics.quantiles(vals, n=4)
            summary[k] = {"median": statistics.median(vals), "min": min(vals),
                          "max": max(vals), "iqr": q[2] - q[0],
                          "spread": (q[2] - q[0]) / statistics.median(vals)}
    print("| median | " + " | ".join(
        f"{summary[k]['median']:.2f}" if k in summary else "-"
        for k in KEYS) + " |")
    print("| min | " + " | ".join(
        f"{summary[k]['min']:.2f}" if k in summary else "-"
        for k in KEYS) + " |")
    print("| max | " + " | ".join(
        f"{summary[k]['max']:.2f}" if k in summary else "-"
        for k in KEYS) + " |")
    print("| iqr/median | " + " | ".join(
        f"{summary[k]['spread']:.3f}" if k in summary else "-"
        for k in KEYS) + " |")
    # what a run of two (or three) cycles would read: consecutive groups
    for n in (2, 3):
        groups = [cycles[i:i + n] for i in range(0, len(cycles) - n + 1, n)]
        vals = [recovery.recover_cycle_s(g) for g in groups]
        if len(vals) >= 2:
            q = statistics.quantiles(vals, n=4)
            print(f"recover_cycle_s over {n} cycles: {[round(v, 2) for v in vals]} "
                  f"median {statistics.median(vals):.2f} spread "
                  f"{(q[2] - q[0]) / statistics.median(vals):.4f}")
    first = chain["children"][0]
    try:
        with open(os.path.join(chain["work"], "window.json")) as fh:
            window = json.load(fh)
    except OSError:
        window = None
    open_ev = first.event("window_open")
    print("setup_s:", (open_ev["t"] - T0) if open_ev else None,
          "window:", {k: v for k, v in (window or {}).items()
                      if k not in ("done_t", "readings")})
    if window:
        print("readings:", json.dumps(window.get("readings"))[:3000])
    out = {"cycles": [{k: v for k, v in c.items()} for c in cycles],
           "summary": summary, "window": window,
           "problems": chain["problems"],
           "events": {c.job: c.events for c in chain["children"]},
           "lines": {c.job: c.lines for c in chain["children"]}}
    with open(os.path.join(args.out, "segment_study.json"), "w") as fh:
        json.dump(out, fh, default=str)
    if args.trace:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        dst = os.path.join(args.out, "train_trace.json")
        subprocess.call([sys.executable,
                         os.path.join(BENCH, "lib", "trace_reduce.py"),
                         os.path.join(chain["work"], "trace"), dst,
                         "--describe"], env=env)
    for c in chain["children"]:
        try:
            with open(c.log_path, "rb") as src, open(os.path.join(
                    args.out, os.path.basename(c.log_path)), "wb") as dst_:
                dst_.write(src.read()[-200000:])
        except OSError:
            pass
    return 0 if not chain["problems"] else 1


if __name__ == "__main__":
    sys.exit(main())
