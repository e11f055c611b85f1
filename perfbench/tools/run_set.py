#!/usr/bin/env python3
"""Run a set of benchmark runs one after another, each a fresh process of
the one command, and keep each run's last line, wall time and compared
numbers (``chiprun_out/perfbench/<tag>.jsonl``). Prints one compact line a
run and, per metric, the spread of each set by the bounds' rule. Not run by
the driver.

    python3 perfbench/tools/run_set.py --workload <cell> --seconds 45 \
        --seeds 21,22,23 --sets 2 [--trace-seeds 31,32,33] --tag preempt
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--tag", default="set")
    ap.add_argument("--out", default="chiprun_out/perfbench")
    ap.add_argument("--extra", default="")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.tag + ".jsonl")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
    plan += [(-1, int(s), 1) for s in args.trace_seeds.split(",") if s]
    sets = {}
    for k, seed, trace in plan:
        t0 = time.time()
        cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--trace", str(trace)]
        cmd += args.extra.split()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.time() - t0
        lines = [l for l in proc.stdout.splitlines() if l.strip()]
        try:
            last = json.loads(lines[-1]) if lines else None
        except ValueError:
            last = None
        notes = [l for l in proc.stderr.splitlines()
                 if l.startswith("perfbench")]
        rec = {"set": k, "seed": seed, "trace": trace, "rc": proc.returncode,
               "wall_s": wall, "line": last, "notes": notes}
        with open(path, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
        if last is None:
            print(f"set {k} seed {seed} trace {trace}: rc {proc.returncode} "
                  f"wall {wall:.0f}s NO RESULT\n"
                  + proc.stderr[-3000:], flush=True)
            continue
        vals = {m: v["value"] for m, v in last["metrics"].items()}
        cmpd = {n: c["value"] for n, c in last["compared"].items()
                if c["value"] not in (0, None)}
        print(f"set {k} seed {seed} trace {trace}: rc {proc.returncode} wall "
              f"{wall:.0f}s correct {last['correct']} attempted "
              f"{last['attempted']} failed {last['failed']} "
              f"mem {last['device'].get('memory_peak_bytes', 0) / 1e9:.2f}GB "
              f"busy {last['device'].get('busy_s')} "
              f"win {last['device'].get('window_s')} | "
              + " ".join(f"{m}={v:.6g}" for m, v in vals.items())
              + " | " + " ".join(f"{n}={v:.4g}" for n, v in cmpd.items()),
              flush=True)
        for n in notes:
            if any(w in n for w in ("reference_s", "ttft_p95", "late_p95",
                                    "requests_in_window", "queue_at_close",
                                    "logit_gap_mean", "tokens_off",
                                    "sample_", "gaps", "control_")):
                print("   " + n[len("perfbench note | "):], flush=True)
        if not trace:
            for m, v in vals.items():
                sets.setdefault(k, {}).setdefault(m, []).append(v)
        if trace and last.get("breakdown"):
            print("   breakdown " + json.dumps(last["breakdown"])[:1500],
                  flush=True)
    for k, metrics in sets.items():
        for m, v in metrics.items():
            if len(v) >= 3:
                q = statistics.quantiles(v, n=4)
                med = statistics.median(v)
                print(f"set {k} {m}: median {med:.6g} spread "
                      f"{(q[2] - q[0]) / med:.5f} min {min(v):.6g} max "
                      f"{max(v):.6g} n {len(v)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
