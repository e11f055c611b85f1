#!/usr/bin/env python3
"""Readings for the limits that decide ``correct``: the program's numbers
on many seeds (the lower reading), the int8 control's on some (the upper
reading), and a planted fault's. One call, many seeds. Not run by the
driver.

    python3 perfbench/tools/limits_study.py --workload <cell> \
        --seeds 101,102,103,104 --control-seeds 101,102,103 \
        [--fault half_batch --fault-seeds 101,102,103] [--seconds 15]
"""

import argparse
import contextlib
import io
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/perfbench")
    args = ap.parse_args()
    from perfbench import run as entry
    from perfbench.lib import manifest

    root = os.getcwd()
    cell = manifest.Cell(args.workload, root, bench_dir=BENCH)
    cell.program_root = entry.program_root(root)
    os.makedirs(args.out, exist_ok=True)
    out_path = os.path.join(args.out, f"limits_{args.workload}.jsonl")
    plan = [(s, "", s in ints(args.control_seeds)) for s in ints(args.seeds)]
    plan += [(s, args.fault, False) for s in ints(args.fault_seeds)
             if args.fault]
    for seed, fault, control in plan:
        t0 = time.time()
        rec = {"seed": seed, "fault": fault, "control": control}
        if cell.kind == "train":
            from perfbench.lib import train_parent

            chain = train_parent.run_chain(
                cell, seed, 0.0, False, t0, cycles=0,
                require_tpu=not args.rehearsal, fault=fault,
                control="int8" if control else "",
                log=lambda *a: print(*a, file=sys.stderr, flush=True))
            rec["problems"] = chain["problems"]
            try:
                with open(os.path.join(chain["work"],
                                       "compare.json")) as fh:
                    cmp = json.load(fh)
                rec.update(gaps=cmp["gaps"], compared=cmp["compared"],
                           control_gaps=cmp.get("control_gaps"),
                           reference=cmp["reference"],
                           program=cmp["program"],
                           reference_s=cmp["reference_s"])
            except OSError as e:
                rec["problems"].append(str(e))
        else:
            from perfbench.lib import kinds

            ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0,
                                    rehearsal=args.rehearsal, fault=fault,
                                    control="int8" if control else "")
            buf, err = io.StringIO(), io.StringIO()
            with contextlib.redirect_stdout(buf), \
                    contextlib.redirect_stderr(err):
                rc = kinds.runner_for(cell.kind)(cell, ns, t0)
            rec["rc"] = rc
            last = [l for l in buf.getvalue().splitlines() if l.strip()]
            if last:
                line = json.loads(last[-1])
                rec.update(compared=line["compared"], correct=line["correct"],
                           metrics=line["metrics"])
            rec["notes"] = {
                l.split(" | ", 1)[1].split(": ", 1)[0]:
                l.split(": ", 1)[1]
                for l in err.getvalue().splitlines()
                if l.startswith("perfbench note | ") and ": " in l
                and not l.startswith("perfbench note | counters")}
        rec["seconds"] = time.time() - t0
        print(json.dumps(rec), flush=True)
        with open(out_path, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
