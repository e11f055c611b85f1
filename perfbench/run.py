#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Finds the cell's files by the names in
``BENCHMARK.json`` (``perfbench/lib/manifest.py``), runs the kind of cell
the traffic file names, and prints as its last line on stdout one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last the numbers ``correct`` was
decided from, each beside its limit. Exits non-zero and prints no result
when there is no TPU, fewer chips than the cell asks for, or no program to
measure. ``--rehearsal`` (tests only) lets it run on the CPU; the line it
prints then names the CPU as its device.
"""

import argparse
import os
import sys
import time

T0 = time.time()  # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def program_root(bench_root: str) -> str:
    for cand in (os.environ.get("PERFBENCH_PROGRAM_ROOT"), bench_root,
                 os.path.dirname(HERE)):
        if cand and os.path.isfile(os.path.join(cand, "train.py")) and (
                os.path.isdir(os.path.join(
                    cand, "fault_tolerant_llm_training_tpu"))):
            return os.path.abspath(cand)
    raise SystemExit("perfbench: no program to measure here (train.py and "
                     "fault_tolerant_llm_training_tpu/ not found)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tests only: accept a CPU backend")
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path underneath")
    ap.add_argument("--control", default="", choices=("", "int8"),
                    help="tests and limits study only: judge the reference "
                         "computed in this lower precision in the "
                         "program's place; correct has to come out false")
    args = ap.parse_args(argv)

    from perfbench.lib import kinds, manifest

    bench_root = os.getcwd()
    if not os.path.isfile(os.path.join(bench_root, "BENCHMARK.json")):
        bench_root = os.path.dirname(HERE)
    cell = manifest.Cell(args.workload, bench_root, bench_dir=HERE)
    cell.program_root = program_root(bench_root)
    runner = kinds.runner_for(cell.kind)
    return runner(cell, args, T0)


if __name__ == "__main__":
    sys.exit(main())
