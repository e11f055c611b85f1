#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once, on the chip: the
highest swept rate at which the tokens/s completed still equal the tokens/s
offered over the window and the backlog at the window's end is no longer
than at its middle. One server build, one window per rate. Not run by the
driver; the cell's traffic file then holds 0.8 x the knee as a number.

    python3 perfbench/tools/knee_sweep.py --workload internlm2-1.8b.chat \
        --rates 2,3,4,5,6 --seconds 40 --seed 11
"""

import argparse
import json
import os
import sys
import time

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/perfbench")
    args = ap.parse_args()
    from perfbench import run as entry
    from perfbench.lib import kind_serve as ks
    from perfbench.lib import manifest, stats, traffic

    root = os.getcwd()
    cell = manifest.Cell(args.workload, root, bench_dir=BENCH)
    cell.program_root = entry.program_root(root)
    sys.path.insert(0, cell.program_root)
    import jax
    import numpy as np

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    spans = ks.Spans()
    engine, sched, d = ks.build_server(cell, args.seed, spans)
    clock = time.monotonic
    rng = np.random.default_rng(args.seed)
    for b in cell.traffic["server"]["prefill_buckets"]:
        sched.submit(Request(id=f"warm.{b}", max_new_tokens=2,
                             prompt=rng.integers(3, d["vocab"], size=min(
                                 b, cell.traffic["server"]["max_len"] - 2)
                             ).astype(np.int32)))
    while sched.pending():
        sched.step()
    print(f"set-up {time.time() - T0:.1f} s", flush=True)
    rows = []
    print("| rate rps | offered tok/s | completed tok/s | ratio | backlog mid"
          " | backlog end | tpot p50 ms | tpot p95 ms | ttft p95 ms | "
          "late p95 ms | requests |")
    print("|" + " --- |" * 11)
    preroll = float(cell.traffic.get("preroll_s", 0.0))
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_rps=rate)
        reqs = traffic.open_loop(mix, args.seed, preroll + args.seconds,
                                 d["vocab"])
        for r in reqs:
            r["id"] = f"k{rate:g}.{r['id']}"
        tracker, qlog, box = ks.Tracker(), [], {}
        sched.completed.clear()

        def open_window():
            box["t_open"] = clock()
            box["tokens0"] = ks.committed_tokens(sched, tracker)

        t_start = clock()
        completed, late, offered = ks.run_open(
            sched, reqs, tracker, t_start, preroll + args.seconds, Request,
            clock, queue_log=qlog, marks=[(preroll, open_window)])
        t_close = clock()
        window = t_close - box["t_open"]
        done_tok = (ks.committed_tokens(sched, tracker)
                    - box["tokens0"]) / window
        inw = [r for r in completed
               if box["t_open"] <= r["finished_at"] <= t_close]
        off_tok = sum(r["max_new_tokens"] for r in reqs
                      if r["t_due"] >= preroll) / window
        tpot = [(r["finished_at"] - r["first_token_at"])
                / (len(r["tokens"]) - 1) for r in inw if len(r["tokens"]) > 1]
        ttft = [r["first_token_at"] - r["t_ref"] for r in inw]
        span = preroll + args.seconds
        mid = [q for t, q in qlog
               if preroll + 0.45 * args.seconds <= t
               <= preroll + 0.55 * args.seconds]
        end = [q for t, q in qlog if t >= span - 0.1 * args.seconds]
        row = {"rate": rate, "offered_tok_s": off_tok,
               "completed_tok_s": done_tok,
               "backlog_mid": sum(mid) / max(len(mid), 1),
               "backlog_end": sum(end) / max(len(end), 1),
               "tpot_p50_ms": (stats.percentile(tpot, 50) or 0) * 1e3,
               "tpot_p95_ms": (stats.percentile(tpot, 95) or 0) * 1e3,
               "ttft_p95_ms": (stats.percentile(ttft, 95) or 0) * 1e3,
               "late_p95_ms": (stats.percentile(late, 95) or 0) * 1e3,
               "requests": len(inw)}
        rows.append(row)
        print(f"| {rate:g} | {off_tok:.1f} | {done_tok:.1f} | "
              f"{done_tok / max(off_tok, 1e-9):.3f} | {row['backlog_mid']:.1f}"
              f" | {row['backlog_end']:.1f} | {row['tpot_p50_ms']:.1f} | "
              f"{row['tpot_p95_ms']:.1f} | {row['ttft_p95_ms']:.0f} | "
              f"{row['late_p95_ms']:.1f} | {len(inw)} |", flush=True)
        ks.drain(sched, tracker, clock, limit=400.0)  # start the next empty
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"knee_{args.workload}.json"),
              "w") as fh:
        json.dump(rows, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
