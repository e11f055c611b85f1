#!/usr/bin/env python3
"""Replay an open-loop traffic file's schedule through a constant-round
server, on the host alone (no JAX, no chip): what would the window read if
only the decode round changed? It is how a new open-loop cell is checked,
BEFORE it is added, for the artefact that took ``chat`` out of
``serve_tok_s``: under its knee the tokens committed between the window's
edges are the schedule's own tokens plus the backlog carried in at the open
less the backlog carried out at the close, so a faster server reads fewer.
Not run by the driver; its numbers are a model's, never a device's.

The server, as ``Scheduler.step`` at the program's defaults: a step admits
every waiting request into a free slot, one prefill each (its first token is
committed when its prefill ends), then runs one decode round that gives every
active request one token; ``kind_serve.run_open``'s loop submits what is due
between steps and opens and closes the window at a step's edge.

    python3 perfbench/tools/schedule_model.py --traffic chat \
        --round-ms 112.5,61.6 --host-ms 5 --prefill-ms 30,0.09 \
        --prefill-at-round-ms 112.5
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from perfbench.lib import manifest, stats, traffic  # noqa: E402

IDLE_TICK_S = 0.002     # run_open's sleep when nothing is pending


def replay(mix: dict, seconds: float, round_ms: float, host_ms: float = 5.0,
           prefill_ms=(30.0, 0.09)) -> dict:
    """One window of ``seconds`` after the file's ``preroll_s``. ``round_ms``
    is the decode round, ``host_ms`` the host's own time a step, a prefill
    takes ``prefill_ms[0] + prefill_ms[1] * prompt tokens``."""
    preroll = float(mix.get("preroll_s", 0.0))
    horizon = preroll + seconds
    reqs = [{"due": r["t_due"], "prompt": len(r["prompt"]),
             "want": r["max_new_tokens"], "times": []}
            for r in traffic.open_loop(mix, 0, horizon, vocab=8)]
    slots = int(mix["server"]["slots"])
    queue, active = [], []
    now, i = 0.0, 0
    t_open = t_close = None
    while True:
        if t_open is None and now >= preroll:
            t_open = now
        if now >= horizon:
            t_close = now
            break
        while i < len(reqs) and reqs[i]["due"] <= now:
            queue.append(reqs[i])
            i += 1
        if not queue and not active:
            nxt = reqs[i]["due"] if i < len(reqs) else horizon
            now = max(now + IDLE_TICK_S, min(nxt, horizon))
            continue
        while queue and len(active) < slots:
            r = queue.pop(0)
            now += (prefill_ms[0] + prefill_ms[1] * r["prompt"]) / 1e3
            r["times"].append(now)
            active.append(r)
        now += round_ms / 1e3
        for r in active:
            if len(r["times"]) < r["want"]:
                r["times"].append(now)
        active = [r for r in active if len(r["times"]) < r["want"]]
        now += host_ms / 1e3

    def inside(t):
        return t_open < t <= t_close

    tokens = sum(inside(t) for r in reqs for t in r["times"])
    carried = sum(inside(t) for r in reqs if r["due"] < t_open
                  for t in r["times"])
    own = [r for r in reqs if r["due"] >= t_open]
    done = [r for r in reqs if len(r["times"]) == r["want"]
            and inside(r["times"][-1])]
    ttft = [r["times"][0] - r["due"] for r in done]
    tpot = [(r["times"][-1] - r["times"][0]) / (r["want"] - 1)
            for r in done if r["want"] > 1]
    window = t_close - t_open
    return {"round_ms": round_ms, "window_s": window,
            "arrivals": len(reqs), "arrivals_in_window": len(own),
            # the schedule's own number: over the nominal window
            "offered_tok_s": sum(r["want"] for r in own) / seconds,
            "window_tok_s": tokens / window,
            "carried_in_share_pct": 100.0 * carried / tokens,
            "own_tok_s": sum(len(r["times"]) for r in own) / window,
            "tpot_p95_ms": stats.percentile(tpot, 95) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "finished": len(done),
            "in_flight_at_close": len(queue) + len(active)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True,
                    help="name under perfbench/traffic/, or a path")
    ap.add_argument("--round-ms", required=True,
                    help="decode rounds to replay, comma-separated")
    ap.add_argument("--host-ms", type=float, default=5.0)
    ap.add_argument("--prefill-ms", default="30,0.09",
                    help="a,b: a prefill takes a + b x prompt tokens")
    ap.add_argument("--prefill-at-round-ms", type=float, default=0.0,
                    help="the round --prefill-ms was read at: a prefill "
                         "then scales with the round (0: it stays)")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="the window; default BENCHMARK.json's run_seconds")
    args = ap.parse_args()
    path = args.traffic if os.path.isfile(args.traffic) else os.path.join(
        BENCH, "traffic", args.traffic + ".json")
    mix = manifest.load_json(path)
    if mix.get("loop") != "open":
        print(f"{path}: not an open loop, nothing to replay",
              file=sys.stderr)
        return 2
    seconds = args.seconds
    if not seconds:
        seconds = float(manifest.load_json(os.path.join(
            os.path.dirname(BENCH), "BENCHMARK.json"))["run_seconds"])
    a, b = (float(x) for x in args.prefill_ms.split(","))
    print("model, host only | round ms | tokens/s in window | of them owed "
          "to arrivals before the open % | tokens/s of the window's own "
          "arrivals by the close | tpot p95 ms | ttft p95 ms | finished | "
          "in flight at close")
    for rnd in (float(x) for x in args.round_ms.split(",")):
        k = rnd / args.prefill_at_round_ms if args.prefill_at_round_ms else 1
        row = replay(mix, seconds, rnd, args.host_ms, (a * k, b * k))
        print(f"{rnd:g} | {row['window_tok_s']:.2f} | "
              f"{row['carried_in_share_pct']:.1f} | {row['own_tok_s']:.2f} | "
              f"{row['tpot_p95_ms']:.1f} | {row['ttft_p95_ms']:.0f} | "
              f"{row['finished']} | {row['in_flight_at_close']}")
    print(f"offered by the window's own arrivals: "
          f"{row['offered_tok_s']:.2f} tokens/s, {row['arrivals_in_window']}"
          f" of {row['arrivals']} arrivals, slots {mix['server']['slots']}, "
          f"rate {mix['rate_rps']} requests/s"
          + (f", knee {mix['knee_rps']}" if "knee_rps" in mix else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
