"""Shared by the rehearsal tests: a temporary checkout that holds only a
``BENCHMARK.json`` and a copy of ``perfbench/`` — with one configuration,
one traffic mix, one cell and one per-layer metric ADDED as files and
entries, nothing edited — and the command run in it on the CPU."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}

# the real cells and the tiny ones that stand for them: a metric is reported
# by the tiny cells of the real cells that report it, end to end or per layer
TINY = {"mistral7b-d4.preempt": ["tiny.tiny-preempt", "tiny.tiny-preempt1"],
        "internlm2-1.8b.longdecode": ["tiny.tiny-longdecode"],
        "internlm2-1.8b.chat": ["tiny.tiny-chat", "tiny2.tiny-chat2"]}


def follow(metric: dict) -> dict:
    m = dict(metric)
    if "workloads" in m:
        m["workloads"] = [t for w in m["workloads"] for t in TINY[w]]
    return m


def make_checkout(tmp) -> str:
    """tmp/BENCHMARK.json + tmp/perfbench: the real manifest's metrics, end
    to end and per layer, re-pointed at tiny cells (so the tiny open loop
    prints what ``chat`` prints and the tiny closed loop what ``longdecode``
    does), plus one of each kind of file added."""
    root = str(tmp)
    bench = os.path.join(root, "perfbench")
    shutil.copytree(os.path.join(ROOT, "perfbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    per_layer = [follow(m) for m in real["per_layer"]]
    # --- added: a configuration, a traffic mix, a cell, a metric ----------
    with open(os.path.join(bench, "configs", "tiny.json")) as fh:
        tiny2 = json.load(fh)
    tiny2["num_hidden_layers"] = 3
    with open(os.path.join(bench, "configs", "tiny2.json"), "w") as fh:
        json.dump(tiny2, fh)
    with open(os.path.join(bench, "traffic", "tiny-chat.json")) as fh:
        chat2 = json.load(fh)
    chat2["rate_rps"] = 10.0
    with open(os.path.join(bench, "traffic", "tiny-chat2.json"), "w") as fh:
        json.dump(chat2, fh)
    shutil.copy(os.path.join(bench, "limits", "tiny.tiny-chat.json"),
                os.path.join(bench, "limits", "tiny2.tiny-chat2.json"))
    with open(os.path.join(bench, "metrics", "decode_calls.py"), "w") as fh:
        fh.write('"""added by the rehearsal: decode dispatches counted."""'
                 "\n\n\ndef read(ctx):\n"
                 "    serve = ctx.get('serve')\n"
                 "    return len(serve['spans'].get('decode', ())) "
                 "if serve else None\n")
    per_layer.append({"name": "decode_calls", "unit": "calls",
                      "better": "lower", "source": "program_counter",
                      "layer": "engine", "moves": "tpot_p95_ms",
                      "workloads": ["tiny2.tiny-chat2"]})
    manifest = {
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 3,
        "configs": [
            {"name": "tiny", "source": "test",
             "file": "perfbench/configs/tiny.json", "reduced": [],
             "why": "rehearsal"},
            {"name": "tiny2", "source": "test",
             "file": "perfbench/configs/tiny2.json",
             "reduced": ["num_hidden_layers"], "why": "added"}],
        "workloads": [
            {"name": w, "config": w.split(".")[0],
             "traffic": w.split(".")[1], "chips": 1, "why": "rehearsal"}
            for cells in TINY.values() for w in cells],
        "end_to_end": [follow(m) for m in real["end_to_end"]],
        "per_layer": per_layer}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    return root


def run_cell(root, workload, *extra, seconds=2, trace=0, seed=2 ** 31 + 77,
             program=True, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PERFBENCH_CONTROL", None)
    if program:
        env["PERFBENCH_PROGRAM_ROOT"] = ROOT
    else:
        env.pop("PERFBENCH_PROGRAM_ROOT", None)
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), *extra], cwd=root, env=env, capture_output=True,
        text=True, timeout=timeout)
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return proc, last


def notes_of(proc) -> dict:
    out = {}
    for line in proc.stderr.splitlines():
        if line.startswith("perfbench note | ") and ": " in line:
            k, v = line[len("perfbench note | "):].split(": ", 1)
            out[k] = v
    return out
