"""Shared by the rehearsal tests: a temporary checkout that holds only a
``BENCHMARK.json`` and a copy of ``perfbench/`` — with one configuration,
one traffic mix, one cell and one per-layer metric ADDED as files and
entries, nothing edited — and the command run in it on the CPU. Which tiny
cells stand for a real cell is data: ``stand_ins/<cell>.json``, found by
the cell's name."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}

STAND_INS = os.path.join("tests", "perfbench", "stand_ins")
# the one cell every rehearsal checkout ADDS, with a configuration, a
# traffic mix and a per-layer metric of its own: it reports what this tiny
# cell reports, whatever real cell that one stands for
ADDED_CELL, ADDED_FOLLOWS = "tiny2.tiny-chat2", "tiny.tiny-chat"
ADDED_METRIC = "decode_calls"


def stand_in_file(root: str, cell: str) -> str:
    """Where a real cell's stand-ins are listed: a JSON list of the tiny
    cells (``<configuration>.<traffic>``, files of those names under
    ``perfbench/configs`` and ``perfbench/traffic``) that report what the
    cell reports, in CPU rehearsals. A PR that adds a cell adds this file,
    a tiny configuration of its family and, where it wants other traffic
    than the tiny mixes there are, a tiny traffic file."""
    return os.path.join(root, STAND_INS, cell + ".json")


def stand_ins(root: str = ROOT) -> dict:
    """{real cell: [its stand-ins]} for every cell of ``root``'s manifest,
    read from the stand-in files. A cell whose file is missing has none
    here: every other cell is still rehearsed, and ``missing_stand_ins``
    names the file for the one test that fails."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        cells = [w["name"] for w in json.load(fh)["workloads"]]
    out = {}
    for cell in cells:
        try:
            with open(stand_in_file(root, cell)) as fh:
                out[cell] = list(json.load(fh))
        except OSError:
            out[cell] = []
    return out


def missing_stand_ins(root: str = ROOT) -> list:
    """The files a tree still lacks before each of its cells is rehearsed:
    a cell's stand-in file, a stand-in's configuration or traffic file."""
    missing = []
    for cell, tiny in stand_ins(root).items():
        if not tiny:
            missing.append(os.path.relpath(stand_in_file(root, cell), root))
        for t in tiny:
            config, traffic = t.split(".", 1)
            for sub, name in (("configs", config), ("traffic", traffic)):
                rel = os.path.join("perfbench", sub, name + ".json")
                if not os.path.exists(os.path.join(root, rel)):
                    missing.append(rel)
    return missing


def rehearsal_cells(root: str = ROOT) -> dict:
    """{real cell: the tiny cells of a rehearsal checkout that report what
    it reports}: its stand-ins, and the added cell beside the one it
    follows."""
    return {cell: tiny + [ADDED_CELL] * (ADDED_FOLLOWS in tiny)
            for cell, tiny in stand_ins(root).items()}


def make_checkout(tmp, root: str = ROOT) -> str:
    """tmp/BENCHMARK.json + tmp/perfbench, from the tree at ``root`` (the
    repo's, or a copy of it that a test has added to): its manifest's
    metrics, end to end and per layer, re-pointed at the tiny cells that
    stand for its cells (so the tiny open loop prints what ``chat`` prints
    and the tiny closed loop what ``longdecode`` does), plus one of each
    kind of file added. Configurations and cells are derived from the
    stand-ins' names."""
    out = str(tmp)
    bench = os.path.join(out, "perfbench")
    shutil.copytree(os.path.join(root, "perfbench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    tiny_of = rehearsal_cells(root)

    def follow(metric: dict) -> dict:
        m = dict(metric)
        if "workloads" in m:
            m["workloads"] = [t for w in m["workloads"] for t in tiny_of[w]]
        return m

    # --- added: a configuration, a traffic mix, a cell, a metric ----------
    added_config, added_traffic = ADDED_CELL.split(".", 1)
    with open(os.path.join(bench, "configs", "tiny.json")) as fh:
        tiny2 = json.load(fh)
    tiny2.update(num_hidden_layers=3, reduced=["num_hidden_layers"],
                 published={"num_hidden_layers": 2})
    with open(os.path.join(bench, "configs", added_config + ".json"),
              "w") as fh:
        json.dump(tiny2, fh)
    with open(os.path.join(bench, "traffic", "tiny-chat.json")) as fh:
        chat2 = json.load(fh)
    chat2["rate_rps"] = 10.0
    with open(os.path.join(bench, "traffic", added_traffic + ".json"),
              "w") as fh:
        json.dump(chat2, fh)
    shutil.copy(os.path.join(bench, "limits", ADDED_FOLLOWS + ".json"),
                os.path.join(bench, "limits", ADDED_CELL + ".json"))
    with open(os.path.join(bench, "metrics", ADDED_METRIC + ".py"),
              "w") as fh:
        fh.write('"""added by the rehearsal: decode dispatches counted."""'
                 "\n\n\ndef read(ctx):\n"
                 "    serve = ctx.get('serve')\n"
                 "    return len(serve['spans'].get('decode', ())) "
                 "if serve else None\n")
    per_layer = [follow(m) for m in real["per_layer"]]
    per_layer.append({"name": ADDED_METRIC, "unit": "calls",
                      "better": "lower", "source": "program_counter",
                      "layer": "engine", "moves": "tpot_p95_ms",
                      "workloads": [ADDED_CELL]})
    cells = list(dict.fromkeys(t for tiny in tiny_of.values() for t in tiny))
    configs = []
    for name in dict.fromkeys(c.split(".", 1)[0] for c in cells):
        rel = os.path.join("perfbench", "configs", name + ".json")
        with open(os.path.join(out, rel)) as fh:
            reduced = json.load(fh).get("reduced", [])
        configs.append({"name": name, "source": "test", "file": rel,
                        "reduced": reduced, "why": "rehearsal"})
    manifest = {
        "command": ["python3", "perfbench/run.py"], "paths": ["perfbench"],
        "run_seconds": 3, "configs": configs,
        "workloads": [
            {"name": c, "config": c.split(".", 1)[0],
             "traffic": c.split(".", 1)[1], "chips": 1, "why": "rehearsal"}
            for c in cells],
        "end_to_end": [follow(m) for m in real["end_to_end"]],
        "per_layer": per_layer}
    with open(os.path.join(out, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    return out


def add_counter_reader(root: str, name: str, kind: str, counter: str,
                       moves: str, cells: list) -> None:
    """What a PR that brings a counter's reader brings, to the tree at
    ``root``: ``perfbench/metrics/<name>.py``, which reads the window's
    change of one counter of the program's registry through the door of
    its kind of cell, and its entry appended to ``BENCHMARK.json``. No file
    of ``perfbench/lib/`` knows the counter's name."""
    door = {"train": "(kind.get('window') or {}).get('counters', {})",
            "serve": "kind.get('program_counters', {})"}[kind]
    with open(os.path.join(root, "perfbench", "metrics", name + ".py"),
              "x") as fh:
        fh.write(f'"""The window\'s change of the program\'s ``{counter}``, '
                 f'through the counters door."""\n\n\ndef read(ctx):\n'
                 f"    kind = ctx.get({kind!r})\n"
                 f"    return {door}.get({counter!r}) if kind else None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["per_layer"].append({
        "name": name, "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "door", "moves": moves,
        "workloads": list(cells)})
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)


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
