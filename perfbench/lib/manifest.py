"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is (configuration, traffic mix). The configuration is the file the
manifest names; the traffic mix is ``perfbench/traffic/<traffic>.json``; a
per-layer metric is ``perfbench/metrics/<name>.py``; the limits that decide
``correct`` are ``perfbench/limits/<workload>.json``; the model family is
``perfbench/families/<family>.py``, by the name in the configuration file's
``program`` group. Adding a cell, a metric or a family adds files and
entries and edits nothing here.
"""

import functools
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DEFAULT_FAMILY = "llama"  # of a configuration file that names none
# ``reduced`` may never name a width: a hidden, intermediate, expert, head,
# latent, state or projection size, or the experts a token is routed to
WIDTH_SUFFIXES = ("_dim", "_rank", "_size")
WIDTH_KEYS = ("num_experts_per_tok",)
# ... but the rows of the vocabulary held here are the chip's share of a
# layer that a deployment divides over chips, as its experts are, not a
# width (model-configs guide, section 4: "a sliced vocabulary is a smaller
# vocabulary": ids, logits, sampling and loss are over the slice, the
# embedding's and the head's width stay the published hidden size)
SLICED_NOT_WIDTHS = ("vocab_size",)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """Everything one run needs, resolved from names."""

    def __init__(self, workload: str, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in by_name:
            raise SystemExit(f"unknown workload {workload!r}; have "
                             f"{sorted(by_name)}")
        self.workload = by_name[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.workload["config"]]
        self.config_name = cfg_entry["name"]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.family = family_name(self.config)
        load_family(self.family, bench_dir)  # an unknown one ends the run
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        limits_path = os.path.join(bench_dir, "limits", workload + ".json")
        self.limits = (load_json(limits_path)
                       if os.path.exists(limits_path) else {})
        self.kind = self.traffic["kind"]

    def _reports(self, metric: dict, end_to_end: set) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        # no list: every cell that reports the end-to-end metric it moves
        return metric.get("moves", metric["name"]) in end_to_end

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> list:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if self._reports(m, e2e)]

    def work_dir(self) -> str:
        """Scratch inside the checkout, at a fixed path (the compile
        cache's key holds paths), emptied by the run that uses it."""
        return os.path.join(self.root, ".perfbench_work", self.name)


def _load_file(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"[^A-Za-z0-9_]", "_", module_name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """The per-layer metric's own reader: ``metrics/<name>.py`` with
    ``read(ctx) -> number | None``."""
    return _load_file(os.path.join(bench_dir, "metrics", name + ".py"),
                      "perfbench_metric_" + name).read


def family_name(config: dict) -> str:
    """The family a configuration file names in its ``program`` group."""
    return config.get("program", {}).get("family", DEFAULT_FAMILY)


@functools.lru_cache(maxsize=None)
def _family_at(path: str, name: str):
    if not NAME_RE.match(name) or not os.path.isfile(path):
        raise SystemExit(f"unknown model family {name!r}: no file {path}")
    return _load_file(path, "perfbench_family_" + name)


def load_family(name: str, bench_dir: str = None):
    """The model family's own file, ``families/<name>.py`` under
    ``bench_dir`` (this benchmark's own directory where none is given): its
    sizes, the program's model for it, its leaves, its plain reference and
    its counts (``families/llama.py`` lists the names). Loaded once a
    process; needs no JAX until its reference runs."""
    return _family_at(os.path.join(bench_dir or BENCH_DIR, "families",
                                   name + ".py"), name)


def check_reduced(entry: dict, config: dict) -> list:
    """Every problem with what a configuration says it cut (tests call
    this): ``entry`` is its entry in ``BENCHMARK.json``, ``config`` its
    file. The two lists agree, no key is a width, and the file states the
    published value of each key under ``published``."""
    bad = []
    if sorted(entry["reduced"]) != sorted(config.get("reduced", [])):
        bad.append(f"{entry['name']}: reduced {entry['reduced']} in the "
                   f"manifest, {config.get('reduced')} in its file")
    for key in entry["reduced"]:
        if key in WIDTH_KEYS or (key.endswith(WIDTH_SUFFIXES)
                                 and key not in SLICED_NOT_WIDTHS):
            bad.append(f"{entry['name']}: reduced names the width {key!r}")
        if key not in config.get("published", {}):
            bad.append(f"{entry['name']}: its file states no published "
                       f"value of {key!r} (\"published\": {{{key!r}: ...}})")
    return bad


def check_names(manifest: dict) -> list:
    """Every name/unit problem in a manifest (tests call this)."""
    bad = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            if not NAME_RE.match(e["name"]):
                bad.append(f"{group}: name {e['name']!r}")
            if "unit" in e and not UNIT_RE.match(e["unit"]):
                bad.append(f"{group}: unit {e['unit']!r} of {e['name']}")
            if "source" in e and group != "configs" and (
                    e["source"] not in SOURCES):
                bad.append(f"{group}: source {e['source']!r}")
    for w in manifest["workloads"]:
        for k in ("config", "traffic"):
            if not NAME_RE.match(w[k]):
                bad.append(f"workload {w['name']}: {k} {w[k]!r}")
    return bad
