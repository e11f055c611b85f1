"""The checks that read a tree's manifest, as functions of the tree's root:
the tests call them on the repo, and ``test_perfbench_room.py`` calls the
same ones on a copy of it that has grown by files and entries. Each holds
the RULE a later cell has to keep, not the list of cells there are today.
A check raises ``AssertionError`` with what is wrong."""

import ast
import glob
import hashlib
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import perfbench_rehearsal as R  # noqa: E402
from perfbench.lib import manifest  # noqa: E402

# what every family file fills (PERF.md section 3, "how a family comes in")
FAMILY_INTERFACE = ("dims_of", "preset_kwargs", "preset", "model_class",
                    "all_leaves", "forward_logits", "batch_logits",
                    "LossAndGrads", "train_flops_per_token", "serve_flops")
KINDS_DRAWN_BY_THE_HARNESS = {"dense", "scale", "embed"}
# a cell that lists one of these asks its family for these counts
COUNTS_A_READER_ASKS_FOR = {
    "flash_attn_roofline": ("flash_attn_flops", "flash_attn_bytes"),
    "paged_attn_roofline": ("paged_read_bytes",),
}
# PR 24's fourteen readers: the kind of cell each reads, the cells each
# list had when it was accepted (a list may grow, by cells of that kind)
PROGRAM_TRACE_READERS = {
    "serve": ({"sched_host_ms_per_step", "decode_dispatch_ms_p50",
               "kv_read_dev_share_pct", "kv_write_dev_share_pct",
               "sample_dev_share_pct", "paged_attn_roofline",
               "serve_unscoped_dev_share_pct"},
              ["internlm2-1.8b.longdecode", "internlm2-1.8b.chat"]),
    "train": ({"mlp_dev_share_pct", "head_ce_dev_share_pct",
               "optimizer_dev_share_pct", "train_unscoped_dev_share_pct",
               "resume_inside_s", "import_s", "ckpt_verify_s"},
              ["mistral7b-d4.preempt"]),
}
FIRST_FIFTEEN = [
    "proc_start_s", "step_ms_p50", "recover_cycle_s", "compile_warm_s",
    "save_s", "restore_s", "data_stall_pct", "flash_attn_roofline",
    "attn_dev_share_pct", "train_mfu_pct", "train_dev_idle_pct",
    "ttft_p95_ms", "decode_step_ms_p50", "serve_mfu_pct",
    "serve_dev_idle_pct"]
# the program's scope table as the accepted cells' device shares were read
# under it (PR 24's, which PR 27's and PR 28's ledger lines were sorted by).
# A program PR ADDS to its table; these names stay, in this relative order
# (first match decides an op's bucket), and these stay opened by the program
ACCEPTED_SCOPES = {
    "scopes": ["kv_write", "kv_read", "rope", "sample", "loss_head",
               "grad_clip", "optimizer", "feed_forward", "attention",
               "tok_embeddings", "output", "attention_norm", "ffn_norm",
               "norm"],
    "opened": ["kv_write", "kv_read", "rope", "sample", "loss_head",
               "grad_clip", "optimizer"]}
# the accepted cells are of the Llama family by name; a later cell is of
# whatever family its configuration names
ACCEPTED_LLAMA_CELLS = ("mistral7b-d4.preempt", "internlm2-1.8b.longdecode",
                        "internlm2-1.8b.chat")


def copy_tree(root: str, tmp) -> str:
    """What the manifest-level checks read of a tree, copied to ``tmp``:
    ``BENCHMARK.json``, ``perfbench/`` and the stand-in files."""
    out = str(tmp)
    os.makedirs(out, exist_ok=True)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), out)
    shutil.copytree(os.path.join(root, "perfbench"), bench_of(out),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(root, R.STAND_INS),
                    os.path.join(out, R.STAND_INS))
    return out


def sha256_of_files(root: str) -> dict:
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def bench_of(root: str) -> str:
    return os.path.join(root, "perfbench")


def manifest_of(root: str) -> dict:
    return manifest.load_json(os.path.join(root, "BENCHMARK.json"))


def cells_of(root: str) -> dict:
    return {w["name"]: manifest.Cell(w["name"], root, bench_of(root))
            for w in manifest_of(root)["workloads"]}


def family_and_dims(root: str, config: dict):
    """A configuration's family file under ``root`` and its sizes there."""
    fam = manifest.load_family(manifest.family_name(config), bench_of(root))
    return fam, fam.dims_of(config)


# ---------------------------------------------------------------- the checks
def check_data_files(root: str) -> None:
    """Every data file loads, every name is permitted, every metric has its
    reader and moves a metric its cells report, every configuration's sizes
    hold by its family's own statement and its cut names no width."""
    bench, dir_ = manifest_of(root), bench_of(root)
    assert manifest.check_names(bench) == []
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for path in glob.glob(os.path.join(dir_, "*", "*.json")):
        manifest.load_json(path)
        rel = os.path.relpath(path, root)
        assert all(c.isalnum() or c in "_.-/" for c in rel), rel
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in (
            "host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(dir_, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for name, cell in cells_of(root).items():
        assert cell.end_to_end() and len(cell.workload["why"]) <= 200
        names = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer(), name
        for m in cell.per_layer():
            assert m["moves"] in names, (name, m["name"])
    for c in bench["configs"]:
        cfg = manifest.load_json(os.path.join(root, c["file"]))
        assert cfg["source"] == c["source"]
        assert manifest.check_reduced(c, cfg) == []
        fam, d = family_and_dims(root, cfg)
        assert "vocab" in d, c["name"]    # the one size the harness reads
        if hasattr(fam, "check_dims"):
            assert fam.check_dims(d) == [], c["name"]


def check_program_trace_lists(root: str) -> None:
    """PR 24's fourteen metrics: each list still contains the cells it
    had, every cell in it is of the kind the metric reads, each reader
    loads; and the first fifteen entries are where they were."""
    bench, cells = manifest_of(root), cells_of(root)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for kind, (names, had) in PROGRAM_TRACE_READERS.items():
        for name in names:
            listed = by_name[name]["workloads"]
            gone = [w for w in had if w not in listed]
            assert not gone, f"{name} no longer lists {gone}"
            wrong = [w for w in listed if cells[w].kind != kind]
            assert not wrong, f"{name} reads {kind} cells; listed {wrong}"
            assert callable(manifest.load_reader(name, bench_of(root)))
            if kind == "serve":
                assert by_name[name]["moves"] == "tpot_p95_ms"
    assert [m["name"] for m in bench["per_layer"]][:15] == FIRST_FIFTEEN


def check_families(root: str) -> None:
    """Family by cell: the accepted cells are Llama's by name; every cell's
    family loads from the tree and fills the interface, with the counts
    its cell's readers ask for and a draw for each kind of leaf of its
    own."""
    cells = cells_of(root)
    for name in ACCEPTED_LLAMA_CELLS:
        assert cells[name].family == "llama", name
    for name, cell in cells.items():
        fam, d = family_and_dims(root, cell.config)
        lacks = [f for f in FAMILY_INTERFACE if not hasattr(fam, f)]
        assert not lacks, f"families/{cell.family}.py lacks {lacks}"
        listed = {m["name"] for m in cell.per_layer()}
        for reader, counts in COUNTS_A_READER_ASKS_FOR.items():
            if reader in listed:
                lacks = [f for f in counts if not hasattr(fam, f)]
                assert not lacks, (f"{name} lists {reader}: families/"
                                   f"{cell.family}.py lacks {lacks}")
        leaves = fam.all_leaves(d)
        assert leaves, name
        own = {k for _, k in leaves.values()} - KINDS_DRAWN_BY_THE_HARNESS
        assert not own or hasattr(fam, "draw_leaf"), (cell.family, own)
        seq = cell.traffic.get("sequence_length", 128)
        assert fam.train_flops_per_token(d, seq) > 0
        assert fam.serve_flops(d, 1, 1) > 0


def check_stand_ins(root: str) -> None:
    """Every cell has its stand-in file, and every stand-in its tiny
    configuration and traffic file, of the cell's own kind and family."""
    missing = R.missing_stand_ins(root)
    assert not missing, ("add " + ", ".join(missing) + " (a JSON list of "
                         "tiny cells, see perfbench_rehearsal.stand_in_file)")
    cells = cells_of(root)
    for name, tiny in R.stand_ins(root).items():
        for t in tiny:
            config, traffic = t.split(".", 1)
            cfg = manifest.load_json(os.path.join(
                bench_of(root), "configs", config + ".json"))
            mix = manifest.load_json(os.path.join(
                bench_of(root), "traffic", traffic + ".json"))
            assert mix["kind"] == cells[name].kind, (name, t)
            assert manifest.family_name(cfg) == cells[name].family, (name, t)


def check_rehearsal_follows(root: str, checkout: str) -> None:
    """Each tiny cell of a rehearsal checkout made from ``root`` reports
    what the cell it stands for reports, end to end and per layer, under
    the same bounds: a change to the manifest is rehearsed."""
    real, tiny = manifest_of(root), manifest_of(checkout)

    def reported(bench, group, cell):
        return [m["name"] for m in bench[group]
                if cell in m.get("workloads", [cell])]

    for cell, stand_ins in R.rehearsal_cells(root).items():
        for t in stand_ins:
            assert reported(tiny, "end_to_end", t) == reported(
                real, "end_to_end", cell), (cell, t)
            extra = [R.ADDED_METRIC] if t == R.ADDED_CELL else []
            assert reported(tiny, "per_layer", t) == reported(
                real, "per_layer", cell) + extra, (cell, t)
    bounds = {m["name"]: m["bound"] for m in real["end_to_end"]}
    assert {m["name"]: m["bound"] for m in tiny["end_to_end"]} == bounds


def check_accepted_buckets_kept(got: dict) -> None:
    """A scope table (``{"scopes", "opened"}``, the program's or one written
    beside a trace) keeps the buckets the accepted cells' shares are read
    by: the accepted names are all there in their relative order, among
    whatever a program PR has added anywhere, and the ones the program
    opened it still opens. Renaming, dropping or reordering one moves time
    between the accepted cells' buckets with no edit under ``paths``: that
    is a change to the yardstick, and this is where it shows."""
    table = list(got["scopes"])
    assert len(set(table)) == len(table), table
    kept = [n for n in table if n in ACCEPTED_SCOPES["scopes"]]
    assert kept == ACCEPTED_SCOPES["scopes"], (
        "the accepted buckets, renamed, dropped or reordered: the table "
        f"has them as {kept}")
    assert set(ACCEPTED_SCOPES["opened"]) <= set(got["opened"]), (
        sorted(set(ACCEPTED_SCOPES["opened"]) - set(got["opened"])))
    assert set(got["opened"]) <= set(table)


def check_scopes_beside_the_trace(work_dir: str, gained=()) -> None:
    """What a traced run left beside its trace is the program's table as
    the tracing process imported it (today's; with ``gained`` where the
    process ran :func:`a_program_whose_table_gained`), and it keeps the
    accepted buckets."""
    from perfbench.lib import program_records

    got = program_records.read_scopes(work_dir)
    assert got is not None, f"no {program_records.SCOPES_NAME} in {work_dir}"
    live = program_records.scopes()
    assert got == {"scopes": list(gained) + live["scopes"],
                   "opened": live["opened"] + list(gained)}
    check_accepted_buckets_kept(got)


def check_no_copy_of_the_programs_tables(root: str, opened) -> None:
    """No file of the benchmark holds a copy of the program's scope table.
    The readers' reduction takes the table as an argument, so a copy could
    only be a literal that names the table: one list, tuple, set or dict
    that holds every scope the program opens. A reader names the few
    buckets it sums, a family file its module names; neither is that."""
    def strings(node):
        if isinstance(node, (ast.List, ast.Tuple, ast.Set)):
            parts = node.elts
        elif isinstance(node, ast.Dict):
            parts = node.keys
        else:
            return set()
        return {p.value for p in parts if isinstance(p, ast.Constant)
                and isinstance(p.value, str)}

    for names in (set(opened), set(ACCEPTED_SCOPES["opened"])):
        for path in glob.glob(os.path.join(bench_of(root), "**", "*.py"),
                              recursive=True):
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                assert not names <= strings(node), (
                    f"{os.path.relpath(path, root)}:{node.lineno} holds "
                    "the program's scope table")


GAINED_BY = "fault_tolerant_llm_training_tpu.obs.trace"


def the_programs_table_gains(monkeypatch, name: str):
    """The program's scope table with ``name`` opened by the program ahead
    of the rest, as a program PR that opens a scope leaves it, in this
    process: (table, opened)."""
    from fault_tolerant_llm_training_tpu.obs import trace

    table = {name: ("kernels", "a scope the table gained"), **trace.SCOPES}
    opened = trace._OPENED_HERE + (name,)
    monkeypatch.setattr(trace, "SCOPES", table)
    monkeypatch.setattr(trace, "_OPENED_HERE", opened)
    return table, opened


def a_program_whose_table_gained(tmp, name: str) -> dict:
    """The same, for the processes a rehearsal starts: the environment
    under which every process that imports the program's ``obs/trace.py``
    finds ``name`` ahead of the rest in ``SCOPES`` and among
    ``_OPENED_HERE`` (a ``sitecustomize`` that wraps that one module's
    import; it imports nothing itself, so a parent stays off JAX)."""
    where = os.path.join(str(tmp), "a_program_pr")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "sitecustomize.py"), "w") as fh:
        fh.write(f'''"""A program PR that opens the scope {name!r}."""
import importlib.abc
import importlib.machinery
import sys


class Gained(importlib.abc.MetaPathFinder):
    def find_spec(self, fullname, path, target=None):
        if fullname != {GAINED_BY!r}:
            return None
        spec = importlib.machinery.PathFinder.find_spec(fullname, path)
        run = spec.loader.exec_module

        def exec_module(module):
            run(module)
            module.SCOPES = {{{name!r}: ("kernels", "gained"),
                             **module.SCOPES}}
            module._OPENED_HERE += ({name!r},)

        spec.loader.exec_module = exec_module
        return spec


sys.meta_path.insert(0, Gained())
''')
    path = os.pathsep.join(p for p in (
        where, os.environ.get("PYTHONPATH")) if p)
    return {"PYTHONPATH": path}


def check_buckets_follow(scopes_table: dict, opened: tuple,
                         work_dir: str) -> None:
    """The readers' bucket list is the program's table as the tracing
    process wrote it beside the trace: its names in its order, a device op
    sorted by it, a share read only where an opened scope shows."""
    from perfbench.lib import program_records
    from perfbench.metrics import _program_trace as pt

    program_records.write_scopes(work_dir)
    got = program_records.read_scopes(work_dir)
    assert got == {"scopes": list(scopes_table), "opened": list(opened)}
    ms = 1_000_000
    ops = []
    for i, name in enumerate(got["scopes"]):
        # a later name of the table sits inside the path as well: the op
        # still goes to the FIRST name of the table that is a component
        inner = "/".join(got["scopes"][i:][::-1])
        ops.append([f"fusion.{i}", f"jit(f)/Model/{inner}/mul:", i * ms, ms])
    summary = pt.reduce({"device_ops": {"/device:TPU:0": ops}, "spans": []},
                        got)
    assert summary["buckets"] == {
        name: pytest.approx(0.001) for name in got["scopes"]}
    assert list(summary["buckets"]) == got["scopes"]
    for name in got["scopes"]:
        assert pt.share_pct(summary, name) == pytest.approx(
            100.0 / len(ops))
    only_flax = [n for n in got["scopes"] if n not in got["opened"]]
    if only_flax:
        bare = pt.reduce({"device_ops": {"/device:TPU:0": [
            ["fusion.0", f"jit(f)/Model/{only_flax[0]}/mul:", 0, ms]]},
            "spans": []}, got)
        assert pt.share_pct(bare, only_flax[0]) is None
