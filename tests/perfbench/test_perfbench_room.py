"""The room a ``model_config`` PR needs: a REAL cell of a family that is not
Llama's comes into a copy of the real tree — the real ``BENCHMARK.json``,
``perfbench/`` and the stand-in files — by new files and appended entries
alone, and every check that reads the manifest, the bucket check and the CPU
rehearsal pass on that copy. Such a PR may edit nothing under the
benchmark's paths, so each of these would have stopped it while it was
written against today's three Llama cells: the stand-ins listed in a
helper, lists held by equality, every cell's family held to ``llama``,
Llama's sizes asked of every configuration and ``vocab_size`` refused with
the widths, the bucket list a copy of the program's table, and no way for
a reader to a counter of the program's.

Two cells are added. ``toy-16x.toy-steady`` is of ``toy_family.py`` (layers
of two kinds, stacked experts, a state that is no KV cache; ``reduced``
names depth, the experts held and the vocabulary's slice). The program
builds only its own model class, so that cell cannot train through
``run.py``; ``alpaca7b-d4.preempt``, of the Llama family's text under
another name, is the one rehearsed, through its stand-in, and its new reader
prints a counter's change through the door."""

import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import perfbench_checks as C  # noqa: E402
import perfbench_rehearsal as R  # noqa: E402
from perfbench.lib import manifest  # noqa: E402

TRAIN_CELL = "mistral7b-d4.preempt"          # whose lists the new cells join
TOY_CELL, ALPACA_CELL = "toy-16x.toy-steady", "alpaca7b-d4.preempt"
DOOR_READER, DOOR_COUNTER = "tokens_trained_counted", "ftl_train_tokens_total"
NO_FLASH_KERNEL = ("flash_attn_roofline",)   # a count the toy has not


def write_json(path: str, data) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    assert not os.path.exists(path), f"{path} was there: only new files"
    with open(path, "w") as fh:
        json.dump(data, fh, indent=1)


def grow(root: str) -> None:
    """What a ``model_config`` PR brings, twice over: new files, and
    entries appended to ``BENCHMARK.json``. Nothing that was there is
    opened for writing but the manifest."""
    bench = C.bench_of(root)

    def there(sub, name):
        return manifest.load_json(os.path.join(bench, sub, name + ".json"))

    # --- a family that is not Llama's ------------------------------------
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                os.path.join(bench, "families", "toy.py"))
    toy = {"width": 16, "depth": 3, "experts": 4, "vocab_size": 64,
           "source": "test://toy/config.json",
           "reduced": ["depth", "experts", "vocab_size"],
           "published": {"depth": 27, "experts": 256, "vocab_size": 512},
           "deployment": "one of 64 chips that share a layer: 4 of 256 "
                         "experts, an eighth of the vocabulary",
           "program": {"family": "toy"}}
    write_json(os.path.join(bench, "configs", "toy-16x.json"), toy)
    write_json(os.path.join(bench, "configs", "tiny-toy.json"),
               dict(toy, depth=2, experts=2, reduced=[], published={}))
    write_json(os.path.join(bench, "traffic", "toy-steady.json"),
               dict(there("traffic", "tiny-preempt1"), cycles=0,
                    why="steady steps of a family with no KV cache"))
    write_json(os.path.join(bench, "limits", TOY_CELL + ".json"),
               there("limits", "tiny.tiny-preempt1"))
    write_json(R.stand_in_file(root, TOY_CELL), ["tiny-toy.tiny-preempt1"])
    # --- the Llama text under another name, which the program can train ---
    shutil.copy(os.path.join(bench, "families", "llama.py"),
                os.path.join(bench, "families", "alpaca.py"))
    for was, now in (("mistral-7b-v0.3-d4", "alpaca-7b-d4"),
                     ("tiny", "tiny-alpaca")):
        cfg = there("configs", was)
        write_json(os.path.join(bench, "configs", now + ".json"),
                   dict(cfg, program=dict(cfg["program"], family="alpaca")))
    write_json(os.path.join(bench, "limits", ALPACA_CELL + ".json"),
               there("limits", TRAIN_CELL))
    write_json(os.path.join(bench, "limits",
                            "tiny-alpaca.tiny-preempt1.json"),
               there("limits", "tiny.tiny-preempt1"))
    write_json(R.stand_in_file(root, ALPACA_CELL),
               ["tiny-alpaca.tiny-preempt1"])
    # --- the entries, appended --------------------------------------------
    path = os.path.join(root, "BENCHMARK.json")
    bm = manifest.load_json(path)
    mistral = next(c for c in bm["configs"]
                   if c["name"] == "mistral-7b-v0.3-d4")
    bm["configs"] += [
        {"name": "toy-16x", "source": toy["source"],
         "file": "perfbench/configs/toy-16x.json",
         "reduced": toy["reduced"], "why": "a family that is not Llama's"},
        dict(mistral, name="alpaca-7b-d4",
             file="perfbench/configs/alpaca-7b-d4.json")]
    bm["workloads"] += [
        {"name": TOY_CELL, "config": "toy-16x", "traffic": "toy-steady",
         "chips": 1, "why": "added: layers of two kinds, stacked experts"},
        {"name": ALPACA_CELL, "config": "alpaca-7b-d4", "traffic": "preempt",
         "chips": 1, "why": "added: a second family the program can train"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if TRAIN_CELL in m.get("workloads", ()):
            m["workloads"] += [c for c in (TOY_CELL, ALPACA_CELL)
                               if not (c == TOY_CELL
                                       and m["name"] in NO_FLASH_KERNEL)]
    with open(path, "w") as fh:
        json.dump(bm, fh, indent=1)
    # --- a reader of a counter, through the door --------------------------
    R.add_counter_reader(root, DOOR_READER, "train", DOOR_COUNTER,
                         "train_tok_s", [TOY_CELL, ALPACA_CELL])


GROWS = ("manifest.configs", "manifest.workloads", "manifest.end_to_end",
         "manifest.per_layer")


def appended_only(was, now, where="manifest") -> list:
    """What differs between two manifests otherwise than by appending: to
    a list of entries, or to an entry's ``workloads``."""
    if isinstance(was, dict) and isinstance(now, dict):
        if set(was) != set(now):
            return [f"{where}: keys {sorted(set(was) ^ set(now))}"]
        return [d for k in was
                for d in appended_only(was[k], now[k], f"{where}.{k}")]
    if isinstance(was, list) and isinstance(now, list):
        if len(now) < len(was):
            return [f"{where}: shrank"]
        if len(now) > len(was) and not (where in GROWS
                                        or where.endswith(".workloads")):
            return [f"{where}: grew"]
        return [d for i, (a, b) in enumerate(zip(was, now))
                for d in appended_only(a, b, f"{where}[{i}]")]
    return [] if was == now else [f"{where}: {was!r} -> {now!r}"]


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """(the copy's root, sha256 of every file that was there, its manifest
    as it was)."""
    root = C.copy_tree(ROOT, tmp_path_factory.mktemp("pb_room"))
    before = C.sha256_of_files(root)
    manifest_before = C.manifest_of(root)
    grow(root)
    return root, before, manifest_before


# ---------------------------------------------------------------- stand-ins
def test_every_real_cell_has_its_stand_in_file():
    C.check_stand_ins(ROOT)


def test_a_cell_without_its_stand_in_file_fails_one_check_that_names_it(
        tmp_path):
    root = C.copy_tree(ROOT, tmp_path / "copy")
    os.remove(R.stand_in_file(root, "internlm2-1.8b.chat"))
    with pytest.raises(AssertionError, match=re.escape(os.path.join(
            "add tests", "perfbench", "stand_ins",
            "internlm2-1.8b.chat.json"))):
        C.check_stand_ins(root)
    # ... and no helper falls over: the other cells are rehearsed as before
    checkout = R.make_checkout(tmp_path / "checkout", root=root)
    tiny = C.manifest_of(checkout)
    assert [w["name"] for w in tiny["workloads"]] == [
        "tiny.tiny-preempt", "tiny.tiny-preempt1", "tiny.tiny-longdecode"]
    C.check_rehearsal_follows(root, checkout)
    # a stand-in that names a configuration nobody added is named too
    with open(R.stand_in_file(root, "internlm2-1.8b.chat"), "w") as fh:
        json.dump(["tiny-mamba.tiny-chat"], fh)
    with pytest.raises(AssertionError, match=re.escape(os.path.join(
            "perfbench", "configs", "tiny-mamba.json"))):
        C.check_stand_ins(root)


def test_no_test_file_maps_real_cells_to_stand_ins():
    cells = "|".join(re.escape(c) for c in C.cells_of(ROOT))
    mapping = re.compile(rf"""["'](?:{cells})["']\s*:\s*\[""")
    for name in sorted(os.listdir(HERE)):
        if name.endswith(".py"):
            with open(os.path.join(HERE, name)) as fh:
                assert not mapping.search(fh.read()), name
    assert sorted(os.listdir(os.path.join(ROOT, R.STAND_INS))) == sorted(
        c + ".json" for c in C.cells_of(ROOT))


# ---------------------------------------------------------------- the proof
def test_the_cells_came_in_by_new_files_and_appended_entries_alone(grown):
    root, before, manifest_before = grown
    after = C.sha256_of_files(root)
    changed = [p for p, sha in before.items() if after.get(p) != sha]
    assert changed == ["BENCHMARK.json"]
    assert appended_only(manifest_before, C.manifest_of(root)) == []
    added = sorted(set(after) - set(before))
    assert added == sorted([
        "perfbench/families/toy.py", "perfbench/families/alpaca.py",
        "perfbench/configs/toy-16x.json", "perfbench/configs/tiny-toy.json",
        "perfbench/configs/alpaca-7b-d4.json",
        "perfbench/configs/tiny-alpaca.json",
        "perfbench/traffic/toy-steady.json",
        f"perfbench/limits/{TOY_CELL}.json",
        f"perfbench/limits/{ALPACA_CELL}.json",
        "perfbench/limits/tiny-alpaca.tiny-preempt1.json",
        f"perfbench/metrics/{DOOR_READER}.py",
        f"tests/perfbench/stand_ins/{TOY_CELL}.json",
        f"tests/perfbench/stand_ins/{ALPACA_CELL}.json"])
    # the copy started as the repo is
    for sub in ("perfbench", R.STAND_INS):
        real = C.sha256_of_files(os.path.join(ROOT, sub))
        assert real == {os.path.relpath(p, sub): sha
                        for p, sha in before.items()
                        if p.startswith(sub + os.sep)}, sub
    # ... and the yardstick's own comparison of manifests sees an edit
    edited = C.manifest_of(root)
    edited["end_to_end"][0]["bound"] = 0.02
    assert appended_only(manifest_before, edited) == [
        "manifest.end_to_end[0].bound: 0.01 -> 0.02"]
    edited = C.manifest_of(root)
    edited["paths"].append("elsewhere")
    edited["per_layer"][0]["workloads"].insert(0, TOY_CELL)
    assert appended_only(manifest_before, edited) == [
        "manifest.paths: grew",
        f"manifest.per_layer[0].workloads[0]: {TRAIN_CELL!r} -> "
        f"{TOY_CELL!r}"]


@pytest.mark.parametrize("check", [
    C.check_data_files, C.check_program_trace_lists, C.check_families,
    C.check_stand_ins], ids=lambda f: f.__name__)
def test_every_manifest_level_check_holds_on_the_grown_tree(grown, check):
    check(grown[0])


def test_the_grown_tree_holds_what_todays_list_would_have_refused(
        grown, tmp_path):
    root = grown[0]
    cells = C.cells_of(root)
    toy = cells[TOY_CELL]
    assert toy.family == "toy" and toy.kind == "train"
    assert "vocab_size" in toy.config["reduced"]
    fam, d = C.family_and_dims(root, toy.config)
    assert "head_dim" not in d and fam.check_dims(d) == []
    reported = {m["name"] for m in toy.end_to_end()}
    assert reported == {"train_tok_s", "setup_s"}
    train_lists = {m["name"] for m in cells[TRAIN_CELL].per_layer()}
    assert {m["name"] for m in toy.per_layer()} == (
        train_lists - set(NO_FLASH_KERNEL)) | {DOOR_READER}
    assert {m["name"] for m in cells[ALPACA_CELL].per_layer()} == (
        train_lists | {DOOR_READER})
    # a width is still refused, and the roofline of a kernel the family
    # has no count for is named
    bm = C.manifest_of(root)
    entry = next(c for c in bm["configs"] if c["name"] == "toy-16x")
    assert manifest.check_reduced(dict(entry, reduced=["width_dim"]),
                                  toy.config)
    by_name = {m["name"]: m for m in bm["per_layer"]}
    by_name["flash_attn_roofline"]["workloads"].append(TOY_CELL)
    worse = str(tmp_path)
    with open(os.path.join(worse, "BENCHMARK.json"), "w") as fh:
        json.dump(bm, fh)
    os.symlink(C.bench_of(root), C.bench_of(worse))
    with pytest.raises(AssertionError, match="lists flash_attn_roofline: "
                       "families/toy.py lacks"):
        C.check_families(worse)


GAINED_SCOPE = "state_mixer"       # what the program PR of the cell opens


def test_a_scope_the_table_gained_sorts_the_grown_trees_ops(
        grown, tmp_path, monkeypatch):
    from perfbench.lib import program_records

    table, opened = C.the_programs_table_gains(monkeypatch, GAINED_SCOPE)
    C.check_buckets_follow(table, opened, str(tmp_path))
    C.check_accepted_buckets_kept(program_records.scopes())
    C.check_accepted_buckets_kept(program_records.read_scopes(str(tmp_path)))
    C.check_no_copy_of_the_programs_tables(grown[0], opened)


def test_the_added_cell_is_rehearsed_and_its_reader_reads_the_counter(
        grown, tmp_path):
    root = grown[0]
    checkout = R.make_checkout(tmp_path / "checkout", root=root)
    C.check_rehearsal_follows(root, checkout)
    tiny = C.manifest_of(checkout)
    assert {"tiny-toy", "tiny-alpaca"} <= {c["name"] for c in tiny["configs"]}
    stand_in = R.stand_ins(root)[ALPACA_CELL][0]
    # ... by a program whose scope table has gained a name, in every
    # process the run starts: what the checks of the rehearsals hold of the
    # table beside the trace, they hold of that one
    proc, line = R.run_cell(
        checkout, stand_in, "--rehearsal", trace=1,
        env_extra=C.a_program_whose_table_gained(tmp_path, GAINED_SCOPE))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, line["compared"]
    work = os.path.join(checkout, ".perfbench_work", stand_in)
    C.check_scopes_beside_the_trace(work, gained=[GAINED_SCOPE])
    window = manifest.load_json(os.path.join(work, "window.json"))
    counted = line["metrics"][DOOR_READER]
    assert counted == {"value": window["counters"][DOOR_COUNTER],
                       "unit": "tokens"}
    assert counted["value"] > 0
    # beside everything the cell it stands beside prints on a CPU
    assert {"step_ms_p50", "data_stall_pct", "resume_inside_s", "import_s",
            "ckpt_verify_s", "recover_cycle_s"} <= set(line["metrics"])


def test_a_serving_cell_is_rehearsed_by_a_program_whose_table_gained(
        grown, tmp_path):
    """The serving cells' process writes the table too: the tiny open loop
    of the grown tree, traced, under the gained table."""
    checkout = R.make_checkout(tmp_path / "checkout", root=grown[0])
    proc, line = R.run_cell(
        checkout, "tiny.tiny-chat", "--rehearsal", trace=1,
        env_extra=C.a_program_whose_table_gained(tmp_path, GAINED_SCOPE))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True
    assert {"sched_host_ms_per_step", "decode_dispatch_ms_p50"} <= set(
        line["metrics"])
    C.check_scopes_beside_the_trace(os.path.join(
        checkout, ".perfbench_work", "tiny.tiny-chat"),
        gained=[GAINED_SCOPE])
