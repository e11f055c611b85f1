"""A model family is a file found by name (``perfbench/families/<family>.py``):
the Llama family is the first such file and nothing moved when it became one
(leaves, weights, the reference's floats and the counts are held to
constants recorded from the tree before the move); a second family comes in
by files alone, end to end through the one command; a family that is not
Llama's fills the same interface; and no file of the harness outside
``families/`` holds a Llama name."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import perfbench_checks as C  # noqa: E402
import perfbench_rehearsal as R  # noqa: E402
from perfbench.lib import manifest, weights  # noqa: E402

BENCH = os.path.join(ROOT, "perfbench")


def config_file(name):
    return manifest.load_json(os.path.join(BENCH, "configs", name + ".json"))


# ------------------------------------------------------------ found by name
def test_a_configuration_names_its_family_and_none_means_llama(tmp_path):
    for name in ("mistral-7b-v0.3-d4", "internlm2-1.8b", "tiny"):
        cfg = config_file(name)
        assert "family" not in cfg["program"]          # no accepted file edited
        d = weights.dims_of(cfg)
        assert d["family"] == "llama"
        assert weights.family_of(d) is manifest.load_family("llama")
    named = dict(config_file("tiny"), program=dict(
        config_file("tiny")["program"], family="llama"))
    assert weights.dims_of(named) == weights.dims_of(config_file("tiny"))
    # the accepted cells are Llama's by name; a later cell is of the family
    # its configuration names, which loads and fills the interface
    C.check_families(ROOT)
    # a directory of the test's own
    os.makedirs(tmp_path / "families")
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                tmp_path / "families" / "toy.py")
    toy = manifest.load_family("toy", str(tmp_path))
    assert toy.dims_of({"width": 8, "depth": 2, "experts": 2,
                        "vocab_size": 32})["vocab"] == 32
    assert manifest.load_family("toy", str(tmp_path)) is toy    # once


def test_an_unknown_family_names_the_file_it_looked_for(tmp_path):
    cfg = dict(config_file("tiny"), program={"family": "mamba9"})
    with pytest.raises(SystemExit) as e:
        weights.dims_of(cfg)
    assert os.path.join("perfbench", "families", "mamba9.py") in str(e.value)
    with pytest.raises(SystemExit):
        manifest.load_family("../lib/weights")     # a name, never a path
    # ... and through the cell, before anything is run
    root = tmp_path / "co"
    os.makedirs(root / "perfbench" / "configs")
    with open(root / "perfbench" / "configs" / "x.json", "w") as fh:
        json.dump(cfg, fh)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump({"configs": [{"name": "x",
                                "file": "perfbench/configs/x.json"}],
                   "workloads": [{"name": "x.tiny-chat", "config": "x",
                                  "traffic": "tiny-chat", "chips": 1}]}, fh)
    with pytest.raises(SystemExit) as e:
        manifest.Cell("x.tiny-chat", str(root))
    assert "mamba9.py" in str(e.value)


@pytest.mark.parametrize("was,now,said", [
    ("def paged_read_bytes(", "def paged_bytes(",
     r"lists paged_attn_roofline: families/llama.py lacks "
     r"\['paged_read_bytes'\]"),
    ("def serve_flops(", "def flops_served(",
     r"families/llama.py lacks \['serve_flops'\]"),
])
def test_a_family_that_lacks_part_of_the_interface_is_named(tmp_path, was,
                                                            now, said):
    root = C.copy_tree(ROOT, tmp_path)
    path = os.path.join(root, "perfbench", "families", "llama.py")
    with open(path) as fh:
        text = fh.read()
    assert was in text
    with open(path, "w") as fh:
        fh.write(text.replace(was, now))
    with pytest.raises(AssertionError, match=said):
        C.check_families(root)


def test_the_training_cells_parent_stays_off_jax():
    """``kind_train`` runs in the process that may not hold the chip: for
    every training cell there is it finds the family, reads its sizes and
    its counts and the scope table a traced child left, and imports no
    JAX."""
    trained = {cell.config_name for cell in C.cells_of(ROOT).values()
               if cell.kind == "train"}
    files = [os.path.join(ROOT, c["file"])
             for c in C.manifest_of(ROOT)["configs"] if c["name"] in trained]
    assert files
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from perfbench.lib import kind_train, manifest, weights\n"
        "from perfbench.lib import program_records\n"
        "for path in %r:\n"
        "    d = weights.dims_of(manifest.load_json(path))\n"
        "    weights.param_count(d)\n"
        "    weights.family_of(d).train_flops_per_token(d, 4096)\n"
        "manifest.load_reader('train_mfu_pct')({'peaks': None, 'e2e': {}})\n"
        "program_records.read_scopes('.')\n"
        "from perfbench.metrics import _program_trace\n"
        "print(json.dumps(sorted(m for m in sys.modules if m == 'jax' or "
        "m.startswith('jax.'))))\n" % (ROOT, files))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == []


# ------------------------------------------------------------ nothing moved
# Recorded on the tree before the move (commit cadc47b, CPU): sha256 of the
# leaves table ``[[path, shape, kind], ...]`` in its order, and the counts.
LEAVES = {
    "mistral-7b-v0.3-d4": (
        "5ea0f8944d9a3d2daf94afd2e540463d6e21d807caee46d46621aafea2aa46c4",
        39, 1_140_887_552),
    "internlm2-1.8b": (
        "be75009efaa8a4680f6706f4e8d39e052c6db16082c7354dfc73d0a18339000f",
        219, 1_889_110_016),
    "tiny": (
        "d92ca39120b1b592beea1cb62e73ce934cdafab78f506487356ef92492966a7e",
        21, 164_160),
}
DIMS = {
    "mistral-7b-v0.3-d4": {
        "dim": 4096, "n_layers": 4, "n_heads": 32, "n_kv_heads": 8,
        "head_dim": 128, "hidden": 14336, "vocab": 32768,
        "rope_theta": 1000000.0, "norm_eps": 1e-05},
    "internlm2-1.8b": {
        "dim": 2048, "n_layers": 24, "n_heads": 16, "n_kv_heads": 8,
        "head_dim": 128, "hidden": 8192, "vocab": 92544,
        "rope_theta": 1000000.0, "norm_eps": 1e-05},
    "tiny": {
        "dim": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "hidden": 192, "vocab": 512, "rope_theta": 10000.0,
        "norm_eps": 1e-05},
}
TREE_SHA = {
    "bfloat16":
        "d72fd06c5609b606563575b52b51118fefb20fb4dcd96b4cd7708c659bdf1e6a",
    "float32":
        "e76b13891bb7502e7076b6023c51e1538bcad0e835ad7110d1802acdc8c95578",
}
REF_LOSS = [6.915256500244141, 6.626068353652954]
REF_GRAD_NORM_RAW = [18.79674069841388, 17.539283209991215]
REF_GRAD_NORMS_SHA = (
    "d424e8232b2b1aeea6211114345a8599723fcd656cfd616a4920c87d329dc2db")
REF_CHANGE_NORMS_SHA = (
    "78dbfd37b11695fc539d25d3aa69bcff32cb9bf7d0637f32f023ec0b0c6964bf")
INT8_LOSS = [6.90173077583313, 6.624701738357544]
FORWARD_SHA = (
    "77cad66f2f620bf44f7fc455bab6ae280eba313a4b2066682eafb68587f6e749")


def sha_of_norms(norms: dict) -> str:
    return hashlib.sha256(json.dumps(sorted(
        (k, repr(v)) for k, v in norms.items())).encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_sizes_leaves_and_counts_through_the_family_are_what_they_were(name):
    sha, n_leaves, n_params = LEAVES[name]
    d = weights.dims_of(config_file(name))
    assert {k: v for k, v in d.items() if k != "family"} == DIMS[name]
    leaves = weights.all_leaves(d)
    table = json.dumps([[p, list(s), k] for p, (s, k) in leaves.items()])
    assert hashlib.sha256(table.encode()).hexdigest() == sha
    assert len(leaves) == n_leaves
    assert weights.param_count(d) == n_params
    assert {k for _, k in leaves.values()} == {"dense", "scale", "embed"}


@pytest.mark.parametrize("dtype", sorted(TREE_SHA))
def test_the_seeds_weights_are_the_same_bits(dtype):
    import jax
    import jax.numpy as jnp

    d = weights.dims_of(config_file("tiny"))
    flat = weights.flatten(weights.make_param_tree(
        jax.random.PRNGKey(7), d, jnp.dtype(dtype)))
    h = hashlib.sha256()
    for p in sorted(flat):
        h.update(p.encode())
        h.update(np.asarray(flat[p]).tobytes())
    assert h.hexdigest() == TREE_SHA[dtype]


def two_steps():
    rng = np.random.default_rng(3)
    toks = rng.integers(3, 100, size=(2, 2, 17)).astype(np.int32)
    return [(t[:, :-1], t[:, 1:]) for t in toks]


def test_the_references_floats_are_the_same_to_the_last_bit():
    import jax
    import jax.numpy as jnp

    from perfbench.lib import reference

    d = weights.dims_of(config_file("tiny"))
    key = jax.random.PRNGKey(11)
    out = reference.run_train_reference(key, d, 1e-3, 0, two_steps(),
                                        dtype=jnp.bfloat16)
    assert out["loss"] == REF_LOSS
    assert out["grad_norm_raw"] == REF_GRAD_NORM_RAW
    assert sha_of_norms(out["grad_norms"]) == REF_GRAD_NORMS_SHA
    assert sha_of_norms(out["change_norms"]) == REF_CHANGE_NORMS_SHA
    ctl = reference.run_train_reference(
        key, d, 1e-3, 0, two_steps(), mm=reference.MATMULS["int8"],
        dtype=jnp.bfloat16)
    assert ctl["loss"] == INT8_LOSS
    logits = weights.family_of(d).forward_logits(
        jax.random.PRNGKey(7), d, np.arange(3, 40, dtype=np.int32), [5, 36],
        reference.mm_f32, jnp.bfloat16)
    assert hashlib.sha256(
        np.asarray(logits).tobytes()).hexdigest() == FORWARD_SHA


# ------------------------------------------- no Llama name outside families/
LLAMA_NAMES = re.compile(
    r"models\.llama|attention/w[qkvo]|feed_forward/w[123]|n_kv_heads|"
    r"ffn_dim_multiplier|multiple_of|ffn_hidden_dim|rope_theta|"
    r"TransformerConfig")


def test_no_llama_name_in_the_harness_outside_families():
    hits = []
    for sub in ("lib", "metrics"):
        for dirpath, _, files in os.walk(os.path.join(BENCH, sub)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    for n, line in enumerate(fh, 1):
                        if LLAMA_NAMES.search(line):
                            hits.append(f"{os.path.relpath(path, ROOT)}:"
                                        f"{n}: {line.strip()}")
    assert hits == []
    with open(os.path.join(BENCH, "families", "llama.py")) as fh:
        assert len(LLAMA_NAMES.findall(fh.read())) > 20   # it finds them


# ------------------------------------------------ a family that is not Llama
TOY = {"width": 16, "depth": 3, "experts": 4, "vocab_size": 64,
       "program": {"family": "toy"}}


@pytest.fixture
def toy_bench(tmp_path, monkeypatch):
    """A bench directory of the test's own that holds one family file."""
    os.makedirs(tmp_path / "families")
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                tmp_path / "families" / "toy.py")
    monkeypatch.setattr(manifest, "BENCH_DIR", str(tmp_path))
    return weights.dims_of(TOY)


def test_a_family_has_layers_by_index_and_kinds_of_its_own(toy_bench):
    import jax
    import jax.numpy as jnp

    d = toy_bench
    assert d["family"] == "toy" and "n_kv_heads" not in d
    leaves = weights.all_leaves(d)
    assert leaves["layer_0/b"] == ((16,), "bias")            # leading layer
    assert "layer_0/experts" not in leaves
    assert leaves["layer_2/experts"] == ((4, 16, 16), "expert_dense")
    assert weights.param_count(d) == (64 * 16 + 16 * 16 + 16
                                      + 2 * (16 + 4 * 16 * 16) + 16 * 64)
    key = jax.random.PRNGKey(5)
    flat = weights.flatten(weights.make_param_tree(key, d, jnp.float32))
    assert set(flat) == set(leaves)
    # stacked over experts: the fan-in is axis 1, not the experts' axis 0
    assert float(jnp.std(flat["layer_1/experts"])) == pytest.approx(
        1 / 4, rel=0.1)
    assert float(jnp.mean(flat["layer_1/decay"])) == pytest.approx(
        1.0, abs=0.1)
    # a leaf of every kind is a function of seed, path and shape alone
    again = weights.make_leaf(key, "layer_1/experts", (4, 16, 16),
                              "expert_dense", jnp.float32, "toy")
    assert (again == flat["layer_1/experts"]).all()
    with pytest.raises(ValueError):
        weights.make_leaf(key, "layer_1/experts", (4, 16, 16),
                          "expert_dense", jnp.float32)
    # the program's side: a model class and a preset that are the family's
    fam = weights.family_of(d)
    tree = fam.model_class()(fam.preset(TOY)).init(key)["params"]
    assert jax.tree_util.tree_structure(tree) == jax.tree_util.tree_structure(
        weights.make_param_tree(key, d, jnp.float32))


def test_a_family_trains_under_the_shared_optimizer_half(toy_bench):
    import jax
    import jax.numpy as jnp

    from perfbench.lib import reference, train_compare

    d = toy_bench
    rng = np.random.default_rng(9)
    toks = rng.integers(0, 64, size=(3, 2, 13)).astype(np.int32)
    batches = [(t[:, :-1], t[:, 1:]) for t in toks]
    key = jax.random.PRNGKey(3)
    out = reference.run_train_reference(key, d, 1e-2, 0, batches,
                                        dtype=jnp.float32)
    assert len(out["loss"]) == 3 and out["loss"][2] < out["loss"][0]
    assert set(out["grad_norms"]) == set(weights.all_leaves(d))
    assert all(v > 0 for v in out["change_norms"].values())
    # the first loss is the family's own forward pass, read another way
    fam = weights.family_of(d)
    x, y = batches[0]
    nll = []
    for row, labels in zip(x, y):
        logits = np.asarray(fam.forward_logits(
            key, d, row, np.arange(len(row)), reference.mm_f32,
            jnp.float32), np.float64)
        lse = np.log(np.exp(logits).sum(-1))
        nll.append(lse - logits[np.arange(len(row)), labels])
    assert out["loss"][0] == pytest.approx(np.mean(nll), rel=1e-5)
    # ... and the comparison that decides ``correct`` takes it as it is
    g = train_compare.gaps(out, out)
    assert g["loss_gap"] == 0 and g["change_norm_gap"] == 0
    ctl = reference.run_train_reference(
        key, d, 1e-2, 0, batches, mm=reference.MATMULS["int8"],
        dtype=jnp.float32)
    assert train_compare.gaps(ctl, out)["loss_gap"] > 0


def test_the_mfu_readers_ask_the_family_for_its_counts(toy_bench):
    d = toy_bench
    mm = 16 * 16 + 2 * 4 * 16 * 16 + 16 * 64
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"dims": d, "peaks": peaks, "traffic": {"sequence_length": 128},
           "e2e": {"train_tok_s": 1e6}}
    assert manifest.load_reader("train_mfu_pct", BENCH)(ctx) == pytest.approx(
        100 * 6 * mm * 1e6 / 197e12)
    ctx = {"dims": d, "peaks": peaks, "serve": {
        "window_s": 2.0, "counters": {"decode_tokens": 30,
                                      "prefill_tokens": 70, "decode_ctx": 999,
                                      "prefill_ctx": 999}}}
    assert manifest.load_reader("serve_mfu_pct", BENCH)(ctx) == pytest.approx(
        100 * 2 * mm * 100 / 2.0 / 197e12)


# ------------------------------------- a second family, by files alone, e2e
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The rehearsal checkout with one family file and one configuration
    file ADDED — the Llama family's text under another name, which imports
    nothing of ``families/llama.py`` — and no file that was there edited."""
    root = R.make_checkout(tmp_path_factory.mktemp("pb_family"))
    bench = os.path.join(root, "perfbench")
    before = {}
    for dirpath, _, files in os.walk(bench):
        for f in files:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    shutil.copy(os.path.join(bench, "families", "llama.py"),
                os.path.join(bench, "families", "alpaca.py"))
    with open(os.path.join(bench, "configs", "tiny.json")) as fh:
        cfg = json.load(fh)
    cfg["program"]["family"] = "alpaca"
    with open(os.path.join(bench, "configs", "tiny-alpaca.json"), "w") as fh:
        json.dump(cfg, fh)
    for cell in ("tiny-preempt1", "tiny-chat"):
        shutil.copy(os.path.join(bench, "limits", f"tiny.{cell}.json"),
                    os.path.join(bench, "limits",
                                 f"tiny-alpaca.{cell}.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bm = json.load(fh)
    bm["configs"].append({"name": "tiny-alpaca", "source": "test",
                          "file": "perfbench/configs/tiny-alpaca.json",
                          "reduced": [], "why": "added family"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [w.replace("tiny.", "tiny-alpaca.")
                               for w in m["workloads"]
                               if w in ("tiny.tiny-preempt1",
                                        "tiny.tiny-chat")]
    bm["workloads"] += [{"name": f"tiny-alpaca.{t}", "config": "tiny-alpaca",
                         "traffic": t, "chips": 1, "why": "added family"}
                        for t in ("tiny-preempt1", "tiny-chat")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bm, fh)
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path
    with open(os.path.join(bench, "families", "alpaca.py")) as fh:
        imports = [l for l in fh if re.match(r"\s*(import|from)\s", l)]
    assert imports and not [l for l in imports if "families" in l]
    return root


def test_added_family_trains_end_to_end_with_the_same_numbers(checkout):
    seed = 2 ** 31 + 1234
    lines = {}
    for cell in ("tiny.tiny-preempt1", "tiny-alpaca.tiny-preempt1"):
        proc, line = R.run_cell(checkout, cell, "--rehearsal", seed=seed)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert line["correct"] is True, line["compared"]
        assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
        lines[cell] = (line, R.notes_of(proc))
    (a, notes_a), (b, notes_b) = lines.values()
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap",
            "frozen_unexpected"} <= set(b["compared"])
    # the same seed, the same weights, the same equations: the same floats
    assert a["compared"] == b["compared"]
    assert notes_a["loss_program_vs_reference"] == notes_b[
        "loss_program_vs_reference"]
    assert notes_a["gaps"] == notes_b["gaps"]


def test_added_family_serves_end_to_end(checkout):
    seed = 2 ** 31 + 4321
    gaps = {}
    for cell in ("tiny.tiny-chat", "tiny-alpaca.tiny-chat"):
        proc, line = R.run_cell(checkout, cell, "--rehearsal", seed=seed,
                                trace=int(cell.startswith("tiny-alpaca")))
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert line["correct"] is True and line["failed"] == 0
        gap = line["compared"]["logit_gap_max"]
        assert gap["ok"] and gap["value"] <= 0.01
        gaps[cell] = gap
        if cell.startswith("tiny-alpaca"):      # its readers read, too
            assert "decode_step_ms_p50" in line["metrics"]
    # which requests finish, and so which are sampled, follows the clock:
    # the numbers agree in kind; the reference itself agrees to the bit
    assert gaps["tiny.tiny-chat"]["limit"] == gaps[
        "tiny-alpaca.tiny-chat"]["limit"]
    code = (
        "import sys, json, hashlib; sys.path.insert(0, %r)\n"
        "import numpy as np, jax, jax.numpy as jnp\n"
        "from perfbench.lib import manifest, weights, serve_compare\n"
        "from perfbench.lib import reference as R\n"
        "rng = np.random.default_rng(1)\n"
        "sample = [{'prompt': rng.integers(3, 500, size=p), 'tokens': "
        "list(rng.integers(3, 500, size=t))} for p, t in ((40, 9), (7, 20))]\n"
        "out = {}\n"
        "for name in ('tiny', 'tiny-alpaca'):\n"
        "    d = weights.dims_of(manifest.load_json("
        "'perfbench/configs/' + name + '.json'))\n"
        "    logits = serve_compare.reference_logits(jax.random.PRNGKey(5), "
        "d, sample, R.mm_f32, jnp.float32)\n"
        "    out[d['family']] = hashlib.sha256(b''.join(np.asarray(l)"
        ".tobytes() for l in logits)).hexdigest()\n"
        "print(json.dumps(out))\n" % checkout)
    out = subprocess.run([sys.executable, "-c", code], cwd=checkout,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    shas = json.loads(out.stdout.splitlines()[-1])
    assert set(shas) == {"llama", "alpaca"}
    assert shas["llama"] == shas["alpaca"]
