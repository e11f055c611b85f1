"""The readers of the program's own records (``perfbench/metrics/
_program_trace.py`` and the per-layer metrics built on it): their arithmetic
on small hand-made traces and on a recorded slice of a chip trace, the
bucket list taken from the program's ``SCOPES`` (no copy of it under
``perfbench/``), the door for the program's counters, and both kinds of cell
rehearsed on the CPU with ``--trace 1`` reporting the new names (host spans
and events are there; a CPU trace has no device plane, so every device
share is left out, never 0)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import perfbench_checks as C  # noqa: E402
import perfbench_rehearsal as R  # noqa: E402
from perfbench.lib import manifest, program_records, weights  # noqa: E402
from perfbench.metrics import _program_trace as pt  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_program_trace.json")
# the program's scope table as it was when that trace was recorded (PR 24):
# the buckets the accepted cells are read by, which a later table keeps
RECORDED_SCOPES = C.ACCEPTED_SCOPES
NEW_SERVE = {"sched_host_ms_per_step", "decode_dispatch_ms_p50",
             "kv_read_dev_share_pct", "kv_write_dev_share_pct",
             "sample_dev_share_pct", "paged_attn_roofline",
             "serve_unscoped_dev_share_pct"}
NEW_TRAIN = {"mlp_dev_share_pct", "head_ce_dev_share_pct",
             "optimizer_dev_share_pct", "train_unscoped_dev_share_pct",
             "resume_inside_s", "import_s", "ckpt_verify_s"}


# ------------------------------------------------------------ the yardstick
def test_buckets_are_the_programs_scopes_in_order(tmp_path):
    from fault_tolerant_llm_training_tpu.obs.trace import (
        _OPENED_HERE,
        SCOPES,
    )

    C.check_buckets_follow(SCOPES, _OPENED_HERE, str(tmp_path))
    # today's table keeps the buckets the recorded trace was sorted by, in
    # their order, among whatever it has gained: every share of every
    # accepted cell reads what it read
    C.check_accepted_buckets_kept(program_records.scopes())


def test_a_scope_the_programs_table_gains_is_a_bucket_by_itself(
        tmp_path, monkeypatch):
    """A program PR opens a scope: it adds the name to ``obs/trace.py`` and
    nothing under ``perfbench/`` (which it may not edit) has to follow."""
    table, opened = C.the_programs_table_gains(monkeypatch, "state_mixer")
    C.check_buckets_follow(table, opened, str(tmp_path))
    got = program_records.read_scopes(str(tmp_path))
    C.check_accepted_buckets_kept(got)
    # inside flax's module scope, as a mixer is: the narrower name wins,
    # and it leaves the unscoped and the parent's share
    path = "jit(train_step)/jvp(Model)/layers_1/attention/state_mixer/dot:"
    assert pt.bucket_of(path, got["scopes"]) == "state_mixer"
    assert pt.bucket_of(path, RECORDED_SCOPES["scopes"]) == "attention"


def _table(change) -> dict:
    got = {k: list(v) for k, v in RECORDED_SCOPES.items()}
    change(got["scopes"], got["opened"])
    return got


@pytest.mark.parametrize("change", [
    lambda scopes, opened: None,
    lambda scopes, opened: scopes.insert(8, "state_mixer"),
    lambda scopes, opened: (scopes.append("router"), opened.append("router")),
], ids=["todays", "gained_in_the_middle", "gained_and_opened"])
def test_a_table_that_gains_scopes_keeps_the_accepted_buckets(change):
    C.check_accepted_buckets_kept(_table(change))


@pytest.mark.parametrize("change", [
    lambda scopes, opened: scopes.__setitem__(1, "kv_gather"),
    lambda scopes, opened: scopes.remove("ffn_norm"),
    lambda scopes, opened: scopes.insert(0, scopes.pop(8)),
    lambda scopes, opened: opened.remove("optimizer"),
    lambda scopes, opened: scopes.append("rope"),
], ids=["renamed", "dropped", "reordered", "no_longer_opened", "twice"])
def test_a_table_that_moves_an_accepted_bucket_is_refused(change):
    """Under ``paths``, where a program PR cannot follow: renaming or
    reordering a scope the accepted cells' shares are read by moves their
    time between buckets, and is a change to the yardstick."""
    with pytest.raises(AssertionError):
        C.check_accepted_buckets_kept(_table(change))


def test_no_copy_of_the_programs_scope_table_under_perfbench():
    from fault_tolerant_llm_training_tpu.obs.trace import _OPENED_HERE

    C.check_no_copy_of_the_programs_tables(ROOT, _OPENED_HERE)


def test_a_copy_of_the_table_is_found_and_a_readers_few_names_are_not(
        tmp_path):
    from fault_tolerant_llm_training_tpu.obs.trace import _OPENED_HERE

    root = C.copy_tree(ROOT, tmp_path)
    metrics = os.path.join(root, "perfbench", "metrics")
    with open(os.path.join(metrics, "mixers_dev_share_pct.py"), "w") as fh:
        fh.write('MODULES = {"attention": 1, "output": 2, "norm": 3, '
                 '"feed_forward": 4}\nSUMMED = ("kv_read", "rope", '
                 '"sample", "state_mixer")\n')
    C.check_no_copy_of_the_programs_tables(root, _OPENED_HERE)
    with open(os.path.join(metrics, "_stale.py"), "w") as fh:
        fh.write("\nBUCKETS = %r\n" % (tuple(RECORDED_SCOPES["scopes"]),))
    with pytest.raises(AssertionError, match="_stale.py:2 holds"):
        C.check_no_copy_of_the_programs_tables(
            root, _OPENED_HERE + ("state_mixer",))


def test_the_programs_counters_change_over_a_window():
    """The door's arithmetic, on the program's own registry: every series
    of kind counter, labelled ones by their labels, a gauge never."""
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    plain = REGISTRY.counter("pb_test_assignments_total", "test")
    by = REGISTRY.counter("pb_test_dropped_total", "test")
    REGISTRY.gauge("pb_test_depth", "test").set(3)
    plain.inc(5)
    before = program_records.counters()
    plain.inc(7)
    by.labels(reason="capacity").inc(2)
    delta = program_records.change(before, program_records.counters())
    assert delta["pb_test_assignments_total"] == 7
    assert delta["pb_test_dropped_total{reason=capacity}"] == 2
    assert "pb_test_depth" not in delta
    assert all(v == 0 for k, v in delta.items()
               if not k.startswith("pb_test_"))


def test_manifest_names_the_fourteen_readers_in_their_cells():
    C.check_program_trace_lists(ROOT)


def test_a_list_grows_only_by_cells_of_the_kind_its_metric_reads(tmp_path):
    root = C.copy_tree(ROOT, tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    bench = manifest.load_json(path)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    by_name["mlp_dev_share_pct"]["workloads"].append("internlm2-1.8b.chat")
    with open(path, "w") as fh:
        json.dump(bench, fh)
    with pytest.raises(AssertionError, match="mlp_dev_share_pct reads train"):
        C.check_program_trace_lists(root)
    by_name["mlp_dev_share_pct"]["workloads"] = ["internlm2-1.8b.chat"]
    with open(path, "w") as fh:
        json.dump(bench, fh)
    with pytest.raises(AssertionError, match="no longer lists"):
        C.check_program_trace_lists(root)        # ... and never shrinks


@pytest.mark.parametrize("scope,bucket", [
    ("jit(train_step)/jvp(Transformer)/Transformer.hidden_states/layers_0/"
     "attention/wq/dot_general:", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/Transformer.hidden_states/"
     "layers_3/feed_forward/w2/dot_general:", "feed_forward"),
    ("jit(f)/Transformer/layers_1/attention/kv_read/jit(_take)/gather:",
     "kv_read"),
    ("jit(f)/Transformer/layers_1/attention/kv_write/scatter:", "kv_write"),
    ("jit(f)/Transformer/layers_1/attention/rope/mul:", "rope"),
    ("jit(train_step)/transpose(jvp(loss_head))/loss_head/div:",
     "loss_head"),
    ("jit(train_step)/jvp(Transformer)/loss_head/output/dot_general:",
     "loss_head"),
    ("jit(f)/Transformer/Transformer.head/output/dot_general:", "output"),
    ("jit(f)/Transformer/layers_0/attention_norm/mul:", "attention_norm"),
    ("jit(f)/Transformer/Transformer.head/norm/rsqrt:", "norm"),
    ("jit(train_step)/grad_clip/sqrt:", "grad_clip"),
    ("jit(train_step)/optimizer/mul:", "optimizer"),
    ("jit(train_step)/jvp(Transformer)/Transformer.hidden_states/layers_0/"
     "add:", pt.UNSCOPED),
    ("", pt.UNSCOPED),
    # a name inside another word is not a component
    ("jit(f)/normalize/renorm:", pt.UNSCOPED),
])
def test_bucket_is_the_first_scope_that_is_a_component(scope, bucket):
    assert pt.bucket_of(scope, RECORDED_SCOPES["scopes"]) == bucket


def test_nested_device_ops_give_each_instant_to_the_innermost():
    # a while op [0, 100) with two body ops, and a later plain op
    segs = pt.self_segments([(0, 100, "while"), (10, 30, "a"),
                             (40, 90, "b"), (50, 60, "c"), (120, 130, "d")])
    by = {}
    for s, e, k in segs:
        by[k] = by.get(k, 0) + e - s
    assert by == {"while": 10 + 10 + 10, "a": 20, "b": 40, "c": 10, "d": 10}
    assert sum(by.values()) == 110            # the busy union, not 180
    flat = sorted(segs)
    assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))   # disjoint
    assert pt.overlap([(0, 10), (20, 30)], [[5, 25]]) == 5 + 5


def _spans():
    ms = 1_000_000
    step = ["ftl:sched.step", 0, 20 * ms, "0.0", {"active": 2, "queued": 0}]
    return [
        step,
        ["ftl:sched.admit", 1 * ms, 4 * ms, "0.0", {}],
        ["ftl:engine.prefill", 2 * ms, 3 * ms, "0.0", {"new_tokens": 7}],
        ["ftl:sched.pack", 5 * ms, 6 * ms, "0.0", {}],
        ["ftl:engine.decode", 6 * ms, 16 * ms, "0.0",
         {"live_tokens": 1000, "slots_active": 2, "n": 1}],
        ["ftl:engine.decode.dispatch", 6 * ms, 7 * ms, "0.0", {}],
        ["ftl:engine.decode.sync", 7 * ms, 16 * ms, "0.0", {}],
        ["ftl:sched.bank", 16 * ms, 18 * ms, "0.0", {}],
        # another thread's span over the same time is nobody's child
        ["ftl:data.prefetch", 0, 20 * ms, "0.1", {}],
    ]


def test_self_time_and_children_add_up_to_the_span():
    spans = _spans()
    # the step less its engine spans: 20 - (1 + 10)
    assert pt.self_ms(spans, "ftl:sched.step", ("ftl:engine.",)) == [9.0]
    parts = pt.by_child_ms(spans, "ftl:sched.step")
    whole = parts.pop("_span_")
    assert parts == {"ftl:sched.admit": 3.0, "ftl:sched.pack": 1.0,
                     "ftl:engine.decode": 10.0, "ftl:sched.bank": 2.0,
                     "_self_": 4.0}
    assert sum(parts.values()) == pytest.approx(whole) == 20.0
    assert pt.median_ms(spans, "ftl:engine.decode.dispatch") == 1.0
    assert pt.median_ms(spans, "ftl:engine.nope") is None


def test_reduce_shares_roofline_inputs_and_idle_gaps():
    ms = 1_000_000
    read = "jit(f)/Transformer/layers_0/attention/kv_read/gather:"
    ops = {"/device:TPU:0": [
        ["fusion.1 f32[8]", read, 6 * ms, 4 * ms],
        ["copy.1 bf16[5121,8,16,128]", "", 10 * ms, 4 * ms],
        ["sort.1 f32[8,64]", "jit(f)/sample/sort:", 14 * ms, 1 * ms],
        # a prefill chunk's read, outside every decode round
        ["fusion.2 f32[8]", read, 2 * ms, 1 * ms],
    ]}
    summary = pt.reduce({"device_ops": ops, "spans": _spans() + [
        ["pb:window", 0, 20 * ms, "0.0", {}]]}, RECORDED_SCOPES)
    assert summary["window_s"] == pytest.approx(0.020)
    assert summary["busy_s"] == pytest.approx(0.010)
    assert summary["buckets"] == pytest.approx(
        {"kv_read": 0.005, pt.UNSCOPED: 0.004, "sample": 0.001})
    assert summary["unscoped_ops"] == [
        ["copy.1 bf16[5121,8,16,128]", pytest.approx(0.004)]]
    assert pt.share_pct(summary, "kv_read") == pytest.approx(50.0)
    assert pt.share_pct(summary, pt.UNSCOPED) == pytest.approx(40.0)
    assert pt.share_pct(summary, "optimizer", "grad_clip") == 0.0
    # only the read inside the decode round counts against its live tokens
    assert summary["decode"] == {"rounds": 1, "live_tokens": 1000,
                                 "kv_read_s": pytest.approx(0.004)}
    # a whole gap goes to the innermost span over its middle, as
    # trace_reduce gives gaps to pb: spans: [0, 2) admit, [3, 6) the step's
    # own time, [15, 20) bank
    gaps = dict(summary["idle_gaps"])
    assert gaps == pytest.approx({"ftl:sched.admit": 0.002,
                                  "ftl:sched.step": 0.003,
                                  "ftl:sched.bank": 0.005})
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


def test_no_scope_or_no_device_reads_nothing_not_zero():
    ms = 1_000_000
    bare = pt.reduce({"device_ops": {"/device:TPU:0": [
        ["fusion.1 f32[8]", "", 0, 5 * ms]]}, "spans": []}, RECORDED_SCOPES)
    assert bare["busy_s"] == pytest.approx(0.005)
    # a program that opens no scope of its own (the parent commit's, or an
    # executable an older program left in the compile cache) still carries
    # flax's module names: not "kv_read 0 %", not "half of it unscoped"
    assert pt.share_pct(bare, pt.UNSCOPED) is None
    assert pt.share_pct(bare, "kv_read") is None
    flax_only = pt.reduce({"device_ops": {"/device:TPU:0": [
        ["fusion.1 f32[8]", "jit(f)/Transformer/layers_0/attention/mul:", 0,
         5 * ms], ["copy.1 f32[8]", "", 5 * ms, 5 * ms]]}, "spans": []},
        RECORDED_SCOPES)
    assert flax_only["buckets"] == pytest.approx(
        {"attention": 0.005, pt.UNSCOPED: 0.005})
    for bucket in ("kv_read", "feed_forward", "attention", pt.UNSCOPED):
        assert pt.share_pct(flax_only, bucket) is None
    cpu = pt.reduce({"device_ops": {}, "spans": _spans()}, RECORDED_SCOPES)
    assert cpu["busy_s"] is None and pt.share_pct(cpu, "kv_read") is None
    assert pt.self_ms(cpu["spans"], "ftl:sched.step", ("ftl:engine.",))
    assert pt.reduce({"device_ops": {}, "spans": []},
                     RECORDED_SCOPES)["window_s"] is None
    assert pt.share_pct(None, "kv_read") is None


class _Cell:
    def __init__(self, work):
        self._work = work

    def work_dir(self):
        return self._work


def test_paged_attn_roofline_and_shares_on_the_recorded_chip_trace(tmp_path):
    """Two decode rounds of a traced ``internlm2-1.8b.longdecode`` window
    on one TPU v5 lite, as ``tools/program_trace_report.py --record`` cut
    them (device ops with their scope paths, ``ftl:`` and ``pb:`` spans)."""
    with open(RECORDED) as fh:
        rec = json.load(fh)
    summary = pt.reduce(rec, RECORDED_SCOPES)
    want = rec["expect"]
    assert summary["devices"] == 1
    assert summary["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for bucket, seconds in want["buckets"].items():
        assert summary["buckets"][bucket] == pytest.approx(seconds, rel=1e-9)
    # every instant of the busy time went to exactly one bucket
    assert sum(summary["buckets"].values()) == pytest.approx(
        summary["busy_s"], rel=1e-6)
    assert summary["decode"]["rounds"] == 2
    assert summary["decode"]["live_tokens"] == want["live_tokens"]
    # the readers, through their own door, on this summary
    dims = weights.dims_of(manifest.load_json(os.path.join(
        ROOT, "perfbench", "configs", "internlm2-1.8b.json")))
    work = str(tmp_path)
    os.makedirs(os.path.join(work, "trace", "plugins", "profile", "x"))
    xplane = os.path.join(work, "trace", "plugins", "profile", "x",
                          "t.xplane.pb")
    with open(xplane, "wb") as fh:
        fh.write(b"not read: the cache below answers for it")
    with open(os.path.join(work, program_records.SCOPES_NAME), "w") as fh:
        json.dump(RECORDED_SCOPES, fh)
    with open(os.path.join(work, pt.CACHE_NAME), "w") as fh:
        json.dump({"stamp": [xplane, os.path.getmtime(xplane),
                             os.path.getsize(xplane), RECORDED_SCOPES],
                   "summary": summary}, fh)
    ctx = {"cell": _Cell(work), "serve": {"window_s": 45.0}, "dims": dims,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    read = lambda name: manifest.load_reader(name)(ctx)  # noqa: E731
    kv_bytes = 2 * 24 * 8 * 128 * 2                       # 96 KiB a token
    least = want["live_tokens"] * kv_bytes / 819e9
    roofline = read("paged_attn_roofline")
    assert roofline == pytest.approx(
        100 * least / summary["decode"]["kv_read_s"])
    assert 0 < roofline < 100
    shares = {n: read(n) for n in (
        "kv_read_dev_share_pct", "kv_write_dev_share_pct",
        "sample_dev_share_pct", "serve_unscoped_dev_share_pct")}
    for name, bucket in (("kv_read_dev_share_pct", "kv_read"),
                         ("kv_write_dev_share_pct", "kv_write"),
                         ("sample_dev_share_pct", "sample"),
                         ("serve_unscoped_dev_share_pct", pt.UNSCOPED)):
        assert shares[name] == pytest.approx(
            100 * want["buckets"][bucket] / want["busy_s"])
    assert read("sched_host_ms_per_step") == pytest.approx(
        want["sched_host_ms_per_step"])
    assert read("decode_dispatch_ms_p50") == pytest.approx(
        want["decode_dispatch_ms_p50"])
    # a training reader in a serving cell, and any reader with no trace
    assert read("mlp_dev_share_pct") is None
    # the cache is keyed on the table too: another table reads the trace
    # afresh (this one cannot be read, so the reader finds nothing)
    with open(os.path.join(work, program_records.SCOPES_NAME), "w") as fh:
        json.dump(dict(RECORDED_SCOPES, scopes=["state_mixer"]
                       + RECORDED_SCOPES["scopes"]), fh)
    assert read("kv_read_dev_share_pct") is None
    assert manifest.load_reader("kv_read_dev_share_pct")(
        dict(ctx, cell=_Cell(str(tmp_path / "none")))) is None


def test_unreadable_trace_and_missing_events_read_nothing(tmp_path):
    work = str(tmp_path)
    os.makedirs(os.path.join(work, "trace", "plugins", "profile", "x"))
    with open(os.path.join(work, "trace", "plugins", "profile", "x",
                           "t.xplane.pb"), "wb") as fh:
        fh.write(b"\xff\xff\xff garbage, not an XSpace")
    with open(os.path.join(work, program_records.SCOPES_NAME), "w") as fh:
        json.dump(RECORDED_SCOPES, fh)
    ctx = {"cell": _Cell(work), "serve": {"window_s": 45.0}, "dims": {},
           "peaks": None,
           "train": {"cycles": [{"to_job": "pbB"}]}}
    for name in NEW_SERVE | NEW_TRAIN:
        assert manifest.load_reader(name)(ctx) is None, name
    # an event file of the parent commit's program: no lifecycle kinds
    ev_dir = os.path.join(work, "ckpts", "events")
    os.makedirs(ev_dir)
    with open(os.path.join(ev_dir, "events_pbB.jsonl"), "w") as fh:
        fh.write(json.dumps({"t": 1.0, "kind": "ckpt_restore",
                             "dur": 2.0}) + "\n")
    for name in ("resume_inside_s", "import_s", "ckpt_verify_s"):
        assert manifest.load_reader(name)(ctx) is None, name
    with open(os.path.join(ev_dir, "events_pbB.jsonl"), "a") as fh:
        for ev in ({"t": 10.0, "kind": "imports_done", "dur": 7.5},
                   {"t": 12.0, "kind": "backend_ready"},
                   {"t": 13.0, "kind": "ckpt_verify", "dur": 0.5},
                   {"t": 20.0, "kind": "first_step_done", "resumed": True}):
            fh.write(json.dumps(ev) + "\n")
    assert manifest.load_reader("resume_inside_s")(ctx) == 8.0
    assert manifest.load_reader("import_s")(ctx) == 7.5
    assert manifest.load_reader("ckpt_verify_s")(ctx) == 0.5


# ------------------------------------------- both kinds of cell, on the CPU
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The rehearsal checkout, with two readers ADDED that read a counter
    of the program's registry through the door, one for each kind of cell:
    no file of ``lib/`` knows either counter's name."""
    root = R.make_checkout(tmp_path_factory.mktemp("pb_program_trace"))
    R.add_counter_reader(root, "tokens_trained_counted", "train",
                         "ftl_train_tokens_total", "train_tok_s",
                         ["tiny.tiny-preempt1"])
    R.add_counter_reader(root, "tokens_generated_counted", "serve",
                         "ftl_serve_tokens_generated_total", "tpot_p95_ms",
                         ["tiny.tiny-chat"])
    return root


def test_serving_rehearsal_reports_the_programs_host_spans(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-chat", "--rehearsal",
                            trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True
    got = set(line["metrics"])
    assert {"sched_host_ms_per_step", "decode_dispatch_ms_p50"} <= got
    assert line["metrics"]["sched_host_ms_per_step"]["unit"] == "ms"
    assert 0 < line["metrics"]["sched_host_ms_per_step"]["value"] < 1000
    assert 0 < line["metrics"]["decode_dispatch_ms_p50"]["value"] < 1000
    # a CPU trace holds no device plane: shares are left out, never 0
    assert not got & (NEW_SERVE - {"sched_host_ms_per_step",
                                   "decode_dispatch_ms_p50"})
    assert not got & NEW_TRAIN
    # through the counters door: what the program's registry counted in
    # the untraced part of the window, beside the harness's own count
    assert line["metrics"]["tokens_generated_counted"]["value"] > 0
    # the table the buckets come from lies beside the trace
    C.check_scopes_beside_the_trace(os.path.join(
        checkout, ".perfbench_work", "tiny.tiny-chat"))
    # the cached summary: children and self time add up to the step span
    with open(os.path.join(checkout, ".perfbench_work", "tiny.tiny-chat",
                           pt.CACHE_NAME)) as fh:
        spans = json.load(fh)["summary"]["spans"]
    parts = pt.by_child_ms(spans, "ftl:sched.step")
    whole = parts.pop("_span_")
    assert sum(parts.values()) == pytest.approx(whole, rel=1e-6)
    assert {"ftl:engine.decode", "ftl:sched.pack", "ftl:sched.bank",
            "_self_"} <= set(parts)


def test_training_rehearsal_reports_the_programs_lifecycle_events(checkout):
    proc, line = R.run_cell(checkout, "tiny.tiny-preempt1", "--rehearsal",
                            trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, line["compared"]
    m = line["metrics"]
    assert {"resume_inside_s", "import_s", "ckpt_verify_s"} <= set(m)
    assert not set(m) & (NEW_TRAIN - {"resume_inside_s", "import_s",
                                      "ckpt_verify_s"})
    assert not set(m) & NEW_SERVE
    cycle = json.loads(R.notes_of(proc)["cycles"])[0]
    # the program's own boundaries against the harness's log-line ones
    assert m["resume_inside_s"]["value"] == pytest.approx(
        cycle["resume_s"], abs=2.0)
    assert 0 < m["ckpt_verify_s"]["value"] <= m["restore_s"]["value"]
    assert 0.5 < m["import_s"]["value"] < 300
    # through the counters door: every counter of the program's registry,
    # its change over the window; the stall's own key reads the same counter
    work = os.path.join(checkout, ".perfbench_work", "tiny.tiny-preempt1")
    window = manifest.load_json(os.path.join(work, "window.json"))
    assert window["counters"]["ftl_data_stall_seconds_total"] == (
        pytest.approx(window["data_stall_s"], abs=1e-9))
    assert m["data_stall_pct"]["value"] == pytest.approx(
        100.0 * window["data_stall_s"] / window["window_s"])
    counted = m["tokens_trained_counted"]["value"]
    assert counted == window["counters"]["ftl_train_tokens_total"]
    per_step = window["tokens"] / window["steps"]
    assert counted % per_step == 0 and 0 < counted <= window["tokens"] + (
        2 * per_step)
    C.check_scopes_beside_the_trace(work)
