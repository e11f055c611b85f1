"""The program's own tracing: host spans (``ftl:`` TraceAnnotations), device
scope names and lifecycle events, and the one table that names them.

- ``span()`` / ``scope()`` take only names from ``obs/trace.py``'s tables;
- a tiny scheduler run and three tiny train steps, each under the profiler,
  leave every span of their path in the xplane, children inside parents,
  with the arguments the code held (``live_tokens`` is checked against the
  sum the test computes from the scheduler's own state);
- the lowered tiny train step and tiny paged decode program carry every
  scope of ``SCOPES`` in their ``op_name``s;
- a fault -> resume chain writes the lifecycle events in order;
- the tables, the call sites and PERF.md §3 cannot drift apart.
"""

import re
import sys
from pathlib import Path

import numpy as np
import pytest

from _tiny import tiny_cfg

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # perfbench/, the readers' own xplane decoder
PKG = REPO / "fault_tolerant_llm_training_tpu"
LIFECYCLE = ("proc_start", "imports_done", "backend_ready", "ckpt_verify",
             "ckpt_manifest", "first_step_done")


def ftl_spans(trace_dir):
    """[name, start_ns, end_ns, thread, args] of the ``ftl:`` spans in the
    newest xplane under ``trace_dir``, read the way the benchmark's readers
    read them. A thread is a line of a plane, known by its place: two
    threads may carry one name."""
    from perfbench.lib import trace_reduce
    from perfbench.metrics import _program_trace

    raw = _program_trace.load_xplane(trace_reduce.newest_xplane(
        str(trace_dir)))
    return sorted((sp for sp in raw["spans"] if sp[0].startswith("ftl:")),
                  key=lambda sp: (sp[1], -sp[2]))


def inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


def assert_nested(spans, child_name, parent_name):
    parents = [s for s in spans if s[0] == parent_name]
    kids = [s for s in spans if s[0] == child_name]
    assert kids, f"no {child_name} span"
    for k in kids:
        assert any(inside(k, p) for p in parents), (
            f"{child_name} at {k[1]} outside every {parent_name}")


# ------------------------------------------------------------ the two tables
def test_span_and_scope_refuse_names_outside_the_tables():
    from fault_tolerant_llm_training_tpu.obs import trace

    with pytest.raises(ValueError, match="not in obs.trace.SPANS"):
        trace.span("ftl:sched.nap")
    with pytest.raises(ValueError, match="obs.trace.SCOPES"):
        trace.scope("kv_peek")
    # a flax module name is listed, never opened by the program
    with pytest.raises(ValueError):
        trace.scope("attention")
    assert all(n.startswith("ftl:") for n in trace.SPANS)
    with trace.span("ftl:sched.step", active=1,
                    queued=lambda: pytest.fail("evaluated with no "
                                               "profiler running")):
        pass


def test_tables_call_sites_and_perf_md_agree():
    """Every name of SPANS, SCOPES and the new event kinds, and the two span
    counters, is in PERF.md §3;
    every TraceAnnotation / named_scope / span() / scope() call in the
    program uses a name from the tables (literal names only), and every
    name the program may open is opened somewhere."""
    from fault_tolerant_llm_training_tpu.obs import trace

    perf = (REPO / "PERF.md").read_text()
    section = perf.split("## 3.")[1].split("\n## 4.")[0]
    for name in [*trace.SPANS, *trace.SCOPES, *LIFECYCLE,
                 trace.SPAN_SECONDS, trace.SPAN_COUNT]:
        assert f"`{name}`" in section, f"{name} missing from PERF.md §3"
    kinds = (PKG / "obs" / "events.py").read_text().split(
        "class FlightRecorder")[0]
    for kind in LIFECYCLE:
        assert re.search(rf"^#   {kind}\b", kinds, re.M), kind

    used_spans, used_scopes = set(), set()
    sources = [*PKG.rglob("*.py"), REPO / "train.py"]
    for path in sources:
        text = path.read_text()
        if path == PKG / "obs" / "trace.py":
            # the wrappers themselves, and StepTraceAnnotation("train")
            text = text.split("def tracing()")[0]
        for call, arg in re.findall(
                r"\b(span|scope|TraceAnnotation|named_scope)\(\s*([^,)\s]+)",
                text):
            assert arg[0] in "\"'", (
                f"{path}: {call}({arg} ...) — names are literals")
            name = arg.strip("\"'")
            if call in ("span", "TraceAnnotation"):
                assert name in trace.SPANS, f"{path}: span {name}"
                used_spans.add(name)
            else:
                assert name in trace.OPENED_SCOPES, f"{path}: scope {name}"
                used_scopes.add(name)
    assert used_spans == set(trace.SPANS)
    assert used_scopes == set(trace.OPENED_SCOPES)
    assert set(trace.OPENED_SCOPES) < set(trace.SCOPES)
    # the public name, and the old one as its alias for the accepted
    # benchmark (perfbench/lib/program_records.py imports it)
    assert trace._OPENED_HERE is trace.OPENED_SCOPES
    # a new scope sits before the flax module it is opened inside
    order = list(trace.SCOPES)
    for name in ("moe_route", "moe_experts", "moe_shared"):
        assert order.index(name) < order.index("feed_forward")
    assert order.index("index_select") < order.index("attention")


# ------------------------------------------------------------- serving spans
@pytest.fixture(scope="module")
def tiny_engine():
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32))[
        "params"]
    return cfg, InferenceEngine(cfg, params, slots=2, max_len=32,
                                prefill_buckets=(8, 16), kv_layout="paged",
                                kv_block_size=8, prefill_batch=2)


def test_scheduler_run_leaves_every_serving_span(tiny_engine, tmp_path):
    import jax

    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.obs.trace import SPANS

    cfg, engine = tiny_engine
    engine.reset()
    sched = Scheduler(engine, prefill_batch=2)
    rng = np.random.default_rng(5)
    plens = {"r0": 20, "r1": 9, "r2": 11}
    for i, (plen, gen) in enumerate([(20, 5), (9, 6), (11, 4)]):
        sched.submit(Request(
            id=f"r{i}", max_new_tokens=gen,
            prompt=rng.integers(3, cfg.vocab_size, size=plen).tolist()))
    want_live, steps = [], 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        while sched.pending():
            # what the decode round of this step will attend to: for each
            # slot active when the round runs, its prompt and tokens so far
            before = {s: len(st.request.prompt) + len(st.tokens)
                      for s, st in sched.active.items()}
            n_dec = sched.iterations
            sched.step()
            steps += 1
            if sched.iterations > n_dec:
                want_live.append(before)
    finally:
        jax.profiler.stop_trace()
    spans = ftl_spans(tmp_path)
    names = {s[0] for s in spans}
    # (the two ``.stats`` spans are a LatentMoEConfig engine's: their test
    # is test_latent_engine_leaves_its_stats_spans_and_counters)
    serving = {n for n in SPANS if n.startswith(("ftl:sched.",
                                                 "ftl:engine."))
               and not n.endswith(".stats")}
    assert serving <= names, serving - names
    assert not {n for n in names if n.endswith(".stats")}
    assert sum(1 for s in spans if s[0] == "ftl:sched.step") == steps
    for child in ("admit", "prefill_round", "pack", "bank"):
        assert_nested(spans, "ftl:sched." + child, "ftl:sched.step")
    for top in ("decode", "prefill"):
        assert_nested(spans, f"ftl:engine.{top}", "ftl:sched.step")
        for leaf in ("dispatch", "sync"):
            assert_nested(spans, f"ftl:engine.{top}.{leaf}",
                          f"ftl:engine.{top}")
    assert_nested(spans, "ftl:engine.prefill", "ftl:sched.prefill_round")
    # arguments are what the code held; requests admitted by a step decode
    # in the same step, so count what is active once admission is done
    decodes = [s for s in spans if s[0] == "ftl:engine.decode"]
    assert len(decodes) == sched.iterations
    first = [s for s in spans if s[0] == "ftl:sched.step"][0]
    assert int(first[4]["active"]) == 0 and int(first[4]["queued"]) == 3
    lives = [int(s[4]["live_tokens"]) for s in decodes]
    actives = [int(s[4]["slots_active"]) for s in decodes]
    assert all(int(s[4]["n"]) == 1 for s in decodes)
    # a slot that was active before the step attends to prompt + tokens
    # positions (its committed length + the one it writes)
    for live, act, before in zip(lives, actives, want_live):
        if len(before) == act:
            assert live == sum(before.values()), (live, before)
    assert any(len(b) == a for b, a in zip(want_live, actives))
    # every request's every decode position is in some round's live_tokens
    total = sum(plens[c.request_id] * (len(c.tokens) - 1)
                + sum(range(1, len(c.tokens))) for c in sched.completed)
    assert sum(lives) == total
    pre = [s for s in spans if s[0] == "ftl:engine.prefill"]
    assert sum(int(s[4]["new_tokens"]) for s in pre) == 20 + 9 + 11
    assert {int(s[4]["bucket"]) for s in pre} <= {8, 16}


def span_tally() -> dict:
    """{span name: (seconds, count)} of the program's span counters now."""
    from fault_tolerant_llm_training_tpu.obs import trace
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    snap = REGISTRY.snapshot()
    return {name: (snap[trace.SPAN_SECONDS]["series"][f"span={name}"],
                   snap[trace.SPAN_COUNT]["series"][f"span={name}"])
            for name in trace.SPANS}


def test_span_counters_and_the_capture_describe_the_same_spans(
        tiny_engine, tmp_path):
    """A tiny scheduler under a capture with the program's own options (the
    Python tracer off): over the capture, each ``ftl:`` span's count is its
    number of events in the xplane, and its time their summed durations
    within 2 % and 3 us a span. The profiler reads its own clock inside its
    own calls, a microsecond or so from where the program reads the host's
    monotonic clock, on each side of a span: on a CPU host, for the tiny
    scheduler's 40-150 us spans (admission with nothing to admit, packing,
    banking), that alone is 1-3 %.
    tests/test_span_tally.py holds spans of a millisecond to 2 % alone."""
    import jax

    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.obs.trace import (SPANS,
                                                           profile_options)
    cfg, engine = tiny_engine
    engine.reset()
    sched = Scheduler(engine, prefill_batch=2)
    rng = np.random.default_rng(9)
    for i, (plen, gen) in enumerate([(20, 6), (9, 8), (11, 5), (14, 7)]):
        sched.submit(Request(
            id=f"s{i}", max_new_tokens=gen,
            prompt=rng.integers(3, cfg.vocab_size, size=plen).tolist()))
    sched.step()                     # compiles outside the capture
    before = span_tally()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=profile_options())
    try:
        while sched.pending():
            sched.step()
    finally:
        jax.profiler.stop_trace()
    after = span_tally()
    spans = ftl_spans(tmp_path)
    seen = set()
    for name in SPANS:
        events = [s for s in spans if s[0] == name]
        seconds = after[name][0] - before[name][0]
        count = after[name][1] - before[name][1]
        assert count == len(events), (name, count, len(events))
        if events:
            seen.add(name)
            traced = sum(s[2] - s[1] for s in events) / 1e9
            assert abs(seconds - traced) <= 0.02 * traced + 3e-6 * count, (
                name, seconds, traced, count)
    assert {"ftl:sched.step", "ftl:sched.admit", "ftl:sched.pack",
            "ftl:sched.bank", "ftl:engine.decode",
            "ftl:engine.decode.dispatch", "ftl:engine.decode.sync",
            "ftl:engine.prefill"} <= seen, seen
    # and not one event of the Python tracer's
    assert not python_call_events(tmp_path)


def python_call_events(trace_dir) -> int:
    """Events the profiler's Python tracer wrote into the newest xplane
    under ``trace_dir``: their names start with ``$``."""
    from perfbench.lib import trace_reduce
    from perfbench.metrics import _program_trace

    space = _program_trace._xspace_class()()
    with open(trace_reduce.newest_xplane(str(trace_dir)), "rb") as fh:
        space.ParseFromString(fh.read())
    n = 0
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        n += sum(1 for line in plane.lines for ev in line.events
                 if names.get(ev.metadata_id, "").startswith("$"))
    return n


def test_step_seconds_is_bounded_with_a_running_total(tiny_engine):
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Scheduler, _StepSeconds)

    ss = _StepSeconds()
    for i in range(_StepSeconds.KEEP + 10):
        ss.append(0.5)
    assert len(ss) == _StepSeconds.KEEP
    assert ss.total == pytest.approx(0.5 * (_StepSeconds.KEEP + 10))
    ss.clear()                       # what the benchmark harness calls
    assert len(ss) == 0 and ss.total == 0.0 and not ss
    sched = Scheduler(tiny_engine[1], prefill_batch=2)
    assert isinstance(sched.step_seconds, _StepSeconds)
    assert sched.metrics()["tokens_per_sec"] == 0.0


# ------------------------------------------------------------- device scopes
def op_names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))


def components(names) -> set:
    return {tok for n in names
            for tok in re.findall(r"[A-Za-z_][A-Za-z0-9_]*", n)}


def test_lowered_programs_name_every_scope(tiny_engine):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models import Transformer
    from fault_tolerant_llm_training_tpu.obs.trace import SCOPES
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer, make_train_step)

    # bfloat16: the trainer's step at the preset's dtype, lowered, not run
    cfg = tiny_cfg(dtype="bfloat16", vocab_size=259)
    model, opt = Transformer(cfg), make_optimizer(1e-3, 2)

    def init_fn(key):
        params = model.init(key, jnp.zeros((1, 64), jnp.int32))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    train = components(op_names(jax.jit(make_train_step(
        model, opt, 1.0)).lower(state, tok, tok)))
    train_scopes = {"loss_head", "grad_clip", "optimizer", "rope",
                    "attention", "feed_forward", "tok_embeddings", "output",
                    "attention_norm", "ffn_norm", "norm"}
    assert train_scopes <= train, train_scopes - train

    _, engine = tiny_engine
    abstract = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    slots = engine.slots
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    decode = components(op_names(jax.jit(engine._paged_decode_fn).lower(
        abstract(engine.params), abstract(engine.cache),
        jax.ShapeDtypeStruct((slots, engine.max_blocks_per_slot),
                             jnp.int32),
        vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.int32))))
    serve_scopes = {"kv_write", "kv_read", "sample", "rope", "attention",
                    "feed_forward", "tok_embeddings", "output", "norm"}
    assert serve_scopes <= decode, serve_scopes - decode

    # the latent / indexer / window / expert class's decode program
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models import (build_model,
                                                        get_config)

    lcfg = get_config("tiny-latent-moe", vocab_size=64, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    lparams = jax.eval_shape(lambda: build_model(lcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    latent = InferenceEngine(lcfg, jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), lparams), slots=2,
        max_len=32, prefill_buckets=(8,), kv_block_size=8)
    vec = lambda dt: jax.ShapeDtypeStruct((2,), dt)  # noqa: E731
    latent_decode = components(op_names(jax.jit(
        latent._latent_decode_fn).lower(
        abstract(latent.params), abstract(latent.cache),
        jax.ShapeDtypeStruct((2, latent.max_blocks_per_slot), jnp.int32),
        vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.int32))))
    latent_scopes = {"moe_route", "moe_experts", "moe_shared",
                     "index_select", "kv_read", "kv_write", "rope", "sample"}
    assert latent_scopes <= latent_decode, latent_scopes - latent_decode
    seen = train | decode | latent_decode
    assert set(SCOPES) <= seen, set(SCOPES) - seen


def test_latent_engine_leaves_its_stats_spans_and_counters(tmp_path):
    """A LatentMoEConfig engine's rounds return counts beside their tokens:
    each decode round and each prefill call opens its ``.stats`` span after
    the read-back, inside the round's span, with the five counts as args,
    and the same counts reach the counters labelled by phase."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.models import (build_model,
                                                        get_config)
    from fault_tolerant_llm_training_tpu.models.latent_moe import STATS
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    cfg = get_config("tiny-latent-moe", vocab_size=64, dtype=jnp.float32,
                     param_dtype=jnp.float32)
    params = build_model(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 16), jnp.int32))["params"]
    engine = InferenceEngine(cfg, params, slots=2, max_len=48,
                             prefill_buckets=(8, 16), kv_block_size=8)
    sched = Scheduler(engine)
    rng = np.random.default_rng(1)
    for i, (plen, gen) in enumerate([(20, 5), (11, 4)]):
        sched.submit(Request(id=f"r{i}", max_new_tokens=gen,
                             prompt=rng.integers(3, 64, size=plen).tolist()))

    def counters():
        snap = REGISTRY.snapshot()
        return {(n, lab): v for n in (
            "moe_pairs_total", "moe_experts_touched_total",
            "index_keys_scanned_total", "latent_rows_read_total",
            "window_rows_read_total")
            for lab, v in snap.get(n, {"series": {}})["series"].items()}

    before = counters()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while sched.pending():
            sched.step()
    finally:
        jax.profiler.stop_trace()
    spans = ftl_spans(tmp_path)
    assert_nested(spans, "ftl:engine.decode.stats", "ftl:engine.decode")
    assert_nested(spans, "ftl:engine.prefill.stats", "ftl:engine.prefill")
    decodes = [s for s in spans if s[0] == "ftl:engine.decode"]
    dstats = [s for s in spans if s[0] == "ftl:engine.decode.stats"]
    pstats = [s for s in spans if s[0] == "ftl:engine.prefill.stats"]
    assert len(dstats) == len(decodes) == sched.iterations
    assert len(pstats) == 2
    assert all(set(STATS) <= set(s[4]) for s in dstats + pstats)
    # a decode round of ``a`` active slots at committed lengths L: each of
    # the 2 full layers scans L + 1 index keys a slot, reads min(L + 1, 8)
    # rows; each of the 3 sliding layers reads min(L + 1, 9) window rows
    for st, dec in zip(dstats, decodes):
        live, act = int(dec[4]["live_tokens"]), int(dec[4]["slots_active"])
        assert int(st[4]["index_keys"]) == 2 * live
        assert int(st[4]["latent_rows"]) == 2 * 8 * act     # L + 1 > 8
        assert int(st[4]["window_rows"]) == 3 * 9 * act     # L + 1 > 9
        assert 0 <= int(st[4]["moe_pairs"]) <= 4 * 2 * act
        assert int(st[4]["moe_touched"]) <= min(4 * 4,
                                                int(st[4]["moe_pairs"]))
    # a prompt of n tokens from 0: sum over positions p of p + 1
    assert sorted(int(s[4]["index_keys"]) for s in pstats) == sorted(
        2 * n * (n + 1) // 2 for n in (20, 11))
    after = counters()
    for j, name in enumerate(("moe_pairs_total", "moe_experts_touched_total",
                              "index_keys_scanned_total",
                              "latent_rows_read_total",
                              "window_rows_read_total")):
        for phase, group in (("decode", dstats), ("prefill", pstats)):
            key = (name, f"phase={phase}")
            assert after[key] - before.get(key, 0.0) == sum(
                int(s[4][STATS[j]]) for s in group), key


# ---------------------------------- train spans and lifecycle events, one chain
@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """train.py: fault at step 3 -> save -> exit; resumed under
    --profile-dir for three more steps."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    sys.path.insert(0, str(REPO / "tests"))
    from test_fault_tolerance import _args, _run

    tmp = tmp_path_factory.mktemp("trace_chain")
    rng = np.random.default_rng(0)
    docs = [" ".join(rng.choice(["alpha", "bravo", "charlie"],
                                size=int(rng.integers(20, 120))))
            for _ in range(64)]
    parquet = str(tmp / "train_data.parquet")
    pq.write_table(pa.table({"text": docs}), parquet)
    small = {"--sequence-length": "64", "--logging-frequency": "1"}
    rc, out = _run(_args(tmp, parquet, **small, **{
        "--training-steps": "6", "--raise-error": "", "--error-step": "3"}),
        job_id="tn1")
    assert rc == 0 and "Checkpoint saved at step" in out, out[-3000:]
    rc, out = _run(_args(tmp, parquet, **small, **{
        "--training-steps": "7", "--checkpoint-id": "tn1",
        "--profile-dir": str(tmp / "trace")}), job_id="tn2")
    assert rc == 0 and "Training completed" in out, out[-3000:]
    return tmp


def test_resume_chain_writes_lifecycle_events_in_order(chain):
    from fault_tolerant_llm_training_tpu.obs.events import read_events
    from fault_tolerant_llm_training_tpu.obs.goodput import stitch

    ev_dir = chain / "ckpts" / "events"
    first = read_events(str(ev_dir / "events_tn1.jsonl"))
    resumed = read_events(str(ev_dir / "events_tn2.jsonl"))
    order = ["proc_start", "imports_done", "backend_ready", "ckpt_verify",
             "ckpt_restore", "first_step_done"]
    kinds = [e["kind"] for e in resumed]
    at = [kinds.index(k) for k in order]          # each is there ...
    assert at == sorted(at), list(zip(order, at))  # ... in this order
    by = {k: resumed[i] for k, i in zip(order, at)}
    ts = [by[k]["t"] for k in order]
    assert ts == sorted(ts)
    assert by["first_step_done"]["resumed"] is True
    assert by["imports_done"]["dur"] == pytest.approx(
        by["imports_done"]["t"] - by["proc_start"]["t"])
    assert 0.5 < by["imports_done"]["dur"] < 300
    assert by["ckpt_verify"]["bytes"] > 0 and by["ckpt_verify"]["dur"] > 0
    assert by["ckpt_verify"]["step"] == by["ckpt_restore"]["step"]
    assert by["ckpt_restore"]["dur"] >= by["ckpt_verify"]["dur"]
    assert "device" in by["backend_ready"]
    # the faulted job: a fresh start, and its save wrote the manifest the
    # resumed job verified, over the same bytes
    kinds1 = [e["kind"] for e in first]
    assert kinds1[:4] == ["proc_start", "imports_done", "backend_ready",
                          "start"]
    done1 = first[kinds1.index("first_step_done")]
    assert done1["resumed"] is False
    manifest = first[kinds1.index("ckpt_manifest")]
    assert manifest["bytes"] == by["ckpt_verify"]["bytes"]
    assert kinds1.count("first_step_done") == kinds.count(
        "first_step_done") == 1
    # the stitcher keeps working on files that hold the new kinds
    report = stitch(first + resumed)
    assert len(report.restarts) == 1 and report.goodput_pct > 0


def test_train_steps_leave_every_training_span(chain):
    from fault_tolerant_llm_training_tpu.obs.trace import SPANS

    spans = ftl_spans(chain / "trace")
    names = {s[0] for s in spans}
    training = {n for n in SPANS if n.startswith(("ftl:train.",
                                                  "ftl:data."))}
    assert training <= names, training - names
    steps = [s for s in spans if s[0] == "ftl:train.step"]
    assert [int(s[4]["step"]) for s in steps] == [4, 5, 6]
    for child in ("signal_check", "fetch", "dispatch", "consume"):
        assert_nested(spans, "ftl:train." + child, "ftl:train.step")
        # --inflight 2: the first step has no older step to consume
        assert sum(1 for s in spans if s[0] == "ftl:train." + child) == (
            len(steps) - (child == "consume"))
    # the prefetcher works on its own thread, outside the step spans
    pre = [s for s in spans if s[0] == "ftl:data.prefetch"]
    assert {s[3] for s in pre}.isdisjoint({s[3] for s in steps})


def test_the_trainers_capture_holds_no_python_call(chain):
    """``--profile-dir``: the trainer's whole-run capture passes the
    program's options (obs/trace.py ``profile_options``), so its trace holds
    the spans and not one event of the Python tracer's."""
    assert any(s[0] == "ftl:train.step" for s in ftl_spans(chain / "trace"))
    assert not python_call_events(chain / "trace")


# --------------------------- the latent / expert class's training step
LATENT_TRAIN_SCOPES = ("attention", "moe_route", "moe_experts", "moe_shared",
                       "loss_head")


def test_latent_training_step_opens_its_scopes_in_forward_order():
    """The lowered training step of the latent / expert class opens, in
    forward order, attention (layer 0), the expert layer's route, grouped
    matmuls and shared experts (layer 1 on), then the head with the loss."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models import build_model, get_config
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer, make_train_step)

    cfg = get_config("tiny-latent-train", vocab_size=259)
    model, opt = build_model(cfg), make_optimizer(1e-3, 0)

    def init_fn(key):
        params = model.init(key, jnp.zeros((1, 64), jnp.int32))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(make_train_step(model, opt, 1.0)).lower(
        state, tok, tok).as_text(debug_info=True)
    first = {}
    for i, line in enumerate(text.splitlines()):
        for name in re.findall(r'loc\("([^"]+)"', line):
            for word in components([name]) & set(LATENT_TRAIN_SCOPES):
                first.setdefault(word, i)
    assert set(first) == set(LATENT_TRAIN_SCOPES), first
    order = sorted(LATENT_TRAIN_SCOPES, key=first.get)
    assert order == list(LATENT_TRAIN_SCOPES), order


_COUNT_ONE_STEP = r"""
import json, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import train as entry
from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY
from fault_tolerant_llm_training_tpu.training import loop as tl
from fault_tolerant_llm_training_tpu.utils.config import get_args
from perfbench.lib import reference, weights

seen = {}
orig = tl.Trainer.__init__

def init(self, *a, **k):
    orig(self, *a, **k)
    inner = self._compiled_step

    def step(state, inputs, labels):
        seen.setdefault("params", weights.flatten(jax.tree_util.tree_map(
            np.asarray, state.params)))
        seen.setdefault("inputs", np.asarray(inputs))
        return inner(state, inputs, labels)

    self._compiled_step = step

tl.Trainer.__init__ = init
try:
    entry.train(get_args(sys.argv[2:]))
except SystemExit as e:
    assert e.code in (0, None), e.code
counts = {f"{name}{{{labels}}}": v
          for name, fam in REGISTRY.snapshot().items()
          for labels, v in fam["series"].items() if name.startswith("moe_")}
config = json.load(open(sys.argv[1] + "/perfbench/configs/tiny-kanana2.json"))
d = weights.dims_of(config)
fam = weights.family_of(d)
p = {k: jnp.asarray(v, jnp.float32) for k, v in seen["params"].items()}
mm = reference.mm_f32
ref = 0
with jax.default_matmul_precision("highest"):
    for row in seen["inputs"]:
        x = p["tok_embeddings/embedding"][jnp.asarray(row)]
        for i in range(d["n_layers"]):
            w = fam.sub(p, f"layers_{i}/")
            if i >= d["first_dense"]:
                u = reference.rmsnorm(
                    x + fam.mixer(fam.sub(w, "attention/"), reference.rmsnorm(
                        x, w["attention_norm/scale"], d["norm_eps"]), d, mm),
                    w["ffn_norm/scale"], d["norm_eps"])
                ref += fam.held_pairs(fam.sub(w, "feed_forward/"), u, d, mm)
            x = fam.block(w, x, d, mm, i)
print(json.dumps({"counts": counts, "reference_pairs": ref}))
"""


def test_latent_training_counts_its_held_pairs_as_the_reference_does(
        tmp_path):
    """One training step of the latent / expert class through ``train.py``:
    ``moe_pairs_total{phase=train}`` is the reference's own count of the
    step's (token, held expert) pairs, and the experts-touched counter
    moved too — both from the values the loop reads each step anyway."""
    import json
    import os
    import subprocess

    import pyarrow as pa
    import pyarrow.parquet as pq

    parquet = str(tmp_path / "d.parquet")
    rng = np.random.default_rng(0)
    pq.write_table(pa.table({"text": [
        " ".join(rng.choice(["alpha", "bravo", "charlie"], size=60))
        for _ in range(16)]}), parquet)
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLURM_JOB_ID="kc1")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _COUNT_ONE_STEP, str(REPO), "--dataset",
         parquet, "--checkpoint-path", str(tmp_path / "ck"),
         "--tokenizer-name-or-path", "byte", "--model", "tiny-latent-train",
         "--vocab-size", "512", "--model-dtype", "fp32",
         "--sequence-length", "64", "--batch-size", "2",
         "--training-steps", "1", "--compile-cache-dir", ""],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    counts = got["counts"]
    assert counts["moe_pairs_total{phase=train}"] == got["reference_pairs"]
    assert got["reference_pairs"] > 0
    assert 0 < counts["moe_experts_touched_total{phase=train}"] <= 2 * 4
