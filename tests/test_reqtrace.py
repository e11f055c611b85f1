"""Request-lifecycle tracing (obs/reqtrace.py) and latency observability.

Five layers of evidence:

1. recorder — spans survive a crash with no flush (line-buffered append,
   the flight-recorder discipline), a torn tail from a SIGKILLed writer
   is skipped at read time, and configure() carries pre-configuration
   ring contents into the file;
2. math — TTFT/TPOT derivation on synthetic traces: the done-span
   payload (serving monotonic clock) is preferred, wall-clock span
   deltas are the crashed-host fallback, and the nearest-rank
   percentile helper matches hand-computed ranks;
3. stitch — trace files from three processes (router + two fleet hosts)
   join by trace_id into ONE request whose hosts list spans the
   migration and whose replayed count matches the migration span;
4. metrics — the registry renders summary-style quantile lines for
   EVERY histogram and snapshot() exposes p50/p95/p99; a scheduler run
   over a fake engine populates the TTFT and TPOT histograms and emits
   the full intake->done span trail;
5. lifecycle (slow) — a real serve.py run with --metrics-port: /metrics
   is scraped LIVE mid-run for the latency histograms, and after the
   drain the trace file stitches into per-request TTFT/TPOT matching
   the [LATENCY] audit lines in the transcript.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from fault_tolerant_llm_training_tpu.obs import reqtrace
from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_recorder():
    reqtrace._RECORDER = reqtrace.SpanRecorder()
    yield
    reqtrace._RECORDER.close()
    reqtrace._RECORDER = reqtrace.SpanRecorder()


# -------------------------------------------------------------- 1. recorder
def test_spans_survive_without_flush_and_torn_tail_is_skipped(tmp_path):
    """The crash contract: every emitted span is on disk BEFORE any
    flush/close (line-buffered append), and a torn final line — the
    mid-write SIGKILL — is skipped by the reader, not fatal."""
    path = str(tmp_path / "trace_h0.jsonl")
    rec = reqtrace.SpanRecorder(path, job="fleet_h0", host="h0")
    tid = reqtrace.mint_trace_id("req0")
    rec.emit(tid, "req0", "intake", prompt_tokens=4)
    rec.emit(tid, "req0", "prefill", dur=0.01, prompt_tokens=4)
    # no flush(), no close(): simulate SIGKILL by abandoning the handle
    spans = reqtrace.read_spans(path)
    assert [s["span"] for s in spans] == ["intake", "prefill"]
    assert all(s["trace_id"] == tid and s["host"] == "h0" for s in spans)
    assert spans[1]["dur"] == pytest.approx(0.01)

    with open(path, "a") as fh:
        fh.write('{"t": 1.0, "trace_id": "' + tid + '", "span": "dec')
    spans = reqtrace.read_spans(path)
    assert [s["span"] for s in spans] == ["intake", "prefill"]
    rec.close()


def test_configure_replays_preconfiguration_ring(tmp_path):
    """Spans emitted through the module singleton before configure()
    (e.g. intake minted before the CLI parsed --trace-log) land in the
    file once a path is configured."""
    tid = reqtrace.mint_trace_id("early")
    reqtrace.emit(tid, "early", "intake", prompt_tokens=2)
    path = str(tmp_path / "trace_router.jsonl")
    reqtrace.configure(path, job="router", host="router")
    reqtrace.emit(tid, "early", "queue", dur=0.5, where="router")
    reqtrace.flush()
    spans = reqtrace.read_spans(path)
    assert [s["span"] for s in spans] == ["intake", "queue"]
    # pre-configuration spans carry their original job/host stamp
    assert spans[1]["job"] == "router"


def test_derive_trace_path_and_mint():
    assert (reqtrace.derive_trace_path("/run/events_router.jsonl")
            == "/run/trace_router.jsonl")
    assert (reqtrace.derive_trace_path("/run/ev.jsonl")
            == "/run/trace_ev.jsonl")
    tid = reqtrace.mint_trace_id("req7")
    assert tid.startswith("req7-") and len(tid) == len("req7-") + 12
    assert reqtrace.mint_trace_id("req7") != tid  # collision-resistant


# ------------------------------------------------------------------ 2. math
def _span(t, tid, span, host="h0", **payload):
    d = {"t": t, "trace_id": tid, "id": "req0", "span": span,
         "job": "test", "host": host}
    d.update(payload)
    return d


def test_derive_prefers_done_payload_and_falls_back_to_wall_clock():
    tid = "req0-abc"
    # fallback path: no done payload — wall-clock deltas
    spans = [_span(100.0, tid, "intake"),
             _span(100.5, tid, "first_token"),
             _span(102.5, tid, "done", tokens=21, reason="length")]
    d = reqtrace.derive(spans)
    assert d["ttft"] == pytest.approx(0.5)
    assert d["tpot"] == pytest.approx(2.0 / 20)  # first token is prefill's
    assert d["tokens"] == 21 and d["done"] and d["reason"] == "length"

    # preferred path: the done span carries the serving clock's own numbers
    spans[-1] = _span(102.5, tid, "done", tokens=21, reason="length",
                      ttft=0.42, tpot=0.033)
    d = reqtrace.derive(spans)
    assert d["ttft"] == pytest.approx(0.42)
    assert d["tpot"] == pytest.approx(0.033)

    # crashed host: no done span at all — UNFINISHED, ttft still derivable
    d = reqtrace.derive(spans[:2])
    assert d["done"] is False and d["tpot"] is None
    assert d["ttft"] == pytest.approx(0.5)
    report = reqtrace.format_report([d])
    assert "UNFINISHED" in report


def test_nearest_rank_percentile():
    vals = [float(v) for v in range(1, 101)]  # 1..100
    assert reqtrace.percentile(vals, 0.5) == 50.0
    assert reqtrace.percentile(vals, 0.95) == 95.0
    assert reqtrace.percentile(vals, 0.99) == 99.0
    assert reqtrace.percentile([7.0], 0.99) == 7.0
    assert reqtrace.percentile([], 0.5) == 0.0


# ---------------------------------------------------------------- 3. stitch
def test_stitch_joins_migrated_trace_across_hosts(tmp_path):
    """A request assigned to h0, killed mid-decode, migrated to h1: the
    three processes' trace files join into ONE record that spans all
    hosts, counts the migration, and carries the replayed-prefix length
    the survivor replayed bit-exactly."""
    tid = "req0-deadbeef0123"
    router = reqtrace.SpanRecorder(str(tmp_path / "trace_router.jsonl"),
                                   job="router", host="router",
                                   clock=iter(np.arange(100.0, 200.0,
                                                        0.25)).__next__)
    h0 = reqtrace.SpanRecorder(str(tmp_path / "trace_h0.jsonl"),
                               job="fleet_h0", host="h0",
                               clock=iter(np.arange(101.0, 200.0,
                                                    0.25)).__next__)
    h1 = reqtrace.SpanRecorder(str(tmp_path / "trace_h1.jsonl"),
                               job="fleet_h1", host="h1",
                               clock=iter(np.arange(110.0, 200.0,
                                                    0.25)).__next__)
    router.emit(tid, "req0", "intake", prompt_tokens=5)
    router.emit(tid, "req0", "queue", dur=0.1, where="router")
    router.emit(tid, "req0", "placement", host="h0", gen=0)
    h0.emit(tid, "req0", "assign", gen=0, committed=0)
    h0.emit(tid, "req0", "prefill", dur=0.02, prompt_tokens=5,
            replayed=0)
    h0.emit(tid, "req0", "first_token", ttft=0.05)
    h0.emit(tid, "req0", "decode_round", tokens=1, mode="token")
    # h0 dies here (no flush needed — line-buffered); router migrates
    router.emit(tid, "req0", "migration", src="h0", dst="h1", gen=1,
                replayed=13)
    h1.emit(tid, "req0", "assign", gen=1, committed=13)
    h1.emit(tid, "req0", "prefill", dur=0.03, prompt_tokens=17,
            replayed=13)
    h1.emit(tid, "req0", "done", reason="length", tokens=48, ttft=0.05,
            tpot=0.002)
    for r in (router, h0, h1):
        r.close()

    reqs = reqtrace.stitch([str(tmp_path)])
    assert len(reqs) == 1
    r = reqs[0]
    assert r["request_id"] == "req0" and r["trace_id"] == tid
    assert r["hosts"] == ["router", "h0", "h1"]
    assert r["migrated"] and r["migrations"] == 1
    assert r["replayed"] == 13
    assert r["done"] and r["tokens"] == 48
    assert r["ttft"] == pytest.approx(0.05)
    assert r["tpot"] == pytest.approx(0.002)
    # the critical path is time-ordered across hosts despite interleaved
    # file reads
    ts = [p["t"] for p in r["critical_path"]]
    assert ts == sorted(ts)
    report = reqtrace.format_report([r], slo_ttft=0.5, slo_tpot=0.05)
    assert "router>h0>h1" in report
    assert "SLO" in report and "1/1 attained (100.0%)" in report


# --------------------------------------------------------------- 4. metrics
def test_registry_histograms_render_quantile_snapshots():
    """EVERY histogram — the pre-existing serving ones included — now
    renders summary-style p50/p95/p99 lines next to its buckets, and
    snapshot() carries the same quantiles (bucket-upper-bound
    resolution)."""
    reg = MetricRegistry()
    h = reg.histogram("ftl_test_latency_seconds", "test",
                      buckets=(0.01, 0.1, 1.0, 10.0))
    for v in [0.005] * 50 + [0.5] * 45 + [5.0] * 5:
        h.observe(v)
    text = reg.render()
    assert 'ftl_test_latency_seconds{quantile="0.5"} 0.01' in text
    assert 'ftl_test_latency_seconds{quantile="0.95"} 1' in text
    assert 'ftl_test_latency_seconds{quantile="0.99"} 10' in text
    snap = reg.snapshot()["ftl_test_latency_seconds"]
    series = snap["series"][""]
    assert series["count"] == 100
    assert series["p50"] == pytest.approx(0.01)
    assert series["p95"] == pytest.approx(1.0)
    assert series["p99"] == pytest.approx(10.0)


class _FakeEngine:
    """Deterministic engine double (test_inference.py idiom)."""

    def __init__(self, slots=2, max_len=64):
        self.slots = slots
        self.max_len = max_len

    def prefill(self, slot, prompt, temperature=0.0, top_p=1.0, seed=0):
        return 100 + slot

    def decode_step(self, tokens, active, temperature, top_p, seeds, steps):
        return np.where(active, np.asarray(tokens) + 1, 0).astype(np.int32)


def test_scheduler_emits_span_trail_and_latency_histograms(tmp_path):
    """A traced request leaves the full intake->queue->prefill->
    first_token->decode_round->done trail, the scheduler's registry
    scrape carries the TTFT and TPOT histograms with quantile lines, and
    derive() on the trace reproduces the Completion's own numbers."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    path = str(tmp_path / "trace_serve.jsonl")
    reqtrace.configure(path, job="serve", host="0")
    reg = MetricRegistry()
    sched = Scheduler(_FakeEngine(slots=2), eos_token_id=None, registry=reg)
    tid = reqtrace.mint_trace_id("r0")
    reqtrace.emit(tid, "r0", "intake", prompt_tokens=2)
    sched.submit(Request(id="r0", prompt=[1, 2], max_new_tokens=6,
                         trace_id=tid))
    sched.submit(Request(id="r1", prompt=[1], max_new_tokens=3))  # untraced
    done = {c.request_id: c for c in sched.run()}
    reqtrace.flush()

    spans = reqtrace.read_spans(path)
    names = [s["span"] for s in spans if s["trace_id"] == tid]
    assert names[0] == "intake" and names[-1] == "done"
    assert {"queue", "prefill", "first_token", "decode_round"} <= set(names)
    assert names.count("decode_round") == 5  # 6 tokens - prefill's first
    # the untraced request emitted NOTHING (tracing is strictly opt-in)
    assert {s["trace_id"] for s in spans} == {tid}

    c = done["r0"]
    assert c.trace_id == tid
    assert c.tpot_seconds > 0
    d = reqtrace.derive([s for s in spans if s["trace_id"] == tid])
    assert d["ttft"] == pytest.approx(c.ttft_seconds)
    assert d["tpot"] == pytest.approx(c.tpot_seconds)
    assert d["tokens"] == 6 and d["decode_rounds"] == 5

    text = reg.render()
    assert "ftl_serve_ttft_seconds_count 2" in text
    assert "ftl_serve_tpot_seconds_count 2" in text
    assert 'ftl_serve_ttft_seconds{quantile="0.99"}' in text
    assert 'ftl_serve_tpot_seconds{quantile="0.5"}' in text
    m = sched.metrics()
    for k in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
              "tpot_p50_ms", "tpot_p95_ms", "tpot_p99_ms"):
        assert m[k] >= 0.0


# ------------------------------------------------------------- 5. lifecycle
def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = "/tmp/jax_test_compile_cache"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return env


def _save_tiny_checkpoint(tmp_path, job, step):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        CheckpointManager)
    from fault_tolerant_llm_training_tpu.models.configs import get_config
    from fault_tolerant_llm_training_tpu.models.llama import Transformer
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import make_optimizer

    cfg = get_config("tiny", vocab_size=259, seq_len=128)
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    state = TrainState(step=jnp.asarray(step, jnp.int32), params=params,
                       opt_state=make_optimizer(1e-4, 1).init(params))
    mngr = CheckpointManager(str(tmp_path), job, enable_async=False,
                             max_to_keep=2)
    mngr.save(step, state, {"next_index": 0}, wait=True)
    mngr.close()


@pytest.mark.slow
def test_serve_e2e_live_metrics_scrape_and_trace_stitch(
        cpu_bf16_serving_dot, tmp_path):
    """The whole pipeline against a REAL serve.py process: requests flow
    in through --request-file (one with a caller-minted trace_id), the
    latency histograms are scraped LIVE from /metrics while the process
    serves, and after a SIGUSR1 drain the trace file stitches into
    per-request TTFT/TPOT that match the [LATENCY] audit lines."""
    import socket

    _save_tiny_checkpoint(tmp_path, "trace_e2e", 5)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    req_file = tmp_path / "requests.jsonl"
    with open(req_file, "w") as fh:
        fh.write(json.dumps({"id": "reqA", "prompt": "alpha bravo",
                             "max_new_tokens": 8,
                             "trace_id": "reqA-cafecafecafe"}) + "\n")
        fh.write(json.dumps({"id": "reqB", "prompt": "charlie delta echo",
                             "max_new_tokens": 8}) + "\n")
    event_log = tmp_path / "events_serve.jsonl"
    argv = [sys.executable, "-m",
            "fault_tolerant_llm_training_tpu.inference.serve",
            "--checkpoint-path", str(tmp_path),
            "--checkpoint-job-id", "trace_e2e", "--model", "tiny",
            "--vocab-size", "259", "--slots", "2", "--max-len", "64",
            "--max-new-tokens", "8", "--no-eos", "--follow",
            "--poll-seconds", "0.2",
            "--request-file", str(req_file),
            "--event-log", str(event_log),
            "--metrics-port", str(port)]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env=_env())
    scrape = None
    try:
        deadline = time.time() + 240
        trace_log = tmp_path / "trace_serve.jsonl"  # derived from event-log
        while time.time() < deadline:
            if proc.poll() is not None:
                break
            # both requests done => the histograms are populated; scrape
            # while the process is STILL serving (follow mode idles)
            try:
                spans = (reqtrace.read_spans(str(trace_log))
                         if trace_log.exists() else [])
            except OSError:
                spans = []
            if sum(1 for s in spans if s["span"] == "done") >= 2:
                scrape = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=10).read().decode()
                break
            time.sleep(0.3)
        assert scrape is not None, "requests never completed"
        proc.send_signal(signal.SIGUSR1)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out

    # live scrape: latency histograms with quantile snapshots were up
    # while the process served
    assert "ftl_serve_ttft_seconds_count 2" in scrape, scrape
    assert 'ftl_serve_ttft_seconds{quantile="0.99"}' in scrape
    assert "ftl_serve_tpot_seconds_count 2" in scrape
    assert 'ftl_serve_tpot_seconds{quantile="0.5"}' in scrape

    # the drain summary printed one [LATENCY] line per request
    lat = {}
    for m in re.finditer(r"\[LATENCY\] Request (\w+) \| trace ([\w.-]+) \| "
                         r"ttft (\d+) ms \| tpot ([\d.]+) ms \| (\d+) tok",
                         out):
        lat[m.group(1)] = (m.group(2), float(m.group(3)),
                           float(m.group(4)), int(m.group(5)))
    assert set(lat) == {"reqA", "reqB"}, out
    assert lat["reqA"][0] == "reqA-cafecafecafe"  # caller's id propagated

    # the trace file stitches to the same story
    reqs = {r["request_id"]: r for r in reqtrace.stitch([str(trace_log)])}
    assert set(reqs) == {"reqA", "reqB"}
    for rid in ("reqA", "reqB"):
        r = reqs[rid]
        assert r["done"] and r["tokens"] == 8
        assert r["ttft"] is not None and r["tpot"] is not None
        # [LATENCY] prints the same derive()d numbers (ms, rounded)
        assert round(r["ttft"] * 1e3) == lat[rid][1]
        assert r["tpot"] * 1e3 == pytest.approx(lat[rid][2], abs=0.005)
    assert reqs["reqA"]["trace_id"] == "reqA-cafecafecafe"

    # latency_report.py runs end-to-end over the same file
    rep = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "latency_report.py"),
         str(trace_log), "--slo-ttft-ms", "60000"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env(), timeout=120)
    assert rep.returncode == 0, rep.stdout
    assert "Request latency report" in rep.stdout
    assert "reqA" in rep.stdout and "reqB" in rep.stdout
    assert "SLO (ttft <= 60000 ms): 2/2 attained (100.0%)" in rep.stdout
