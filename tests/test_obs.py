"""Observability layer tests: metric registry rendering, flight recorder,
goodput stitching across a synthetic 3-restart chain, the /metrics endpoint,
heartbeats, trace windows, and the resume-aware throughput meter — plus one
end-to-end run of train.py with a live /metrics scrape."""

import json
import logging
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from fault_tolerant_llm_training_tpu.obs import events as events_mod
from fault_tolerant_llm_training_tpu.obs.events import (
    FlightRecorder,
    read_events,
)
from fault_tolerant_llm_training_tpu.obs.goodput import (
    failure_class,
    format_report,
    load_chain,
    stitch,
)
from fault_tolerant_llm_training_tpu.obs.prometheus import (
    HeartbeatThread,
    MetricsServer,
)
from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry
from fault_tolerant_llm_training_tpu.obs.trace import parse_window
from fault_tolerant_llm_training_tpu.utils import metrics as metrics_mod
from fault_tolerant_llm_training_tpu.utils.metrics import Throughput

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """The module recorder deliberately carries its ring across configure()
    (pre-configuration events must survive into the file); tests need a
    clean slate instead."""
    events_mod._RECORDER = events_mod.FlightRecorder()
    yield
    events_mod._RECORDER = events_mod.FlightRecorder()


# ------------------------------------------------------------------ registry

def test_registry_counter_gauge_histogram_render():
    r = MetricRegistry()
    c = r.counter("ftl_test_total", "a counter")
    c.inc()
    c.inc(2)
    g = r.gauge("ftl_test_gauge", "a gauge")
    g.set(1.5)
    h = r.histogram("ftl_test_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.render()
    assert "# HELP ftl_test_total a counter" in text
    assert "# TYPE ftl_test_total counter" in text
    assert "ftl_test_total 3" in text
    assert "ftl_test_gauge 1.5" in text
    # cumulative buckets + +Inf == count
    assert 'ftl_test_seconds_bucket{le="0.1"} 1' in text
    assert 'ftl_test_seconds_bucket{le="1"} 2' in text
    assert 'ftl_test_seconds_bucket{le="+Inf"} 3' in text
    assert "ftl_test_seconds_count 3" in text
    assert text.endswith("\n")


def test_registry_labels_and_kind_conflict():
    r = MetricRegistry()
    fam = r.counter("ftl_req_total", "requests")
    fam.labels(reason="eos").inc()
    fam.labels(reason="length").inc(4)
    text = r.render()
    assert 'ftl_req_total{reason="eos"} 1' in text
    assert 'ftl_req_total{reason="length"} 4' in text
    # same family object on re-registration; conflicting kind rejected
    assert r.counter("ftl_req_total") is fam
    with pytest.raises(ValueError):
        r.gauge("ftl_req_total")
    with pytest.raises(ValueError):
        fam.inc(-1)


def test_histogram_quantile_bucket_resolution():
    r = MetricRegistry()
    h = r.histogram("ftl_q_seconds", buckets=(0.1, 1.0, 10.0))
    for _ in range(9):
        h.observe(0.05)
    h.observe(5.0)
    child = h.labels()
    assert child.quantile(0.5) == 0.1
    assert child.quantile(0.99) == 10.0


# ----------------------------------------------------------- flight recorder

def test_flight_recorder_ring_file_and_torn_tail(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    rec = FlightRecorder(path, capacity=4, job="j9", host=1,
                         clock=lambda: 123.0)
    for i in range(6):
        rec.emit("step", step=i, steps=1)
    rec.flush()
    # ring keeps only the last `capacity`
    assert [e["step"] for e in rec.ring] == [2, 3, 4, 5]
    # the file keeps everything, with job/host/clock stamped
    evs = read_events(path)
    assert [e["step"] for e in evs] == list(range(6))
    assert evs[0]["job"] == "j9" and evs[0]["host"] == 1
    assert evs[0]["t"] == 123.0
    rec.close()
    # a torn tail line (crash mid-write) must not poison the reader
    with open(path, "a") as fh:
        fh.write('{"t": 124.0, "kind": "ste')
    assert len(read_events(path)) == 6


def test_configure_carries_preconfig_events_into_file(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    events_mod.configure(None)  # reset to memory-only
    events_mod.emit(kind="signal", signum=10)  # before the file exists
    rec = events_mod.configure(path, job="jj")
    events_mod.emit(kind="exit", error_type=10)
    events_mod.flush()
    kinds = [e["kind"] for e in read_events(path)]
    assert kinds == ["signal", "exit"]
    rec.close()
    events_mod.configure(None)


def test_emit_audit_logs_text_and_emits_exactly_one_event(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    events_mod.configure(path, job="audit")
    log = logging.getLogger("ftl-test-audit")
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    log.addHandler(_Capture())
    log.setLevel(logging.INFO)
    text = "[EXIT HANDLER] Checkpoint saved at step 427"
    events_mod.emit_audit(log, text, "exit", step=427, cls="timeout")
    events_mod.flush()
    assert records == [text]  # byte-identical, logged exactly once
    evs = read_events(path)
    assert len(evs) == 1
    assert evs[0]["kind"] == "exit" and evs[0]["step"] == 427
    assert evs[0]["audit"] is True and evs[0]["cls"] == "timeout"
    events_mod.configure(None)


# ------------------------------------------------------------------- goodput

def _chain_events():
    """Synthetic 3-restart chain: timeout (clean save, no replay) →
    injected error (clean save) → scancel (NO save: 5 steps replayed).

    Tokens/step = 100; step windows of 5 steps over 10 s each.
    """
    ev = []

    def step(job, t, last, dur=10.0, steps=5, tokens=500):
        ev.append({"t": t, "kind": "step", "job": job, "host": 0,
                   "step": last, "dur": dur, "steps": steps,
                   "tokens": tokens})

    # job a: steps 1..10, USR1 timeout at t=25, saved @10
    ev.append({"t": 0.0, "kind": "start", "job": "a", "host": 0, "step": 0,
               "tokens_per_step": 100})
    step("a", 10.0, 5)
    step("a", 20.0, 10)
    ev.append({"t": 25.0, "kind": "signal", "job": "a", "host": 0,
               "signum": 10, "cls": "timeout"})
    ev.append({"t": 27.0, "kind": "exit", "job": "a", "host": 0,
               "error_type": 10, "cls": "timeout", "saved": True,
               "saved_step": 10})
    # job b: restores @10, steps 11..20, injected error at t=90, saved @20
    ev.append({"t": 57.0, "kind": "ckpt_restore", "job": "b", "host": 0,
               "step": 10, "dur": 2.0})
    step("b", 70.0, 15)
    step("b", 80.0, 20)
    ev.append({"t": 90.0, "kind": "signal", "job": "b", "host": 0,
               "signum": -1, "cls": "error"})
    ev.append({"t": 92.0, "kind": "exit", "job": "b", "host": 0,
               "error_type": -1, "cls": "error", "saved": True,
               "saved_step": 20})
    # job c: restores @15 (periodic save gap!), replays 16..20, reaches 30,
    # then scancel with NO save
    ev.append({"t": 112.0, "kind": "ckpt_restore", "job": "c", "host": 0,
               "step": 15, "dur": 2.0})
    step("c", 130.0, 20)   # steps 16..20: all replay
    step("c", 140.0, 25)
    step("c", 150.0, 30)
    ev.append({"t": 152.0, "kind": "exit", "job": "c", "host": 0,
               "error_type": 15, "cls": "cancel", "saved": False})
    return ev


def test_goodput_three_restart_chain(tmp_path):
    report = stitch(_chain_events())
    assert report.jobs == ["a", "b", "c"]
    assert report.steps_reached == 30
    # productive windows: a(2) + b(2) + c's last two = 60 s; replay = 10 s
    assert report.productive_seconds == pytest.approx(60.0)
    assert report.replay_seconds == pytest.approx(10.0)
    assert report.wall_seconds == pytest.approx(152.0)
    assert report.goodput_pct == pytest.approx(100 * 60 / 152.0)
    # MTTR: a→b fault 25 → first b window 70 = 45; b→c 90 → 130 = 40
    assert len(report.restarts) == 2
    assert report.restarts[0].failure == "timeout"
    assert report.restarts[0].mttr_seconds == pytest.approx(45.0)
    assert report.restarts[1].failure == "error"
    assert report.restarts[1].mttr_seconds == pytest.approx(40.0)
    assert report.mttr_seconds == pytest.approx(42.5)
    # replay: only the b→c restart re-trained tokens (steps 16..20)
    assert report.restarts[0].replayed_tokens == 0
    assert report.restarts[1].replayed_steps == 5
    assert report.restarts[1].replayed_tokens == 500
    assert report.tokens_replayed == 500
    assert report.tokens_trained == 3000  # 30 net-new steps x 100
    lost = report.lost_by_class
    assert set(lost) == {"timeout", "error"}
    assert lost["timeout"] == pytest.approx(45.0)
    assert lost["error"] == pytest.approx(50.0)  # 40 restart + 10 replay
    # the human report renders every headline number
    text = format_report(report)
    assert "goodput" in text and "MTTR" in text
    assert "timeout" in text and "error" in text


def test_goodput_cli_prints_headline_numbers(tmp_path):
    by_job = {}
    for ev in _chain_events():
        by_job.setdefault(ev["job"], []).append(ev)
    for job, evs in by_job.items():
        with open(tmp_path / f"events_{job}.jsonl", "w") as fh:
            for ev in evs:
                fh.write(json.dumps(ev) + "\n")
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "goodput_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "goodput" in out.stdout
    assert "39.5 %" in out.stdout            # 100 * 60 / 152
    assert "MTTR 42.5 s" in out.stdout
    assert "timeout" in out.stdout and "error" in out.stdout
    # --json emits the same accounting machine-readably
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "goodput_report.py"),
         "--json", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    data = json.loads(out.stdout)
    assert data["tokens_replayed"] == 500
    assert data["restarts"][1]["failure"] == "error"


def test_goodput_stitch_single_job_no_restarts():
    evs = [{"t": 0.0, "kind": "start", "job": "x", "host": 0},
           {"t": 10.0, "kind": "step", "job": "x", "host": 0, "step": 5,
            "dur": 10.0, "steps": 5, "tokens": 500},
           {"t": 10.5, "kind": "complete", "job": "x", "host": 0}]
    r = stitch(evs)
    assert not r.restarts and r.mttr_seconds == 0.0
    assert r.goodput_pct == pytest.approx(100 * 10.0 / 10.5)


def test_failure_class_mapping():
    assert failure_class(10) == "timeout"
    assert failure_class(15) == "cancel"
    assert failure_class(-1) == "error"
    assert failure_class(None) == "unknown"
    assert failure_class(99) == "unknown"


def test_load_chain_accepts_files_dirs_and_globs(tmp_path):
    p = tmp_path / "events_a.jsonl"
    p.write_text('{"t": 1.0, "kind": "start", "job": "a", "host": 0}\n')
    assert len(load_chain([str(p)])) == 1
    assert len(load_chain([str(tmp_path)])) == 1
    assert len(load_chain([str(tmp_path / "events_*.jsonl")])) == 1


# ---------------------------------------------------------- /metrics + beats

def test_metrics_server_scrape_and_healthz():
    r = MetricRegistry()
    r.counter("ftl_scrape_total", "scrapes").inc(7)
    srv = MetricsServer(r, host="127.0.0.1")
    port = srv.start()
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10)
        assert resp.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        body = resp.read().decode()
        assert "ftl_scrape_total 7" in body
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read()
        assert health == b"ok\n"
    finally:
        srv.stop()


def test_heartbeat_single_process_self_beat():
    r = MetricRegistry()
    hb = HeartbeatThread(step_fn=lambda: 42, registry=r,
                         clock=lambda: 1000.0)
    hb.beat_once()
    snap = r.snapshot()
    steps = snap["ftl_host_heartbeat_step"]["series"]
    ages = snap["ftl_host_heartbeat_age_seconds"]["series"]
    assert len(steps) == 1
    (label, step), = steps.items()
    assert step == 42 and label.startswith("host=")
    assert list(ages.values())[0] >= 0.0


def test_heartbeat_stop_joins_the_thread():
    """stop() joins (Trainer.close relies on it: no thread of ours may be
    alive when the interpreter finalizes). The stop flag must not shadow
    threading.Thread's own ``_stop`` — join() calls that."""
    hb = HeartbeatThread(step_fn=lambda: 1, registry=MetricRegistry(),
                         interval_seconds=3600.0)
    hb.start()
    hb.stop()
    assert not hb.is_alive()
    hb.stop()  # idempotent


# -------------------------------------------------------------- trace window

def test_parse_window():
    assert parse_window("3:7") == (3, 7)
    assert parse_window("5") == (5, 5)
    for bad in ("", "a:b", "5:3", "-1:4", "1:2:3"):
        with pytest.raises(ValueError):
            parse_window(bad)


def test_auto_trace_arms_once_on_regression_with_bounded_capture():
    from fault_tolerant_llm_training_tpu.obs.trace import AutoTraceWindow

    starts, stops = [], []
    w = AutoTraceWindow("/tmp/t", threshold=2.0, min_samples=4,
                        capture_steps=3, profiler_start=starts.append,
                        profiler_stop=lambda: stops.append(True))
    # warmup: too few samples — even a huge outlier cannot arm yet
    for step in range(3):
        assert w.observe(step, 100.0 if step == 2 else 0.1) is None
    assert not starts
    w2 = AutoTraceWindow("/tmp/t", threshold=2.0, min_samples=4,
                         capture_steps=3, profiler_start=starts.append,
                         profiler_stop=lambda: stops.append(True))
    for step in range(6):
        assert w2.observe(step, 0.1) is None
    assert w2.observe(6, 0.15) is None, "below 2x median: no arm"
    ratio = w2.observe(7, 0.5)  # 5x the rolling median
    assert ratio == pytest.approx(5.0)
    assert starts == ["/tmp/t"] and w2.active
    assert w2.trigger_step == 7
    for step in (8, 9, 10):
        assert w2.observe(step, 0.5) is None  # captured steps don't re-arm
    assert stops == [True] and w2.done and not w2.active
    # once per run: a later, larger regression never re-arms
    assert w2.observe(11, 9.0) is None
    assert starts == ["/tmp/t"]


def test_auto_trace_close_stops_armed_capture_and_validates():
    from fault_tolerant_llm_training_tpu.obs.trace import AutoTraceWindow

    with pytest.raises(ValueError):
        AutoTraceWindow("/tmp/t", threshold=1.0)
    stops = []
    w = AutoTraceWindow("/tmp/t", min_samples=2, profiler_start=lambda d: None,
                        profiler_stop=lambda: stops.append(True))
    for step in range(4):
        w.observe(step, 0.1)
    assert w.observe(4, 1.0) is not None and w.active
    w.close()  # loop exited inside the window
    assert stops == [True] and w.done
    w.close()  # idempotent
    assert stops == [True]


def test_profile_tool_reexports_shared_parser():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "profile_step", REPO / "scripts" / "profile_step.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from fault_tolerant_llm_training_tpu.obs.trace import parse_trace
    assert mod.parse_trace is parse_trace


# ------------------------------------------------- resume-aware throughput

def test_throughput_reset_restarts_warmup_and_tags_window():
    tp = Throughput(tokens_per_step=100, warmup_steps=1)
    for _ in range(3):
        tp.step()
    assert tp.tokens_per_sec > 0
    tp.reset(tag="post_resume")
    # the meter restarted: the pre-reset (restore-skewed) window is gone
    assert tp.tokens_per_sec == 0.0
    assert tp.window_tag == "post_resume"
    for _ in range(3):
        tp.step()
    assert tp.tokens_per_sec > 0
    tp.clear_tag()
    assert tp.window_tag is None


def test_device_memory_stats_picks_most_loaded_device(monkeypatch):
    monkeypatch.setattr(
        metrics_mod, "per_device_memory_stats",
        lambda: [("0", 100, 1000), ("1", 900, 1000), ("2", 400, 1000)])
    used, limit = metrics_mod.device_memory_stats()
    assert (used, limit) == (900, 1000)
    assert metrics_mod.hbm_usage_str() == "0.0/0.0 GB"  # 900 B in GB


def test_device_memory_stats_none_without_backend_stats(monkeypatch):
    monkeypatch.setattr(metrics_mod, "per_device_memory_stats", lambda: [])
    assert metrics_mod.device_memory_stats() == (None, None)
    assert metrics_mod.hbm_usage_str() == ""


# -------------------------------------------------------------- end to end

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_train_e2e_live_metrics_scrape_and_event_log(tmp_path, tiny_parquet):
    """Run the real CLI with --metrics-port and scrape /metrics while it
    trains: the step-time histogram, tokens/s gauge, and checkpoint-duration
    series must be live; afterwards the flight-recorder JSONL must contain
    the full start → steps → ckpt_save → complete trail."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = "/tmp/jax_test_compile_cache"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["SLURM_JOB_ID"] = "obs1"
    argv = [sys.executable, str(REPO / "train.py"),
            "--dataset", tiny_parquet,
            "--checkpoint-path", str(tmp_path / "ckpts"),
            "--tokenizer-name-or-path", "byte",
            "--model", "tiny",
            "--sequence-length", "128",
            "--batch-size", "2",
            "--training-steps", "40",
            "--lr-warmup-steps", "5",
            "--learning-rate", "1e-3",
            "--logging-frequency", "1",
            "--checkpoint-frequency", "10",
            "--metrics-port", str(port)]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    scraped = None
    try:
        deadline = time.time() + 300
        while time.time() < deadline and proc.poll() is None:
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ).read().decode()
            except OSError:
                time.sleep(0.5)
                continue
            if ("ftl_train_tokens_per_sec{" in body
                    and "ftl_ckpt_save_seconds_count" in body
                    and "ftl_train_step_seconds_count" in body):
                scraped = body
                break
            time.sleep(0.5)
        out, _ = proc.communicate(timeout=max(10.0, deadline - time.time()))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, out[-4000:]
    assert scraped is not None, f"no live scrape captured:\n{out[-4000:]}"
    # the three required series, live mid-run
    assert "ftl_train_step_seconds_bucket" in scraped
    assert 'ftl_train_tokens_per_sec{window=' in scraped
    assert "ftl_ckpt_save_seconds_count" in scraped
    assert "ftl_train_tokens_total" in scraped
    # flight recorder: default location <ckpt-path>/events/events_<job>.jsonl
    ev_path = tmp_path / "ckpts" / "events" / "events_obs1.jsonl"
    assert ev_path.exists(), out[-4000:]
    evs = read_events(str(ev_path))
    kinds = [e["kind"] for e in evs]
    # the process's own lifecycle first (obs/events.py), then the loop's
    assert kinds[:4] == ["proc_start", "imports_done", "backend_ready",
                         "start"]
    assert "step" in kinds and "ckpt_save" in kinds
    # "complete" closes the loop's trail; closing the checkpoint manager
    # afterwards may still write the last async save's integrity manifest
    assert [k for k in kinds if k != "ckpt_manifest"][-1] == "complete"
    step_evs = [e for e in evs if e["kind"] == "step"]
    # every step event is either a paired audit emission or the synthetic
    # tail window that closes the accounting after a trailing pre-save drain
    assert all(e.get("audit") or e.get("tail") for e in step_evs)
    assert step_evs[-1]["step"] == 39  # steps are 0-indexed
    # window accounting covers every trained step exactly once
    assert sum(e["steps"] for e in step_evs) == 40
    assert sum(e["tokens"] for e in step_evs) == 40 * 2 * 128
    # and the stitcher accepts a real single-job log
    report = stitch(evs)
    assert report.steps_reached == 39  # highest 0-indexed step
    assert not report.restarts
    assert report.goodput_pct > 0
