"""Frozen audit-string contract.

The audit strings in utils/logging.py are the system's verification API —
the reference README greps Slurm ``.out`` files for them, and the
fault-tolerance tests assert on them. This module freezes each string
against a pinned literal (NOT imported constants compared to themselves:
the pin must break when anyone edits the string), and enforces the
flight-recorder invariant: audit strings are only ever emitted through
``obs.events.emit_audit``, which pairs every byte-identical log line with
exactly one structured event.
"""

import logging
import re
from pathlib import Path

from fault_tolerant_llm_training_tpu.obs import events as events_mod
from fault_tolerant_llm_training_tpu.utils import logging as ftl_logging

import pytest

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "fault_tolerant_llm_training_tpu"


@pytest.fixture(autouse=True)
def _fresh_recorder():
    events_mod._RECORDER = events_mod.FlightRecorder()
    yield
    events_mod._RECORDER = events_mod.FlightRecorder()

# Pinned byte-for-byte. ref: utils.py:68,71,73,81,86,88,90; train.py:81,84,
# 116,118 — plus the serving trail introduced with inference/serve.py.
FROZEN = {
    "AUDIT_CANCELLED": "[EXIT HANDLER] Job cancelled, terminating.",
    "AUDIT_TIMEOUT_SAVING": "[EXIT HANDLER] Job timed out, saving checkpoint.",
    "AUDIT_ERROR_SAVING":
        "[EXIT HANDLER] Error during training encountered, saving checkpoint.",
    "AUDIT_SAVED_FMT": "[EXIT HANDLER] Checkpoint saved at step {step}",
    "AUDIT_REQUEUE_FAILED_FMT":
        "[EXIT HANDLER] Failed to requeue job {job_id}.",
    "AUDIT_REQUEUED":
        "[EXIT HANDLER] sbatch requeued, new job will load the last checkpoint",
    "AUDIT_UNKNOWN_FMT":
        "[EXIT HANDLER] Unknown exit signal {type}, terminating.",
    "AUDIT_RESUME_FMT": "Resuming training from training_step {step}",
    "AUDIT_START": "Starting training!",
    "AUDIT_COMPLETED": "Training completed",
    "AUDIT_STEP_FMT": "Training step: {step} | Loss: {loss:.2f}",
    "AUDIT_SERVE_START": "Starting serving!",
    "AUDIT_SERVE_READY_FMT":
        "Serving ready | model {model} | checkpoint step {step} | "
        "slots {slots}",
    "AUDIT_SERVE_STEP_FMT":
        "Serve step: {step} | Active: {active} | Queued: {queued} | "
        "Done: {done}",
    "AUDIT_SERVE_DRAINING_FMT":
        "[EXIT HANDLER] Signal {signum} received, draining {active} "
        "in-flight request(s), admission stopped.",
    "AUDIT_SERVE_DRAINED_FMT":
        "[EXIT HANDLER] Drained; {completed} request(s) completed, "
        "{queued} queued request(s) not admitted.",
    "AUDIT_REQUEST_DONE_FMT":
        "Request {id} done | {reason} | prompt {prompt_tokens} tok | "
        "generated {new_tokens} tok | ttft {ttft_ms:.0f} ms | "
        "{tps:.1f} tok/s",
    "AUDIT_SERVE_COMPLETED": "Serving completed",
    "AUDIT_SERVE_PREFIX_FMT":
        "Prefix cache | lookups {lookups} | hit rate {rate:.3f} | "
        "hit tokens {hit_tokens} | cached blocks {cached} | "
        "cow copies {cow} | evictions {evictions}",
    "AUDIT_SERVE_PREFILL_FMT":
        "Packed prefill | rounds {rounds} | rows {rows} | occupancy "
        "{occupancy:.3f} | inplace chunks {inplace} | gather chunks "
        "{gather}",
    "AUDIT_SERVE_TREE_SPEC_FMT":
        "Tree spec | shape {shape} | rounds {rounds} | nodes {nodes} | "
        "accepted/round {per_round:.2f} | branch util {util:.3f}",
    "AUDIT_KV_LEAK_FMT":
        "[KV LEAK] {pool} pool: {leaked} block(s) leaked after drain "
        "({used} allocated, {cached} prefix-cached)",
    "AUDIT_CHAOS_INJECT_FMT": "[CHAOS] Injected {fault} at step {step}",
    "AUDIT_CKPT_VERIFY_FAILED_FMT":
        "[CKPT VERIFY] Checkpoint step {step} failed integrity check: "
        "{detail}",
    "AUDIT_CKPT_FALLBACK_FMT":
        "[CKPT VERIFY] Falling back to checkpoint step {step} "
        "(newest passing)",
    "AUDIT_CKPT_PARTIAL_SKIPPED_FMT":
        "[CKPT FINALIZE] Skipped partial checkpoint directory {name}",
    "AUDIT_TRACE_AUTO_FMT":
        "[TRACE] Step time regressed {ratio:.1f}x vs rolling median; "
        "capturing profiler window at step {step}",
    "AUDIT_PUBLISH_FMT":
        "[DEPLOY] Published checkpoint step {step} (digest {digest})",
    "AUDIT_RELOAD_FMT":
        "[DEPLOY] Weights reloaded: step {old} -> {new} | {active} "
        "in-flight | swap {ms:.0f} ms",
    "AUDIT_RELOAD_REJECTED_FMT":
        "[DEPLOY] Publish of step {step} rejected: {detail}; serving "
        "continues on step {current}",
    "AUDIT_FLEET_JOIN_FMT":
        "[FLEET] Host {host} joined: {slots} slot(s), {blocks} free "
        "block(s), lease ttl {ttl:.1f}s",
    "AUDIT_FLEET_LEAVE_FMT": "[FLEET] Host {host} left ({reason})",
    "AUDIT_FLEET_DEAD_FMT":
        "[FLEET] Host {host} declared dead: lease age {age:.1f}s > ttl "
        "{ttl:.1f}s; fencing and migrating {inflight} in-flight "
        "request(s)",
    "AUDIT_FLEET_MIGRATE_FMT":
        "[FLEET] Migrating request {id}: {src} -> {dst} (gen {gen}, "
        "{committed} committed token(s) replayed)",
    "AUDIT_FLEET_REQUEUE_FMT":
        "[FLEET] Requeued request {id} to the journal ({committed} "
        "committed token(s), reason {reason})",
    "AUDIT_LATENCY_FMT":
        "[LATENCY] Request {id} | trace {trace} | ttft {ttft_ms:.0f} ms "
        "| tpot {tpot_ms:.2f} ms | {tokens} tok | {reason}",
    "AUDIT_KV_TIER_FMT":
        "[KV TIER] Spill {action} request {id}: {blocks} block(s), "
        "{bytes} byte(s) (tier={tier})",
    "AUDIT_HANDOFF_FMT":
        "[HANDOFF] Block-shipment {action} request {id} (gen {gen}): "
        "{blocks} block(s), {detail}",
    "AUDIT_KV_QUANT_FMT":
        "[KV QUANT] dtype={dtype} | {bytes_per_block} B/block "
        "({ratio:.2f}x vs bf16) | {blocks_total} pool block(s)",
    "AUDIT_DISAGG_SHIP_FMT":
        "[DISAGG] Shipment {action} request {id} seq {seq} (gen {gen}): "
        "blocks [{start}, {end}), {detail}",
    "AUDIT_DISAGG_PLACE_FMT":
        "[DISAGG] Placement {action} request {id} (gen {gen}): {detail}",
    "AUDIT_KV_STORE_FMT":
        "[KV STORE] {action} key {key} request {id}: {blocks} block(s), "
        "{detail}",
    "AUDIT_KV_XPORT_FMT":
        "[KV XPORT] {action} lane {lane} request {id}: {blocks} block(s), "
        "{detail}",
    "AUDIT_FLEETSCOPE_FEDERATE_FMT":
        "[FLEETSCOPE] Federated {hosts} host(s): {series} series, "
        "{rollups} fleet rollup(s), {stale} stale, {failures} "
        "scrape failure(s)",
    "AUDIT_FLEETSCOPE_TIMELINE_FMT":
        "[FLEETSCOPE] Timeline: {events} event(s) from {hosts} host(s) "
        "in HLC order, {anomalies} anomalie(s)",
    "AUDIT_FLEETSCOPE_TREND_OK_FMT":
        "[FLEETSCOPE] Bench trend: {metrics} pinned metric(s) across "
        "{receipts} receipt(s) within {tolerance_pct}% of baseline",
    "AUDIT_FLEETSCOPE_TREND_REGRESSION_FMT":
        "[FLEETSCOPE] Bench trend REGRESSION: {receipt} {metric} "
        "{delta_pct:+.1f}% ({baseline} -> {current}, {direction} is "
        "better)",
    "AUDIT_ADAPTER_FMT":
        "[ADAPTER] {action} adapter {name}: {pages} page(s), {detail}",
    "AUDIT_ADAPTER_SUMMARY_FMT":
        "[ADAPTER] drain summary | served {served} adapter(s) | "
        "page-ins {pageins} | evictions {evictions} | resident "
        "{resident_bytes} byte(s) | rejects {rejects}",
}


def test_audit_strings_are_byte_identical_to_pins():
    for name, pinned in FROZEN.items():
        actual = getattr(ftl_logging, name)
        assert actual == pinned, (
            f"{name} drifted from the frozen contract:\n"
            f"  pinned : {pinned!r}\n  actual : {actual!r}\n"
            f"These strings are the grep-the-.out-file verification API — "
            f"changing one silently breaks the reference's checks.")


def test_no_new_unpinned_audit_strings():
    declared = {n for n in dir(ftl_logging) if n.startswith("AUDIT_")}
    assert declared == set(FROZEN), (
        "utils/logging.py and the frozen pin table disagree; add the new "
        "string (and its pin) here so it is contract-checked too")


def test_audit_strings_emitted_only_through_emit_audit():
    """``logger.info(AUDIT_*`` must not exist outside obs/events.py: the raw
    form logs the text without the paired structured event, so the flight
    recorder would silently miss that emission."""
    pattern = re.compile(r"\.\s*info\(\s*AUDIT_")
    offenders = []
    for path in [REPO / "train.py", *PKG.rglob("*.py")]:
        if path == PKG / "obs" / "events.py":
            continue  # the docstring naming the banned form
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if pattern.search(line):
                offenders.append(f"{path.relative_to(REPO)}:{i}: {line.strip()}")
    assert not offenders, (
        "raw logger.info(AUDIT_*) call sites found — route these through "
        "obs.events.emit_audit:\n" + "\n".join(offenders))


def test_engine_tests_take_their_model_from_the_tiny_helper():
    """A test file that builds an ``InferenceEngine`` builds no ``tiny``
    configuration of its own: ``get_config("tiny", ...)`` defaults to bf16,
    and a bf16 engine dies on this XLA:CPU before its first assertion
    (``tests/_tiny.py`` has the dot and the reason). The one place that
    decides the dtype of an engine-level CPU test is ``_tiny.tiny_cfg``.
    Training-side files that call ``get_config("tiny"`` are not its
    business; neither is ``tests/perfbench/`` (the benchmark's own)."""
    import ast

    def called(node):
        f = node.func
        return f.id if isinstance(f, ast.Name) else getattr(f, "attr", "")

    offenders = []
    for path in sorted((REPO / "tests").glob("test_*.py")):
        calls = [n for n in ast.walk(ast.parse(path.read_text()))
                 if isinstance(n, ast.Call)]
        own = [n.lineno for n in calls
               if called(n) == "get_config" and n.args
               and isinstance(n.args[0], ast.Constant)
               and n.args[0].value == "tiny"]
        if own and any(called(n) == "InferenceEngine" for n in calls):
            offenders += [f"tests/{path.name}:{ln}" for ln in own]
    assert not offenders, (
        "build the model of an engine-level test with "
        "`from _tiny import tiny_cfg` (float32 on this CPU), not with a "
        "get_config(\"tiny\", ...) of the file's own:\n"
        + "\n".join(offenders))


def test_emit_audit_pairs_one_event_per_emission(tmp_path):
    """Every emit_audit call: the audit text logged exactly once,
    byte-identical, plus exactly one structured event with matching step."""
    path = str(tmp_path / "ev.jsonl")
    events_mod.configure(path, job="contract")
    log = logging.getLogger("ftl-test-contract")
    lines = []

    class _Capture(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    log.addHandler(_Capture())
    log.setLevel(logging.INFO)

    emissions = [
        (ftl_logging.AUDIT_STEP_FMT.format(step=7, loss=2.5), "step", 7),
        (ftl_logging.AUDIT_SAVED_FMT.format(step=7), "exit", 7),
        (ftl_logging.AUDIT_TIMEOUT_SAVING, "signal", None),
        (ftl_logging.AUDIT_RESUME_FMT.format(step=7), "resume", 7),
    ]
    for text, kind, step in emissions:
        events_mod.emit_audit(log, text, kind, step=step)
    events_mod.flush()
    evs = events_mod.read_events(path)
    assert len(evs) == len(emissions) == len(lines)
    for (text, kind, step), ev, line in zip(emissions, evs, lines):
        assert line == text
        assert ev["kind"] == kind
        assert ev.get("step") == step
        assert ev["audit"] is True
    events_mod.configure(None)
