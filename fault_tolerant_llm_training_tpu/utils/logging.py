"""Logging + machine-checkable audit strings (ref: utils.py:10,21-29).

The reference's log strings are effectively the system's verification API —
its README asserts fault-tolerance correctness by grepping the Slurm ``.out``
files for the ``[EXIT HANDLER]`` audit trail and the resume breadcrumbs
(ref: utils.py:68,71,73,81,86,88,90; train.py:81). We keep those strings
byte-identical so the same checks (and our tests) work unchanged.
"""

import logging
import sys

logger = logging.getLogger()


def init_logger(level: int = logging.INFO) -> None:
    """Root logger -> stdout with the reference's format (ref: utils.py:21-29)."""
    logger.setLevel(level)
    logger.handlers.clear()  # absl/jax may have installed a basicConfig handler
    ch = logging.StreamHandler(sys.stdout)
    ch.setLevel(level)
    formatter = logging.Formatter("%(asctime)s - %(name)s - %(levelname)s - %(message)s")
    ch.setFormatter(formatter)
    logger.addHandler(ch)
    # Orbax/absl INFO chatter would drown the audit trail the .out files are
    # grepped for (SURVEY.md §4.3).
    logging.getLogger("absl").setLevel(logging.WARNING)


# --- Audit strings (byte-identical to the reference where behavior matches) ---
# ref: utils.py:68
AUDIT_CANCELLED = "[EXIT HANDLER] Job cancelled, terminating."
# ref: utils.py:71
AUDIT_TIMEOUT_SAVING = "[EXIT HANDLER] Job timed out, saving checkpoint."
# ref: utils.py:73
AUDIT_ERROR_SAVING = "[EXIT HANDLER] Error during training encountered, saving checkpoint."
# ref: utils.py:81 (formatted with the step)
AUDIT_SAVED_FMT = "[EXIT HANDLER] Checkpoint saved at step {step}"
# ref: utils.py:86
AUDIT_REQUEUE_FAILED_FMT = "[EXIT HANDLER] Failed to requeue job {job_id}."
# ref: utils.py:88
AUDIT_REQUEUED = "[EXIT HANDLER] sbatch requeued, new job will load the last checkpoint"
# ref: utils.py:90
AUDIT_UNKNOWN_FMT = "[EXIT HANDLER] Unknown exit signal {type}, terminating."
# ref: train.py:81
AUDIT_RESUME_FMT = "Resuming training from training_step {step}"
# ref: train.py:84
AUDIT_START = "Starting training!"
# ref: train.py:118
AUDIT_COMPLETED = "Training completed"
# ref: train.py:116 (formatted)
AUDIT_STEP_FMT = "Training step: {step} | Loss: {loss:.2f}"

# --- Serving audit strings (inference/serve.py) — same grep-the-.out-file
# discipline as the training trail: the drain lifecycle is asserted by
# tests/test_inference.py exactly like the exit-handler strings above. ---
AUDIT_SERVE_START = "Starting serving!"
AUDIT_SERVE_READY_FMT = ("Serving ready | model {model} | checkpoint step "
                         "{step} | slots {slots}")
AUDIT_SERVE_STEP_FMT = ("Serve step: {step} | Active: {active} | "
                        "Queued: {queued} | Done: {done}")
AUDIT_SERVE_DRAINING_FMT = ("[EXIT HANDLER] Signal {signum} received, "
                            "draining {active} in-flight request(s), "
                            "admission stopped.")
AUDIT_SERVE_DRAINED_FMT = ("[EXIT HANDLER] Drained; {completed} request(s) "
                           "completed, {queued} queued request(s) not "
                           "admitted.")
AUDIT_REQUEST_DONE_FMT = ("Request {id} done | {reason} | prompt "
                          "{prompt_tokens} tok | generated {new_tokens} tok "
                          "| ttft {ttft_ms:.0f} ms | {tps:.1f} tok/s")
AUDIT_SERVE_COMPLETED = "Serving completed"
AUDIT_SERVE_PREFIX_FMT = ("Prefix cache | lookups {lookups} | hit rate "
                          "{rate:.3f} | hit tokens {hit_tokens} | cached "
                          "blocks {cached} | cow copies {cow} | evictions "
                          "{evictions}")
AUDIT_SERVE_PREFILL_FMT = ("Packed prefill | rounds {rounds} | rows {rows} "
                           "| occupancy {occupancy:.3f} | inplace chunks "
                           "{inplace} | gather chunks {gather}")
AUDIT_SERVE_TREE_SPEC_FMT = ("Tree spec | shape {shape} | rounds {rounds} "
                             "| nodes {nodes} | accepted/round "
                             "{per_round:.2f} | branch util {util:.3f}")
AUDIT_KV_LEAK_FMT = ("[KV LEAK] {pool} pool: {leaked} block(s) leaked "
                     "after drain ({used} allocated, {cached} "
                     "prefix-cached)")

# --- Chaos + checkpoint-integrity audit trail (chaos/injector.py,
# checkpoint/manager.py) — same contract: these strings are what
# scripts/chaos_campaign.py and tests/test_chaos.py grep for, frozen in
# tests/test_audit_contract.py like the rest. ---
AUDIT_CHAOS_INJECT_FMT = "[CHAOS] Injected {fault} at step {step}"
AUDIT_TRACE_AUTO_FMT = ("[TRACE] Step time regressed {ratio:.1f}x vs "
                        "rolling median; capturing profiler window at "
                        "step {step}")
AUDIT_CKPT_VERIFY_FAILED_FMT = ("[CKPT VERIFY] Checkpoint step {step} "
                                "failed integrity check: {detail}")
AUDIT_CKPT_FALLBACK_FMT = ("[CKPT VERIFY] Falling back to checkpoint step "
                           "{step} (newest passing)")
AUDIT_CKPT_PARTIAL_SKIPPED_FMT = ("[CKPT FINALIZE] Skipped partial "
                                  "checkpoint directory {name}")

# --- Deployment-loop audit trail (deploy/publish.py, deploy/reload.py) —
# the continuous train->serve loop's grep surface: publishes, hot weight
# swaps and rejected (corrupt) publishes are asserted by
# tests/test_deploy.py and scripts/chaos_campaign.py exactly like the
# drain lifecycle above. ---
AUDIT_PUBLISH_FMT = ("[DEPLOY] Published checkpoint step {step} "
                     "(digest {digest})")
AUDIT_RELOAD_FMT = ("[DEPLOY] Weights reloaded: step {old} -> {new} | "
                    "{active} in-flight | swap {ms:.0f} ms")
AUDIT_RELOAD_REJECTED_FMT = ("[DEPLOY] Publish of step {step} rejected: "
                             "{detail}; serving continues on step "
                             "{current}")

# --- Serving-fleet audit trail (inference/fleet.py, inference/router.py) —
# membership and migration lifecycle: hosts audit their own join/leave,
# the router audits dead verdicts and migrations. scripts/chaos_campaign.py's
# fleet scenario and tests/test_fleet.py grep these, frozen in
# tests/test_audit_contract.py like the rest. ---
AUDIT_FLEET_JOIN_FMT = ("[FLEET] Host {host} joined: {slots} slot(s), "
                        "{blocks} free block(s), lease ttl {ttl:.1f}s")
AUDIT_FLEET_LEAVE_FMT = "[FLEET] Host {host} left ({reason})"
AUDIT_FLEET_DEAD_FMT = ("[FLEET] Host {host} declared dead: lease age "
                        "{age:.1f}s > ttl {ttl:.1f}s; fencing and "
                        "migrating {inflight} in-flight request(s)")
AUDIT_FLEET_MIGRATE_FMT = ("[FLEET] Migrating request {id}: {src} -> {dst} "
                           "(gen {gen}, {committed} committed token(s) "
                           "replayed)")
AUDIT_FLEET_REQUEUE_FMT = ("[FLEET] Requeued request {id} to the journal "
                           "({committed} committed token(s), reason "
                           "{reason})")

# --- Request-latency audit trail (inference/serve.py, inference/fleet.py) —
# the drain summary prints one per-request latency verdict so operators
# (and scripts/chaos_campaign.py) can grep TTFT/TPOT off the .out file;
# obs/reqtrace.py holds the machine-readable span trail behind it. ---
AUDIT_LATENCY_FMT = ("[LATENCY] Request {id} | trace {trace} | ttft "
                     "{ttft_ms:.0f} ms | tpot {tpot_ms:.2f} ms | "
                     "{tokens} tok | {reason}")

# --- Tiered-KV audit trail (inference/scheduler.py spill tier,
# inference/fleet.py + router.py block-shipment handoff) — every block
# movement across tiers is audited: spill exports, verified restores,
# CRC rejects (which fall back to the bit-exact committed-prefix
# replay), and handoff shipments. scripts/chaos_campaign.py's tiered
# scenario and tests/test_kv_tier.py grep these, frozen in
# tests/test_audit_contract.py like the rest. ---
AUDIT_KV_TIER_FMT = ("[KV TIER] Spill {action} request {id}: {blocks} "
                     "block(s), {bytes} byte(s) (tier={tier})")
AUDIT_HANDOFF_FMT = ("[HANDOFF] Block-shipment {action} request {id} "
                     "(gen {gen}): {blocks} block(s), {detail}")

# --- Quantized-KV audit trail (inference/serve.py, inference/fleet.py) —
# the drain summary's --kv-dtype receipt: what the pool stored its blocks
# as, the bytes one block costs (scale rows included), and the capacity
# ratio against the bf16 layout at the same geometry. Emitted for every
# paged engine (bf16 reads ratio 1.00), so the line is always on the
# grep surface; frozen in tests/test_audit_contract.py like the rest. ---
AUDIT_KV_QUANT_FMT = ("[KV QUANT] dtype={dtype} | {bytes_per_block} "
                      "B/block ({ratio:.2f}x vs bf16) | {blocks_total} "
                      "pool block(s)")

# --- Disaggregated prefill/decode audit trail (inference/scheduler.py,
# inference/router.py, inference/fleet.py) — the prefill->decode pipeline's
# grep surface: every incremental block shipment a prefill engine exports,
# every verified/rejected import on a decode engine, and the router's
# role-aware placements (including the placement-time mixed-dtype
# rejection). scripts/chaos_campaign.py's disagg scenario and
# tests/test_disagg.py grep these, frozen in tests/test_audit_contract.py
# like the rest. ---
AUDIT_DISAGG_SHIP_FMT = ("[DISAGG] Shipment {action} request {id} seq "
                         "{seq} (gen {gen}): blocks [{start}, {end}), "
                         "{detail}")
AUDIT_DISAGG_PLACE_FMT = ("[DISAGG] Placement {action} request {id} "
                          "(gen {gen}): {detail}")

# --- Fleet-global KV store audit trail (inference/kvstore.py via
# inference/scheduler.py) — the content-addressed block store's grep
# surface: publishes of committed prefix trains, verified cross-host
# fetches with their hit depth, CRC rejects (which degrade to local
# chunked prefill), and the sweeper's LRU evictions. The campaign's
# kvstore scenario and tests/test_kv_store.py grep these, frozen in
# tests/test_audit_contract.py like the rest. ---
AUDIT_KV_STORE_FMT = ("[KV STORE] {action} key {key} request {id}: "
                      "{blocks} block(s), {detail}")

# --- KV transport audit trail (inference/transport.py via
# inference/scheduler.py) — the pluggable block-train lane's grep
# surface: mem-lane pushes riding each shipment/publish export, which
# lane a train actually landed on, lane fallbacks (a mem metadata
# mismatch degrading to the fs artifact, the fs CRC reject degrading to
# replay), partial store hits, and paced prefill admissions. The
# campaign's transport scenario and tests/test_transport.py grep these,
# frozen in tests/test_audit_contract.py like the rest. ---
AUDIT_KV_XPORT_FMT = ("[KV XPORT] {action} lane {lane} request {id}: "
                      "{blocks} block(s), {detail}")

# --- Fleet-wide observability plane audit trail (obs/federate.py,
# scripts/fleet_timeline.py) — the aggregation layer's grep surface: each
# federation sweep (hosts scraped, series re-exported, fleet rollups
# derived) and each HLC-ordered timeline fold with its anomaly count.
# ci_nightly's federation drill and tests/test_fleetscope.py grep these,
# frozen in tests/test_audit_contract.py like the rest. The two TREND
# formats have no writer (ROADMAP D5). ---
AUDIT_FLEETSCOPE_FEDERATE_FMT = ("[FLEETSCOPE] Federated {hosts} host(s): "
                                 "{series} series, {rollups} fleet "
                                 "rollup(s), {stale} stale, {failures} "
                                 "scrape failure(s)")
AUDIT_FLEETSCOPE_TIMELINE_FMT = ("[FLEETSCOPE] Timeline: {events} event(s) "
                                 "from {hosts} host(s) in HLC order, "
                                 "{anomalies} anomalie(s)")
AUDIT_FLEETSCOPE_TREND_OK_FMT = ("[FLEETSCOPE] Bench trend: {metrics} "
                                 "pinned metric(s) across {receipts} "
                                 "receipt(s) within {tolerance_pct}% of "
                                 "baseline")
AUDIT_FLEETSCOPE_TREND_REGRESSION_FMT = ("[FLEETSCOPE] Bench trend "
                                         "REGRESSION: {receipt} "
                                         "{metric} {delta_pct:+.1f}% "
                                         "({baseline} -> {current}, "
                                         "{direction} is better)")

# --- Multi-tenant adapter serving audit trail (inference/adapters.py via
# scheduler/serve/fleet) — one action-shaped line for the adapter pool's
# lifecycle (page-in, evict, swap, reject) and a drain summary mirroring
# the prefix-cache line. FROZEN; pinned by tests/test_audit_contract.py.
AUDIT_ADAPTER_FMT = ("[ADAPTER] {action} adapter {name}: {pages} page(s), "
                     "{detail}")
AUDIT_ADAPTER_SUMMARY_FMT = ("[ADAPTER] drain summary | served {served} "
                             "adapter(s) | page-ins {pageins} | evictions "
                             "{evictions} | resident {resident_bytes} "
                             "byte(s) | rejects {rejects}")
