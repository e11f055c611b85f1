#!/bin/bash
# Nightly CI: the heavy verification the per-commit tier-1 run skips
# (ROADMAP "chaos-in-CI cadence" follow-up).
#
# 1. slow-marked suite — chaos end-to-end through train.py, the
#    speculative and prefix-cache compiled stream-equality tests;
# 2. chaos survival campaign — the five fault classes under the
#    fake_slurm shim plus the deploy scenario (publish -> hot reload ->
#    verify drill: a live serve absorbs two publishes with requests in
#    flight, rejects a chaos-corrupted one, bit-matches a fresh
#    restore), with the per-class survival verdicts diffed against the
#    committed receipt logs/chaos_campaign.txt (goodput and MTTR
#    columns are wall-clock noisy, so only class + survived are pinned;
#    a class flipping to "no" fails the night) and the deploy drill's
#    key checks pinned line-for-line; the fleet scenario (two heartbeat-
#    leased hosts, one SIGKILLed mid-decode, the router fences it and
#    migrates its journaled requests onto the survivor with bit-exact
#    replayed continuations) is pinned the same way, as is the tiered
#    scenario (a --handoff drain ships checksummed KV-block artifacts,
#    chaos corrupts one handoff and one spill artifact, the router and
#    the survivor CRC-reject exactly the poisoned ones and fall back to
#    committed-prefix replay, all streams bit-match an unfailed
#    reference), and the disagg scenario (two dedicated prefill engines
#    stream committed KV-block shipments to a dedicated decode engine;
#    chaos SIGKILLs one prefill host mid-prompt — its requests
#    re-prefill on the surviving peer — and flips a byte in one
#    shipment, which the router CRC-rejects into committed-prefix
#    replay; zero lost, every engine drains leak-clean, and all streams
#    bit-match an unfailed colocated reference), and the kvstore
#    scenario (one host publishes a shared prompt train into the
#    fleet-global block store, chaos poisons the published artifact and
#    SIGKILLs the publisher mid-decode; cache-affinity routing still
#    placed the follow-up request with the train, overflow intake
#    landed on the cold host by slot domination, the fetching survivor
#    CRC-rejects exactly once into local recompute, the shared train's
#    content address published exactly once fleet-wide, a post-mortem
#    journal fold finds no torn state and no leaked refcounts, and all
#    streams bit-match an unfailed single-host reference);
# 3. fused-dequant parity — compiles the int8 KV decode parity check
#    (scripts/kernel_checks.py check_quantized_decode_parity) at D=64
#    and D=128 over the adversarial pool matrix and requires it green;
# 4. adapter publish/reject drill — a CRC-manifested adapter artifact
#    publishes through published.json's tenant->adapter sub-pointer and
#    verifies green, then one flipped payload byte must fail
#    verify_pointer naming the adapter AND be rejected at page-in with
#    the adapter pool untouched;
# 5. fleet observability plane — (a) federation drill: two live
#    /metrics servers behind heartbeat leases (ports discovered from
#    the lease values, the real path), the aggregator's fleet rollups
#    must bit-match the per-host sums (gauges, counters, every
#    cumulative histogram bucket) with host=-labelled re-export and
#    HELP/TYPE deduped, and the CLI --once mode must render the same
#    scrape; (b) the chaos campaign's fleet post-mortem timeline
#    (postmortem_fleet.txt) must exist and its SIGKILL -> fence ->
#    migrate chain must appear in HLC (causal) order spanning both
#    hosts.
#
# The campaign's transport drill (chaos poisons one mem-lane push's
# fabric metadata AND the same request's fs payload, a second push takes
# only the mem poison: the ladder must degrade mem -> fs ->
# committed-prefix replay with zero requests lost, the frozen [KV XPORT]
# fallback audits present, every other train landing zero-copy on the
# mem lane, and all streams bit-matching an unfailed colocated
# reference) is pinned line-for-line in section 2.
#
# Speeds are the chip benchmark's (perfbench/, BENCHMARK.json, the
# driver's ledger); the exact counts and bit-matches of the serving
# paths are tier-1 tests (tests/test_paged_kv.py, test_paged_kernel.py,
# test_prefix_cache.py, test_kv_store.py, test_kv_tier.py,
# test_kv_quant.py, test_disagg.py, test_transport.py,
# test_adapter_serving.py). Nothing here times anything.
#
# Runs on CPU in a few minutes (tiny models, synthetic data).
set -euo pipefail

cd "$(dirname "$0")/.."
. scripts/demo_common.sh
demo_cpu_env
WORK=${CI_WORKDIR:-/tmp/ftl_ci_nightly}
rm -rf "$WORK"
mkdir -p "$WORK"

echo "== slow-marked suite"
python -m pytest tests/ -q -m slow --continue-on-collection-errors \
    -p no:cacheprovider -p no:randomly

echo "== chaos survival campaign (5 fault classes + deploy/fleet/tiered/disagg/kvstore/transport drills)"
export FAKE_SLURM_DIR="$WORK/slurm"
cat > "$WORK/requeue.sh" <<EOF
#!/bin/bash
#SBATCH --output=$WORK/slurm/requeue_%j.out
echo "requeue accepted: job \$SLURM_JOB_ID"
EOF
python scripts/chaos_campaign.py --seed 0 \
    --workdir "$WORK/campaign" \
    --sbatch "scripts/fake_slurm/sbatch $WORK/requeue.sh" \
    --out "$WORK/chaos_campaign.txt"

# survival verdicts must match the committed receipt class-for-class
extract_survival() {
    awk '/^class /{t=1; next} t && /^-+$/{next} t && NF==0{exit} t{print $1, $2}' "$1"
}
extract_survival logs/chaos_campaign.txt   > "$WORK/want.survival"
extract_survival "$WORK/chaos_campaign.txt" > "$WORK/got.survival"
if ! diff -u "$WORK/want.survival" "$WORK/got.survival"; then
    echo "FAIL: survival table drifted from committed logs/chaos_campaign.txt"
    exit 1
fi
echo "ok: survival verdicts match the committed receipt"

# the deploy drill's substance, not just its one-word verdict: both
# hot swaps carried live requests, the corrupt publish was rejected,
# and the post-swap streams bit-matched a fresh restore
for want in \
    "ok: swap 10->20 carried in-flight requests" \
    "ok: swap 20->30 carried in-flight requests" \
    "ok: corrupt publish rejected before load; serving continues on step 30" \
    "ok: post-swap streams bit-identical to a fresh restore of step 30"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: deploy drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: deploy drill (publish -> hot reload -> verify) checks present"

# the fleet migration drill's substance: the SIGKILLed host was
# declared dead and fenced, its requests were migrated with a committed
# prefix replayed, nothing was lost, the slow-but-alive host was NOT
# declared dead, the survivor drained leak-clean, and every stream
# bit-matched an unfailed single-host reference serve
for want in \
    "ok: host h0 SIGKILLed mid-decode by chaos (rc -9)" \
    "ok: router declared h0 dead and fenced it" \
    "ok: zero requests lost: all 4 served" \
    "ok: heartbeat-delayed h1 stayed under its ttl (no false dead verdict)" \
    "ok: survivor drained leak-clean and exited 0 (got rc 0)" \
    "ok: migrated streams bit-identical to the unfailed reference serve" \
    "ok: stitched trace: migrated request spans h0 and h1, replay count matches the journal committed prefix"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: fleet drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: fleet drill (lease -> dead verdict -> fence -> migrate) checks present"

# the tiered drill's substance: the --handoff drain exported checksummed
# block artifacts, chaos poisoned one handoff and one spill artifact,
# the router and the survivor CRC-rejected exactly the poisoned ones
# (falling back to committed-prefix replay), the good artifact's blocks
# were imported instead of replayed, the survivor's constrained pool
# spilled to the host tier and drained leak-clean across both tiers,
# and every stream bit-matched an unfailed reference serve
for want in \
    "ok: h0 drained via --handoff and exported both in-flight requests' blocks" \
    "ok: chaos flipped a payload byte in h0's first handoff artifact (manifest spared)" \
    "ok: router CRC-rejected exactly the corrupt artifact and shipped the other" \
    "ok: survivor imported the verified artifact's blocks instead of replaying" \
    "ok: survivor's constrained pool spilled a request to the host tier and chaos corrupted the artifact" \
    "ok: poisoned spill artifact CRC-rejected at restore and fell back to committed-prefix replay" \
    "ok: survivor drained leak-clean across device pool + spill tier and exited 0 (got rc 0)" \
    "ok: all streams (imported, replayed, spill-restored) bit-identical to the unfailed reference serve"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: tiered drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: tiered drill (handoff export -> CRC gate -> import-or-replay, spill -> reject -> replay) checks present"

# the disagg drill's substance: a prefill engine was SIGKILLed
# mid-prompt and its requests re-prefilled on the surviving prefill
# peer, chaos poisoned one of the survivor's block shipments and the
# router CRC-rejected exactly that one into committed-prefix replay,
# every request decoded on the dedicated decode engine, both surviving
# engines drained leak-clean, and all streams bit-matched an unfailed
# colocated reference serve
for want in \
    "ok: prefill host pre0 SIGKILLed mid-prompt by chaos (rc -9)" \
    "ok: router declared pre0 dead and fenced it" \
    "ok: dead host's mid-prompt requests re-prefilled on the surviving prefill peer" \
    "ok: chaos flipped a payload byte in one of pre1's shipments (manifest spared)" \
    "ok: router CRC-rejected exactly the poisoned shipment" \
    "ok: every request handed to the decode engine exactly once" \
    "ok: zero requests lost: all 4 served" \
    "ok: all four streams decoded on the dedicated decode engine" \
    "ok: prefill survivor drained leak-clean and exited 0" \
    "ok: decode engine drained leak-clean and exited 0" \
    "ok: disaggregated streams (shipped-block imports and the CRC-reject replay alike) bit-identical to the unfailed colocated reference" \
    "ok: stitched trace: all four requests flagged disaggregated with the decode host on the critical path"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: disagg drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: disagg drill (prefill kill -> re-prefill, ship corrupt -> CRC reject -> replay, decode placement) checks present"

# the kvstore drill's substance: the publisher's train was poisoned and
# the publisher SIGKILLed, cache-affinity placement still landed the
# follow-up request with the published train, the fetching host
# CRC-rejected exactly once into local recompute, exactly one publish
# happened fleet-wide (content-address dedup), nothing was lost, no
# torn store state survived the kill, and every stream bit-matched an
# unfailed single-host reference serve
for want in \
    "ok: h0 published the shared train to the fleet store" \
    "ok: chaos poisoned the published store artifact (manifest spared)" \
    "ok: publishing host h0 SIGKILLed mid-decode (rc -9)" \
    "ok: cache-affinity placement: req1 landed with the published train on h0" \
    "ok: free slots dominate affinity: overflow intake landed on the cold host h1" \
    "ok: content-address dedup: shared prompt train published exactly once fleet-wide, by h0" \
    "ok: exactly one CRC reject, on h1, degrading to local recompute (got 1)" \
    "ok: zero requests lost: all 4 served" \
    "ok: store post-mortem: exactly the one poisoned train fails CRC" \
    "ok: no leaked store refcounts: every journaled fetch ref was released" \
    "ok: store-fetched, reject-recomputed and migrated streams all bit-identical to the unfailed single-host reference serve"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: kvstore drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: kvstore drill (publish -> poison -> affinity place -> CRC reject -> recompute) checks present"

# the transport drill's substance: one pushed train lost BOTH its mem
# metadata and its fs payload (ladder bottoms out at replay), a second
# lost only its mem metadata (one rung down, onto the fs artifact),
# the untouched trains landed zero-copy on the mem lane, the frozen
# [KV XPORT] fallback audit fired for both poisoned trains, nothing
# was lost or leaked, and every stream bit-matched an unfailed
# colocated reference
for want in \
    "ok: chaos poisoned exactly the first mem push's fabric metadata (mem_corrupt, ordinal 0)" \
    "ok: every exported train was pushed to the shared fabric" \
    "ok: zero requests lost: decode completed 4/4 across all three degradation rungs" \
    "ok: all decode streams — mem-landed, fs-degraded and replayed alike — bit-identical to the unfailed colocated reference" \
    "ok: untouched trains landed zero-copy on the mem lane" \
    "ok: degradation ladder: two mem->fs fallbacks, one of which fell through to replay (fallbacks 2, rejects 1)" \
    "ok: audit trail: [KV XPORT] fallback lane mem logged for both poisoned trains (got 2)" \
    "ok: no leaked KV blocks on either role after the ladder"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: transport drill check missing from report: $want"
        exit 1
    fi
done
echo "ok: transport drill (mem poison -> fs artifact -> committed-prefix replay, zero loss) checks present"

echo "== fused-dequant parity check (int8 KV, D=64/128)"
python - <<'EOF'
import sys

sys.path.insert(0, ".")
from scripts.kernel_checks import check_quantized_decode_parity

ok = check_quantized_decode_parity()
ok &= check_quantized_decode_parity(h=8, kv=4, d=128)
assert ok, "quantized decode parity check failed"
print("ok: fused-dequant kernels within error bounds at D=64 and D=128")
EOF

echo "== adapter publish/reject drill (verified sub-pointer, corrupt page-in)"
ADPT_DIR="$WORK/adapter_drill"
rm -rf "$ADPT_DIR"
mkdir -p "$ADPT_DIR"
python - "$ADPT_DIR" <<'EOF'
import os
import sys

sys.path.insert(0, ".")
root = sys.argv[1]

from fault_tolerant_llm_training_tpu.checkpoint.manager import (
    write_manifest)
from fault_tolerant_llm_training_tpu.deploy.publish import (
    Publisher, adapter_pointer, verify_pointer)
from fault_tolerant_llm_training_tpu.inference.adapters import (
    AdapterIntegrityError, AdapterLayout, AdapterManager,
    init_adapter_factors, write_adapter_artifact)
from fault_tolerant_llm_training_tpu.models.configs import get_config

cfg = get_config("tiny", vocab_size=64, layer_impl="loop")
layout = AdapterLayout.from_cfg(cfg, 4)

step_dir = os.path.join(root, "checkpoint_pub", "20")
os.makedirs(step_dir)
with open(os.path.join(step_dir, "payload.bin"), "wb") as fh:
    fh.write(b"weights" * 64)
write_manifest(step_dir, 20)

facts = init_adapter_factors(layout, seed=3, scale=0.5)
ent = write_adapter_artifact(root, "tenant-a", 20, facts, rank=4,
                             alpha=32.0)
art = os.path.join(root, ent["path"])
sub = adapter_pointer(root, "tenant-a", art)
assert sub is not None and sub["rank"] == 4
ptr = Publisher(root, "pub").publish(20, adapters={"tenant-a": sub})
assert ptr is not None
assert verify_pointer(root, ptr) == (True, "ok")
print("ok: adapter artifact published as a tenant sub-pointer and "
      "verified green (manifest digest + per-file CRC)")

victim = sorted(f for f in os.listdir(art) if f.endswith(".npy"))[0]
with open(os.path.join(art, victim), "r+b") as fh:
    fh.seek(-1, os.SEEK_END)
    b = fh.read(1)
    fh.seek(-1, os.SEEK_END)
    fh.write(bytes([b[0] ^ 0xFF]))
ok, detail = verify_pointer(root, ptr)
assert not ok and "adapter tenant-a" in detail, detail
print("ok: one flipped payload byte fails verify-before-load naming "
      "the adapter")

written = []
mgr = AdapterManager(layout, 2 * layout.pages_per_adapter + 1,
                     lambda rows, pages: written.append(rows))
mgr.register("tenant-a", art)
try:
    mgr.page_in("tenant-a")
    raise AssertionError("corrupt artifact paged in")
except AdapterIntegrityError:
    pass
assert mgr.allocator.used_count == 0 and not written
print("ok: corrupt adapter rejected at page-in with the adapter pool "
      "untouched (0 pages allocated, 0 pages written)")
EOF

echo "== fleet metrics federation drill (2 hosts -> rollups == per-host sums)"
FED_DIR="$WORK/feddrill"
rm -rf "$FED_DIR"
mkdir -p "$FED_DIR"
python - "$FED_DIR" <<'EOF'
import sys
import urllib.request

sys.path.insert(0, ".")
from fault_tolerant_llm_training_tpu.ft.lease import (FileKVStore,
                                                      LeaseRegistry)
from fault_tolerant_llm_training_tpu.obs import federate
from fault_tolerant_llm_training_tpu.obs.federate import (
    Federator, parse_metrics_text)
from fault_tolerant_llm_training_tpu.obs.prometheus import MetricsServer
from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

root = sys.argv[1]
store = FileKVStore(root + "/store")
specs = {"h0": (12.5, 128, [0.03, 0.08, 0.4]),
         "h1": (30.0, 320, [0.06, 0.9])}
servers, per_host = [], {}
for host, (tps, tok, ttfts) in sorted(specs.items()):
    reg = MetricRegistry()
    reg.gauge("ftl_serve_tokens_per_sec", "decode throughput").set(tps)
    reg.counter("ftl_serve_tokens_generated_total", "tokens").inc(tok)
    hist = reg.histogram("ftl_serve_ttft_seconds", "ttft")
    for v in ttfts:
        hist.observe(v)
    srv = MetricsServer(registry=reg, port=0)
    port = srv.start()
    servers.append(srv)
    LeaseRegistry(store, host_id=host).renew(
        slots_free=4, blocks_free=64, block_size=16, metrics_port=port)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
        per_host[host] = parse_metrics_text(
            resp.read().decode("utf-8"))

# the aggregator discovers the ports from the lease values and scrapes
# the same endpoints over loopback — the real path, no injection
fed = Federator(root + "/store", slo_ttft_ms=100.0)
text = fed.render()
with open(root + "/federated.txt", "w") as fh:
    fh.write(text)
meta, samples = parse_metrics_text(text)
got = {}
for name, labels, value in samples:
    got.setdefault(name, []).append((labels, value))


def host_sum(sample_name):
    return sum(v for _m, ss in per_host.values()
               for n, lb, v in ss if n == sample_name)


assert got["fleet_hosts_live"][0][1] == 2
assert got["fleet_hosts_scraped"][0][1] == 2
assert got["fleet_scrape_failures_total"][0][1] == 0
# bit-match: the rollups ARE the per-host sums, not approximations
assert got["fleet_tokens_per_sec"][0][1] \
    == host_sum("ftl_serve_tokens_per_sec") == 42.5
assert got["fleet_ftl_serve_tokens_generated_total"][0][1] \
    == host_sum("ftl_serve_tokens_generated_total") == 448
assert got["fleet_ttft_seconds_count"][0][1] \
    == host_sum("ftl_serve_ttft_seconds_count") == 5
assert got["fleet_ttft_seconds_sum"][0][1] \
    == round(host_sum("ftl_serve_ttft_seconds_sum"), 9)
fleet_buckets = {lb["le"]: v
                 for lb, v in got["fleet_ttft_seconds_bucket"]}
for le, v in fleet_buckets.items():
    per = sum(val for _m, ss in per_host.values()
              for n, lb, val in ss
              if n == "ftl_serve_ttft_seconds_bucket"
              and lb["le"] == le)
    assert v == per, f"bucket le={le}: fleet {v} != per-host sum {per}"
# every per-host series is re-exported with a host= label
hosts = {lb["host"] for lb, _v in got["ftl_serve_tokens_per_sec"]}
assert hosts == {"h0", "h1"}
# HELP/TYPE exactly once per family across both hosts
for line in ("# TYPE ftl_serve_ttft_seconds histogram",
             "# TYPE ftl_serve_tokens_per_sec gauge",
             "# TYPE fleet_ttft_seconds histogram"):
    assert text.count(line) == 1, line
# 3 of 5 requests under the 100 ms SLO bar at bucket resolution
slo = {lb["slo"]: v for lb, v in got["fleet_slo_attainment"]}
assert slo["ttft"] == 0.6, slo
# the CLI --once path renders the identical scrape (modulo lease age)
rc = federate.main(["--store", root + "/store", "--once",
                    "--out", root + "/federated_cli.txt"])
assert rc == 0
cli = open(root + "/federated_cli.txt").read()
assert "fleet_tokens_per_sec 42.5" in cli
assert "fleet_hosts_live 2" in cli
for srv in servers:
    srv.stop()
print("ok: federation drill — fleet rollups bit-match the per-host "
      "sums (tokens/s 42.5, counters 448, ttft count 5, every "
      "cumulative bucket), host= re-export + deduped headers, "
      "SLO attainment 0.6, CLI --once green")
EOF

echo "== chaos post-mortem timeline (fleet scenario, HLC causal order)"
if ! test -s "$WORK/campaign/seed0/postmortem_fleet.txt"; then
    echo "FAIL: campaign did not emit postmortem_fleet.txt"
    exit 1
fi
for want in \
    "ok: post-mortem timeline generated from the scenario's event/trace/journal trails" \
    "ok: post-mortem annotates the chaos kill, the fence verdict and the migration" \
    "ok: SIGKILL -> fence -> migrate chain appears in HLC (causal) order in the post-mortem timeline" \
    "ok: the annotated kill belongs to host h0's trail" \
    "ok: the timeline spans the surviving host's trail too"
do
    if ! grep -qF "$want" "$WORK/chaos_campaign.txt"; then
        echo "FAIL: fleet post-mortem check missing from report: $want"
        exit 1
    fi
done
echo "ok: fleet post-mortem (SIGKILL -> fence -> migrate in HLC order) checks present"

echo "OK: nightly green (slow suite, chaos survival, fleet migration, tiered handoff+spill, disagg, fleet kv store, kv transport, int8 parity, adapter publish drill, federation drill, fleet post-mortem)"
