"""Fleet observability plane tests: hybrid logical clocks (monotonicity
under injected clock skew, two-writer merge, fold determinism), HLC
stamps on every recorder, the /metrics federation aggregator (per-host
re-export, fleet rollups, histogram merges, stale-host gauge), the
exposition parser's escaping roundtrip, and the HLC-causal timeline."""

import json
import os
import sys
from pathlib import Path

import pytest

from fault_tolerant_llm_training_tpu.ft.lease import (
    FileKVStore,
    LeaseRegistry,
)
from fault_tolerant_llm_training_tpu.obs import events as events_mod
from fault_tolerant_llm_training_tpu.obs import federate, hlc
from fault_tolerant_llm_training_tpu.obs.federate import (
    Federator,
    family_of,
    parse_metrics_text,
)
from fault_tolerant_llm_training_tpu.obs.registry import (
    MetricRegistry,
    escape_help,
    escape_label_value,
)

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from scripts import fleet_timeline  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_clock_and_recorder():
    """Zero the process HLC and flight recorder per test."""
    hlc.reset()
    events_mod._RECORDER = events_mod.FlightRecorder()
    yield
    hlc.reset()
    events_mod._RECORDER = events_mod.FlightRecorder()


class FakeTime:
    """Injectable physical clock that tests can step (even backwards)."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t


# ------------------------------------------------------------------ HLC

def test_hlc_pack_order_is_string_order():
    stamps = [hlc.pack(w, c)
              for w in (0, 1, 5, 1 << 40) for c in (0, 1, 255)]
    assert sorted(stamps) == sorted(
        stamps, key=lambda s: hlc.unpack(s))
    assert hlc.ZERO < hlc.pack(1, 0)
    assert hlc.unpack("garbage") == (0, 0)
    assert hlc.unpack(None) == (0, 0)
    assert hlc.unpack(hlc.pack(123, 7)) == (123, 7)


def test_hlc_monotonic_when_clock_steps_backwards():
    ft = FakeTime(100.0)
    c = hlc.HLC(physical=ft)
    stamps = [c.tick()]
    ft.t = 50.0  # OS clock stepped back mid-sequence
    for _ in range(5):
        stamps.append(c.tick())
    ft.t = 200.0  # clock recovers
    stamps.append(c.tick())
    assert stamps == sorted(stamps)
    assert len(set(stamps)) == len(stamps)
    # wall component never went backwards; counter absorbed the rewind
    walls = [hlc.unpack(s)[0] for s in stamps]
    assert walls == sorted(walls)
    # after recovery the wall advances and the counter resets
    assert hlc.unpack(stamps[-1]) == (int(200.0 * 1e6), 0)


def test_hlc_two_writer_merge_orders_receive_after_send():
    ahead = hlc.HLC(physical=FakeTime(200.0))   # writer with fast clock
    behind = hlc.HLC(physical=FakeTime(100.0))  # reader 100 s behind
    sent = ahead.tick()
    # before the merge the behind clock stamps below the remote
    assert behind.tick() < sent
    got = behind.merge(sent)
    assert got > sent
    # every subsequent local tick also sorts after the merged stamp,
    # even though the reader's physical clock is still behind
    assert behind.tick() > sent


def test_hlc_observe_advances_without_minting():
    c = hlc.HLC(physical=FakeTime(100.0))
    remote = hlc.pack(int(500.0 * 1e6), 3)
    c.observe(remote)
    assert c.read() == remote  # adopted, not incremented
    assert c.tick() > remote   # the next real event sorts after it
    c.observe("not-a-stamp")   # garbage is a no-op, never a crash
    c.observe(None)


def test_recorders_stamp_hlc(tmp_path):
    ft = FakeTime(100.0)
    hlc.reset(ft)
    rec = events_mod.FlightRecorder(str(tmp_path / "ev.jsonl"),
                                    job="t", host=0, clock=ft)
    rec.emit("step", step=1)
    ft.t = 50.0  # skew: wall t goes backwards, hlc must not
    rec.emit("step", step=2)
    rec.flush()
    evs = events_mod.read_events(str(tmp_path / "ev.jsonl"))
    assert all(e.get("hlc") for e in evs)
    assert evs[0]["hlc"] < evs[1]["hlc"]
    assert evs[1]["t"] < evs[0]["t"]  # the wall clock DID lie


def test_journal_fold_observes_hlc_deterministically(tmp_path):
    from fault_tolerant_llm_training_tpu.inference import journal
    hlc.reset(FakeTime(100.0))
    j1 = journal.RequestJournal(str(tmp_path), writer="h0")
    j1.assign("r1", "h0", [1, 2], 8, 0.0, 1.0, 0)
    j1.progress("r1", "h0", [5], gen=0)
    folded_a = journal.fold(str(tmp_path))
    stamp_after_first_fold = hlc.clock().read()
    # a fresh reader folding the same files lands on the same HLC state
    hlc.reset(FakeTime(100.0))
    folded_b = journal.fold(str(tmp_path))
    assert hlc.clock().read() == stamp_after_first_fold
    assert sorted(folded_a) == sorted(folded_b)
    # and the reader's next stamp sorts after every folded record
    top = max(r.get("hlc", hlc.ZERO)
              for r in _jsonl_records(tmp_path))
    assert hlc.tick() > top


def _jsonl_records(root):
    out = []
    for path in Path(root).rglob("*.jsonl"):
        for line in path.read_text().splitlines():
            if line.strip():
                out.append(json.loads(line))
    return out


def test_lease_renewal_carries_and_merges_hlc(tmp_path):
    store = FileKVStore(str(tmp_path))
    hlc.reset(FakeTime(500.0))
    LeaseRegistry(store, host_id="h0").renew(
        slots_free=4, blocks_free=8, block_size=16, metrics_port=9100)
    sent = hlc.clock().read()
    # a reader 400 s behind sweeps the lease and must advance past it
    hlc.reset(FakeTime(100.0))
    reader = LeaseRegistry(store, host_id=None)
    leases = reader.leases()
    assert leases["h0"].metrics_port == 9100
    assert leases["h0"].hlc
    assert hlc.tick() > sent


# ------------------------------------------------------------ exposition

def test_registry_escapes_labels_and_help():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert escape_help("up\\down\nnext") == "up\\\\down\\nnext"
    r = MetricRegistry()
    c = r.counter("ftl_esc_total", 'tricky "help"\nwith newline')
    c.labels(tok='bad "tok"\nnl').inc(3)
    text = r.render()
    assert '\\"tok\\"\\nnl' in text
    assert "# HELP ftl_esc_total" in text
    assert "\nwith" not in text  # HELP newline escaped, single line
    meta, samples = parse_metrics_text(text)
    (name, labels, value), = [s for s in samples
                              if s[0] == "ftl_esc_total"]
    assert labels["tok"] == 'bad "tok"\nnl'  # roundtrip exact
    assert value == 3


def test_registry_histogram_renders_sum_and_count():
    r = MetricRegistry()
    h = r.histogram("ftl_esc_seconds", "h", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.render()
    assert "ftl_esc_seconds_sum" in text
    assert "ftl_esc_seconds_count 2" in text
    meta, samples = parse_metrics_text(text)
    assert meta["ftl_esc_seconds"]["kind"] == "histogram"
    assert family_of("ftl_esc_seconds_bucket", meta) == "ftl_esc_seconds"
    assert family_of("ftl_esc_seconds_count", meta) == "ftl_esc_seconds"


# ------------------------------------------------------------ federation

def _host_registry(tps, tokens_total, ttfts):
    r = MetricRegistry()
    r.gauge("ftl_serve_tokens_per_sec", "tput").set(tps)
    r.counter("ftl_serve_tokens_generated_total", "tok").inc(tokens_total)
    h = r.histogram("ftl_serve_ttft_seconds", "ttft")
    for v in ttfts:
        h.observe(v)
    return r


def _fleet(tmp_path, clock, pages, renew=((32, 9101), (32, 9102)),
           **kw):
    store = FileKVStore(str(tmp_path / "fleet"))
    for i, (blocks, port) in enumerate(renew):
        LeaseRegistry(store, host_id=f"h{i}", clock=clock).renew(
            slots_free=4, blocks_free=blocks, block_size=16,
            metrics_port=port)

    def fetch(host, port):
        if host not in pages:
            raise OSError("scrape refused")
        return pages[host]

    return Federator(str(tmp_path / "fleet"), clock=clock, fetch=fetch,
                     **kw)


def test_federator_rollups_match_per_host_sums(tmp_path):
    clock = FakeTime(1000.0)
    pages = {
        "h0": _host_registry(10.0, 100, [0.05, 0.08]).render(),
        "h1": _host_registry(25.0, 250, [0.05, 3.0]).render(),
    }
    fed = _fleet(tmp_path, clock, pages, slo_ttft_ms=100.0)
    text = fed.render()
    meta, samples = parse_metrics_text(text)
    by = {}
    for name, labels, value in samples:
        by.setdefault(name, []).append((labels, value))
    # per-host re-export carries host= labels
    hosts = {lb["host"] for lb, _ in by["ftl_serve_tokens_per_sec"]}
    assert hosts == {"h0", "h1"}
    # fleet rollups are the exact per-host sums
    assert by["fleet_tokens_per_sec"][0][1] == 35.0
    assert by["fleet_ftl_serve_tokens_generated_total"][0][1] == 350.0
    assert by["fleet_hosts_live"][0][1] == 2
    assert by["fleet_hosts_stale"][0][1] == 0
    free = {lb["role"]: v for lb, v in by["fleet_kv_blocks_free"]}
    assert free == {"both": 64}
    # merged histogram: count is the fleet count, buckets cumulative
    assert by["fleet_ttft_seconds_count"][0][1] == 4
    inf_bucket = [v for lb, v in by["fleet_ttft_seconds_bucket"]
                  if lb["le"] == "+Inf"]
    assert inf_bucket == [4.0]
    # 3 of 4 requests under the 100 ms SLO bar (bucket resolution)
    slo = {lb["slo"]: v for lb, v in by["fleet_slo_attainment"]}
    assert slo["ttft"] == 0.75
    # HELP/TYPE exactly once per family, however many hosts carry it
    for line in ("# TYPE ftl_serve_tokens_per_sec gauge",
                 "# TYPE fleet_ttft_seconds histogram"):
        assert text.count(line) == 1
    assert fed.last["hosts"] == 2
    assert fed.last["failures"] == 0


def test_federator_flags_stale_host_before_fence(tmp_path):
    clock = FakeTime(1000.0)
    pages = {"h0": _host_registry(10.0, 1, [0.05]).render(),
             "h1": _host_registry(10.0, 1, [0.05]).render()}
    fed = _fleet(tmp_path, clock, pages)
    # h1's lease ages past stale_factor*ttl but NOT past ttl: live by
    # the router's fence rules, wedged by the operator's
    ttl = fed.leases.ttl
    clock.t += 0.8 * ttl
    store = FileKVStore(str(tmp_path / "fleet"))
    LeaseRegistry(store, host_id="h0", clock=clock).renew(
        slots_free=4, blocks_free=32, block_size=16, metrics_port=9101)
    meta, samples = parse_metrics_text(fed.render())
    vals = {name: (labels, value) for name, labels, value in samples}
    assert vals["fleet_hosts_stale"][1] == 1
    assert vals["fleet_hosts_live"][1] == 2
    ages = {lb["host"]: v for n, lb, v in samples
            if n == "fleet_lease_age_seconds"}
    assert ages["h1"] > ages["h0"]


def test_federator_counts_scrape_failures(tmp_path):
    clock = FakeTime(1000.0)
    pages = {"h0": _host_registry(10.0, 1, [0.05]).render()}  # h1 refuses
    fed = _fleet(tmp_path, clock, pages)
    meta, samples = parse_metrics_text(fed.render())
    vals = {name: value for name, labels, value in samples
            if not labels}
    assert vals["fleet_scrape_failures_total"] == 1
    assert vals["fleet_hosts_scraped"] == 1
    assert vals["fleet_tokens_per_sec"] == 10.0


def test_federator_rolls_up_block_store_bytes(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.kvstore import (
        BLOCK_MANIFEST_NAME,
        BlockStore,
    )
    clock = FakeTime(1000.0)
    store = BlockStore(str(tmp_path / "kv"), writer="h0", clock=clock)
    for key, nbytes in (("aa", 4096), ("bb", 1024)):
        store._append({"kind": "put", "key": key, "blocks": 1,
                       "bytes": nbytes, "length": 16, "host": "h0"})
        os.makedirs(store.train_dir(key))
        Path(store.train_dir(key), BLOCK_MANIFEST_NAME).touch()
    store._append({"kind": "evict", "key": "bb"})  # swept by the LRU
    fed = _fleet(tmp_path, clock, {},
                 renew=(), kv_store_dir=str(tmp_path / "kv"))
    meta, samples = parse_metrics_text(fed.render())
    vals = {name: value for name, labels, value in samples if not labels}
    assert vals["fleet_kv_store_resident_bytes"] == 4096
    assert vals["fleet_kv_store_evicted_bytes"] == 1024


# ------------------------------------------------------------- timeline

def test_timeline_orders_by_hlc_not_wall_clock(tmp_path):
    # router clock runs 50 s BEHIND: wall order says the fence happened
    # before the kill it reacted to; the HLC (merged when the router
    # read h0's trail) restores the causal order
    killer = hlc.HLC(physical=FakeTime(100.0))
    router = hlc.HLC(physical=FakeTime(50.0))
    kill = {"t": 100.0, "hlc": killer.tick(), "kind": "chaos_host_kill",
            "job": "fleet_h0", "host": 0, "fault": "host_kill"}
    router.merge(kill["hlc"])  # router reads h0's trail (receive event)
    fence = {"t": 50.0, "hlc": router.tick(), "kind": "fleet_dead",
             "job": "router", "host": 0, "reason": "lease expired"}
    migrate = {"t": 50.1, "hlc": router.tick(), "kind": "fleet_migrate",
               "job": "router", "host": 0, "src": "h0", "dst": "h1"}
    legacy = {"t": 70.0, "kind": "step", "job": "fleet_h1", "host": 1}
    (tmp_path / "events_h0.jsonl").write_text(json.dumps(kill) + "\n")
    (tmp_path / "events_router.jsonl").write_text(
        json.dumps(fence) + "\n" + json.dumps(migrate) + "\n")
    (tmp_path / "events_h1.jsonl").write_text(json.dumps(legacy) + "\n")
    files = fleet_timeline.collect([str(tmp_path)])
    entries = fleet_timeline.build_timeline(files)
    kinds = [e["rec"]["kind"] for e in entries]
    # wall order would read [fence, migrate, step, kill] — backwards;
    # the unstamped legacy record interleaves at its wall position
    assert kinds == ["step", "chaos_host_kill", "fleet_dead",
                     "fleet_migrate"]
    assert [e["anomaly"] for e in entries] == [
        None, "CHAOS", "FENCE", "MIGRATE"]
    # reading the files in any order folds to the identical timeline
    assert fleet_timeline.build_timeline(reversed(files)) == entries
    text = fleet_timeline.format_timeline(entries)
    assert "[CHAOS]" in text and "[FENCE]" in text
    assert text.index("[CHAOS]") < text.index("[FENCE]")
    # the pre-HLC record is flagged as wall-clock-ordered
    legacy_line = [ln for ln in text.splitlines() if " step" in ln][0]
    assert " ~ " in legacy_line
