"""Serving fleet (ft/lease.py, ft/retry.py, inference/journal.py,
inference/router.py, inference/scheduler.py replay admission).

Four layers of evidence:

1. substrate — bounded-deadline retry semantics under a fake clock, and
   the file KV store's atomic round-trips;
2. membership — lease expiry renders a dead verdict, tombstones fence,
   and a host that cannot renew self-fences (all fake-clock, no sleeps);
3. journal — per-writer append files fold to one per-request state,
   requeue/migrate generations outrank stale assigns, prefix-divergent
   committed streams raise (the determinism contract is checked, not
   assumed), and a torn tail from a SIGKILLed writer is skipped;
4. migration — the router assigns by free-block count, never migrates
   the same dead host twice, completes fully-committed requests in
   place, and — on a REAL tiny engine — a request re-admitted from its
   journaled committed prefix continues bit-identically to the unfailed
   stream for both greedy and sampled decoding, with the survivor's
   block-leak audit clean afterwards.
"""

import json
import os

import pytest

from _tiny import tiny_cfg
from fault_tolerant_llm_training_tpu.ft.lease import (
    FileKVStore,
    LeaseRegistry,
)
from fault_tolerant_llm_training_tpu.ft.retry import (
    RetryDeadlineExceeded,
    retry_with_backoff,
)

@pytest.fixture(autouse=True, scope="module")
def _inference_names():
    # inference/ must not be imported at collect time
    # (test_no_test_module_imports_inference_at_module_scope); these names
    # are used in ~every test below, so bind them at run time instead of
    # repeating the import in each function.
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal,
        fold,
        persist_unserved,
    )
    from fault_tolerant_llm_training_tpu.inference.router import Router

    globals().update(RequestJournal=RequestJournal, fold=fold,
                     persist_unserved=persist_unserved, Router=Router)


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


# ------------------------------------------------------------- 1. retry layer
def test_retry_succeeds_after_transient_failures():
    clock = _Clock()
    calls = []

    def flaky():
        calls.append(clock.t)
        if len(calls) < 3:
            raise OSError("transient")
        return "value"

    out = retry_with_backoff(flaky, deadline_seconds=5.0, clock=clock,
                             sleep=clock.sleep)
    assert out == "value"
    assert len(calls) == 3


def test_retry_deadline_is_bounded_and_raises():
    clock = _Clock()

    def always_down():
        raise OSError("store down")

    with pytest.raises(RetryDeadlineExceeded) as ei:
        retry_with_backoff(always_down, deadline_seconds=2.0, clock=clock,
                           sleep=clock.sleep, what="lease renew")
    # one deadline for the WHOLE call: the fake clock advanced past it and
    # no further (backoff is clipped to the remaining window)
    assert clock.t - 100.0 <= 2.0 + 1e-6
    assert ei.value.attempts >= 2
    assert "lease renew" in str(ei.value)


def test_retry_does_not_catch_unlisted_exceptions():
    with pytest.raises(KeyError):
        retry_with_backoff(lambda: {}["missing"], deadline_seconds=1.0,
                           clock=_Clock(), sleep=lambda dt: None)


# ---------------------------------------------------------------- 2. KV store
def test_kv_store_round_trip_and_list(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    assert store.get("fleet/lease/h0") is None
    store.set("fleet/lease/h0", "a")
    store.set("fleet/lease/h1", "b")
    store.set("fleet/lease/h0", "a2")  # atomic replace
    assert store.get("fleet/lease/h0") == "a2"
    assert store.list("fleet/lease") == {"h0": "a2", "h1": "b"}
    store.delete("fleet/lease/h0")
    assert store.get("fleet/lease/h0") is None
    with pytest.raises(ValueError):
        store.set("../escape", "nope")


# --------------------------------------------------------------- 3. membership
def _registry(store, host_id, clock):
    return LeaseRegistry(store, host_id=host_id, ttl_seconds=2.0,
                         clock=clock, monotonic=clock, sleep=clock.sleep)


def test_lease_expiry_renders_dead_verdict(tmp_path):
    clock = _Clock()
    store = FileKVStore(str(tmp_path / "kv"))
    h0 = _registry(store, "h0", clock)
    h1 = _registry(store, "h1", clock)
    router = _registry(store, None, clock)
    assert h0.register(2, 30, 16)
    assert h1.register(2, 30, 16)
    assert router.live() == ["h0", "h1"]
    assert router.dead() == []

    # h0 stops renewing; h1 keeps its heartbeat
    clock.t += 1.5
    assert h1.renew(1, 20, 16)
    clock.t += 1.0  # h0's lease is now 2.5s old > ttl 2.0
    assert router.live() == ["h1"]
    assert router.dead() == ["h0"]
    leases = router.leases()
    assert not leases["h0"].live and leases["h0"].age > 2.0
    assert leases["h1"].slots_free == 1 and leases["h1"].blocks_free == 20


def test_tombstone_fences_even_a_live_lease(tmp_path):
    clock = _Clock()
    store = FileKVStore(str(tmp_path / "kv"))
    h0 = _registry(store, "h0", clock)
    router = _registry(store, None, clock)
    assert h0.register(2, 30, 16)
    assert not h0.fenced()
    router.tombstone("h0")
    assert h0.fenced()  # sticky verdict: renewal cannot un-fence
    assert h0.renew(2, 30, 16) and h0.fenced()
    assert router.dead() == ["h0"] and router.live() == []


def test_host_self_fences_when_renewal_goes_stale(tmp_path):
    clock = _Clock()
    h0 = _registry(FileKVStore(str(tmp_path / "kv")), "h0", clock)
    assert h0.register(2, 30, 16)
    clock.t += 1.0
    assert not h0.fenced()
    clock.t += 1.5  # 2.5s since the last successful renewal > ttl
    assert h0.fenced()


# ------------------------------------------------------------------ 4. journal
def _params(rid="reqA", prompt=(1, 2, 3)):
    return dict(request_id=rid, prompt=list(prompt), max_new_tokens=8,
                temperature=0.0, top_p=1.0, seed=7)


def test_journal_fold_round_trip(tmp_path):
    jd = str(tmp_path / "journal")
    router = RequestJournal(jd, writer="router")
    host = RequestJournal(jd, writer="host_h0")
    p = _params()
    router.assign(p["request_id"], "h0", p["prompt"], p["max_new_tokens"],
                  p["temperature"], p["top_p"], p["seed"])
    host.progress("reqA", "h0", [5], gen=0)
    host.progress("reqA", "h0", [5, 6], gen=0)
    st = fold(jd)["reqA"]
    assert (st.host, st.gen, st.committed, st.done) == ("h0", 0, [5, 6],
                                                        False)
    assert st.prompt == [1, 2, 3] and st.seed == 7
    host.done("reqA", "h0", [5, 6, 7], "length", gen=0)
    st = fold(jd)["reqA"]
    assert st.done and st.done_tokens == [5, 6, 7] and st.reason == "length"
    assert st.committed == [5, 6, 7]


def test_journal_migrate_outranks_stale_assign(tmp_path):
    jd = str(tmp_path / "journal")
    router = RequestJournal(jd, writer="router")
    p = _params()
    router.assign("reqA", "h0", p["prompt"], 8, 0.0, 1.0, 7)
    router.migrate("reqA", "h0", "h1", gen=1, prompt=p["prompt"],
                   max_new_tokens=8, temperature=0.0, top_p=1.0, seed=7,
                   committed=[5, 6])
    st = fold(jd)["reqA"]
    assert (st.host, st.gen, st.migrations) == ("h1", 1, 1)
    assert st.committed == [5, 6]


def test_journal_divergent_streams_raise(tmp_path):
    jd = str(tmp_path / "journal")
    host = RequestJournal(jd, writer="host_h0")
    host.progress("reqA", "h0", [5, 6], gen=0)
    host.progress("reqA", "h0", [5, 9, 9], gen=0)  # NOT a prefix extension
    with pytest.raises(ValueError, match="journal divergence"):
        fold(jd)


def test_journal_torn_tail_is_skipped(tmp_path):
    jd = str(tmp_path / "journal")
    host = RequestJournal(jd, writer="host_h0")
    host.progress("reqA", "h0", [5], gen=0)
    with open(host.path, "a") as fh:
        fh.write('{"kind":"progress","id":"reqA","committed":[5,6')  # torn
    assert fold(jd)["reqA"].committed == [5]


def test_persist_unserved_writes_requeue_at_next_gen(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    jd = str(tmp_path / "journal")
    router = RequestJournal(jd, writer="router")
    p = _params()
    router.assign("reqA", "h0", p["prompt"], 8, 0.0, 1.0, 7)
    host = RequestJournal(jd, writer="host_h0")
    n = persist_unserved(
        host, [Request(id="reqA", prompt=[1, 2, 3], max_new_tokens=8,
                       seed=7, committed=(5,))],
        reason="drain", gens={"reqA": 0})
    assert n == 1
    st = fold(jd)["reqA"]
    # the requeue outranks the assign regardless of file read order
    assert st.requeued and st.host is None and st.gen == 1
    assert st.committed == [5]


# ---------------------------------------------------- 5. router state machine
def _fleet(tmp_path):
    clock = _Clock()
    store = FileKVStore(str(tmp_path / "kv"))
    jd = str(tmp_path / "journal")
    router = Router(store, jd, clock=clock)
    # Router's lease registry must share the fake clock end to end
    router.lease.monotonic = clock
    router.lease.sleep = clock.sleep
    return clock, store, jd, router


def test_router_assigns_to_host_with_most_free_blocks(tmp_path):
    clock, store, jd, router = _fleet(tmp_path)
    _registry(store, "h0", clock).register(1, 10, 16)
    _registry(store, "h1", clock).register(1, 40, 16)
    router.submit("reqA", [1, 2, 3], 8, 0.0, 1.0, 7)
    router.refresh()
    assert router.assign_pending() == 1
    assert fold(jd)["reqA"].host == "h1"
    # the estimate was charged locally: a second request (before any new
    # heartbeat) must not dogpile h1 once its slot estimate is consumed
    router.submit("reqB", [4, 5], 8, 0.0, 1.0, 8)
    assert router.assign_pending() == 1
    assert fold(jd)["reqB"].host == "h0"


def test_router_holds_requests_with_no_live_host(tmp_path):
    clock, store, jd, router = _fleet(tmp_path)
    router.submit("reqA", [1, 2, 3], 8, 0.0, 1.0, 7)
    assert router.assign_pending() == 0
    assert len(router.pending) == 1
    _registry(store, "h0", clock).register(2, 30, 16)
    router.refresh()
    assert router.assign_pending() == 1
    assert fold(jd)["reqA"].host == "h0"


def test_router_sweep_migrates_dead_host_exactly_once(tmp_path):
    clock, store, jd, router = _fleet(tmp_path)
    h0 = _registry(store, "h0", clock)
    h1 = _registry(store, "h1", clock)
    h0.register(2, 30, 16)
    h1.register(2, 30, 16)
    router.submit("reqA", [1, 2, 3], 8, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()
    victim = fold(jd)["reqA"].host
    survivor = "h1" if victim == "h0" else "h0"
    RequestJournal(jd, writer=f"host_{victim}").progress(
        "reqA", victim, [5, 6], gen=0)

    clock.t += 3.0  # victim's lease expires; survivor renews
    (h1 if survivor == "h1" else h0).renew(2, 30, 16)
    assert router.sweep() == 1
    router.assign_pending()
    st = fold(jd)["reqA"]
    assert (st.host, st.gen, st.committed) == (survivor, 1, [5, 6])
    assert router.lease.is_tombstoned(victim)

    # a fresh router (restart) sweeps again: the request already moved,
    # so the second verdict migrates nothing — exactly-once by fold
    router2 = Router(store, jd, clock=clock)
    router2.lease.monotonic = clock
    assert router2.sweep() == 0
    assert fold(jd)["reqA"].migrations == 1


def test_router_completes_fully_committed_migration_in_place(tmp_path):
    clock, store, jd, router = _fleet(tmp_path)
    _registry(store, "h0", clock).register(2, 30, 16)
    router.submit("reqA", [1, 2, 3], 4, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()
    # h0 journaled all 4 tokens but died before the done record landed
    RequestJournal(jd, writer="host_h0").progress(
        "reqA", "h0", [5, 6, 7, 8], gen=0)
    clock.t += 3.0
    router.sweep()
    router.assign_pending()
    st = fold(jd)["reqA"]
    assert st.done and st.reason == "length" and st.done_tokens == [5, 6, 7, 8]
    assert st.migrations == 0  # completed from the journal, not re-decoded


def test_router_adopts_requeued_requests(tmp_path):
    clock, store, jd, router = _fleet(tmp_path)
    # a draining serve.py persisted an unserved request (gen bump included)
    serve = RequestJournal(jd, writer="serve_123")
    serve.requeue("reqA", [1, 2, 3], 8, 0.0, 1.0, 7, committed=[],
                  gen=1)
    _registry(store, "h0", clock).register(2, 30, 16)
    router.refresh()
    assert router.adopt_requeued() == 1
    assert router.adopt_requeued() == 0  # idempotent while pending
    router.assign_pending()
    st = fold(jd)["reqA"]
    assert st.host == "h0" and st.gen == 2 and not st.requeued
    assert router.adopt_requeued() == 0  # and after re-admission


def test_fleet_metric_names_on_registry():
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    text = REGISTRY.render()
    for name in ("fleet_hosts_live", "requests_migrated_total",
                 "fleet_lease_age_seconds"):
        assert name in text


# ------------------------------------------- 6. bit-exact migration (real engine)
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_migrated_stream_bitmatches_unfailed_run(tmp_path, temperature):
    """The zero-lost guarantee's strong form: re-admitting a request from
    its journaled committed prefix (prompt + committed replay, fold_in
    PRNG) continues the EXACT stream the dead host would have produced —
    greedy and sampled — and the survivor drains leak-clean."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request,
        Scheduler,
    )

    cfg = tiny_cfg()
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    def run(committed=()):
        engine = InferenceEngine(cfg, params, slots=2, max_len=48)
        sched = Scheduler(engine)
        sched.submit(Request(id="r", prompt=[5, 9, 2, 7],
                             max_new_tokens=10, temperature=temperature,
                             seed=123, committed=tuple(committed)))
        while sched.pending():
            sched.step()
        sched.audit_block_leaks(strict=True)  # survivor leak guard
        return sched.completed[-1].tokens

    full = run()
    assert len(full) == 10
    for cut in (1, 4, 9):
        assert run(committed=full[:cut]) == full, (
            f"replay from {cut} committed token(s) diverged "
            f"(temperature={temperature})")


def test_scheduler_rejects_fully_committed_submission():
    """A request whose committed prefix already reaches max_new_tokens has
    nothing to decode: the router must complete it from the journal, and
    the scheduler refuses it loudly rather than underflowing the replay."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request,
        Scheduler,
    )

    class _NoEngine:
        slots = 1
        max_len = 64

    sched = Scheduler(_NoEngine())
    with pytest.raises(ValueError, match="nothing to decode"):
        sched.submit(Request(id="r", prompt=[1], max_new_tokens=2,
                             committed=(3, 4)))
