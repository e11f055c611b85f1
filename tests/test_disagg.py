"""Disaggregated prefill/decode serving (inference/scheduler.py roles,
inference/journal.py ship/prefill_done/decode records, inference/router.py
role-aware placement).

Evidence ladder:

1. roles — the scheduler validates its role, refuses shipments on a
   prefill engine, and dedicated roles require the paged layout;
2. shipping — a prefill-role run exports each committed chunk as a
   CRC-manifested artifact the moment it commits: seq-ordered,
   contiguously tiled from block 0, every non-final shipment covering
   FULL committed blocks only (a decode engine can never read an
   uncommitted position), each artifact verifiable before its record
   exists;
3. decode admission — importing the shipments reproduces the colocated
   stream BITWISE for greedy and sampled decoding, shared-prompt
   prefixes are deduped through the decode engine's prefix cache instead
   of re-imported, and a poisoned shipment degrades to the bit-exact
   committed-prefix replay;
4. router — placement is role- and dtype-aware: fresh intake lands on
   prefill capacity, ``prefill_done`` advances to a decode host via a
   ``decode`` record carrying router-VERIFIED shipments (one bad
   artifact drops the list into replay), and a mixed-dtype
   prefill->decode pair is refused AT PLACEMENT TIME, before any prefill
   runs;
5. drain — both roles stop admission, persist unserved work, and leave
   the block-leak audit clean.
"""

import glob
import json
import os

import numpy as np
import pytest

from _tiny import tiny_cfg


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def disagg_setup():
    """One tiny model + the colocated reference streams every
    disaggregated pipeline below must reproduce bitwise. Prompts are
    long enough (40+ tokens, chunk 32) to cross chunk boundaries, so
    prefill ships MORE than one incremental artifact per request."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg(seq_len=128)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    def build(slots=4, num_blocks=None):
        return InferenceEngine(cfg, params, slots=slots, max_len=128,
                               prefill_buckets=(16, 32), kv_layout="paged",
                               kv_block_size=8, kv_num_blocks=num_blocks)

    rng = np.random.default_rng(17)
    common = rng.integers(3, 64, size=16).tolist()
    reqs = [
        Request(id="g", prompt=rng.integers(3, 64, size=41).tolist(),
                max_new_tokens=20, seed=1),
        Request(id="s", prompt=rng.integers(3, 64, size=37).tolist(),
                max_new_tokens=16, temperature=0.8, top_p=0.9, seed=2),
        Request(id="p1", prompt=common + rng.integers(3, 64,
                                                      size=20).tolist(),
                max_new_tokens=12, seed=3),
        Request(id="p2", prompt=common + rng.integers(3, 64,
                                                      size=23).tolist(),
                max_new_tokens=12, temperature=0.7, seed=4),
    ]
    sched = Scheduler(build())
    for r in reqs:
        sched.submit(r)
    sched.run()
    ref = {c.request_id: c.tokens for c in sched.completed}
    assert set(ref) == {"g", "s", "p1", "p2"}
    return {"build": build, "reqs": reqs, "ref": ref,
            "Request": Request, "Scheduler": Scheduler}


def _run_prefill(setup, tmp_path, reqs=None, corrupt=None):
    """Run a prefill-role scheduler to completion; returns (sched, ships)
    where ships[rid] is the seq-ordered journal-shaped shipment list."""
    Scheduler = setup["Scheduler"]
    ships = {}

    def on_ship(req, art_dir, ordinal, seq, start, end, length):
        if corrupt is not None:
            corrupt(req, art_dir, ordinal, seq)
        ships.setdefault(req.id, []).append(
            {"artifact": art_dir, "seq": seq, "start_block": start,
             "end_block": end, "length": length})

    pre = Scheduler(setup["build"](), role="prefill",
                    ship_dir=str(tmp_path / "ships"), on_ship=on_ship)
    for r in (reqs if reqs is not None else setup["reqs"]):
        pre.submit(r)
    pre.run()
    return pre, ships


def _run_decode(setup, ships, prefill_completed, reqs=None):
    Request, Scheduler = setup["Request"], setup["Scheduler"]
    first = {c.request_id: c.tokens for c in prefill_completed}
    dec = Scheduler(setup["build"](), role="decode")
    for r in (reqs if reqs is not None else setup["reqs"]):
        dec.submit(Request(id=r.id, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens,
                           temperature=r.temperature, top_p=r.top_p,
                           seed=r.seed, committed=tuple(first[r.id])),
                   shipments=ships.get(r.id), ship_gen=0)
    dec.run()
    return dec, {c.request_id: c.tokens for c in dec.completed}


# ---------------------------------------------------------------- 1. roles
def test_role_validation(disagg_setup):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    with pytest.raises(ValueError, match="unknown engine role"):
        Scheduler(disagg_setup["build"](), role="hybrid")

    # dedicated roles ship block artifacts: the paged layout is required
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    ring = InferenceEngine(cfg, params, slots=2, max_len=48,
                           kv_layout="ring")
    with pytest.raises(ValueError, match="paged"):
        Scheduler(ring, role="prefill")

    # a prefill engine exports shipments; it can never accept them
    pre = Scheduler(disagg_setup["build"](), role="prefill")
    with pytest.raises(ValueError, match="cannot[\\s\\S]*accept"):
        pre.submit(Request(id="x", prompt=[1, 2, 3], max_new_tokens=4,
                           committed=(9,)),
                   shipments=[{"artifact": "/nope", "seq": 0,
                               "start_block": 0, "end_block": 1,
                               "length": 3}], ship_gen=0)


# -------------------------------------------------------------- 2. shipping
def test_incremental_shipment_ordering(disagg_setup, tmp_path):
    """Shipments leave the prefill engine AS chunks commit — seq-ordered,
    contiguous from block 0, and never covering a position the prefill
    has not committed (full blocks only until the final shipment)."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        verify_block_artifact)

    pre, ships = _run_prefill(disagg_setup, tmp_path)
    assert pre.ship_exports == sum(len(lst) for lst in ships.values()) == 8
    assert all(c.reason == "prefill" for c in pre.completed)
    assert all(len(c.tokens) == 1 for c in pre.completed)
    bs = 8
    for r in disagg_setup["reqs"]:
        lst = ships[r.id]
        n_blocks = -(-len(r.prompt) // bs)
        # 40-ish-token prompts with chunk 32 cross a chunk boundary:
        # the pipeline is INCREMENTAL, not one artifact at the end
        # — exactly one shipment a committed chunk of the largest bucket
        assert len(lst) == -(-len(r.prompt) // 32), (
            f"{r.id}: expected one shipment a chunk")
        assert [s["seq"] for s in lst] == list(range(len(lst)))
        assert lst[0]["start_block"] == 0
        for a, b in zip(lst, lst[1:]):
            assert b["start_block"] == a["end_block"]
        assert lst[-1]["end_block"] == n_blocks
        assert lst[-1]["length"] == len(r.prompt)
        for s in lst:
            man = verify_block_artifact(s["artifact"])
            assert man["length"] == s["length"]
            assert man["meta"]["request_id"] == r.id
            assert len(man["blocks"]) == s["end_block"] - s["start_block"]
            if s is not lst[-1]:
                # decode must never read an uncommitted position: every
                # non-final shipment ends at or before the commit point
                assert s["end_block"] * bs <= s["length"]
    assert pre.audit_block_leaks(strict=True) == []


# ------------------------------------------------------- 3. decode admission
@pytest.mark.parametrize("which", ["greedy", "sampled"])
def test_disagg_bitmatch(disagg_setup, tmp_path, which):
    """The tentpole guarantee: prefill engine -> shipped blocks -> decode
    engine emits the EXACT stream the colocated engine does, for greedy
    and sampled requests alike (fold_in(seed, step) statelessness)."""
    pre, ships = _run_prefill(disagg_setup, tmp_path)
    dec, out = _run_decode(disagg_setup, ships, pre.completed)
    ids = (["g", "p1"] if which == "greedy" else ["s", "p2"])
    for rid in ids:
        assert out[rid] == disagg_setup["ref"][rid], (
            f"{rid}: disaggregated stream diverged from colocated")
    assert dec.ship_imports >= 1 and dec.ship_rejects == 0
    assert dec.audit_block_leaks(strict=True) == []


def test_prefix_cache_dedupes_shipped_blocks(disagg_setup, tmp_path):
    """p1/p2 share a 16-token (2-block) prompt prefix: the decode engine
    must satisfy the second import's leading blocks from its own prefix
    cache instead of re-importing them from the artifact."""
    reqs = [r for r in disagg_setup["reqs"] if r.id in ("p1", "p2")]
    pre, ships = _run_prefill(disagg_setup, tmp_path, reqs=reqs)
    dec, out = _run_decode(disagg_setup, ships, pre.completed, reqs=reqs)
    assert out == {r.id: disagg_setup["ref"][r.id] for r in reqs}
    # the second admission hit the shared prefix: fewer blocks imported
    # than shipped, and the prefix cache records the hit tokens
    m = dec.metrics()
    assert m["engine_role"] == "decode"
    assert dec.ship_imports == 2
    assert (m["prefix_hits"], m["prefix_hit_tokens"]) == (1, 16)
    assert dec.audit_block_leaks(strict=True) == []


def test_poisoned_shipment_falls_back_to_replay(disagg_setup, tmp_path):
    """A flipped payload byte in one shipment (manifest spared — the
    chaos ``ship_corrupt`` shape): the decode admission CRC-rejects the
    import and replays the committed prefix, emitting the exact
    reference stream with nothing lost."""
    def corrupt(req, art_dir, ordinal, seq):
        if req.id == "g" and seq == 1:
            p = sorted(glob.glob(os.path.join(art_dir, "block_*.bin")))[0]
            raw = bytearray(open(p, "rb").read())
            raw[5] ^= 0xFF
            open(p, "wb").write(bytes(raw))

    pre, ships = _run_prefill(disagg_setup, tmp_path, corrupt=corrupt)
    dec, out = _run_decode(disagg_setup, ships, pre.completed)
    assert dec.ship_rejects == 1
    assert out == disagg_setup["ref"]
    assert dec.audit_block_leaks(strict=True) == []


def test_batch_import_verifies_before_any_device_write(disagg_setup,
                                                       tmp_path):
    """``import_block_batch`` is the admission fast path: a request's
    whole shipment train lands as ONE scatter per pool array. Atomicity
    contract: a CRC failure in ANY artifact of the batch — here the
    last — raises before the FIRST device write, so the earlier, intact
    artifacts must not land either: the pool stays bit-identical."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KVBlockIntegrityError)

    pre, ships = _run_prefill(disagg_setup, tmp_path)
    train = ships["g"]
    assert len(train) >= 2                 # a real multi-chunk train
    eng = disagg_setup["build"]()
    before = [np.asarray(a) for a in (*eng.cache.k, *eng.cache.v)]
    p = sorted(glob.glob(os.path.join(
        str(train[-1]["artifact"]), "block_*.bin")))[0]
    raw = bytearray(open(p, "rb").read())
    raw[3] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    parts, dest = [], 1
    for s in train:
        n = int(s["end_block"]) - int(s["start_block"])
        parts.append((str(s["artifact"]), list(range(dest, dest + n))))
        dest += n
    with pytest.raises(KVBlockIntegrityError):
        eng.import_pool_block_batch(parts)
    after = [np.asarray(a) for a in (*eng.cache.k, *eng.cache.v)]
    for b, a in zip(before, after):
        assert np.array_equal(b, a)


# ------------------------------------------------------------------ 4. router
def _registry(store, host_id, clock, ttl=2.0):
    from fault_tolerant_llm_training_tpu.ft.lease import LeaseRegistry

    return LeaseRegistry(store, host_id=host_id, ttl_seconds=ttl,
                         clock=clock, monotonic=clock, sleep=clock.sleep)


def _router(tmp_path):
    from fault_tolerant_llm_training_tpu.ft.lease import FileKVStore
    from fault_tolerant_llm_training_tpu.inference.router import Router

    clock = _Clock()
    store = FileKVStore(str(tmp_path / "kv"))
    jd = str(tmp_path / "journal")
    router = Router(store, jd, clock=clock)
    router.lease.monotonic = clock
    router.lease.sleep = clock.sleep
    return clock, store, jd, router


def test_role_aware_placement(tmp_path):
    """Fresh intake needs prefill capacity, committed history needs
    decode capacity — a request is never parked on a host whose role
    cannot advance it."""
    from fault_tolerant_llm_training_tpu.inference.journal import fold

    clock, store, jd, router = _router(tmp_path)
    _registry(store, "pre0", clock).register(2, 40, 8, role="prefill")
    _registry(store, "dec0", clock).register(2, 30, 8, role="decode")
    router.submit("fresh", [1, 2, 3], 8, 0.0, 1.0, 7)
    router.refresh()
    assert router.assign_pending() == 1
    assert fold(jd)["fresh"].host == "pre0"

    # a requeued request with committed history is decode-stage work
    router.journal.requeue("cont", [4, 5, 6], 8, 0.0, 1.0, 9,
                           committed=[11, 12], gen=1)
    router.refresh()
    router.adopt_requeued()
    assert router.assign_pending() == 1
    assert fold(jd)["cont"].host == "dec0"


def test_prefill_done_advances_to_decode_host(tmp_path):
    """``prefill_done`` + verified shipments become ONE ``decode`` record
    at gen+1: ownership moves to the dtype-matching decode host with the
    shipment list attached; a second loop never re-places it."""
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks, init_paged_cache)

    clock, store, jd, router = _router(tmp_path)
    _registry(store, "pre0", clock).register(2, 40, 8, role="prefill")
    _registry(store, "dec0", clock).register(2, 30, 8, role="decode")
    router.submit("rA", list(range(3, 19)), 8, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()
    assert fold(jd)["rA"].host == "pre0"

    cache = init_paged_cache(tiny_cfg(), slots=2, max_len=32,
                             block_size=8)
    art = str(tmp_path / "ship_rA_00")
    export_blocks(cache, [1, 2], art, length=16)
    host = RequestJournal(jd, writer="host_pre0")
    host.ship("rA", "pre0", art, seq=0, start_block=0, end_block=2,
              length=16, gen=0)
    host.prefill_done("rA", "pre0", [42], gen=0, kv_dtype="bf16")

    assert router.advance_prefilled() == 1
    st = fold(jd)["rA"]
    assert (st.host, st.gen, st.committed) == ("dec0", 1, [42])
    rec = [json.loads(l) for l in open(os.path.join(jd, "router.jsonl"))
           if '"decode"' in l][-1]
    assert rec["kind"] == "decode" and rec["host"] == "dec0"
    assert [s["artifact"] for s in rec["shipments"]] == [art]
    assert router.advance_prefilled() == 0  # idempotent across loops


def test_router_rejects_poisoned_shipment_into_replay(tmp_path):
    """One bad artifact drops the WHOLE shipment list: the decode record
    still lands (ownership advances) but with shipments=[] — the decode
    host replays the committed prefix instead of importing."""
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks, init_paged_cache)

    clock, store, jd, router = _router(tmp_path)
    _registry(store, "pre0", clock).register(2, 40, 8, role="prefill")
    _registry(store, "dec0", clock).register(2, 30, 8, role="decode")
    router.submit("rB", list(range(3, 19)), 8, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()

    cache = init_paged_cache(tiny_cfg(), slots=2, max_len=32,
                             block_size=8)
    host = RequestJournal(jd, writer="host_pre0")
    arts = []
    for seq, blocks in enumerate(([1], [2])):
        art = str(tmp_path / f"ship_rB_{seq:02d}")
        export_blocks(cache, blocks, art, length=8 * (seq + 1))
        host.ship("rB", "pre0", art, seq=seq, start_block=seq,
                  end_block=seq + 1, length=8 * (seq + 1), gen=0)
        arts.append(art)
    p = glob.glob(os.path.join(arts[1], "block_*.bin"))[0]
    raw = bytearray(open(p, "rb").read())
    raw[0] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    host.prefill_done("rB", "pre0", [42], gen=0, kv_dtype="bf16")

    assert router.advance_prefilled() == 1
    rec = [json.loads(l) for l in open(os.path.join(jd, "router.jsonl"))
           if '"decode"' in l][-1]
    assert rec["shipments"] == []  # replay fallback, ownership advanced
    assert fold(jd)["rB"].host == "dec0"


def test_mixed_dtype_pair_rejected_at_placement_time(tmp_path):
    """An int8 prefill host with only a bf16 decode peer can never
    produce an importable shipment: the router refuses the pair BEFORE
    any prefill runs (the request waits), and admits the moment an int8
    decode host joins."""
    from fault_tolerant_llm_training_tpu.inference.journal import fold

    clock, store, jd, router = _router(tmp_path)
    _registry(store, "pre8", clock).register(2, 40, 8, role="prefill",
                                             kv_dtype="int8")
    _registry(store, "dec16", clock).register(2, 30, 8, role="decode",
                                              kv_dtype="bf16")
    router.submit("rC", [1, 2, 3], 8, 0.0, 1.0, 7)
    router.refresh()
    assert router.assign_pending() == 0  # refused before prefill started
    assert ("rC", "pre8") in router._place_rejected
    assert "rC" not in fold(jd)

    _registry(store, "dec8", clock).register(2, 30, 8, role="decode",
                                             kv_dtype="int8")
    router.refresh()
    assert router.assign_pending() == 1
    assert fold(jd)["rC"].host == "pre8"


def test_prefill_host_death_keeps_shipments_alive(tmp_path):
    """The prefill host dies AFTER prefill_done: the sweep must NOT
    migrate the request into a re-prefill — the shipments live on shared
    disk and advance_prefilled still places the decode half."""
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks, init_paged_cache)

    clock, store, jd, router = _router(tmp_path)
    pre = _registry(store, "pre0", clock)
    dec = _registry(store, "dec0", clock)
    pre.register(2, 40, 8, role="prefill")
    dec.register(2, 30, 8, role="decode")
    router.submit("rD", list(range(3, 19)), 8, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()

    cache = init_paged_cache(tiny_cfg(), slots=2, max_len=32,
                             block_size=8)
    art = str(tmp_path / "ship_rD_00")
    export_blocks(cache, [1, 2], art, length=16)
    host = RequestJournal(jd, writer="host_pre0")
    host.ship("rD", "pre0", art, seq=0, start_block=0, end_block=2,
              length=16, gen=0)
    host.prefill_done("rD", "pre0", [42], gen=0, kv_dtype="bf16")

    clock.t += 3.0  # pre0's lease expires; dec0 renews
    dec.renew(2, 30, 8, role="decode")
    assert router.sweep() == 0  # prefill-done work is NOT lost with pre0
    assert router.advance_prefilled() == 1
    st = fold(jd)["rD"]
    assert st.host == "dec0" and st.gen == 1


def test_single_token_prefill_completes_in_place(tmp_path):
    """max_new_tokens == 1: the sampled first token IS the stream — the
    router records done at gen+1 instead of writing a decode record the
    scheduler would refuse."""
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)

    clock, store, jd, router = _router(tmp_path)
    _registry(store, "pre0", clock).register(2, 40, 8, role="prefill")
    _registry(store, "dec0", clock).register(2, 30, 8, role="decode")
    router.submit("r1", [1, 2, 3], 1, 0.0, 1.0, 7)
    router.refresh()
    router.assign_pending()
    RequestJournal(jd, writer="host_pre0").prefill_done(
        "r1", "pre0", [42], gen=0, kv_dtype="bf16")
    router.advance_prefilled()
    st = fold(jd)["r1"]
    assert st.done and st.done_tokens == [42] and st.reason == "length"


def test_stale_generation_shipments_are_dropped(tmp_path):
    """Ship records fold newest-generation-only: a re-prefill after a
    migration re-ships at its own gen and the stale set must not mix."""
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)

    jd = str(tmp_path / "journal")
    host = RequestJournal(jd, writer="host_pre0")
    host.ship("rS", "pre0", "/tmp/old_0", seq=0, start_block=0,
              end_block=1, length=8, gen=0)
    host.ship("rS", "pre1", "/tmp/new_0", seq=0, start_block=0,
              end_block=1, length=8, gen=2)
    host.ship("rS", "pre1", "/tmp/new_1", seq=1, start_block=1,
              end_block=2, length=16, gen=2)
    st = fold(jd)["rS"]
    assert st.ship_gen == 2
    assert [s["artifact"] for s in st.shipments] == ["/tmp/new_0",
                                                     "/tmp/new_1"]


# ------------------------------------------------------------------- 5. drain
def test_drain_on_both_roles(disagg_setup, tmp_path):
    """Both roles honor the drain contract: admission stops, unserved
    work persists with its committed baseline, and the strict block-leak
    audit is clean."""
    Request, Scheduler = disagg_setup["Request"], disagg_setup["Scheduler"]

    # prefill role: one request finishes its prefill, one never admits
    pre, ships = _run_prefill(disagg_setup, tmp_path,
                              reqs=[disagg_setup["reqs"][0]])
    pre.stop_admission()
    pre.submit(Request(id="late", prompt=[5, 6, 7], max_new_tokens=4,
                       seed=9))
    uns = pre.unserved()
    assert [r.id for r in uns] == ["late"]
    assert pre.audit_block_leaks(strict=True) == []

    # decode role: drain mid-decode, the slot's committed stream persists
    dec = Scheduler(disagg_setup["build"](), role="decode")
    r = disagg_setup["reqs"][0]
    first = {c.request_id: c.tokens for c in pre.completed}
    dec.submit(Request(id=r.id, prompt=list(r.prompt),
                       max_new_tokens=r.max_new_tokens, seed=r.seed,
                       committed=tuple(first[r.id])),
               shipments=ships[r.id], ship_gen=0)
    for _ in range(3):
        dec.step()
    dec.stop_admission()
    slot = next(iter(dec.active))
    info = dec.export_handoff(slot, str(tmp_path / "handoff_drain"),
                              gen=1)
    uns = dec.unserved()
    assert [u.id for u in uns] == [r.id]
    assert list(uns[0].committed) == info["tokens"]
    ref = disagg_setup["ref"][r.id]
    assert list(uns[0].committed) == ref[:len(uns[0].committed)]
    assert dec.audit_block_leaks(strict=True) == []
