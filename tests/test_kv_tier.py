"""Tiered KV-block lifecycle (inference/kv_cache.py export/import, the
scheduler's spill tier, and the drain-time block-shipment handoff).

Evidence ladder:

1. primitive — ``export_blocks``/``import_blocks`` round-trip a scattered
   set of pool blocks through a checksummed host artifact BITWISE, refuse
   the reserved null block on both sides, and the reject matrix (flipped
   payload byte, truncated file, missing file, torn manifest, geometry
   mismatch) raises ``KVBlockIntegrityError`` BEFORE any device write;
2. spill tier — on pool exhaustion the scheduler preempts the coldest
   request to the host tier and restores it on demand: every stream is
   bitwise identical to an unconstrained-pool reference (fold_in(seed,
   step) statelessness), shared prefix-cache blocks are never spilled,
   a corrupted spill artifact degrades to a bit-exact replay, and the
   strict leak guard audits blocks ACROSS tiers (a vanished artifact is
   a leak, same as a lost device block);
3. handoff — a draining host exports an in-flight request's committed
   blocks as an artifact a second scheduler imports instead of replaying
   the prefix; the continuation is bitwise identical either way, and a
   CRC-rejected artifact falls back to the replay with the same stream;
4. journal/router — ``handoff`` records fold into advisory artifact
   pointers that never touch ownership, ride along on exactly the next
   migration (stale artifacts are dropped), and the router's
   verify-before-ship rejects a corrupt artifact into replay.
"""

import glob
import os

import numpy as np
import pytest

from _tiny import tiny_cfg


# ------------------------------------------------------------- 1. primitive
def _filled_cache(cfg, seed=0, slots=2, max_len=32, block_size=8):
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_paged_cache)

    cache = init_paged_cache(cfg, slots=slots, max_len=max_len,
                             block_size=block_size)
    rng = np.random.default_rng(seed)
    k = tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype)
              for a in cache.k)
    v = tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype)
              for a in cache.v)
    return cache.replace(k=k, v=v)


def test_block_roundtrip_bitwise(tmp_path):
    """Export scattered blocks [3, 1, 2], import them as [5, 6, 7] of a
    zeroed cache: every layer's K and V must match bitwise, untouched
    rows must stay zero, and lengths are the caller's business."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        artifact_bytes, export_blocks, import_blocks, init_paged_cache,
        verify_block_artifact)

    cfg = tiny_cfg()
    cache = _filled_cache(cfg)
    d = str(tmp_path / "art")
    man = export_blocks(cache, [3, 1, 2], d, length=17,
                        meta={"request_id": "r0"})
    assert artifact_bytes(man) > 0
    assert verify_block_artifact(d)["length"] == 17

    fresh = init_paged_cache(cfg, slots=2, max_len=32, block_size=8)
    out, man2 = import_blocks(fresh, d, [5, 6, 7])
    assert man2["meta"]["request_id"] == "r0"
    for l in range(len(cache.k)):
        for src, dst in ((3, 5), (1, 6), (2, 7)):
            np.testing.assert_array_equal(np.asarray(out.k[l][dst]),
                                          np.asarray(cache.k[l][src]))
            np.testing.assert_array_equal(np.asarray(out.v[l][dst]),
                                          np.asarray(cache.v[l][src]))
        np.testing.assert_array_equal(np.asarray(out.k[l][4]),
                                      np.zeros_like(np.asarray(out.k[l][4])))
    # import never touches lengths — the engine wrapper owns the slot
    np.testing.assert_array_equal(np.asarray(out.lengths),
                                  np.asarray(fresh.lengths))


def test_null_block_refused_both_ways(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks, import_blocks)

    cfg = tiny_cfg()
    cache = _filled_cache(cfg)
    with pytest.raises(ValueError, match="null block"):
        export_blocks(cache, [0, 1], str(tmp_path / "a"), length=4)
    export_blocks(cache, [1], str(tmp_path / "b"), length=4)
    with pytest.raises(ValueError, match="null block"):
        import_blocks(cache, str(tmp_path / "b"), [0])


def test_import_reject_matrix(tmp_path):
    """Flipped byte, truncated file, missing file, torn manifest and a
    geometry mismatch must all raise KVBlockIntegrityError — and the
    verify runs BEFORE any device write, so the target cache is never
    half-imported."""
    import json

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        BLOCK_MANIFEST_NAME, KVBlockIntegrityError, export_blocks,
        import_blocks, init_paged_cache)

    cfg = tiny_cfg()
    cache = _filled_cache(cfg)
    fresh = init_paged_cache(cfg, slots=2, max_len=32, block_size=8)

    def fresh_artifact(name):
        d = str(tmp_path / name)
        export_blocks(cache, [3, 1], d, length=9)
        return d

    # flipped payload byte
    d = fresh_artifact("flip")
    p = os.path.join(d, "block_00001.bin")
    raw = bytearray(open(p, "rb").read())
    raw[7] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    with pytest.raises(KVBlockIntegrityError, match="CRC"):
        import_blocks(fresh, d, [5, 6])
    # the failed import wrote nothing
    for l in range(len(fresh.k)):
        np.testing.assert_array_equal(
            np.asarray(fresh.k[l][5]),
            np.zeros_like(np.asarray(fresh.k[l][5])))

    # truncated payload
    d = fresh_artifact("trunc")
    p = os.path.join(d, "block_00000.bin")
    open(p, "wb").write(open(p, "rb").read()[:-3])
    with pytest.raises(KVBlockIntegrityError, match="size"):
        import_blocks(fresh, d, [5, 6])

    # missing payload
    d = fresh_artifact("gone")
    os.unlink(os.path.join(d, "block_00001.bin"))
    with pytest.raises(KVBlockIntegrityError, match="missing"):
        import_blocks(fresh, d, [5, 6])

    # torn manifest (files/blocks disagree)
    d = fresh_artifact("torn")
    man_path = os.path.join(d, BLOCK_MANIFEST_NAME)
    man = json.load(open(man_path))
    man["files"].popitem()
    json.dump(man, open(man_path, "w"))
    with pytest.raises(KVBlockIntegrityError, match="torn"):
        import_blocks(fresh, d, [5, 6])

    # geometry mismatch: same artifact, different block size
    d = fresh_artifact("geom")
    other = init_paged_cache(cfg, slots=2, max_len=32, block_size=16)
    with pytest.raises(KVBlockIntegrityError, match="geometry"):
        import_blocks(other, d, [1, 2])

    # dest-count mismatch is a caller bug, not corruption
    d = fresh_artifact("count")
    with pytest.raises(ValueError):
        import_blocks(fresh, d, [5])


# ------------------------------------------------------------- 2. spill tier
@pytest.fixture(scope="module")
def tier_setup():
    """One param set + the unconstrained-pool reference streams every
    spill/handoff test must reproduce bitwise."""
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

    rng = np.random.default_rng(3)
    reqs = [
        Request(id="A", prompt=rng.integers(3, 64, size=17).tolist(),
                max_new_tokens=40, seed=1),
        Request(id="B", prompt=rng.integers(3, 64, size=19).tolist(),
                max_new_tokens=40, seed=2),
        Request(id="C", prompt=rng.integers(3, 64, size=16).tolist(),
                max_new_tokens=12, temperature=0.8, top_p=0.9, seed=3),
    ]
    sched = Scheduler(build())
    for r in reqs:
        sched.submit(r)
    sched.run()
    ref = {c.request_id: c.tokens for c in sched.completed}
    assert set(ref) == {"A", "B", "C"}
    return {"build": build, "reqs": reqs, "ref": ref}


def _run_constrained(tier_setup, tmp_path, on_spill=None, num_blocks=18,
                     spill=True):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    sched = Scheduler(tier_setup["build"](num_blocks=num_blocks),
                      spill_dir=str(tmp_path / "tier") if spill else None,
                      on_spill=on_spill)
    for r in tier_setup["reqs"]:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


@pytest.mark.parametrize("spill", [True, False], ids=["spill", "wait"])
def test_spill_restore_bitwise(tier_setup, tmp_path, spill):
    """17-usable-block pool vs three requests needing 20: the scheduler
    must spill, restore, and still produce the exact unconstrained
    streams — with the cross-tier leak guard clean at drain. Without a
    spill tier ("wait") the late request head-of-line waits instead:
    nothing is exported and the streams are the same again."""
    sched, out = _run_constrained(tier_setup, tmp_path, spill=spill)
    if spill:
        assert sched.spill_exports >= 1 and sched.spill_restores >= 1
    else:
        assert (sched.spill_exports, sched.spill_restores) == (0, 0)
    assert sched.spill_rejects == 0
    assert out == tier_setup["ref"]
    assert sched.audit_block_leaks(strict=True) == []
    assert not sched._spilled and sched.discard_spilled() == 0


def test_spill_corrupt_falls_back_to_replay(tier_setup, tmp_path):
    """A byte flipped in every spill artifact (the chaos ``spill_corrupt``
    shape, manifest spared): each restore must CRC-reject and re-admit
    via replay — streams still bitwise equal the reference."""
    def corrupt(art_dir, ordinal):
        payloads = sorted(glob.glob(os.path.join(art_dir, "block_*.bin")))
        raw = bytearray(open(payloads[0], "rb").read())
        raw[3] ^= 0xFF
        open(payloads[0], "wb").write(bytes(raw))

    sched, out = _run_constrained(tier_setup, tmp_path, on_spill=corrupt)
    assert sched.spill_exports >= 1 and sched.spill_rejects >= 1
    assert sched.spill_restores == 0
    assert out == tier_setup["ref"]
    assert sched.audit_block_leaks(strict=True) == []


def test_explicit_spill_api_and_double_raises(tier_setup, tmp_path):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    sched = Scheduler(tier_setup["build"](),
                      spill_dir=str(tmp_path / "tier"))
    sched.submit(tier_setup["reqs"][0])
    for _ in range(4):
        sched.step()
    slot = next(iter(sched.active))
    sched.spill(slot)
    assert tier_setup["reqs"][0].id in sched._spilled
    with pytest.raises(KeyError):
        sched.spill(slot)  # slot is empty now
    with pytest.raises(RuntimeError, match="double restore"):
        sched._restore_one("nope", slot, [])
    # disabled tier refuses explicitly
    plain = Scheduler(tier_setup["build"]())
    plain.submit(tier_setup["reqs"][1])
    plain.step()
    with pytest.raises(RuntimeError, match="disabled"):
        plain.spill(next(iter(plain.active)))
    plain.run()
    # the spilled request restores and completes bit-exactly
    sched.run()
    out = {c.request_id: c.tokens for c in sched.completed}
    assert out[tier_setup["reqs"][0].id] == \
        tier_setup["ref"][tier_setup["reqs"][0].id]


def test_leak_guard_sees_vanished_artifact(tier_setup, tmp_path):
    """A spilled artifact whose manifest disappears is a leaked block set
    — strict audit must raise, same contract as a lost device block."""
    import shutil

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        BLOCK_MANIFEST_NAME)
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    sched = Scheduler(tier_setup["build"](),
                      spill_dir=str(tmp_path / "tier"))
    sched.submit(tier_setup["reqs"][0])
    for _ in range(4):
        sched.step()
    sched.spill(next(iter(sched.active)))
    sp = sched._spilled[tier_setup["reqs"][0].id]
    os.unlink(os.path.join(sp.artifact_dir, BLOCK_MANIFEST_NAME))
    with pytest.raises(RuntimeError, match="leak"):
        sched.audit_block_leaks(strict=True)
    shutil.rmtree(sp.artifact_dir, ignore_errors=True)
    sched.discard_spilled()


def test_shared_prefix_stays_on_device(tier_setup, tmp_path):
    """Two requests sharing a 16-token prompt prefix: spilling one must
    export only its PRIVATE blocks (the shared leading blocks stay warm
    under the prefix cache) and the restore re-acquires them by content
    — continuation bitwise equal to never having spilled."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    rng = np.random.default_rng(11)
    common = rng.integers(3, 64, size=16).tolist()
    ra = Request(id="sa", prompt=common + [5, 6], max_new_tokens=24, seed=4)
    rb = Request(id="sb", prompt=common + [9], max_new_tokens=24, seed=5)

    ref_sched = Scheduler(tier_setup["build"]())
    for r in (ra, rb):
        ref_sched.submit(r)
    ref_sched.run()
    ref = {c.request_id: c.tokens for c in ref_sched.completed}

    sched = Scheduler(tier_setup["build"](),
                      spill_dir=str(tmp_path / "tier"))
    for r in (ra, rb):
        sched.submit(r)
    for _ in range(4):
        sched.step()
    victim = next(s for s, st in sched.active.items()
                  if st.request.id == "sb")
    sched.spill(victim)
    sp = sched._spilled["sb"]
    assert sp.private_positions[0] > 0, \
        "shared leading blocks must not be exported"
    assert sp.shared_tokens == common[:len(sp.shared_tokens)]
    sched.run()
    out = {c.request_id: c.tokens for c in sched.completed}
    assert out == ref
    assert sched.audit_block_leaks(strict=True) == []


# ---------------------------------------------------------------- 3. handoff
def test_handoff_ship_and_replay_fallback(tier_setup, tmp_path):
    """Host 1 decodes 7 rounds then drain-exports its slot; host 2 admits
    from the artifact (block import, no prefill replay) and must emit the
    exact reference continuation. With a flipped payload byte the import
    is CRC-rejected and the replay fallback emits the same stream."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    rng = np.random.default_rng(5)
    req = Request(id="H", prompt=rng.integers(3, 64, size=17).tolist(),
                  max_new_tokens=24, temperature=0.7, top_p=0.9, seed=9)
    ref_sched = Scheduler(tier_setup["build"](slots=2))
    ref_sched.submit(req)
    ref_sched.run()
    ref = ref_sched.completed[0].tokens

    s1 = Scheduler(tier_setup["build"](slots=2))
    s1.submit(req)
    for _ in range(7):
        s1.step()
    art = str(tmp_path / "handoff_H_g0")
    info = s1.export_handoff(next(iter(s1.active)), art, gen=0)
    assert info["blocks"] >= 1
    uns = s1.unserved()
    assert uns and uns[0].id == "H"
    assert list(uns[0].committed) == info["tokens"]
    assert s1.audit_block_leaks(strict=True) == []

    s2 = Scheduler(tier_setup["build"](slots=2))
    s2.submit(uns[0], handoff_artifact=art, handoff_gen=1)
    s2.run()
    assert s2.handoff_imports == 1 and s2.handoff_rejects == 0
    assert s2.completed[0].tokens == ref

    payloads = sorted(glob.glob(os.path.join(art, "block_*.bin")))
    raw = bytearray(open(payloads[1], "rb").read())
    raw[5] ^= 0xFF
    open(payloads[1], "wb").write(bytes(raw))
    s3 = Scheduler(tier_setup["build"](slots=2))
    s3.submit(uns[0], handoff_artifact=art, handoff_gen=1)
    s3.run()
    assert s3.handoff_rejects == 1 and s3.handoff_imports == 0
    assert s3.completed[0].tokens == ref


# ---------------------------------------------------------- 4. journal/router
def test_journal_handoff_fold_is_advisory(tmp_path):
    """A ``handoff`` record must set the artifact pointer WITHOUT taking
    ownership, and the router attaches it to exactly the next migration
    (a later generation means some survivor already consumed it)."""
    from fault_tolerant_llm_training_tpu.ft.lease import FileKVStore
    from fault_tolerant_llm_training_tpu.inference.journal import (
        RequestJournal, fold)
    from fault_tolerant_llm_training_tpu.inference.router import Router

    jdir = str(tmp_path / "journal")
    host = RequestJournal(jdir, writer="host_h0")
    host.handoff("r1", "h0", "/tmp/handoff_r1_g0", [7, 8], gen=0)
    host.requeue("r1", [1, 2, 3], 16, 0.0, 1.0, 0, [7, 8], gen=1)
    st = fold(jdir)["r1"]
    assert st.handoff_artifact == "/tmp/handoff_r1_g0"
    assert st.handoff_gen == 0
    assert st.gen == 1 and st.requeued and st.host is None

    router = Router(FileKVStore(str(tmp_path / "store")), jdir)
    item = router._item_from_state(st, src="h0")
    assert item["handoff"] == "/tmp/handoff_r1_g0"

    # after a migration at gen 2 the artifact is stale: never re-shipped
    router.journal.migrate("r1", "h0", "h1", 2, [1, 2, 3], 16, 0.0, 1.0,
                           0, [7, 8], handoff="/tmp/handoff_r1_g0")
    st2 = fold(jdir)["r1"]
    assert st2.gen == 2 and st2.host == "h1"
    assert router._item_from_state(st2, src="h1")["handoff"] == ""


def test_router_verifies_artifact_before_shipping(tmp_path):
    """The router's migrate path CRC-verifies the artifact: a good one is
    named in the migrate record, a corrupt one is rejected (counter +
    audit) and the migration degrades to plain replay."""
    from fault_tolerant_llm_training_tpu.ft.lease import FileKVStore
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks)
    from fault_tolerant_llm_training_tpu.inference.router import Router

    cfg = tiny_cfg()
    cache = _filled_cache(cfg)
    art = str(tmp_path / "handoff_rv_g0")
    export_blocks(cache, [1, 2], art, length=9)

    router = Router(FileKVStore(str(tmp_path / "store")),
                    str(tmp_path / "journal"))
    item = {"id": "rv", "gen": 1, "handoff": art}
    assert router._verify_handoff(item) == art

    p = os.path.join(art, "block_00000.bin")
    raw = bytearray(open(p, "rb").read())
    raw[0] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    assert router._verify_handoff(item) == ""
    assert router._verify_handoff({"id": "rv", "gen": 1, "handoff": ""}) \
        == ""
