"""Fleet-global KV-block store (inference/kvstore.py + scheduler fetch/
publish, router cache-affinity placement, ft/retry.py seeded jitter).

Evidence ladder:

1. journal — per-writer fsync'd JSONL folds to per-train state across
   handles (a restarted sweeper re-folds to the same view), refcount
   double-release raises both at the handle and in the fold, torn tails
   from a SIGKILLed writer are skipped, a torn put (no manifest) is
   invisible;
2. artifacts — on a REAL tiny paged engine: publish round-trips the
   exact pool bytes (artifact payloads byte-equal ``block_payload`` of
   the canonical cached blocks), identical chain hashes dedup to one
   resident train, publish rejects key/block count mismatches;
3. eviction — fleet-global LRU by journaled last-use never evicts a
   refcounted train, evicts it once released, and a half-evicted
   directory is finished without new journal records;
4. scheduler — a second engine-reset scheduler FETCHES the published
   train (batched verify-before-first-device-write import) and streams
   bit-identically to a cold local prefill; a poisoned payload is
   rejected with the pool byte-for-byte untouched and zero references
   left behind, then degrades to the local chunked prefill with the
   stream still bit-exact;
5. placement — the router's pick_host prefers the host whose published
   trains cover the deepest prefix of the intake prompt, but a free
   slot still dominates affinity (a full affinity host never starves a
   cold peer);
6. retry jitter — seeded full jitter draws every sleep from
   [0, min(delay, remaining)), replays exactly under a fixed seed, and
   the default (no seed) keeps the deterministic full-delay ladder.

Module scope imports nothing from the package inference/ tree
(collect-only guard in test_spec_decode.py).
"""

import json
import os

import numpy as np
import pytest

from _tiny import tiny_cfg
from fault_tolerant_llm_training_tpu.ft.retry import (
    RetryDeadlineExceeded,
    retry_with_backoff,
)

CACHE = "/tmp/jax_test_compile_cache"


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- 1. journal
def test_fold_restart_idempotence_and_refcounts(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore

    clock = _Clock()
    store = BlockStore(str(tmp_path), writer="h0", clock=clock)
    # hand-journal a train's life: the fold needs no artifact on disk
    store._append({"kind": "put", "key": "k1", "blocks": 2, "bytes": 64,
                   "length": 32, "host": "h0"})
    clock.advance(1.0)
    store.acquire("k1", "fetch-a")
    clock.advance(1.0)
    store.touch("k1")
    st = store.fold()["k1"]
    assert st.refs == 1 and st.blocks == 2 and st.bytes == 64
    assert st.last_use == pytest.approx(102.0)
    assert st.hosts == {"h0"}

    # a second handle (the restarted sweeper) folds to the SAME state
    other = BlockStore(str(tmp_path), writer="sweeper", clock=clock)
    st2 = other.fold()["k1"]
    assert (st2.refs, st2.blocks, st2.last_use) == (1, 2, st.last_use)

    store.release("k1", "fetch-a")
    assert other.fold()["k1"].refs == 0
    # releasing a ref this handle does not hold raises at the handle...
    with pytest.raises(ValueError, match="double release"):
        store.release("k1", "fetch-a")
    # ...and an unbalanced unref in the JOURNAL raises at fold time
    store._append({"kind": "unref", "key": "k1", "owner": "ghost"})
    with pytest.raises(ValueError, match="double release"):
        other.fold()


def test_fold_skips_torn_tail_and_bad_writer_names(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore

    store = BlockStore(str(tmp_path), writer="h0")
    store._append({"kind": "put", "key": "k1", "blocks": 1, "bytes": 8,
                   "length": 16, "host": "h0"})
    # SIGKILL mid-append: a torn, newline-less tail must be skipped
    with open(store._journal_path, "a") as fh:
        fh.write('{"kind": "put", "key": "k2", "blo')
    folded = BlockStore(str(tmp_path), writer="h1").fold()
    assert "k1" in folded and "k2" not in folded
    with pytest.raises(ValueError, match="bad store writer"):
        BlockStore(str(tmp_path), writer="../escape")


def test_torn_put_is_invisible(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)

    store = BlockStore(str(tmp_path), writer="h0")
    key = chain_hashes(list(range(16)), 16)[0].hex()
    # a publisher SIGKILLed between payload write and manifest rename
    # leaves payloads but no manifest: never visible, never matched
    os.makedirs(store.train_dir(key))
    with open(os.path.join(store.train_dir(key), "block_00000.bin"),
              "wb") as fh:
        fh.write(b"\0" * 64)
    assert not store.has(key)
    assert store.match(chain_hashes(list(range(16)), 16)) is None
    assert store.resident() == {}


# ----------------------------------------------------------- 2. artifacts
@pytest.fixture(scope="module")
def compiled_engine():
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    )["params"]
    eng = InferenceEngine(cfg, params, slots=2, max_len=48,
                          prefill_buckets=(16,), kv_layout="paged",
                          kv_block_size=16)
    return cfg, params, eng


def _serve(engine, reqs, store):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    engine.enable_prefix_cache = True
    engine.reset()
    sched = Scheduler(engine, eos_token_id=None, kv_store=store)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


def _prompt(cfg, n, seed=11):
    rng = np.random.default_rng(seed)
    return rng.integers(3, cfg.vocab_size, size=n).tolist()


def test_publish_roundtrip_bitwise_and_dedup(tmp_path, compiled_engine):
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        block_payload)
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    cfg, _, eng = compiled_engine
    store = BlockStore(str(tmp_path), writer="h0")
    prompt = _prompt(cfg, 32)  # two full 16-token blocks
    sched, _ = _serve(eng, [Request(id="a", prompt=list(prompt),
                                    max_new_tokens=4)], store)
    assert sched.store_publishes == 1
    key = chain_hashes(prompt, 16)[-1].hex()
    assert store.has(key)
    st = store.resident()[key]
    assert st.blocks == 2 and st.host == "h0" and st.length == 32

    # artifact payloads are byte-identical to the canonical cached pool
    # blocks — a fetch therefore reproduces the publisher's exact bytes
    hit = sched.prefix_cache.match(prompt)
    assert hit.depth == 2
    for i, blk in enumerate(hit.blocks):
        with open(os.path.join(store.train_dir(key),
                               f"block_{i:05d}.bin"), "rb") as fh:
            assert fh.read() == block_payload(eng.cache, blk)

    # identical chain hashes dedup: a second serve of the same prompt
    # fetches (tested below) but publishes nothing new
    sched2, _ = _serve(eng, [Request(id="b", prompt=list(prompt),
                                     max_new_tokens=4)], store)
    assert sched2.store_publishes == 0
    assert store.puts == 1

    with pytest.raises(ValueError, match="one key per block"):
        store.publish(eng.cache, chain_hashes(prompt, 16), [1],
                      length=32)


# ------------------------------------------------------------ 3. eviction
def test_lru_sweep_respects_refcounts(tmp_path, compiled_engine):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)

    cfg, _, eng = compiled_engine
    clock = _Clock()
    store = BlockStore(str(tmp_path), writer="h0", clock=clock)
    old_keys = chain_hashes(list(range(16)), 16)
    new_keys = chain_hashes(list(range(16, 32)), 16)
    store.publish(eng.cache, old_keys, [1], length=16)
    clock.advance(5.0)
    store.publish(eng.cache, new_keys, [2], length=16)
    old, new = old_keys[0].hex(), new_keys[0].hex()

    # the LRU victim (old) is mid-fetch: the sweeper must skip it and
    # take the next unreferenced train instead
    store.acquire(old, "importer")
    assert store.sweep(max_bytes=0) == [new]
    assert store.has(old) and not store.has(new)
    store.release(old, "importer")
    assert store.sweep(max_bytes=0) == [old]
    assert store.resident() == {} and store.resident_bytes() == 0


def test_sweep_finishes_half_evicted_dirs_without_new_records(
        tmp_path, compiled_engine):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)

    cfg, _, eng = compiled_engine
    store = BlockStore(str(tmp_path), writer="h0")
    keys = chain_hashes(list(range(16)), 16)
    store.publish(eng.cache, keys, [1], length=16)
    key = keys[0].hex()
    # the sweeper journaled the evict, then died before the rmtree
    store._append({"kind": "evict", "key": key})
    assert os.path.isdir(store.train_dir(key))

    def evict_records():
        n = 0
        jdir = os.path.join(str(tmp_path), "journal")
        for name in os.listdir(jdir):
            with open(os.path.join(jdir, name)) as fh:
                n += sum(1 for ln in fh if '"evict"' in ln)
        return n

    before = evict_records()
    restarted = BlockStore(str(tmp_path), writer="sweeper")
    assert restarted.sweep(max_bytes=1 << 30) == []
    assert not os.path.isdir(store.train_dir(key))  # death finished
    assert evict_records() == before                # re-migrated nothing


# ----------------------------------------------------------- 4. scheduler
def test_fetched_stream_bitmatches_local_prefill(tmp_path, compiled_engine):
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    cfg, _, eng = compiled_engine
    store = BlockStore(str(tmp_path), writer="h0")
    prompt = _prompt(cfg, 32, seed=23)
    reqs = lambda: [Request(id="r", prompt=list(prompt), max_new_tokens=8),
                    Request(id="s", prompt=list(prompt[:16]) + [5],
                            max_new_tokens=8, temperature=0.8, top_p=0.9,
                            seed=3)]
    _, cold = _serve(eng, reqs(), None)            # no store: pure local

    pub, _ = _serve(eng, reqs(), store)            # publisher host
    # one train a distinct terminal hash: r's two blocks, s's one
    assert (pub.store_publishes, pub.store_fetches) == (2, 0)

    fetch_store = BlockStore(str(tmp_path), writer="h1")
    con, warm = _serve(eng, reqs(), fetch_store)   # consumer host
    # r's whole prompt comes over the store in ONE fetch of its 2 blocks
    # (cross-host hit rate 32 / 32); s then hits r's first block locally,
    # and nothing is published twice (content-addressed dedup)
    assert (con.store_fetches, con.store_fetch_blocks) == (1, 2)
    assert (con.store_publishes, con.store_rejects) == (0, 0)
    assert warm == cold                            # bit-exact streams
    m = con.metrics()
    assert m["kv_store_fetches"] == con.store_fetches
    assert m["kv_store_fetch_blocks"] == con.store_fetch_blocks
    # the fetch's journaled refs all released; h1 is residency evidence
    assert fetch_store._held == set()
    assert any("h1" in st.hosts
               for st in fetch_store.resident().values())


def test_poisoned_train_rejects_with_zero_device_writes(
        tmp_path, compiled_engine):
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        block_layout)
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    cfg, _, eng = compiled_engine
    store = BlockStore(str(tmp_path), writer="h0")
    prompt = _prompt(cfg, 32, seed=31)
    _, cold = _serve(eng, [Request(id="r", prompt=list(prompt),
                                   max_new_tokens=8)], None)
    _serve(eng, [Request(id="r", prompt=list(prompt),
                         max_new_tokens=8)], store)

    # poison one payload byte; the manifest (and so `has`) still commits
    key = chain_hashes(prompt, 16)[-1].hex()
    path = os.path.join(store.train_dir(key), "block_00001.bin")
    raw = bytearray(open(path, "rb").read())
    raw[7] ^= 0xFF
    with open(path, "wb") as fh:
        fh.write(raw)

    eng.enable_prefix_cache = True
    eng.reset()
    sched = Scheduler(eng, eos_token_id=None,
                      kv_store=BlockStore(str(tmp_path), writer="h1"))
    req = Request(id="p", prompt=list(prompt), max_new_tokens=8)
    before = [np.asarray(seg["array"]).copy()
              for seg in block_layout(eng.cache)]
    free_before = sched.allocator.free_count
    sched._maybe_store_fetch(req)
    # verify-before-first-device-write: the reject left the ENTIRE pool
    # byte-identical, every allocated block freed, every store ref dropped
    assert sched.store_rejects == 1
    after = [np.asarray(seg["array"]) for seg in block_layout(eng.cache)]
    assert all(a.tobytes() == b.tobytes() for a, b in zip(before, after))
    assert sched.allocator.free_count == free_before
    assert sched.kv_store._held == set()

    # ...and the degraded path (local chunked prefill) still streams
    # bit-exactly; the poisoned key dedups the republish
    sched.submit(req)
    sched.run()
    assert {c.request_id: c.tokens for c in sched.completed} == {
        "p": cold["r"]}
    assert sched.store_rejects == 2 and sched.store_publishes == 0


# ------------------------------------------------------------ 5. placement
def test_router_affinity_prefers_deepest_prefix_host(tmp_path):
    from fault_tolerant_llm_training_tpu.ft.lease import FileKVStore
    from fault_tolerant_llm_training_tpu.inference.kvstore import BlockStore
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)
    from fault_tolerant_llm_training_tpu.inference.router import Router

    store_dir = str(tmp_path / "kvstore")
    prompt = list(range(3, 35))  # two full 16-token blocks
    keys = chain_hashes(prompt, 16)
    pub = BlockStore(store_dir, writer="h1")
    pub._append({"kind": "put", "key": keys[-1].hex(), "blocks": 2,
                 "bytes": 64, "length": 32, "host": "h1"})
    # residency needs the manifest on disk; content is irrelevant here
    os.makedirs(pub.train_dir(keys[-1].hex()))
    with open(os.path.join(pub.train_dir(keys[-1].hex()),
                           "integrity.json"), "w") as fh:
        fh.write("{}")

    router = Router(FileKVStore(str(tmp_path / "lease")),
                    str(tmp_path / "journal"), kv_store_dir=store_dir)
    est = lambda slots, blocks: {"stamp": 1.0, "slots": slots,
                                 "blocks": blocks, "block_size": 16,
                                 "role": "both", "kv_dtype": "bf16"}
    # h0 has MORE free blocks; affinity still sends the intake to h1,
    # where the published train makes admission a fetch, not a prefill
    router.est = {"h0": est(2, 100), "h1": est(2, 10)}
    item = {"id": "r", "prompt": prompt, "max_new_tokens": 8, "gen": 0}
    assert router.pick_host(item) == "h1"
    depths = router._affinity_depths(item)
    assert depths == {"h1": 2}
    # a free slot dominates affinity: h1 full => the cold host admits now
    router.est = {"h0": est(2, 100), "h1": est(0, 10)}
    assert router.pick_host(item) == "h0"
    # no matching prefix anywhere: classic most-free-blocks placement
    other = {"id": "q", "prompt": [9] * 32, "max_new_tokens": 8, "gen": 0}
    router.est = {"h0": est(2, 100), "h1": est(2, 10)}
    assert router.pick_host(other) == "h0"


# --------------------------------------------------------- 6. retry jitter
def _jitter_sleeps(seed, deadline=10.0, attempts=6):
    clock = _Clock()
    sleeps = []

    def sleep(dt):
        sleeps.append(dt)
        clock.advance(dt or 1e-3)  # zero draws still make progress

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < attempts:
            raise OSError("transient")
        return "ok"

    out = retry_with_backoff(flaky, deadline_seconds=deadline, clock=clock,
                             sleep=sleep, jitter_seed=seed)
    assert out == "ok"
    return sleeps


def test_seeded_jitter_bounds_and_determinism():
    a = _jitter_sleeps(seed=42)
    b = _jitter_sleeps(seed=42)
    assert a == b                       # replays exactly under a fixed seed
    assert a != _jitter_sleeps(seed=43)  # and the seed actually matters
    # FULL jitter: every sleep drawn from [0, min(delay, remaining)) where
    # delay doubles 0.05 -> 0.1 -> ... capped at 1.0
    delay = 0.05
    for s in a:
        assert 0.0 <= s <= delay
        delay = min(delay * 2.0, 1.0)


def test_unseeded_backoff_keeps_deterministic_ladder():
    sleeps = _jitter_sleeps(seed=None)
    assert sleeps == [0.05, 0.1, 0.2, 0.4, 0.8]


def test_seeded_jitter_still_bounded_by_deadline():
    clock = _Clock()

    def always_down():
        raise OSError("down")

    with pytest.raises(RetryDeadlineExceeded):
        retry_with_backoff(always_down, deadline_seconds=2.0, clock=clock,
                           sleep=clock.advance, jitter_seed=7)
    assert clock.t - 100.0 <= 2.0 + 1e-6
