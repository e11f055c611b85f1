"""Prefix caching + copy-on-write (inference/prefix_cache.py + scheduler,
engine, ops integration).

Evidence ladder for content-addressed prefix reuse over the paged pool:

1. keying — chain hashes commit the ENTIRE token prefix per block (shared
   prefixes share keys, any earlier divergence changes every later key,
   partial trailing blocks are never keyed);
2. refcounts — the allocator's per-block refcount matrix: blocks are born
   at 1, incref/free nest correctly, shared blocks survive one holder's
   free, double-free and incref-of-unallocated fail loudly;
3. cache policy — match/acquire/insert against a real allocator, LRU
   eviction of childless refcount-1 nodes only (in-use prefixes are
   protected; chains unwind leaf-first), flush releases everything;
4. ops — a pool block referenced by TWO table rows gathers bitwise
   identically to two private copies of the same bytes (why sharing needs
   no kernel change);
5. scheduler lifecycle — against a fake cache-aware engine: shared
   admission increfs, full-prompt hits copy-on-write exactly once,
   eviction is the release valve under pool pressure (no head-of-line
   deadlock), a drain with shared blocks in flight frees every holder's
   reference exactly once, the post-drain leak guard audits and raises,
   and the /metrics surface carries the ROADMAP-named series;
6. streams — real compiled engines: cache-on streams (partial hits AND a
   COW full-prompt repeat) are BIT-identical to cache-off streams, the
   packed multi-request prefill lane reproduces the sequential lane's
   streams bitwise over a pre-warmed tree (partial hits and a full-hit
   COW repeat riding the same packed wave), and (slow) the speculative
   exact-verify path stays bit-identical to non-speculative decoding
   with shared prefixes in play.

Module scope imports nothing from the package (collect-only guard in
test_spec_decode.py).
"""

import logging

import numpy as np
import pytest

from _tiny import tiny_cfg

CACHE = "/tmp/jax_test_compile_cache"


# --------------------------------------------------------------- 1. keying
def test_chain_hashes_commit_whole_prefix():
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)

    a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], block_size=4)
    assert len(a) == 2
    # shared first block -> shared first key; divergent second block ->
    # divergent second key
    b = chain_hashes([1, 2, 3, 4, 9, 9, 9, 9], block_size=4)
    assert b[0] == a[0] and b[1] != a[1]
    # divergence in block 0 poisons EVERY later key (chain, not per-block)
    c = chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], block_size=4)
    assert c[0] != a[0] and c[1] != a[1]
    # partial trailing block contributes no key; shorter prefix = prefix of
    # the key list
    assert chain_hashes([1, 2, 3, 4, 5, 6], block_size=4) == a[:1]
    assert chain_hashes([1, 2, 3], block_size=4) == []


# ------------------------------------------------------------ 2. refcounts
def test_allocator_refcount_matrix():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        BlockAllocator)

    a = BlockAllocator(num_blocks=5)
    blocks = a.alloc(2)
    b0, b1 = blocks
    assert a.refcount(b0) == 1 and a.refcount(b1) == 1
    assert a.shared_count == 0
    a.incref([b0])
    assert a.refcount(b0) == 2 and a.shared_count == 1
    a.free([b0])                       # one holder gone, block survives
    assert a.refcount(b0) == 1 and a.used_count == 2
    a.free([b0, b1])                   # last holders: both return to pool
    assert a.refcount(b0) == 0 and a.free_count == a.capacity
    with pytest.raises(ValueError, match="double free"):
        a.free([b1])
    with pytest.raises(ValueError, match="unallocated"):
        a.incref([b1])
    # freed blocks are reusable
    again = a.alloc(4)
    assert again is not None and a.free_count == 0


# --------------------------------------------------------- 3. cache policy
def _cache(num_blocks=10, block_size=4):
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        PrefixCache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        BlockAllocator)

    alloc = BlockAllocator(num_blocks=num_blocks)
    return alloc, PrefixCache(alloc, block_size)


def test_match_insert_acquire_refcounts():
    alloc, pc = _cache()
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    slot_blocks = alloc.alloc(2)
    assert pc.insert(prompt, slot_blocks) == 2
    # each node holds the cache's own reference on top of the slot's
    assert all(alloc.refcount(b) == 2 for b in slot_blocks)
    # re-insert (e.g. a COW'd private copy) must NOT displace the canonical
    # blocks or take more references
    other = alloc.alloc(2)
    assert pc.insert(prompt, other) == 0
    assert all(alloc.refcount(b) == 2 for b in slot_blocks)
    alloc.free(other)

    hit = pc.match(prompt)
    assert hit.full and hit.tokens == 8 and hit.blocks == list(slot_blocks)
    hit = pc.match(prompt + [9])               # longer prompt: partial hit
    assert not hit.full and hit.tokens == 8
    hit = pc.match([1, 2, 3, 4, 9, 9, 9, 9])   # diverges in block 1
    assert hit.tokens == 4 and hit.blocks == [slot_blocks[0]]
    assert pc.match([9] * 8).blocks == []      # miss

    pc.acquire(hit)                            # the admitted slot's ref
    assert alloc.refcount(slot_blocks[0]) == 3
    alloc.free(hit.blocks)


def test_eviction_lru_childless_refcount1_only():
    alloc, pc = _cache()
    prompt = list(range(12))                   # 3 chained blocks
    blocks = alloc.alloc(3)
    pc.insert(prompt, blocks)
    alloc.free(blocks)                         # slot finished: cache-only
    assert alloc.used_count == 3

    # a live slot still reads the full chain: nothing is evictable
    pc.acquire(pc.match(prompt))
    assert pc.evict(3) == 0 and pc.cached_blocks == 3
    alloc.free(blocks)                         # slot done

    # now the chain unwinds leaf-first, LRU — one block per evict unit
    assert pc.evict(1) == 1
    assert pc.cached_blocks == 2 and alloc.refcount(blocks[2]) == 0
    assert pc.match(prompt).tokens == 8        # surviving prefix still hits
    assert pc.evict(99) == 2 and pc.cached_blocks == 0
    assert alloc.free_count == alloc.capacity
    assert pc.evictions == 3


def test_eviction_prefers_lru_branch():
    alloc, pc = _cache(block_size=4)
    old = [1, 2, 3, 4]
    new = [5, 6, 7, 8]
    b_old, b_new = alloc.alloc(1), alloc.alloc(1)
    pc.insert(old, b_old)
    pc.insert(new, b_new)
    alloc.free(b_old + b_new)
    pc.match(new)                              # touch: new becomes MRU
    assert pc.evict(1) == 1
    assert pc.match(old).blocks == [] and pc.match(new).blocks == b_new


def test_flush_releases_every_cache_reference():
    alloc, pc = _cache()
    blocks = alloc.alloc(2)
    pc.insert(list(range(8)), blocks)
    alloc.free(blocks)
    assert alloc.used_count == 2
    assert pc.flush() == 2
    assert pc.cached_blocks == 0 and alloc.free_count == alloc.capacity
    assert pc.evictions == 0                   # flush is not eviction


# --------------------------------------------------------------- 4. ops
def test_shared_block_gathers_bitwise_like_private_copy():
    """Two table rows pointing at the SAME pool block must gather exactly
    what two rows pointing at duplicated copies of those bytes gather —
    the device-side reason prefix sharing needs no kernel change."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.ops.attention import (
        gather_kv_blocks)

    rng = np.random.default_rng(3)
    K, bs, D = 2, 4, 8
    pool = rng.standard_normal((5, K, bs, D)).astype(np.float32)
    shared = jnp.asarray(pool)
    tables_shared = jnp.asarray([[1, 2], [1, 3]], jnp.int32)   # block 1 shared
    dup = pool.copy()
    dup[4] = pool[1]                                           # private copy
    tables_private = jnp.asarray([[1, 2], [4, 3]], jnp.int32)
    a = np.asarray(gather_kv_blocks(shared, tables_shared))
    b = np.asarray(gather_kv_blocks(jnp.asarray(dup), tables_private))
    assert (a == b).all()


# ------------------------------------------------- 5. scheduler lifecycle
class _FakeCacheEngine:
    """Cache-aware paged-engine double: advertises ``enable_prefix_cache``
    so the scheduler builds a PrefixCache, accepts the ``start_pos`` resume
    offset, and records ``cow_copy`` calls — no XLA anywhere."""

    def __init__(self, slots=4, max_len=64, block_size=8, num_blocks=None,
                 bucket=16):
        self.slots = slots
        self.max_len = max_len
        self.kv_layout = "paged"
        self.block_size = block_size
        self.max_blocks_per_slot = -(-max_len // block_size)
        self.num_blocks = num_blocks or slots * self.max_blocks_per_slot + 1
        self.bucket = bucket
        self.enable_prefix_cache = True
        self.cow_calls = []
        self.prefilled_positions = 0           # compute the cache absorbed

    def cow_copy(self, src, dst):
        self.cow_calls.append((src, dst))

    def prefill(self, slot, token_ids, block_row=None, temperature=0.0,
                top_p=1.0, seed=0, stop_check=None, on_chunk=None,
                start_pos=0):
        n = len(token_ids)
        start = start_pos
        self.prefilled_positions += n - start
        while start < n:
            start += min(self.bucket, n - start)
            if on_chunk is not None:
                on_chunk()
            if start < n and stop_check is not None and stop_check():
                return None
        return 1

    def decode_step(self, tokens, active, temperature, top_p, seeds, steps,
                    block_tables=None):
        assert block_tables is not None
        return np.where(active, tokens + 1, 0).astype(np.int32)


def test_shared_admission_points_tables_at_same_blocks():
    """Second request sharing a 16-token (2-block) prefix reuses the first
    request's pool blocks: tables overlap, allocator reports them shared,
    prefill resumes past the hit, and the drained pool passes the leak
    audit with only cache-held blocks outstanding."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=32, block_size=8)
    sched = Scheduler(eng, eos_token_id=None)
    shared = list(range(100, 116))
    sched.submit(Request(id="a", prompt=shared + [1, 2, 3],
                         max_new_tokens=4))
    sched.submit(Request(id="b", prompt=shared + [7, 8, 9],
                         max_new_tokens=4))
    sched.step()                               # both admitted
    assert (sched.block_tables[0, :2] == sched.block_tables[1, :2]).all()
    assert sched.block_tables[0, 2] != sched.block_tables[1, 2]
    assert sched.allocator.shared_count == 2   # cache ref + two slot refs
    # request b prefilled only its 3-token tail (19 - 16 hit positions)
    assert eng.prefilled_positions == 19 + 3
    sched.run()
    m = sched.metrics()
    assert m["prefix_hits"] == 1 and m["prefix_hit_tokens"] == 16
    assert m["prefix_hit_rate"] == pytest.approx(16 / 38)
    assert m["prefix_cow_copies"] == 0 and not eng.cow_calls
    # drain contract: every outstanding block is cache-held, audit clean
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    assert sched.audit_block_leaks(strict=True) == []
    sched.prefix_cache.flush()
    assert sched.allocator.free_count == sched.allocator.capacity


def test_full_prompt_hit_copies_on_write_once():
    """An identical block-aligned prompt is a FULL hit: prefill must resume
    at prompt_len - 1 to recover the last position's logits, which writes
    inside the final shared block — so admission COWs it into a private
    block, remaps the table, and never re-inserts the copy over the
    canonical cached block."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=32, block_size=8)
    sched = Scheduler(eng, eos_token_id=None)
    prompt = list(range(200, 216))             # exactly 2 blocks
    sched.submit(Request(id="a", prompt=list(prompt), max_new_tokens=4))
    sched.submit(Request(id="b", prompt=list(prompt), max_new_tokens=4))
    sched.step()
    assert len(eng.cow_calls) == 1
    src, dst = eng.cow_calls[0]
    # b shares block 0, owns a private copy of block 1
    assert sched.block_tables[0, 0] == sched.block_tables[1, 0]
    assert sched.block_tables[1, 1] == dst != sched.block_tables[0, 1] == src
    # b prefilled exactly ONE position (the last prompt token)
    assert eng.prefilled_positions == 16 + 1
    sched.run()
    m = sched.metrics()
    assert m["prefix_cow_copies"] == 1
    assert m["prefix_hit_tokens"] == 15        # resumed at prompt_len - 1
    # the canonical cached block is still the original, not the COW copy
    assert sched.prefix_cache.match(prompt).blocks[-1] == src
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    sched.prefix_cache.flush()
    assert sched.allocator.free_count == sched.allocator.capacity


def test_eviction_valve_prevents_head_of_line_deadlock():
    """Pool sized so cached prefixes from finished requests must be evicted
    before the next distinct request fits: without the valve the queue
    head would wait forever behind cache-held blocks."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    # 5 usable blocks; each request needs 3 (16 prompt + 4 gen @ bs 8) and
    # leaves 2 cached — the third admission must evict to fit
    eng = _FakeCacheEngine(slots=1, max_len=24, block_size=8, num_blocks=6)
    sched = Scheduler(eng, eos_token_id=None)
    for i in range(3):
        sched.submit(Request(id=f"r{i}",
                             prompt=list(range(100 * i, 100 * i + 16)),
                             max_new_tokens=4))
    sched.run()
    assert len(sched.completed) == 3
    m = sched.metrics()
    assert m["prefix_evictions"] > 0
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    sched.prefix_cache.flush()
    assert sched.allocator.free_count == sched.allocator.capacity


def test_drain_mid_decode_frees_shared_blocks_exactly_once():
    """Chaos-style drain with SHARED blocks in flight: two slots reading
    the same prefix blocks finish under drain, each releasing its own
    reference through the one uniform free path — the refcounted pool must
    come back to cache-only with no double-free and a clean audit."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=64, block_size=8, bucket=16)
    fired = {"on": False}
    sched = Scheduler(eng, eos_token_id=None, stop_check=lambda: fired["on"])
    shared = list(range(300, 316))
    sched.submit(Request(id="a", prompt=shared + [1], max_new_tokens=8))
    sched.submit(Request(id="b", prompt=shared + [2], max_new_tokens=8))
    sched.step()                               # both admitted, sharing
    assert sched.allocator.shared_count == 2
    fired["on"] = True                         # drain lands mid-decode
    # c's 40-token prompt spans multiple chunks past its 16-token hit, so
    # the drain probe fires between its prefill chunks and rolls it back
    sched.submit(Request(id="c", prompt=shared + list(range(24)),
                         max_new_tokens=8))
    while sched.pending():
        sched.step()
    assert [r.id for r in sched.unserved()] == ["c"]
    assert sorted(c.request_id for c in sched.completed) == ["a", "b"]
    # a and b each freed their references exactly once: only the cache's
    # remain, no block is shared, audit is clean
    assert sched.allocator.shared_count == 0
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    assert sched.audit_block_leaks(strict=True) == []
    sched.prefix_cache.flush()
    assert sched.allocator.free_count == sched.allocator.capacity


def test_drain_mid_prefill_rolls_back_hit_references():
    """Drain firing INSIDE a chunked prefill that resumed from a hit: the
    admission rollback frees fresh AND acquired shared references exactly
    once — the shared blocks survive under the cache's reference and the
    request is reported unserved."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=64, block_size=8, bucket=16)
    fired = {"on": False}
    sched = Scheduler(eng, eos_token_id=None, stop_check=lambda: fired["on"])
    shared = list(range(400, 416))
    sched.submit(Request(id="warm", prompt=shared + [1], max_new_tokens=2))
    sched.run()                                # seeds the cache, completes
    sched.admission_open = True                # fresh serving phase
    fired["on"] = True                         # signal already pending
    sched.submit(Request(id="long", prompt=shared + list(range(40)),
                         max_new_tokens=4))
    while sched.pending():
        sched.step()
    assert [r.id for r in sched.unserved()] == ["long"]
    assert sched.allocator.shared_count == 0
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    assert sched.audit_block_leaks(strict=True) == []


@pytest.mark.parametrize("how", ["length", "eos"])
def test_a_normal_finish_caches_the_blocks_decode_wrote(how):
    """A request that ends by its budget or by EOS leaves the whole blocks
    of what its slot WROTE — prompt + every generated token but the last —
    in the cache, taken BEFORE the slot's one free: the cache holds one
    reference a block, nothing is shared, the leak guard is clean, and the
    stream's continuation hits up to the last whole block."""
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=64, block_size=8)
    # the double's stream is 1, 2, 3, ...: EOS = 14 ends it at 14 tokens
    sched = Scheduler(eng, eos_token_id=14 if how == "eos" else None)
    prompt = list(range(100, 112))              # 12 tokens: 1 whole block
    sched.submit(Request(id="a", prompt=list(prompt),
                         max_new_tokens=14 if how == "length" else 40))
    sched.step()                                # admitted: the prompt's block
    assert sched.prefix_cache.cached_blocks == 1
    table = sched.block_tables[0].copy()
    (c,) = sched.run()
    assert c.reason == how and c.tokens == list(range(1, 15))
    # wrote 12 + 13 = 25 positions: 3 whole blocks, the slot's own
    written = prompt + c.tokens[:-1]
    assert sched.prefix_cache.cached_blocks == 3
    hit = sched.prefix_cache.match(prompt + c.tokens + [7, 7, 7])
    assert hit.tokens == 24 and hit.blocks == list(table[:3])
    assert hit.keys == chain_hashes(written, 8)
    assert all(sched.allocator.refcount(b) == 1 for b in hit.blocks)
    assert sched.allocator.used_count == 3
    assert sched.allocator.shared_count == 0
    assert sched.audit_block_leaks(strict=True) == []
    # the next turn resumes past everything the slot wrote in whole blocks
    eng.prefilled_positions = 0
    sched.submit(Request(id="b", prompt=prompt + c.tokens + [7, 7, 7],
                         max_new_tokens=2))
    sched.run()
    assert eng.prefilled_positions == 12 + 14 + 3 - 24
    # eviction frees them like any cached block: leaf first, all of them
    n = sched.prefix_cache.cached_blocks
    assert sched.prefix_cache.evict(99) == n
    assert sched.allocator.free_count == sched.allocator.capacity


def test_a_second_identical_finish_adds_no_node():
    """The same stream finishing twice (the second over a full COW hit of
    the first's blocks) caches nothing twice: the canonical blocks stay
    the first's, and the second's private copies are freed with its slot."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=1, max_len=64, block_size=8)
    sched = Scheduler(eng, eos_token_id=None)
    prompt = list(range(200, 216))              # 2 whole blocks
    for rid in ("a", "b"):
        sched.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=10))
        sched.run()
        # 16 + 9 = 25 positions: 3 whole blocks, once
        assert sched.prefix_cache.cached_blocks == 3
        assert sched.allocator.used_count == 3
        if rid == "a":
            first = sched.prefix_cache.match(prompt + list(range(1, 10)))
    again = sched.prefix_cache.match(prompt + list(range(1, 10)))
    assert again.blocks == first.blocks and again.tokens == 24
    assert sched.metrics()["prefix_cow_copies"] == 1
    assert sched.audit_block_leaks(strict=True) == []


@pytest.mark.parametrize("how", ["drain_roll_back", "one_token"])
def test_no_other_end_of_a_request_caches_more_than_its_prompt(how):
    """A prefill rolled back by a drain caches nothing of its request (the
    request is unserved, its blocks freed exactly once), and a request
    that ends straight out of its prefill wrote nothing past its prompt."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=1, max_len=64, block_size=8, bucket=16)
    fired = {"on": how == "drain_roll_back"}
    sched = Scheduler(eng, eos_token_id=None, stop_check=lambda: fired["on"])
    sched.submit(Request(id="r", prompt=list(range(300, 340)),
                         max_new_tokens=1 if how == "one_token" else 8))
    while sched.pending():
        sched.step()
    if how == "drain_roll_back":
        assert [r.id for r in sched.unserved()] == ["r"]
        assert sched.prefix_cache.cached_blocks == 0
        assert sched.allocator.free_count == sched.allocator.capacity
    else:
        assert [c.reason for c in sched.completed] == ["length"]
        assert sched.prefix_cache.cached_blocks == 5    # 40 // 8, the prompt
    assert sched.audit_block_leaks(strict=True) == []


def test_leak_guard_audits_once_and_raises_strict(caplog):
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeCacheEngine(slots=2, max_len=32, block_size=8)
    sched = Scheduler(eng, eos_token_id=None)
    sched.submit(Request(id="a", prompt=list(range(12)), max_new_tokens=2))
    sched.run()                                # clean: no audit, no raise
    assert not sched._leak_audited

    sched.allocator.alloc(1)                   # simulate a leaked block
    with caplog.at_level(logging.INFO):
        leaks = sched.audit_block_leaks(strict=False)
    assert len(leaks) == 1 and leaks[0].startswith("[KV LEAK] target pool")
    assert any("[KV LEAK]" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.INFO):
        with pytest.raises(RuntimeError, match="KV block leak"):
            sched.audit_block_leaks(strict=True)
    # audited exactly once — the latch stops repeat emissions
    assert not any("[KV LEAK]" in r.message for r in caplog.records)


def test_prefix_metrics_surface():
    """The ROADMAP-named series exist on the registry and move: gauge
    ``kv_prefix_hit_rate`` (unprefixed, like the chaos series), gauge
    ``kv_blocks_shared``, counter ``prefix_evictions_total``."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

    reg = MetricRegistry()
    eng = _FakeCacheEngine(slots=2, max_len=32, block_size=8)
    sched = Scheduler(eng, eos_token_id=None, registry=reg)
    shared = list(range(16))
    sched.submit(Request(id="a", prompt=shared + [1], max_new_tokens=2))
    sched.submit(Request(id="b", prompt=shared + [2], max_new_tokens=2))
    sched.run()
    text = reg.render()
    values = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#") and " " in ln:
            name, val = ln.rsplit(" ", 1)
            values[name] = val
    assert float(values["kv_prefix_hit_rate"]) > 0
    assert "kv_blocks_shared" in values
    # the counter has no samples until the first eviction; the family
    # itself must already be declared on the scrape surface
    assert "# TYPE prefix_evictions_total counter" in text


# ------------------------------------------------------------- 6. streams
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


def _run_streams(engine, reqs, cache_on):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    engine.enable_prefix_cache = cache_on
    engine.reset()
    sched = Scheduler(engine, eos_token_id=None)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


def test_cached_streams_bitmatch_uncached(compiled_engine):
    """Compiled end-to-end: greedy AND sampled requests sharing a 16-token
    (one block) prefix — plus an exact repeat that forces a full-hit COW —
    produce BIT-identical token streams with the cache on and off. Shared
    blocks are the same device bytes and resumed chunks run the identical
    bucket programs, so this must hold bitwise, not approximately."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    cfg, _, eng = compiled_engine
    rng = np.random.default_rng(7)
    shared = rng.integers(3, cfg.vocab_size, size=16).tolist()
    tails = [rng.integers(3, cfg.vocab_size, size=n).tolist()
             for n in (5, 9, 0)]
    reqs = [
        Request(id="greedy-a", prompt=shared + tails[0], max_new_tokens=8),
        Request(id="sampled", prompt=shared + tails[1], max_new_tokens=8,
                temperature=0.8, top_p=0.9, seed=3),
        Request(id="repeat", prompt=list(shared), max_new_tokens=8),
        Request(id="repeat2", prompt=list(shared), max_new_tokens=8),
    ]
    on_sched, on_out = _run_streams(eng, reqs, cache_on=True)
    m = on_sched.metrics()
    # block accounting is exact: "sampled" hits the shared block (16), each
    # full-prompt repeat hits 15 (its last token is computed again, on a
    # COW copy); the rate is hit tokens over all prompt tokens looked up
    assert (m["prefix_hits"], m["prefix_hit_tokens"]) == (3, 16 + 15 + 15)
    assert m["prefix_hit_rate"] == 46 / sum(len(r.prompt) for r in reqs)
    assert m["prefix_cow_copies"] == 2          # the full-prompt repeats
    assert on_sched.allocator.used_count == on_sched.prefix_cache.cached_blocks

    off_sched, off_out = _run_streams(eng, reqs, cache_on=False)
    assert off_sched.prefix_cache is None
    assert on_out == off_out
    assert len(on_out) == 4
    eng.enable_prefix_cache = True              # restore for other tests


def test_packed_prefill_streams_bitmatch_sequential_with_hits(compiled_engine):
    """Packed admission allocates before any same-wave insert, so hits come
    from a PRE-WARMED tree: warm one shared-prefix request to completion,
    then serve a wave with two partial hits and a full-hit COW repeat
    through the packed lane — streams must be BITWISE identical to the
    sequential lane over the same warmed cache (hit-resumed rows enter the
    packed program at their own start offsets, same chunk shapes)."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    cfg, params, eng = compiled_engine
    packed = InferenceEngine(cfg, params, slots=2, max_len=48,
                             prefill_buckets=(16,), kv_layout="paged",
                             kv_block_size=16, prefill_batch=2)
    rng = np.random.default_rng(13)
    shared = rng.integers(3, cfg.vocab_size, size=16).tolist()
    tails = [rng.integers(3, cfg.vocab_size, size=n).tolist() for n in (5, 9)]
    warm = Request(id="warm", prompt=shared + [4], max_new_tokens=2)
    wave = [
        Request(id="hit-a", prompt=shared + tails[0], max_new_tokens=6),
        Request(id="hit-b", prompt=shared + tails[1], max_new_tokens=6,
                temperature=0.8, top_p=0.9, seed=5),
        Request(id="repeat", prompt=list(shared), max_new_tokens=6),
    ]

    def run(engine, pb):
        engine.enable_prefix_cache = True
        engine.reset()
        sched = Scheduler(engine, eos_token_id=None, prefill_batch=pb)
        sched.submit(warm)
        sched.run()                            # seeds the tree, completes
        for r in wave:
            sched.submit(r)
        sched.run()
        return sched, {c.request_id: c.tokens for c in sched.completed}

    seq_sched, seq_out = run(eng, 1)
    pak_sched, pak_out = run(packed, 2)
    assert pak_out == seq_out
    assert len(pak_out) == 4
    ms, mp = seq_sched.metrics(), pak_sched.metrics()
    assert mp["prefill_packed_rounds"] > 0
    assert mp["prefill_chunks"] == ms["prefill_chunks"]   # same chunking
    assert mp["prefix_hits"] == ms["prefix_hits"] >= 3
    assert mp["prefix_cow_copies"] >= 1        # the full-prompt repeat
    assert (pak_sched.allocator.used_count
            == pak_sched.prefix_cache.cached_blocks)
    pak_sched.prefix_cache.flush()
    assert pak_sched.allocator.free_count == pak_sched.allocator.capacity


@pytest.mark.slow
def test_spec_exact_shared_prefix_stream_bitmatches(compiled_engine):
    """Speculative decoding (exact verify) with prefix caching on: shared
    and repeated prompts still produce the non-speculative engine's exact
    greedy streams — the dual-pool admission (the draft pool mirrors the
    cache) and the COW path compose without breaking the PR-4 bitwise
    guarantee."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg, params, base = compiled_engine
    rng = np.random.default_rng(11)
    shared = rng.integers(3, cfg.vocab_size, size=16).tolist()
    reqs = [
        Request(id="a", prompt=shared + [5, 6, 7], max_new_tokens=6),
        Request(id="b", prompt=shared + [8, 9], max_new_tokens=6),
        Request(id="c", prompt=list(shared), max_new_tokens=6),
    ]
    _, want = _run_streams(base, reqs, cache_on=True)

    draft_params = Transformer(cfg).init(
        jax.random.PRNGKey(9), jnp.zeros((1, cfg.seq_len), jnp.int32)
    )["params"]
    spec = InferenceEngine(cfg, params, slots=2, max_len=48,
                           prefill_buckets=(16,), kv_layout="paged",
                           kv_block_size=16, draft_cfg=cfg,
                           draft_params=draft_params, spec_k=2,
                           spec_verify_impl="exact")
    spec_sched, got = _run_streams(spec, reqs, cache_on=True)
    assert got == want
    m = spec_sched.metrics()
    assert m["spec_rounds"] > 0
    assert m["prefix_hits"] >= 2 and m["prefix_cow_copies"] >= 1
    # the draft pool mirrors the radix scheme (scheduler.py, "DRAFT-pool
    # mirror"): after drain it holds its cached blocks and nothing else
    assert (spec_sched.draft_allocator.used_count
            == spec_sched.draft_prefix_cache.cached_blocks)
    spec_sched.draft_prefix_cache.flush()
    assert (spec_sched.draft_allocator.free_count
            == spec_sched.draft_allocator.capacity)
    assert (spec_sched.allocator.used_count
            == spec_sched.prefix_cache.cached_blocks)
    spec_sched.prefix_cache.flush()
    assert (spec_sched.allocator.free_count
            == spec_sched.allocator.capacity)
