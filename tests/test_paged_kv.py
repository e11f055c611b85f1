"""Paged KV cache + chunked prefill (ops/attention.py, inference/).

Evidence ladder for the block-paged serving cache:

1. ops — ``paged_cached_attention`` over a scattered block pool BIT-MATCHES
   ``cached_attention`` over the contiguous layout, including when freed
   table entries point at a garbage-filled null block (masked positions
   contribute exact fp32 zeros, so stale blocks cannot leak);
2. allocator — exhaustion returns None (callers queue, never crash), block
   0 is never handed out, double-frees fail loudly;
3. engine — the paged engine's greedy AND sampled token streams equal the
   ring engine's over a mixed eviction/refill workload (same params, same
   seeds), chunked prefill is logit-identical to single-shot prefill (eager
   at the model level; compiled engine-vs-engine for the token stream — the
   two XLA regimes differ at bf16 so each is compared within its own), and
   the ring layout rejects the long prompt the pages now serve;
4. scheduler — admission by free-block count queues on pool exhaustion and
   still completes everything, blocks are freed exactly once on eviction,
   and a drain signal landing mid-chunked-prefill stops at a chunk
   boundary with the request reported unserved and its blocks returned;
5. packed prefill — with ``prefill_batch > 1`` the scheduler streams up to
   P pending requests' next chunks through ONE (P, bucket) dispatch per
   round: token streams are BITWISE identical to sequential one-at-a-time
   prefill (batch is a parallel GEMM dimension — per-row contraction
   shapes are unchanged), a drain landing mid-packed-prefill frees every
   pending row's blocks exactly once, and the lane's invariants are
   enforced (engine/scheduler width agreement, paged-only, no spec mode).
"""

import numpy as np
import pytest

from _tiny import tiny_cfg


# --------------------------------------------------------------------- 1. ops
def test_paged_attention_bitmatches_contiguous():
    """Scatter a contiguous (B, K, T, D) cache into a shuffled block pool;
    the gathered attention must equal the contiguous attention bitwise."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.ops.attention import (
        cached_attention, gather_kv_blocks, paged_cached_attention)

    rng = np.random.default_rng(0)
    B, K, H, bs, NB, D = 2, 2, 4, 4, 4, 8
    T = NB * bs
    k = rng.standard_normal((B, K, T, D)).astype(np.float32)
    v = rng.standard_normal((B, K, T, D)).astype(np.float32)
    q = rng.standard_normal((B, 3, H, D)).astype(np.float32)
    offsets = np.array([5, T - 3], np.int32)

    # blocks 1..B*NB in shuffled order; block 0 stays garbage (null block)
    perm = rng.permutation(np.arange(1, B * NB + 1))
    tables = perm.reshape(B, NB).astype(np.int32)
    pool_k = rng.standard_normal((B * NB + 1, K, bs, D)).astype(np.float32)
    pool_v = rng.standard_normal((B * NB + 1, K, bs, D)).astype(np.float32)
    for b in range(B):
        for n in range(NB):
            pool_k[tables[b, n]] = k[b, :, n * bs:(n + 1) * bs]
            pool_v[tables[b, n]] = v[b, :, n * bs:(n + 1) * bs]

    np.testing.assert_array_equal(
        np.asarray(gather_kv_blocks(jnp.asarray(pool_k),
                                    jnp.asarray(tables))), k)
    ref = cached_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(offsets))
    out = paged_cached_attention(jnp.asarray(q), jnp.asarray(pool_k),
                                 jnp.asarray(pool_v), jnp.asarray(tables),
                                 jnp.asarray(offsets))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # free the blocks wholly beyond each slot's valid region: their table
    # entries fall back to the garbage null block, output must not move —
    # masked positions are exact zeros, stale content cannot leak
    tables2 = tables.copy()
    for b in range(B):
        first_dead = -(-(int(offsets[b]) + q.shape[1]) // bs)
        tables2[b, first_dead:] = 0
    out2 = paged_cached_attention(jnp.asarray(q), jnp.asarray(pool_k),
                                  jnp.asarray(pool_v), jnp.asarray(tables2),
                                  jnp.asarray(offsets))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))


def test_write_paged_kv_masks_invalid_positions():
    """Invalid (padding / inactive-slot) writes divert into null block 0;
    no allocated block is touched."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        write_paged_kv)

    K, bs, D = 2, 4, 3
    pool = jnp.zeros((4, K, bs, D), jnp.float32)
    new = jnp.ones((1, K, 6, D), jnp.float32)  # 6 positions, only 5 valid
    tables = jnp.asarray([[2, 3]], jnp.int32)
    valid = jnp.asarray([[True] * 5 + [False]])
    out = np.asarray(write_paged_kv(pool, new,
                                    tables, jnp.zeros((1,), jnp.int32),
                                    valid))
    assert out[2].sum() == bs * K * D          # block 2: positions 0..3
    assert out[3, :, 0, :].sum() == K * D      # block 3: position 4 only
    assert out[3, :, 1:, :].sum() == 0         # padding position diverted
    assert out[1].sum() == 0                   # unrelated block untouched


# Geometry of the direct-write cases: 12 pool blocks of K=2 x bs=4 x D=8, a
# block table of 3 columns (12 positions of reach). Each case is (tables,
# start, lens, S): slot b's rows [0, lens[b]) are valid, the rest padding.
_WRITE_CASES = {
    # every slot one row, each at its own offset inside its own block
    "decode_4x1": ([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 0]],
                   [0, 5, 7, 3], [1, 1, 1, 1], 1),
    # inactive slots (stale start, live table) touch no allocated block
    "decode_inactive": ([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 0]],
                        [2, 5, 11, 6], [1, 0, 1, 0], 1),
    # S not a multiple of bs, start mid-block: rows share blocks
    "prefill_1x7_midblock": ([[3, 9, 5]], [2], [7], 7),
    "prefill_1x10_from_0": ([[3, 9, 5]], [0], [10], 10),
    # bucket padding: the last real block keeps its tail as it was
    "prefill_1x8_padded": ([[6, 2, 8]], [3], [5], 8),
    "packed_3x7": ([[1, 2, 3], [4, 5, 6], [7, 8, 9]],
                   [0, 3, 5], [7, 4, 0], 7),
    "all_invalid": ([[1, 2, 3], [4, 5, 6]], [1, 6], [0, 0], 5),
    # positions 12.. are past the table's reach; slot 1 also meets a free
    # (0) table entry inside its reach
    "past_reach": ([[1, 2, 3], [4, 0, 6]], [9, 2], [6, 6], 6),
    # blocks 1 and 2 are a prefix both slots share read-only: slot 0 writes
    # on in its own block 3, slot 1 is inactive with a start INSIDE block 2
    "shared_prefix": ([[1, 2, 3], [1, 2, 4]], [8, 5], [3, 0], 3),
    # a (B, 1) mask over S = 4 rows: the whole slot is live or is not
    "verify_slot_mask": ([[1, 2, 3], [4, 5, 6]], [6, 3], [4, 0], 4),
}


def _write_case(name, rng):
    tables, start, lens, s = _WRITE_CASES[name]
    b = len(tables)
    valid = np.arange(s)[None, :] < np.asarray(lens)[:, None]
    if name == "verify_slot_mask":      # as the chunk verify passes it
        valid = valid[:, :1]
    new = rng.standard_normal((b, 2, s, 8)).astype(np.float32)
    return (np.asarray(tables, np.int32), np.asarray(start, np.int32),
            valid, new)


def _rows_of(tables, start, valid, new, bs):
    """The contract, row by row: (b, r, block, offset) of every row of
    ``new`` (B, K, S, D) that lands in an allocated block, in sequence
    order."""
    valid = np.broadcast_to(valid, new.shape[0::2])
    for b, r in np.ndindex(valid.shape):
        pos = int(start[b]) + r
        if valid[b, r] and pos // bs < tables.shape[1]:
            blk = int(tables[b, pos // bs])
            if blk:
                yield b, r, blk, pos % bs


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_write_paged_kv_matches_row_reference(case, dtype):
    """``write_paged_kv`` against a NumPy row-by-row write, bit for bit on
    every allocated block: valid rows land at (table[pos // bs], :,
    pos % bs, :), rows that share a block all survive, and nothing else
    moves — invalid rows, rows past the table's reach and rows behind a
    free table entry change null block 0 at most, which stays finite."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        write_paged_kv)

    rng = np.random.default_rng(sorted(_WRITE_CASES).index(case))
    tables, start, valid, new = _write_case(case, rng)
    dt = jnp.dtype(dtype)
    pool = jnp.asarray(rng.standard_normal((12, 2, 4, 8)), dt)
    new = jnp.asarray(new, dt)
    want = np.asarray(pool).copy()
    for b, r, blk, off in _rows_of(tables, start, valid, new, 4):
        want[blk, :, off, :] = np.asarray(new)[b, :, r, :]
    got = np.asarray(write_paged_kv(pool, new, jnp.asarray(tables),
                                    jnp.asarray(start), jnp.asarray(valid)))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got[1:], want[1:])
    assert np.isfinite(got[0].astype(np.float32)).all()


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_write_paged_kv_int8_matches_row_reference(case):
    """The int8 pool through the same cases: a row at block offset 0 sets
    its block's per-head scale (amax / 127), every row quantizes at the
    scale its block then holds, and no other block's bytes or scale move."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KV_QUANT_QMAX, QuantPool, write_paged_kv)

    rng = np.random.default_rng(100 + sorted(_WRITE_CASES).index(case))
    tables, start, valid, new = _write_case(case, rng)
    q0 = rng.integers(-127, 128, (12, 2, 4, 8)).astype(np.int8)
    s0 = (rng.random((12, 2)) + 0.5).astype(np.float32)
    want_q, want_s = q0.copy(), s0.copy()
    qmax = np.float32(KV_QUANT_QMAX)
    for b, r, blk, off in _rows_of(tables, start, valid, new, 4):
        row = new[b, :, r, :]
        if off == 0:
            want_s[blk] = np.abs(row).max(axis=-1) / qmax
        safe = np.where(want_s[blk] > 0, want_s[blk], np.float32(1))
        want_q[blk, :, off, :] = np.clip(
            np.round(row / safe[:, None]), -qmax, qmax).astype(np.int8)
    got = write_paged_kv(
        QuantPool(q=jnp.asarray(q0), scale=jnp.asarray(s0)),
        jnp.asarray(new), jnp.asarray(tables), jnp.asarray(start),
        jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got.q)[1:], want_q[1:])
    np.testing.assert_array_equal(np.asarray(got.scale)[1:], want_s[1:])
    assert np.isfinite(np.asarray(got.scale)[0]).all()


# (tables, start, path rows (B, depth), accepted): the tree window's rows sit
# at start + row, the winners move to start + 1 + j
_REMAP_CASES = {
    # a sibling at depth 1, then its line: sources and targets overlap
    "sibling_path": ([[1, 2, 3]], [2], [[2, 4, 5]], [3]),
    # the primary chain moves onto itself; slot 1 accepts nothing
    "primary_and_none": ([[1, 2, 3], [4, 5, 6]], [3, 6],
                         [[1, 2, 3], [2, 3, 5]], [3, 0]),
    # rows move across a block edge, up to the last position in reach
    "block_edge": ([[7, 8, 9], [1, 2, 3]], [7, 0],
                   [[2, 3, 4], [1, 3, 6]], [3, 2]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_REMAP_CASES))
def test_remap_paged_path_matches_row_reference(case, dtype):
    """``remap_paged_path`` against a NumPy move that reads every source
    row before it writes any: accepted rows land at start + 1 + j inside
    the slot's own blocks, bit for bit, and no other allocated block
    moves."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        remap_paged_path)

    tables, start, path, acc = (np.asarray(x, np.int32)
                                for x in _REMAP_CASES[case])
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal((12, 2, 4, 8)), jnp.dtype(dtype))
    before = np.asarray(pool)
    want = before.copy()
    for b in range(len(start)):
        for j in range(int(acc[b])):
            src, dst = int(start[b] + path[b, j]), int(start[b]) + 1 + j
            want[tables[b, dst // 4], :, dst % 4, :] = before[
                tables[b, src // 4], :, src % 4, :]
    got = np.asarray(remap_paged_path(
        pool, jnp.asarray(tables), jnp.asarray(start), jnp.asarray(path),
        jnp.asarray(acc)))
    np.testing.assert_array_equal(got[1:], want[1:])
    assert np.isfinite(got[0].astype(np.float32)).all()


# --------------------------------------------------------------- 2. allocator
def test_block_allocator_contract():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        BlockAllocator)

    a = BlockAllocator(num_blocks=5)
    assert a.capacity == 4                     # block 0 reserved
    first = a.alloc(3)
    assert first is not None and 0 not in first
    assert a.alloc(2) is None                  # exhaustion queues...
    assert a.free_count == 1                   # ...and takes nothing
    rest = a.alloc(1)
    assert 0 not in rest and not (set(first) & set(rest))
    a.free(first)
    with pytest.raises(ValueError, match="double free"):
        a.free(first)
    a.free(rest)
    assert a.free_count == a.capacity


# ------------------------------------------------------------------ 3. engine
@pytest.fixture(scope="module")
def engines():
    """One param set, two layouts: paged (block_size 8, buckets 8/16) and
    ring (same buckets) over the same 32-position, 2-slot cache."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    paged = InferenceEngine(cfg, params, slots=2, max_len=32,
                            prefill_buckets=(8, 16), kv_layout="paged",
                            kv_block_size=8)
    # ring gets a 32 bucket so it can single-shot the prompt the paged
    # engine must chunk; for prompts <= 16 both engines pick the same bucket
    ring = InferenceEngine(cfg, params, slots=2, max_len=32,
                           prefill_buckets=(8, 16, 32), kv_layout="ring")
    return cfg, model, params, paged, ring


def _stream(engine, requests, eos=None):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    engine.reset()
    sched = Scheduler(engine, eos_token_id=eos)
    for r in requests:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


def test_paged_stream_bitmatches_ring(engines):
    """Mixed greedy/sampled workload with slot eviction + refill: token
    streams must be identical across layouts, and every block must come
    home to the allocator afterwards."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    cfg, _, _, paged, ring = engines
    rng = np.random.default_rng(1)
    reqs = [Request(id=f"r{i}",
                    prompt=rng.integers(3, cfg.vocab_size, size=pl).tolist(),
                    max_new_tokens=gen, temperature=t, top_p=0.9, seed=i)
            for i, (pl, gen, t) in enumerate(
                [(6, 8, 0.0), (12, 10, 0.8), (16, 6, 0.0), (9, 12, 0.7)])]
    ring_sched, ring_out = _stream(ring, list(reqs))
    paged_sched, paged_out = _stream(paged, list(reqs))
    assert paged_out == ring_out
    assert len(paged_out) == 4
    # after drain the prefix cache legitimately retains committed prompt
    # blocks (one cache reference each); flushing must return ALL of them
    assert (paged_sched.allocator.used_count
            == paged_sched.prefix_cache.cached_blocks)
    paged_sched.prefix_cache.flush()
    assert paged_sched.allocator.free_count == paged_sched.allocator.capacity
    assert not paged_sched.block_tables.any()


def test_chunked_prefill_logits_bitmatch_single_shot():
    """Model level, eager: feeding a 20-token prompt through the paged cache
    in two chunks (16 then 4) yields BITWISE the same last-chunk logits as
    one single-shot 20-token call, and both equal the uncached forward.

    A bf16 contract, so it asks the helper for bfloat16 (one slot: XLA:CPU
    executes that dot). The chunks are matmuls of other shapes than the
    single shot, so their float32 accumulation order differs (1.8e-6 at
    float32, seen); bf16 products are exact in float32 and every matmul's
    float32 sum is rounded to 8 bits of mantissa, which takes such
    last-place differences away. At float32 only ``allclose`` would hold."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_paged_cache)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg(dtype="bfloat16")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(1, 20)),
                      jnp.int32)
    full = np.asarray(model.apply({"params": params}, ids))

    row = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    cache = init_paged_cache(cfg, 1, 32, 8)
    one_shot, _ = model.apply({"params": params}, ids, cache.k, cache.v,
                              jnp.zeros((1,), jnp.int32), block_tables=row,
                              method="forward_with_cache")
    np.testing.assert_array_equal(np.asarray(one_shot), full)

    cache = init_paged_cache(cfg, 1, 32, 8)
    c1, (k, v) = model.apply({"params": params}, ids[:, :16], cache.k,
                             cache.v, jnp.zeros((1,), jnp.int32),
                             block_tables=row, method="forward_with_cache")
    c2, _ = model.apply({"params": params}, ids[:, 16:], k, v,
                        jnp.full((1,), 16, jnp.int32), block_tables=row,
                        method="forward_with_cache")
    np.testing.assert_array_equal(np.asarray(c1),
                                  np.asarray(one_shot)[:, :16])
    np.testing.assert_array_equal(np.asarray(c2),
                                  np.asarray(one_shot)[:, 16:])


def test_chunked_prefill_stream_matches_ring_single_shot(engines):
    """Engine level, compiled: the paged engine CHUNKS a 20-token prompt
    (largest bucket 16), the ring engine single-shots it through its 32
    bucket — greedy continuations must be token-identical."""
    cfg, _, _, paged, ring = engines
    rng = np.random.default_rng(2)
    prompt = rng.integers(3, cfg.vocab_size, size=20).tolist()
    gen = 6
    zeros2 = np.zeros(2, np.float32)
    ones2 = np.ones(2, np.float32)
    izeros2 = np.zeros(2, np.int32)
    active = np.array([True, False])

    ring.reset()
    ring_got = [ring.prefill(0, prompt)]
    for step in range(1, gen):
        nxt = ring.decode_step(np.array([ring_got[-1], 0], np.int32),
                               active, zeros2, ones2, izeros2,
                               np.full(2, step, np.int32))
        ring_got.append(int(nxt[0]))

    paged.reset()
    row = np.arange(1, paged.max_blocks_per_slot + 1, dtype=np.int32)
    chunks = []
    first = paged.prefill(0, prompt, block_row=row,
                          on_chunk=lambda: chunks.append(1))
    assert len(chunks) == 2            # 16 + 4 (best-fit bucket 8)
    got = [first]
    tables = np.zeros((paged.slots, paged.max_blocks_per_slot), np.int32)
    tables[0] = row
    for step in range(1, gen):
        nxt = paged.decode_step(
            np.array([got[-1], 0], np.int32), active, zeros2, ones2,
            izeros2, np.full(2, step, np.int32), block_tables=tables)
        got.append(int(nxt[0]))
    assert got == ring_got


def test_long_prompt_served_paged_rejected_ring(engines):
    """The capability the pages bought: a prompt longer than the largest
    AOT prefill bucket is served (chunked) under paged, rejected by ring."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    cfg, _, params, paged, _ = engines
    prompt = list(range(3, 3 + 24))  # 24 > paged's largest bucket 16
    paged.reset()
    row = np.arange(1, paged.max_blocks_per_slot + 1, dtype=np.int32)
    assert isinstance(paged.prefill(0, prompt, block_row=row), int)
    small_ring = InferenceEngine(cfg, params, slots=1, max_len=32,
                                 prefill_buckets=(16,), kv_layout="ring")
    with pytest.raises(ValueError, match="outside"):
        small_ring.prefill(0, prompt)


# --------------------------------------------------------------- 4. scheduler
class _FakePagedEngine:
    """Paged-engine façade for scheduler-policy tests (no XLA): echoes a
    deterministic token, honors the chunked-prefill stop_check contract."""

    def __init__(self, slots=4, max_len=32, block_size=8, num_blocks=None,
                 bucket=16):
        self.slots = slots
        self.max_len = max_len
        self.kv_layout = "paged"
        self.block_size = block_size
        self.max_blocks_per_slot = -(-max_len // block_size)
        self.num_blocks = num_blocks or slots * self.max_blocks_per_slot + 1
        self.bucket = bucket

    def prefill(self, slot, token_ids, block_row=None, temperature=0.0,
                top_p=1.0, seed=0, stop_check=None, on_chunk=None):
        n = len(token_ids)
        start = 0
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


def test_admission_queues_on_block_exhaustion():
    """4 free slots but only 4 usable blocks at 2 blocks/request: admission
    is bounded by BLOCKS (2 concurrent), everything still completes."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakePagedEngine(slots=4, max_len=32, block_size=8, num_blocks=5)
    sched = Scheduler(eng)
    for i in range(5):
        sched.submit(Request(id=f"r{i}", prompt=[5] * 8, max_new_tokens=8))
    sched.run()
    assert len(sched.completed) == 5
    assert sched.max_concurrent == 2           # blocks, not slots, bound it
    assert sched.allocator.free_count == sched.allocator.capacity
    assert not sched.block_tables.any()


def test_submit_rejects_request_larger_than_pool():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    sched = Scheduler(_FakePagedEngine(slots=2, max_len=32, block_size=8,
                                       num_blocks=3))
    with pytest.raises(ValueError, match="usable blocks"):
        sched.submit(Request(id="big", prompt=[5] * 20, max_new_tokens=12))
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit(Request(id="huge", prompt=[5] * 30, max_new_tokens=10))


def test_drain_mid_chunked_prefill_reports_unserved():
    """stop_check fires between prefill chunks: the current chunk finishes,
    the request is reported unserved, its blocks come back, admission
    closes — then completed in-flight work still drains."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakePagedEngine(slots=2, max_len=64, block_size=8, bucket=16)
    fired = {"on": False}
    sched = Scheduler(eng, stop_check=lambda: fired["on"])
    sched.submit(Request(id="short", prompt=[5] * 8, max_new_tokens=4))
    sched.step()                               # short admitted, decoding
    fired["on"] = True                         # signal lands mid-queue
    sched.submit(Request(id="long", prompt=[5] * 40, max_new_tokens=8))
    while sched.pending():
        sched.step()
    assert not sched.admission_open
    assert [r.id for r in sched.unserved()] == ["long"]
    assert [c.request_id for c in sched.completed] == ["short"]
    assert sched.allocator.free_count == sched.allocator.capacity
    assert not sched.block_tables.any()
    assert sched.prefill_chunks >= 2           # short's + long's first chunk


def test_paged_metrics_surface():
    """The /metrics gauges the obs satellite added: block gauges move with
    allocation and the chunk counter lands in scheduler metrics()."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

    reg = MetricRegistry()
    eng = _FakePagedEngine(slots=2, max_len=32, block_size=8, bucket=4)
    sched = Scheduler(eng, registry=reg)
    sched.submit(Request(id="r0", prompt=[5] * 10, max_new_tokens=6))
    sched.step()
    text = reg.render()
    assert "ftl_serve_kv_blocks_free" in text
    assert "ftl_serve_kv_block_utilization" in text
    assert "ftl_serve_prefill_chunks_total" in text
    m = sched.metrics()
    assert m["prefill_chunks"] == 3            # 10 tokens / 4-token bucket
    assert m["kv_blocks_total"] == sched.allocator.capacity
    assert m["kv_block_utilization_peak"] > 0


# ---------------------------------------------------------- 5. packed prefill
@pytest.fixture(scope="module")
def packed_engine(engines):
    """Same params as the ``engines`` fixture's paged engine, but compiled
    with the packed (P=2, bucket) prefill programs alongside the
    sequential ladder."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    cfg, _, params, _, _ = engines
    return InferenceEngine(cfg, params, slots=2, max_len=32,
                           prefill_buckets=(8, 16), kv_layout="paged",
                           kv_block_size=8, prefill_batch=2)


def _run_sched(engine, requests, prefill_batch=1):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    engine.reset()
    sched = Scheduler(engine, prefill_batch=prefill_batch)
    for r in requests:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


def test_packed_prefill_streams_bitmatch_sequential(engines, packed_engine,
                                                    monkeypatch):
    """Mixed greedy/sampled workload with multi-chunk prompts and a slot
    turnover: the packed lane's token streams must be BITWISE identical
    to sequential one-prompt-at-a-time prefill (same per-row chunk
    shapes, same gather kernel), with the round/occupancy accounting the
    metrics satellite added."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    cfg, _, _, paged, _ = engines
    rng = np.random.default_rng(3)
    reqs = [Request(id=f"r{i}",
                    prompt=rng.integers(3, cfg.vocab_size, size=pl).tolist(),
                    max_new_tokens=gen, temperature=t, top_p=0.9, seed=i)
            for i, (pl, gen, t) in enumerate(
                [(20, 6, 0.0), (9, 8, 0.8), (24, 5, 0.0), (11, 7, 0.7)])]
    seq_sched, seq_out = _run_sched(paged, list(reqs))
    pak_sched, pak_out = _run_sched(packed_engine, list(reqs),
                                    prefill_batch=2)
    assert pak_out == seq_out
    assert len(pak_out) == 4
    m = pak_sched.metrics()
    # identical chunking discipline: the packed rows walked the same
    # bucket sequence the sequential lane did
    assert m["prefill_chunks"] == seq_sched.metrics()["prefill_chunks"]
    assert m["prefill_packed_rounds"] > 0
    assert m["prefill_packed_rows"] >= m["prefill_packed_rounds"]
    assert 0.0 < m["prefill_packed_occupancy"] <= 1.0
    # the fixture engines read through the gather kernel -> every chunk
    # lands on the gather counter, none on the in-place one
    assert m["prefill_gather_chunks"] == m["prefill_chunks"]
    assert m["prefill_inplace_chunks"] == 0

    # a FULL wave (P = 2 equal prompts of 16 + 8 tokens) fills every row of
    # every packed round: 2 rounds x 2 rows, occupancy exactly 1
    wave = [Request(id=f"w{i}", prompt=[5 + i] * 24, max_new_tokens=2)
            for i in range(2)]
    full, _ = _run_sched(packed_engine, wave, prefill_batch=2)
    fm = full.metrics()
    assert (fm["prefill_packed_rounds"], fm["prefill_packed_rows"]) == (2, 4)
    assert fm["prefill_packed_occupancy"] == 1.0

    # a packed round is bounded (P x bucket positions), so a short request
    # decodes BETWEEN the packed rounds a long prompt still needs
    timeline = []

    def noting(tag, call):
        def spy(*a, **k):
            timeline.append(tag)
            return call(*a, **k)
        return spy

    monkeypatch.setattr(packed_engine, "prefill_packed",
                        noting("P", packed_engine.prefill_packed))
    monkeypatch.setattr(packed_engine, "decode_step",
                        noting("D", packed_engine.decode_step))
    _run_sched(packed_engine,
               [Request(id="short", prompt=[7] * 8, max_new_tokens=6),
                Request(id="long", prompt=[9] * 24, max_new_tokens=2)],
               prefill_batch=2)
    last_p = len(timeline) - 1 - timeline[::-1].index("P")
    assert "D" in timeline[timeline.index("P"):last_p], timeline


def test_drain_mid_packed_prefill_frees_all_rows(packed_engine):
    """The drain signal lands between packed rounds while BOTH slots hold
    half-prefilled rows: every pending row's blocks come back exactly
    once, both requests are reported unserved, and the leak audit stays
    clean."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    packed_engine.reset()
    fired = {"on": False}
    sched = Scheduler(packed_engine, prefill_batch=2,
                      stop_check=lambda: fired["on"])
    for i in range(2):
        sched.submit(Request(id=f"long{i}", prompt=[5 + i] * 24,
                             max_new_tokens=4))
    sched.step()                     # both admitted; round 1 of 2 runs
    assert len(sched._pending_prefill) == 2
    fired["on"] = True               # signal lands between rounds
    while sched.pending():
        sched.step()
    assert not sched.admission_open
    assert sorted(r.id for r in sched.unserved()) == ["long0", "long1"]
    assert sched.completed == []
    assert sched.allocator.free_count == sched.allocator.capacity
    assert not sched.block_tables.any()
    assert sched.audit_block_leaks(strict=True) == []


def test_packed_lane_validates(engines, packed_engine):
    """The lane's mutual exclusions, both layers: engine bounds P by slots
    and requires pages; the scheduler refuses spec mode, engines without
    the packed entry point, and width disagreement with the engine."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    cfg, _, params, _, _ = engines
    with pytest.raises(ValueError, match="prefill_batch"):
        InferenceEngine(cfg, params, slots=2, max_len=32,
                        prefill_buckets=(8,), kv_layout="paged",
                        kv_block_size=8, prefill_batch=3)
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, params, slots=2, max_len=32,
                        prefill_buckets=(8, 16, 32), kv_layout="ring",
                        prefill_batch=2)
    fake = _FakePagedEngine(slots=4)
    with pytest.raises(ValueError, match="prefill_packed"):
        Scheduler(fake, prefill_batch=2)
    fake_spec = _FakePagedEngine(slots=4)
    fake_spec.spec_k = 2
    with pytest.raises(ValueError, match="speculative"):
        Scheduler(fake_spec, prefill_batch=2)
    with pytest.raises(ValueError, match="prefill_batch"):
        Scheduler(packed_engine, prefill_batch=3)  # engine compiled P=2
