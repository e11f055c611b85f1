"""Speculative decoding (inference/ spec mode): five layers of evidence.

1. kernel — ``spec_accept`` degenerates to exact argmax matching for
   greedy rows, and for sampled rows its emitted tokens follow the TARGET
   distribution in closed form (the Leviathan/Chen guarantee) on a
   3-token toy vocab;
2. numerics — ``verify_with_cache``'s chunked scoring agrees with the
   sequential S=1 steps it replaces (argmax + allclose on an fp32 model;
   the engine's AOT verify program micro-steps S=1 shapes precisely so
   this agreement is bitwise in production — engine.py ``_verify_fn``);
3. streams — a greedy speculative stream is BIT-identical to the
   non-speculative paged path across chunked prefill and block-pool
   eviction/refill (slow: builds two real engines);
4. lifecycle — dual-pool admission/rollback/double-free contracts and
   mid-prompt drain exactness, pinned against a fake spec engine;
5. tree — multi-branch rejection matches the target law in closed form,
   scheduler tree rounds refeed/bank/attribute branches correctly and
   drain leak-free, greedy EXACT-mode tree streams (prefix caches on AND
   off, draft mirror included) bit-match non-spec decode, and the
   ``fork_slot`` COW beam primitive honors the allocator contract.

Module scope imports nothing from the package: the collect-only guard at
the bottom asserts NO test module pays the draft path's import cost (or
any inference/ import) at collection time.
"""

import ast
from pathlib import Path

import numpy as np
import pytest

from _tiny import tiny_cfg

REPO = Path(__file__).resolve().parent.parent
CACHE = "/tmp/jax_test_compile_cache"


# ------------------------------------------------------- 1. accept kernel
def test_spec_accept_greedy_is_exact_argmax_matching():
    """With temperature <= 0 both q and p are one-hots: the accept test
    ``u * q(d) < p(d)`` keeps exactly the leading run of draft tokens that
    equal the target argmax, and the bonus/correction token IS the target
    argmax at the first divergence — so greedy needs no randomness and the
    emitted prefix equals what sequential argmax decoding would produce."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.sampler import spec_accept

    rng = np.random.default_rng(0)
    v, k = 7, 3
    for trial in range(50):
        target_logits = rng.normal(size=(k + 1, v)).astype(np.float32)
        draft_tokens = rng.integers(0, v, size=k).astype(np.int32)
        # greedy draft distributions are one-hots at the proposal
        draft_probs = np.eye(v, dtype=np.float32)[draft_tokens]
        out, acc = spec_accept(
            jnp.asarray(draft_tokens), jnp.asarray(draft_probs),
            jnp.asarray(target_logits),
            jax.random.PRNGKey(trial), jnp.float32(0.0), jnp.float32(1.0))
        argmax = target_logits.argmax(axis=-1)
        expect_a = 0
        while expect_a < k and draft_tokens[expect_a] == argmax[expect_a]:
            expect_a += 1
        assert int(acc) == expect_a
        expected = list(draft_tokens[:expect_a]) + [argmax[expect_a]]
        assert np.asarray(out)[: expect_a + 1].tolist() == expected


def test_spec_rejection_sampling_matches_target_distribution():
    """k=1 on a 3-token vocab with draft law q != target law p: across many
    independent rounds the emitted first token must be distributed as p
    EXACTLY (not as q, not as some blend), and the acceptance probability
    equals sum_a min(p_a, q_a) — the closed forms from Leviathan et al.
    2023, Thm 1. Empirical check at ~4 sigma on 8000 trials."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.sampler import spec_accept

    q = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    p = np.array([0.2, 0.5, 0.3], np.float32)
    target_logits = jnp.log(jnp.asarray(p))[None, :].repeat(2, axis=0)
    n = 8000

    def one_round(key):
        kd, ka = jax.random.split(key)
        d = jax.random.categorical(kd, jnp.log(q)).astype(jnp.int32)
        out, acc = spec_accept(d[None], q[None, :], target_logits, ka,
                               jnp.float32(1.0), jnp.float32(1.0))
        return out[0], (acc > 0).astype(jnp.int32)

    keys = jax.random.split(jax.random.PRNGKey(42), n)
    toks, accepted = jax.jit(jax.vmap(one_round))(keys)
    toks, accepted = np.asarray(toks), np.asarray(accepted)

    emp = np.bincount(toks, minlength=3) / n
    se = np.sqrt(p * (1 - p) / n)
    np.testing.assert_allclose(emp, p, atol=float((4 * se).max()))
    accept_rate = accepted.mean()
    expect_accept = float(np.minimum(p, np.asarray(q)).sum())
    se_a = np.sqrt(expect_accept * (1 - expect_accept) / n)
    assert abs(accept_rate - expect_accept) < 4 * se_a


# ---------------------------------------------------- 2. verify-k numerics
def test_verify_chunk_scores_agree_with_sequential_steps():
    """``verify_with_cache`` scores (B, k+1) candidates in one forward; its
    row j must agree with the j-th sequential S=1 ``forward_with_cache``
    step on the same committed prefix — same masked attention, same
    positions. On an fp32 model the two differ only by shape-dependent
    matmul accumulation order, so argmax equality plus allclose pins the
    contract (the engine's AOT verify program micro-steps the S=1 shapes
    exactly, making this agreement bitwise in production)."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_paged_cache)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(3, 64, size=(1, 8)), jnp.int32)
    cand = jnp.asarray(rng.integers(3, 64, size=(1, 3)), jnp.int32)

    bs = 8
    cache = init_paged_cache(cfg, slots=1, max_len=32, block_size=bs)
    tables = jnp.arange(1, 32 // bs + 1, dtype=jnp.int32)[None, :]
    _, (k0, v0) = model.apply(
        {"params": params}, prompt, cache.k, cache.v,
        jnp.zeros((1,), jnp.int32), block_tables=tables,
        method="forward_with_cache")

    offsets = jnp.full((1,), 8, jnp.int32)
    chunk, _ = model.apply(
        {"params": params}, cand, k0, v0, offsets, block_tables=tables,
        method="verify_with_cache")

    ck, cv, rows = k0, v0, []
    for j in range(3):
        step, (ck, cv) = model.apply(
            {"params": params}, cand[:, j:j + 1], ck, cv, offsets + j,
            block_tables=tables, method="forward_with_cache")
        rows.append(np.asarray(step)[:, 0])
    seq_logits = np.stack(rows, axis=1)

    np.testing.assert_allclose(np.asarray(chunk), seq_logits,
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(chunk).argmax(-1) == seq_logits.argmax(-1)).all()


# ----------------------------------------------------- 3. stream equality
@pytest.mark.slow
def test_greedy_spec_stream_bitmatches_nonspec_paged():
    """End to end: the same request set (chunked long prompts, more
    requests than the block pools admit at once, so slots evict and refill
    into reused blocks) generates BIT-identical greedy token streams with
    and without speculation — the tentpole invariant. The draft is an
    independently-initialized model, so acceptance is poor: exactness must
    come from the verify/commit path, not from a lucky good draft."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    draft_params = Transformer(cfg).init(
        jax.random.PRNGKey(9),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    rng = np.random.default_rng(5)
    lens = [20, 9, 36, 13, 20, 5]  # 36 and 20 exceed the 16 bucket: chunked
    reqs = [(rng.integers(3, 64, size=n).tolist(), 10) for n in lens]
    kw = dict(slots=2, max_len=48, prefill_buckets=(16,), kv_layout="paged",
              kv_block_size=16, kv_num_blocks=7)  # 6 usable: 2 concurrent

    def streams(engine):
        sched = Scheduler(engine, eos_token_id=None)
        for i, (prompt, gen) in enumerate(reqs):
            sched.submit(Request(id=f"r{i}", prompt=prompt,
                                 max_new_tokens=gen))
        done = sched.run()
        assert len(done) == len(reqs)
        return {c.request_id: c.tokens for c in done}, sched

    base = InferenceEngine(cfg, params, **kw)
    want, _ = streams(base)
    del base

    spec = InferenceEngine(cfg, params, draft_cfg=cfg,
                           draft_params=draft_params, spec_k=2,
                           draft_num_blocks=7, **kw)
    got, sched = streams(spec)
    assert got == want
    # both pools fully drained back to the free lists via a prefix-cache
    # flush each: committed prompt blocks stay cache-held after drain in
    # BOTH pools now (the draft runs a mirror of the target's radix tree)
    assert sched.allocator.used_count == sched.prefix_cache.cached_blocks
    sched.prefix_cache.flush()
    assert sched.allocator.free_count == sched.allocator.capacity
    assert (sched.draft_allocator.used_count
            == sched.draft_prefix_cache.cached_blocks)
    sched.draft_prefix_cache.flush()
    assert sched.draft_allocator.free_count == sched.draft_allocator.capacity
    m = sched.metrics()
    assert m["spec_rounds"] > 0 and m["spec_draft_tokens"] > 0


# ------------------------------------------------ 4. dual-pool lifecycle
class _FakeSpecEngine:
    """Host-side double of the spec engine: chunked prefill that consults
    ``stop_check`` between chunks, and accept-all spec rounds. Lets the
    scheduler's dual-pool bookkeeping be pinned without any compiles."""

    kv_layout = "paged"

    def __init__(self, slots=2, block_size=4, num_blocks=13,
                 draft_num_blocks=13, spec_k=2, max_len=32):
        self.slots, self.block_size = slots, block_size
        self.num_blocks, self.draft_num_blocks = num_blocks, draft_num_blocks
        self.spec_k, self.max_len = spec_k, max_len
        self.max_blocks_per_slot = -(-max_len // block_size)
        self.prefill_chunk = 4

    def prefill(self, slot, prompt, block_row=None, draft_block_row=None,
                temperature=0.0, top_p=1.0, seed=0, stop_check=None,
                on_chunk=None):
        start = 0
        while start < len(prompt):
            if on_chunk is not None:
                on_chunk()
            start += self.prefill_chunk
            if start < len(prompt) and stop_check is not None and stop_check():
                return None  # drain fired between chunks
        return 1

    def spec_round(self, tokens, lengths, active, temperature, top_p, seeds,
                   steps, block_tables=None, draft_block_tables=None):
        out = np.full((self.slots, self.spec_k + 1), 2, np.int32)
        acc = np.full((self.slots,), self.spec_k, np.int32)
        return out, acc


def test_mid_prompt_drain_frees_both_pools_and_reports_unserved():
    """A drain signal landing BETWEEN prefill chunks must abort the
    admission, free the target AND draft blocks it grabbed, report the
    request unserved, and let already-active requests run to completion —
    the signal-drain exactness contract extended to the dual-pool mode."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeSpecEngine(slots=2)
    chunks = {"n": 0}
    sched = Scheduler(eng, eos_token_id=None,
                      stop_check=lambda: chunks["n"] >= 2)
    orig = sched._count_chunk

    def counting():
        chunks["n"] += 1
        orig()

    sched._count_chunk = counting
    sched.submit(Request(id="short", prompt=[1] * 4, max_new_tokens=6))
    sched.submit(Request(id="long", prompt=[1] * 12, max_new_tokens=6))
    done = sched.run()

    assert [c.request_id for c in done] == ["short"]
    assert [r.id for r in sched.unserved()] == ["long"]
    assert not sched.admission_open
    # every block of both pools is back on the free lists; the long
    # request's partial grab did not leak
    assert sched.allocator.free_count == sched.allocator.capacity
    assert sched.draft_allocator.free_count == sched.draft_allocator.capacity
    assert (sched.block_tables == 0).all()
    assert (sched.draft_block_tables == 0).all()
    # the accept-all fake banks k+1 tokens per round: 2 rounds for 6
    sc = done[0]
    assert sc.spec_proposed > 0 and sc.spec_emitted_not_proposed > 0


def test_draft_pool_shortage_rolls_back_target_grab():
    """Combined-footprint admission: when the draft pool cannot cover the
    head of the queue, the target blocks already grabbed for it must be
    returned immediately (not stranded until the request eventually
    admits), and the request waits FIFO until BOTH pools can cover it."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    # target pool covers two 3-block requests, draft pool only one
    eng = _FakeSpecEngine(slots=2, num_blocks=13, draft_num_blocks=4,
                          max_len=12)
    sched = Scheduler(eng, eos_token_id=None)
    sched.submit(Request(id="a", prompt=[1] * 6, max_new_tokens=6))
    sched.submit(Request(id="b", prompt=[1] * 6, max_new_tokens=6))
    sched.step()
    assert len(sched.active) == 1
    # b's aborted admission left NO target blocks allocated beyond a's
    assert (sched.allocator.used_count
            == sched._blocks_needed(sched.active[0].request))
    done = sched.run()
    assert {c.request_id for c in done} == {"a", "b"}
    assert sched.allocator.free_count == sched.allocator.capacity
    assert sched.draft_allocator.free_count == sched.draft_allocator.capacity


def test_block_allocator_double_free_raises():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        BlockAllocator)

    alloc = BlockAllocator(num_blocks=5)
    blocks = alloc.alloc(3)
    assert blocks is not None and alloc.free_count == 1
    assert alloc.alloc(2) is None  # exhaustion queues, never crashes
    alloc.free(blocks)
    assert alloc.free_count == alloc.capacity
    with pytest.raises(ValueError, match="double free"):
        alloc.free(blocks)


# ------------------------------------------------- 5. tree speculation
def test_tree_accept_multibranch_matches_target_distribution():
    """Multi-branch rejection on a 3-token vocab, shape (2,): the primary
    child is sampled from its draft law q, the sibling is a deterministic
    pick (given the primary) whose honest proposal law is therefore a
    point mass — exactly the one-hot q row the engine writes for
    siblings. Every branch trial is a valid rejection-sampling step, so
    the FIRST emitted token's marginal must be the target p EXACTLY, and
    the acceptance rate has a closed form strictly above linear
    speculation's sum(min(p, q)). Checked at ~4 sigma on 8000 rounds.

    Closed form for this construction (p=[.2,.5,.3], q=[.5,.3,.2],
    sibling = primary+1 mod 3): linear acceptance sum(min(p,q)) = 0.7;
    only primary 0 can be rejected (mass .5 * .6 = .3), the residual is
    [0, 2/3, 1/3] and its sibling is token 1 — accepted with prob 2/3 —
    so tree acceptance = 0.7 + 0.3 * 2/3 = 0.9."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.sampler import tree_accept

    q = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    p = np.array([0.2, 0.5, 0.3], np.float32)
    child = jnp.asarray([[1, 2], [-1, -1], [-1, -1]], jnp.int32)
    logits = jnp.log(jnp.asarray(p))[None, :].repeat(3, axis=0)
    n = 8000

    def one_round(key):
        kd, ka = jax.random.split(key)
        t0 = jax.random.categorical(kd, jnp.log(q)).astype(jnp.int32)
        sib = (t0 + 1) % 3
        toks = jnp.stack([jnp.int32(0), t0, sib])
        probs = jnp.stack([q, q, jax.nn.one_hot(sib, 3)])
        out, path, a = tree_accept(toks, probs, logits, ka,
                                   jnp.float32(1.0), jnp.float32(1.0),
                                   child, 1)
        return out[0], a

    keys = jax.random.split(jax.random.PRNGKey(7), n)
    toks, acc = jax.jit(jax.vmap(one_round))(keys)
    toks, acc = np.asarray(toks), np.asarray(acc)

    emp = np.bincount(toks, minlength=3) / n
    se = np.sqrt(p * (1 - p) / n)
    np.testing.assert_allclose(emp, p, atol=float((4 * se).max()))
    expect_accept = 0.9
    se_a = np.sqrt(expect_accept * (1 - expect_accept) / n)
    assert abs(acc.mean() - expect_accept) < 4 * se_a


def test_tree_round_banking_attributes_branches_and_drains_clean():
    """Scheduler tree rounds against a host-side double: refeed windows
    carry exactly the tokens the previous round banked (prefill = round 0
    with one token), acceptance lands in the spec counters under the tree
    budget, off-primary path rows feed the branch-utilization gauge, and
    a mid-stream drain leaves both pools leak-free (strict leak guard
    runs inside Scheduler.run)."""
    from fault_tolerant_llm_training_tpu.inference.engine import TreeShape
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    shape = TreeShape((2, 1))

    class _FakeTreeEngine(_FakeSpecEngine):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.spec_tree = shape
            self._tree_refeed = shape.depth + 1
            self.seen_refeed = []

        def spec_tree_round(self, refeed, refeed_len, lengths, active,
                            temperature, top_p, seeds, rounds,
                            block_tables=None, draft_block_tables=None,
                            shape=None):
            s = self.spec_tree
            for i in range(self.slots):
                if active[i]:
                    self.seen_refeed.append(
                        list(refeed[i, :refeed_len[i]]))
            out = np.full((self.slots, s.depth + 1), 2, np.int32)
            acc = np.full((self.slots,), s.depth, np.int32)
            path = np.zeros((self.slots, s.depth), np.int32)
            path[:, 0] = s.primary_rows[0] + 1  # accepted SIBLING at L1
            path[:, 1] = s.primary_rows[1]
            return out, acc, path

    eng = _FakeTreeEngine(slots=2)
    sched = Scheduler(eng, eos_token_id=None)
    sched.submit(Request(id="a", prompt=[1] * 4, max_new_tokens=7))
    sched.submit(Request(id="b", prompt=[1] * 4, max_new_tokens=5))
    done = sched.run()
    assert {c.request_id for c in done} == {"a", "b"}
    # round 1's refeed is the prefill token alone; every later round
    # refeeds the 3 tokens (accepted pair + bonus) banked before it
    assert eng.seen_refeed[:2] == [[1], [1]]
    assert all(r == [2, 2, 2] for r in eng.seen_refeed[2:])
    m = sched.metrics()
    assert m["spec_tree_rounds"] > 0
    assert m["spec_tree_nodes"] > 0
    assert m["spec_tree_nodes"] % shape.size == 0
    # each round accepts one off-primary and one primary node
    assert m["spec_tree_branch_utilization"] == 0.5
    assert m["spec_draft_tokens"] % (shape.size - 1) == 0
    assert sched.allocator.free_count == sched.allocator.capacity
    assert sched.draft_allocator.free_count == sched.draft_allocator.capacity

    # mid-stream drain: stop after the first tree round — active slots
    # finish, the queued request is reported unserved, leak guard clean
    eng2 = _FakeTreeEngine(slots=1)
    sched2 = Scheduler(eng2, eos_token_id=None)
    for i in range(3):
        sched2.submit(Request(id=f"r{i}", prompt=[1] * 4, max_new_tokens=9))
    sched2.run(stop=lambda: sched2.iterations >= 1)  # strict guard inside
    assert len(sched2.unserved()) >= 1
    assert not sched2.admission_open
    assert sched2.allocator.free_count == sched2.allocator.capacity
    assert (sched2.draft_allocator.free_count
            == sched2.draft_allocator.capacity)


@pytest.mark.slow
def test_greedy_tree_spec_stream_bitmatches_nonspec_paged():
    """Tree tentpole end to end: greedy EXACT-mode tree streams are
    BIT-identical to non-speculative paged decode across chunked prefill
    and block-pool eviction/refill, cache-on AND cache-off — the repeated
    prompt additionally pins the satellite contract that prefix-cache
    hits (including the DRAFT-pool mirror's) leave spec streams
    unchanged. The draft is independently initialized, so exactness must
    come from the verify/commit path, not draft quality."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    draft_params = Transformer(cfg).init(
        jax.random.PRNGKey(9),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    rng = np.random.default_rng(5)
    shared = rng.integers(3, 64, size=20).tolist()
    reqs = [(shared, 10), (shared, 8)]  # adjacent duplicates: cache hits
    for n in (9, 36, 13, 5):            # 36 exceeds the 16 bucket: chunked
        reqs.append((rng.integers(3, 64, size=n).tolist(), 10))
    kw = dict(slots=2, max_len=48, prefill_buckets=(16,), kv_layout="paged",
              kv_block_size=16, kv_num_blocks=7)  # 6 usable: evict/refill

    def streams(engine):
        sched = Scheduler(engine, eos_token_id=None)
        for i, (prompt, gen) in enumerate(reqs):
            sched.submit(Request(id=f"r{i}", prompt=prompt,
                                 max_new_tokens=gen))
        done = sched.run()
        assert len(done) == len(reqs)
        return {c.request_id: c.tokens for c in done}, sched

    base = InferenceEngine(cfg, params, **kw)
    want, _ = streams(base)
    del base

    spec_kw = dict(draft_cfg=cfg, draft_params=draft_params, spec_k=3,
                   spec_tree="2,1,1", draft_num_blocks=7)
    tree = InferenceEngine(cfg, params, **spec_kw, **kw)
    got, sched = streams(tree)
    assert got == want
    m = sched.metrics()
    assert m["spec_tree_rounds"] > 0 and m["spec_tree_nodes"] > 0
    # the adjacent duplicate prompt hit BOTH radix trees: the draft
    # mirror absorbed at least its one fully-committed block
    assert m["draft_prefix_hit_tokens"] >= 16
    assert m["prefix_hit_tokens"] >= 16
    del tree

    off = InferenceEngine(cfg, params, prefix_cache=False, **spec_kw, **kw)
    got_off, sched_off = streams(off)
    assert got_off == want
    assert "draft_prefix_hit_rate" not in sched_off.metrics()


@pytest.mark.slow
def test_fork_slot_cow_beam_contract():
    """COW beam fork over the paged substrate: ``engine.fork_slot``
    aliases full shared blocks (refcount 2 — the prefix cache's sharing
    contract), duplicates only the partial boundary block into a fresh
    allocation, both beams decode independently afterwards, and each row
    frees through the uniform allocator path exactly once — the second
    free of the same row raises. Exhaustion acquires nothing."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        BlockAllocator)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    )["params"]
    eng = InferenceEngine(cfg, params, slots=2, max_len=32,
                          prefill_buckets=(16,), kv_layout="paged",
                          kv_block_size=8, prefix_cache=False)
    alloc = BlockAllocator(eng.num_blocks)
    rng = np.random.default_rng(3)
    prompt = rng.integers(3, 64, size=12).tolist()  # 1.5 blocks committed
    src_blocks = alloc.alloc(3)
    src_row = np.zeros((eng.max_blocks_per_slot,), np.int32)
    src_row[:3] = src_blocks
    first = eng.prefill(0, prompt, block_row=src_row, seed=1)

    dst_row = eng.fork_slot(0, 1, length=12, src_row=src_row,
                            allocator=alloc)
    assert dst_row is not None
    # full block aliased (refcount 2), boundary block freshly private
    assert dst_row[0] == src_row[0] and alloc.refcount(src_row[0]) == 2
    assert dst_row[1] != src_row[1] and alloc.refcount(dst_row[1]) == 1
    assert int(np.asarray(eng.cache.lengths)[1]) == 12

    # both beams decode through their own tables (shared prefix read-only)
    tables = np.stack([src_row, dst_row])
    toks = np.array([first, first], np.int32)
    for i in range(3):
        toks = eng.decode_step(
            toks, np.array([True, True]),
            np.array([0.9, 0.9], np.float32), np.ones(2, np.float32),
            np.array([1, 2], np.int32),
            np.full(2, 12 + i, np.int32), block_tables=tables)

    # exhaustion acquires nothing: drain the pool, then fork at a
    # non-aligned length must return None without touching refcounts
    rest = alloc.alloc(alloc.free_count)
    used_before = alloc.used_count
    assert eng.fork_slot(0, 1, length=12, src_row=src_row,
                         allocator=alloc) is None
    assert alloc.used_count == used_before
    alloc.free(rest)

    # uniform free path: each row exactly once; the second free raises
    dst_blocks = [int(b) for b in dst_row[:2]]
    alloc.free(dst_blocks)
    assert alloc.refcount(src_row[0]) == 1
    alloc.free(src_blocks)
    assert alloc.free_count == alloc.capacity
    with pytest.raises(ValueError, match="double free"):
        alloc.free(dst_blocks)


# ------------------------------------------------- 6. collect-only guard
def test_no_test_module_imports_inference_at_module_scope():
    """Collecting the test suite must not import the inference package
    (and with it jax program-building code): every test imports it inside
    the test function. Walks only module-scope statements — imports inside
    functions are the sanctioned pattern."""
    offenders = []
    for path in sorted((REPO / "tests").glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        stack = list(tree.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.If, ast.Try)):
                stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            for name in names:
                if name.startswith("fault_tolerant_llm_training_tpu"
                                   ".inference"):
                    offenders.append(f"{path.name}: {name}")
    assert not offenders, (
        "module-scope inference/ imports break collect-time isolation: "
        f"{offenders}")
