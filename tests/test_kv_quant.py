"""Quantized serving: int8 paged KV pools + quantize-at-publish weights.

Layers, cheapest first:

1. numerics — symmetric per-block quantization round-trips inside the
   half-scale error bound, zero rows exactly;
2. geometry — the int8 mode halves the per-block byte cost (the capacity
   receipt the bench pins at fleet scale) and the scale pool rides every
   lifecycle primitive: offset-0 writes own their block's scale, COW
   copies carry scales bitwise, export/import round-trips q AND scale;
3. integrity — the block-artifact reject matrix holds with scale
   segments in the payload, and a bf16 artifact can never be imported
   into an int8 pool (dtype is part of the wire geometry);
4. engine — ``kv_dtype`` validation, gather-vs-pallas stream equality,
   and the within-dtype bit-exactness contracts (exact spec-verify,
   spill/restore) asserted unchanged under int8 KV;
5. deploy — ``--weights-dtype int8``: the quantized artifact publishes
   with its own CRC manifest, hot-reloads without touching the
   full-precision checkpoint, and a corrupt or step-mismatched artifact
   is rejected while serving continues.
"""

import os
from pathlib import Path

import numpy as np
import pytest

from _tiny import tiny_cfg

REPO = Path(__file__).resolve().parent.parent
CACHE = "/tmp/jax_test_compile_cache"


# ------------------------------------------------------------- 1. numerics
def test_quantize_rows_roundtrip_error_bound():
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KV_QUANT_QMAX, quantize_rows)

    rng = np.random.default_rng(0)
    rows = rng.standard_normal((32, 4, 16)).astype(np.float32)
    scale = np.abs(rows).max(axis=-1) / KV_QUANT_QMAX      # (R, K)
    q = np.asarray(quantize_rows(jnp.asarray(rows), jnp.asarray(scale)))
    assert q.dtype == np.int8 and np.abs(q).max() <= KV_QUANT_QMAX
    deq = q.astype(np.float32) * scale[:, :, None]
    # round-to-nearest at the row's own amax scale: error <= scale/2
    assert (np.abs(deq - rows) <= scale[:, :, None] * 0.5 + 1e-7).all()

    # zero rows (and their zero scales) round-trip exactly
    zq = np.asarray(quantize_rows(jnp.zeros((2, 4, 16)),
                                  jnp.zeros((2, 4))))
    assert (zq == 0).all()


def test_int8_pool_halves_block_bytes():
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        QuantPool, bf16_block_bytes, block_bytes, block_layout,
        init_paged_cache)

    # bfloat16: the capacity receipt is int8 against a 2-byte pool
    cfg = tiny_cfg(dtype="bfloat16")
    import jax.numpy as jnp

    bf16 = init_paged_cache(cfg, slots=2, max_len=32, block_size=8)
    int8 = init_paged_cache(cfg, slots=2, max_len=32, block_size=8,
                            dtype=jnp.int8)
    assert all(isinstance(p, QuantPool) for p in int8.k + int8.v)
    assert int8.num_blocks == bf16.num_blocks
    assert int8.block_size == bf16.block_size

    # the parallel scale pool appears in the wire layout...
    fields = [str(seg["field"]) for seg in block_layout(int8)]
    assert any(f.endswith("_scale") for f in fields)
    assert not any(f.endswith("_scale")
                   for f in (str(s["field"]) for s in block_layout(bf16)))
    # ...and the capacity receipt holds: >= 1.9x blocks at a byte budget
    assert bf16_block_bytes(int8) == block_bytes(bf16)
    ratio = block_bytes(bf16) / block_bytes(int8)
    assert ratio >= 1.9, f"int8 block only {ratio:.2f}x smaller"


# ------------------------------------------------------ 2. scale lifecycle
def test_scale_set_at_offset0_and_kept_at_higher_offsets():
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KV_QUANT_QMAX, init_paged_cache, write_paged_kv)

    cfg = tiny_cfg()
    cache = init_paged_cache(cfg, slots=1, max_len=32, block_size=8,
                             dtype=jnp.int8)
    pool = cache.k[0]
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    rng = np.random.default_rng(1)
    r0 = rng.standard_normal((1, cfg.kv_heads, 1, cfg.head_dim)) * 2.0
    pool = write_paged_kv(pool, jnp.asarray(r0, jnp.float32), tables,
                          jnp.asarray([0], jnp.int32),
                          jnp.ones((1, 1), bool))
    want = np.abs(r0[0, :, 0, :]).max(axis=-1) / KV_QUANT_QMAX
    np.testing.assert_allclose(np.asarray(pool.scale)[1], want, rtol=1e-6)

    # a LOUDER row at offset 1 quantizes at the existing scale (clipped),
    # never rewrites it — the no-requantization invariant
    scale_before = np.asarray(pool.scale).copy()
    r1 = rng.standard_normal((1, cfg.kv_heads, 1, cfg.head_dim)) * 50.0
    pool = write_paged_kv(pool, jnp.asarray(r1, jnp.float32), tables,
                          jnp.asarray([1], jnp.int32),
                          jnp.ones((1, 1), bool))
    np.testing.assert_array_equal(np.asarray(pool.scale)[1:],
                                  scale_before[1:])
    assert np.abs(np.asarray(pool.q)[1, :, 1, :]).max() == KV_QUANT_QMAX


def test_cow_copy_carries_scale_bitwise():
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        QuantPool, copy_kv_block, init_paged_cache)

    cfg = tiny_cfg()
    cache = init_paged_cache(cfg, slots=1, max_len=32, block_size=8,
                             dtype=jnp.int8)
    rng = np.random.default_rng(2)
    pool = QuantPool(
        q=jnp.asarray(rng.integers(-127, 128, cache.k[0].q.shape),
                      jnp.int8),
        scale=jnp.asarray(rng.random(cache.k[0].scale.shape),
                          jnp.float32))
    out = copy_kv_block(pool, jnp.asarray(2), jnp.asarray(4))
    np.testing.assert_array_equal(np.asarray(out.q[4]),
                                  np.asarray(pool.q[2]))
    np.testing.assert_array_equal(np.asarray(out.scale[4]),
                                  np.asarray(pool.scale[2]))
    np.testing.assert_array_equal(np.asarray(out.q[3]),
                                  np.asarray(pool.q[3]))


# ------------------------------------------------- 3. artifact + integrity
def _filled_int8_cache(cfg, seed=0, slots=2, max_len=32, block_size=8):
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        QuantPool, init_paged_cache)

    cache = init_paged_cache(cfg, slots=slots, max_len=max_len,
                             block_size=block_size, dtype=jnp.int8)
    rng = np.random.default_rng(seed)

    def fill(p):
        return QuantPool(
            q=jnp.asarray(rng.integers(-127, 128, p.q.shape), jnp.int8),
            scale=jnp.asarray(rng.random(p.scale.shape), jnp.float32))

    return cache.replace(k=tuple(fill(p) for p in cache.k),
                         v=tuple(fill(p) for p in cache.v))


def test_export_import_roundtrips_q_and_scale(tmp_path):
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        export_blocks, import_blocks, init_paged_cache,
        verify_block_artifact)

    cfg = tiny_cfg()
    cache = _filled_int8_cache(cfg)
    d = str(tmp_path / "art")
    man = export_blocks(cache, [3, 1, 2], d, length=17,
                        meta={"request_id": "q0"})
    assert man["geometry"]["dtype"] == "int8"
    assert verify_block_artifact(d)["length"] == 17

    fresh = init_paged_cache(cfg, slots=2, max_len=32, block_size=8,
                             dtype=jnp.int8)
    out, _ = import_blocks(fresh, d, [5, 6, 7])
    for l in range(len(cache.k)):
        for src, dst in ((3, 5), (1, 6), (2, 7)):
            for pools in ((cache.k, out.k), (cache.v, out.v)):
                np.testing.assert_array_equal(
                    np.asarray(pools[1][l].q[dst]),
                    np.asarray(pools[0][l].q[src]))
                np.testing.assert_array_equal(
                    np.asarray(pools[1][l].scale[dst]),
                    np.asarray(pools[0][l].scale[src]))
        np.testing.assert_array_equal(
            np.asarray(out.k[l].q[4]),
            np.zeros_like(np.asarray(out.k[l].q[4])))


def test_import_reject_matrix_int8(tmp_path):
    """The 6-way reject matrix (flipped byte, truncated payload, missing
    payload, torn manifest, geometry mismatch, dest-count bug) holds with
    scale segments in the payload — and nothing lands on device before
    verification completes."""
    import json

    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        BLOCK_MANIFEST_NAME, KVBlockIntegrityError, export_blocks,
        import_blocks, init_paged_cache)

    cfg = tiny_cfg()
    cache = _filled_int8_cache(cfg)
    fresh = init_paged_cache(cfg, slots=2, max_len=32, block_size=8,
                             dtype=jnp.int8)

    def fresh_artifact(name):
        d = str(tmp_path / name)
        export_blocks(cache, [3, 1], d, length=9)
        return d

    d = fresh_artifact("flip")
    p = os.path.join(d, "block_00001.bin")
    raw = bytearray(open(p, "rb").read())
    raw[7] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    with pytest.raises(KVBlockIntegrityError, match="CRC"):
        import_blocks(fresh, d, [5, 6])
    for l in range(len(fresh.k)):
        np.testing.assert_array_equal(
            np.asarray(fresh.k[l].q[5]),
            np.zeros_like(np.asarray(fresh.k[l].q[5])))

    d = fresh_artifact("trunc")
    p = os.path.join(d, "block_00000.bin")
    open(p, "wb").write(open(p, "rb").read()[:-3])
    with pytest.raises(KVBlockIntegrityError, match="size"):
        import_blocks(fresh, d, [5, 6])

    d = fresh_artifact("gone")
    os.unlink(os.path.join(d, "block_00001.bin"))
    with pytest.raises(KVBlockIntegrityError, match="missing"):
        import_blocks(fresh, d, [5, 6])

    d = fresh_artifact("torn")
    man_path = os.path.join(d, BLOCK_MANIFEST_NAME)
    man = json.load(open(man_path))
    man["files"].popitem()
    json.dump(man, open(man_path, "w"))
    with pytest.raises(KVBlockIntegrityError, match="torn"):
        import_blocks(fresh, d, [5, 6])

    # geometry: same dtype, different block size
    d = fresh_artifact("geom")
    other = init_paged_cache(cfg, slots=2, max_len=32, block_size=16,
                             dtype=jnp.int8)
    with pytest.raises(KVBlockIntegrityError, match="geometry"):
        import_blocks(other, d, [1, 2])

    d = fresh_artifact("count")
    with pytest.raises(ValueError):
        import_blocks(fresh, d, [5])


def test_mixed_dtype_import_rejected_both_ways(tmp_path):
    """dtype is wire geometry: a bf16 artifact can never scatter into an
    int8 pool (or vice versa) — the fleet's mixed-dtype-host guard."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KVBlockIntegrityError, export_blocks, import_blocks,
        init_paged_cache)

    cfg = tiny_cfg()
    int8_cache = _filled_int8_cache(cfg)
    bf16_cache = init_paged_cache(cfg, slots=2, max_len=32, block_size=8)

    d8 = str(tmp_path / "int8")
    export_blocks(int8_cache, [1, 2], d8, length=9)
    with pytest.raises(KVBlockIntegrityError, match="geometry"):
        import_blocks(bf16_cache, d8, [1, 2])

    rng = np.random.default_rng(3)
    bf16_full = bf16_cache.replace(
        k=tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                for a in bf16_cache.k),
        v=tuple(jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                for a in bf16_cache.v))
    d16 = str(tmp_path / "bf16")
    export_blocks(bf16_full, [1, 2], d16, length=9)
    fresh8 = init_paged_cache(cfg, slots=2, max_len=32, block_size=8,
                              dtype=jnp.int8)
    with pytest.raises(KVBlockIntegrityError, match="geometry"):
        import_blocks(fresh8, d16, [1, 2])


# ----------------------------------------------------------- 4. the engine
def _init_params(cfg, seed=0):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    return Transformer(cfg).init(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]


def _streams(engine, reqs):
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    sched = Scheduler(engine, eos_token_id=None)
    for i, (prompt, gen, kw) in enumerate(reqs):
        sched.submit(Request(id=f"r{i}", prompt=list(prompt),
                             max_new_tokens=gen, **kw))
    done = sched.run()
    assert len(done) == len(reqs)
    return {c.request_id: c.tokens for c in done}, sched


def test_engine_kv_dtype_validation():
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    cfg = tiny_cfg()
    params = _init_params(cfg)
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        InferenceEngine(cfg, params, slots=1, max_len=32, kv_dtype="fp8")
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, params, slots=1, max_len=32,
                        kv_layout="ring", kv_dtype="int8")
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="conflicts"):
        InferenceEngine(cfg, params, slots=1, max_len=32,
                        kv_layout="paged", kv_block_size=8,
                        kv_dtype="int8", cache_dtype=jnp.float32)


def test_int8_streams_deterministic_and_burst_bitmatches_per_token():
    """Within-dtype, within-kernel bit-exactness under int8, for BOTH the
    gather oracle and the fused-dequant pallas kernels: streams are
    deterministic across reset(), and burst decode bit-matches per-token
    decode. (Cross-kernel greedy agreement is NOT a contract in int8 mode
    — the oracle dequantizes through bf16 while the fused kernels keep
    the fp32 dequant in-register, so a near-tie argmax may flip; the
    kernel parity check bounds that gap numerically.)"""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.kv_cache import QuantPool
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params = _init_params(cfg)
    rng = np.random.default_rng(5)
    reqs = [Request(id="g", prompt=rng.integers(3, 64, size=12).tolist(),
                    max_new_tokens=8),
            Request(id="s", prompt=rng.integers(3, 64, size=9).tolist(),
                    max_new_tokens=8, temperature=0.8, top_p=0.9, seed=7)]
    kw = dict(slots=2, max_len=32, prefill_buckets=(16,),
              kv_layout="paged", kv_block_size=8, kv_dtype="int8")

    def stream(engine, burst):
        engine.reset()
        sched = Scheduler(engine, eos_token_id=None, decode_burst=burst)
        for r in reqs:
            sched.submit(Request(id=r.id, prompt=list(r.prompt),
                                 max_new_tokens=r.max_new_tokens,
                                 temperature=r.temperature, top_p=r.top_p,
                                 seed=r.seed))
        sched.run()
        return {c.request_id: c.tokens for c in sched.completed}

    for impl in ("gather", "pallas"):
        engine = InferenceEngine(cfg, params, paged_kernel=impl, **kw)
        assert engine.kv_dtype == "int8"
        assert all(isinstance(p, QuantPool) for p in engine.cache.k)
        seq = stream(engine, burst=1)
        assert all(isinstance(p, QuantPool) for p in engine.cache.k), (
            "reset() lost the QuantPool mode")
        assert stream(engine, burst=1) == seq, (
            f"{impl}: int8 decode not deterministic across reset")
        assert stream(engine, burst=4) == seq, (
            f"{impl}: int8 burst decode diverged from per-token")
        del engine


def test_int8_fused_sampler_bitmatches_host_sampler():
    """The fused-sampling contract under int8: sampling inside the fused
    pallas decode program emits the same stream as syncing the logits
    plane and sampling on host."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.sampler import (
        sample_slot_tokens)

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params = _init_params(cfg)
    eng = InferenceEngine(cfg, params, slots=2, max_len=32,
                          prefill_buckets=(8, 16), kv_block_size=8,
                          paged_kernel="pallas", kv_dtype="int8")
    rng = np.random.default_rng(12)
    prompts = [rng.integers(3, cfg.vocab_size, size=n).tolist()
               for n in (6, 11)]
    rows = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    temperature = np.array([0.0, 0.8], np.float32)
    top_p = np.array([1.0, 0.9], np.float32)
    seeds = np.array([0, 123], np.int32)
    active = np.array([True, True])

    def run(fused):
        eng.reset()
        toks = np.array([eng.prefill(s, prompts[s], block_row=rows[s],
                                     temperature=float(temperature[s]),
                                     top_p=float(top_p[s]),
                                     seed=int(seeds[s]))
                         for s in (0, 1)], np.int32)
        stream = [toks.copy()]
        for step in range(1, 7):
            steps = np.full(2, step, np.int32)
            if fused:
                toks = eng.decode_step(toks, active, temperature, top_p,
                                       seeds, steps, block_tables=rows)
            else:
                logits = eng.decode_logits(toks, active, block_tables=rows)
                toks = np.asarray(sample_slot_tokens(
                    logits, seeds, steps, temperature, top_p, eng.top_k))
            stream.append(np.asarray(toks).copy())
        return np.stack(stream)

    np.testing.assert_array_equal(run(fused=True), run(fused=False))


def test_greedy_spec_stream_bitmatches_nonspec_under_int8():
    """The exact spec-verify contract survives quantization: with BOTH
    pools int8 (target and draft share cache_dtype), greedy spec streams
    bit-match plain int8 decode — rejected speculative rows cannot
    disturb a committed block's scale (the offset-0 ownership
    invariant)."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params = _init_params(cfg, seed=0)
    draft_params = _init_params(cfg, seed=9)
    rng = np.random.default_rng(6)
    reqs = [(rng.integers(3, 64, size=n).tolist(), 8, {})
            for n in (20, 9, 13)]
    kw = dict(slots=2, max_len=48, prefill_buckets=(16,),
              kv_layout="paged", kv_block_size=16, kv_num_blocks=7,
              kv_dtype="int8")

    base = InferenceEngine(cfg, params, **kw)
    want, _ = _streams(base, reqs)
    del base

    spec = InferenceEngine(cfg, params, draft_cfg=cfg,
                           draft_params=draft_params, spec_k=2,
                           draft_num_blocks=7, **kw)
    got, sched = _streams(spec, reqs)
    assert got == want
    m = sched.metrics()
    assert m["spec_rounds"] > 0
    assert m["kv_dtype"] == "int8"
    assert m["kv_bytes_per_block"] > 0


def test_spill_restore_bitwise_under_int8(tmp_path):
    """Spill-to-host and restore stay bit-exact WITHIN the int8 mode: a
    block-starved pool producing the same streams as an unconstrained
    one proves the scale pool survives the round trip."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg(seq_len=128)
    params = _init_params(cfg)

    def build(num_blocks=None):
        return InferenceEngine(cfg, params, slots=4, max_len=128,
                               prefill_buckets=(16, 32),
                               kv_layout="paged", kv_block_size=8,
                               kv_num_blocks=num_blocks, kv_dtype="int8")

    rng = np.random.default_rng(3)
    reqs = [Request(id="A", prompt=rng.integers(3, 64, size=17).tolist(),
                    max_new_tokens=40, seed=1),
            Request(id="B", prompt=rng.integers(3, 64, size=19).tolist(),
                    max_new_tokens=40, seed=2)]

    ref_sched = Scheduler(build())
    for r in reqs:
        ref_sched.submit(r)
    ref_sched.run()
    ref = {c.request_id: c.tokens for c in ref_sched.completed}

    sched = Scheduler(build(num_blocks=12),
                      spill_dir=str(tmp_path / "tier"))
    for r in reqs:
        sched.submit(r)
    sched.run()
    out = {c.request_id: c.tokens for c in sched.completed}
    assert out == ref
    assert sched.spill_rejects == 0


# ---------------------------------------------------- 5. quantized weights
def test_weights_artifact_publish_verify_reload(tmp_path):
    """End to end: --weights-dtype int8's artifact publishes with its own
    CRC manifest, the hot swap installs it bit-identically to an engine
    built from the artifact directly, a corrupt artifact and a
    step-mismatched sub-pointer are both rejected with serving intact."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        CheckpointManager)
    from fault_tolerant_llm_training_tpu.deploy.publish import (
        Publisher, load_weights_artifact, quantize_tensor, read_pointer,
        verify_pointer)
    from fault_tolerant_llm_training_tpu.deploy.reload import (
        HotReloader, PointerWatcher)
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine, enable_compilation_cache)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import make_optimizer

    enable_compilation_cache(CACHE)
    cfg = tiny_cfg()
    params_a = _init_params(cfg, seed=0)
    params_b = _init_params(cfg, seed=1)
    state = TrainState(step=jnp.asarray(20, jnp.int32), params=params_b,
                       opt_state=make_optimizer(1e-4, 1).init(params_b))
    mngr = CheckpointManager(str(tmp_path), "pub", enable_async=False,
                             max_to_keep=4)
    mngr.save(20, state, {"next_index": 0}, wait=True)
    mngr.close()

    # per-tensor quantization error bound, on a real leaf
    import jax

    leaf = np.asarray(jax.tree_util.tree_leaves(params_b)[0], np.float32)
    q, s = quantize_tensor(leaf)
    assert q.dtype == np.int8
    assert (np.abs(q.astype(np.float32) * s - leaf) <= s * 0.5 + 1e-7).all()

    pub = Publisher(str(tmp_path), "pub")
    w = pub.quantize_weights(20, cfg)
    assert w["dtype"] == "int8" and w["nbytes"] > 0
    ptr = pub.publish(20, weights=w)
    assert ptr.weights == w
    assert verify_pointer(str(tmp_path), ptr) == (True, "ok")
    # int8 payload: at most half the bf16 checkpoint's parameter bytes
    assert w["nbytes"] * 2 <= sum(
        a.nbytes for a in jax.tree_util.tree_leaves(params_b))

    def fresh_engine():
        e = InferenceEngine(cfg, params_a, slots=2, max_len=48)
        e.restored_step = 0
        return e

    engine = fresh_engine()
    sched = Scheduler(engine)
    reloader = HotReloader(engine, sched, cfg, str(tmp_path))
    assert reloader.maybe_reload(PointerWatcher(str(tmp_path)).poll())
    assert engine.restored_step == 20 and reloader.rejects == 0

    prompt = [5, 9, 2, 14, 7]

    def run(sch, rid):
        sch.submit(Request(id=rid, prompt=list(prompt), max_new_tokens=8,
                           temperature=0.0))
        done = []
        while sch.pending():
            done.extend(sch.step())
        return {c.request_id: c.tokens for c in done}[rid]

    got = run(sched, "swapped")
    ref_engine = InferenceEngine(cfg, load_weights_artifact(
        str(tmp_path), w), slots=2, max_len=48)
    assert got == run(Scheduler(ref_engine), "ref"), (
        "post-swap stream diverged from the artifact's weights")

    # corrupt one payload byte: verify-before-load rejects, serving holds
    victim = os.path.join(str(tmp_path), w["path"], "t0000.npy")
    raw = bytearray(open(victim, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(raw))
    engine2 = fresh_engine()
    sched2 = Scheduler(engine2)
    rel2 = HotReloader(engine2, sched2, cfg, str(tmp_path))
    assert rel2.maybe_reload(read_pointer(str(tmp_path))) is False
    assert rel2.rejects == 1 and engine2.restored_step == 0
    assert sched2.admission_open
    raw[len(raw) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(raw))

    # a weights sub-entry naming the wrong step is rejected up front
    pub.publish(20, weights=dict(w, step=19))
    engine3 = fresh_engine()
    rel3 = HotReloader(engine3, Scheduler(engine3), cfg, str(tmp_path))
    assert rel3.maybe_reload(read_pointer(str(tmp_path))) is False
    assert rel3.rejects == 1 and engine3.restored_step == 0
