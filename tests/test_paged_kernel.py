"""Fused paged-attention decode kernel + burst decode (ops/paged_attention.py,
ops/attention.py dispatch, inference/engine.py, inference/scheduler.py).

Evidence ladder for the in-place decode path:

1. kernel — the Pallas block-indexed kernels (S=1 decode and S>1 chunk) run
   in interpret mode equal the gather-then-attend reference within fp32
   accumulation tolerance over ADVERSARIAL pool states (garbage null block,
   freed entries fallen back to 0, stale table entries aimed at orphaned
   garbage blocks, prefix-cache rows sharing blocks, a copy-on-write final
   block, offsets landing exactly on block boundaries, chunks straddling
   block boundaries), and their output is BITWISE invariant to the bytes in
   masked positions — stale content cannot leak through the online softmax;
2. dispatch — ``paged_attention`` routes "gather" bit-exactly, routes
   "pallas" by query length (S == 1 -> decode kernel, S > 1 -> chunk kernel;
   the former silent gather fallback for S > 1 is gone), rejects unknown
   impls; ``multihead_attention`` accepts the "ring" impl configs.py admits
   and resolves it to the dense equivalent instead of raising;
3. engine — the fused sampling epilogue's token stream bit-matches the
   unfused baseline (sync full logits, sample on host with the SAME
   sampler.py function) for greedy and seeded sampled slots alike;
4. scheduler — burst decode (n tokens per dispatch) emits bit-identical
   streams to per-token decode across burst in {1, 4, 8} and across both
   kernels, EOS/budget overshoot is truncated on banking, and the dispatch
   accounting (``decode_dispatches_total`` / ``decode_host_syncs_total`` /
   ``decode_burst_tokens``) shows dispatches/token <= 1/(n * active slots).
"""

import numpy as np
import pytest

from _tiny import tiny_cfg


# -------------------------------------------------------------------- 1. kernel
def _attend(q, pool_k, pool_v, tables, offsets, impl):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.ops.attention import paged_attention

    # tree_map: an int8 pool is a QuantPool of two arrays
    return np.asarray(paged_attention(*jax.tree_util.tree_map(
        jnp.asarray, (q, pool_k, pool_v, tables, offsets)), impl=impl))


def _adversarial_pool(rng, dtype=np.float32):
    """Four slots over one pool, each an adversarial table/offset shape.

    slot 0: offset == 2*bs  (decode query lands on the FIRST position of
            block 2; blocks past it freed -> null-block 0 fallback)
    slot 1: offset == bs-1  (query on the LAST position of block 0; tail
            entries left STALE, aimed at orphaned garbage blocks)
    slot 2: prefix-cache row — shares its first two blocks with slot 3
    slot 3: same shared prefix, but its FINAL block is a copy-on-write
            private copy of slot 2's block 2 that diverges at the end
    """
    K, H, bs, NB, D = 2, 4, 8, 4, 16
    B = 4
    N = 16                                    # pool blocks incl. null block 0
    pool_k = rng.standard_normal((N, K, bs, D)).astype(dtype)
    pool_v = rng.standard_normal((N, K, bs, D)).astype(dtype)

    tables = np.zeros((B, NB), np.int32)
    tables[0] = [1, 2, 3, 0]                  # block 3 covers the boundary pos
    tables[1] = [4, 14, 15, 0]                # 14/15 stale: nobody owns them
    tables[2] = [5, 6, 7, 0]                  # shared prefix: blocks 5, 6
    tables[3] = [5, 6, 8, 0]                  # COW copy of block 7 -> block 8
    pool_k[8], pool_v[8] = pool_k[7].copy(), pool_v[7].copy()
    pool_k[8, :, -1], pool_v[8, :, -1] = 0.25, -0.5     # diverged tail

    offsets = np.array([2 * bs, bs - 1, 2 * bs + 5, 2 * bs + 7], np.int32)
    q = rng.standard_normal((B, 1, H, D)).astype(dtype)
    return q, pool_k, pool_v, tables, offsets


def _grouped_pool(rng, offsets, width=(4, 2, 16), int8=False, shared=False):
    """Slots over tables of 10 pages of 8, every live page a block of its
    own (``shared``: slot 1's first pages are slot 0's), and past each
    slot's length what an allocator leaves: null block 0. ``width`` is
    (heads, kv heads, head dim). The decode cases below run it with the
    kernel's page group patched to 4 pages = 32 positions, so 10 pages are
    two whole groups and a short one."""
    H, K, D = width
    bs, NB, B = 8, 10, len(offsets)
    N = B * NB + 1
    pool_k = rng.standard_normal((N, K, bs, D)).astype(np.float32)
    pool_v = rng.standard_normal((N, K, bs, D)).astype(np.float32)
    tables = np.zeros((B, NB), np.int32)
    for b, off in enumerate(offsets):
        if off < 0:                    # an inactive slot: nothing allocated
            continue
        live = off // bs + 1
        tables[b, :live] = 1 + b * NB + np.arange(live)
    if shared:
        tables[1, :2] = tables[0, :2]
    offs = np.maximum(np.asarray(offsets, np.int32), 0)
    q = rng.standard_normal((B, 1, H, D)).astype(np.float32)
    if int8:
        from fault_tolerant_llm_training_tpu.inference.kv_cache import (
            QuantPool)

        def quant(x):
            scale = np.abs(x).max(axis=(2, 3)) / 127.0
            return QuantPool(
                q=np.clip(np.rint(x / scale[..., None, None]),
                          -127, 127).astype(np.int8),
                scale=scale.astype(np.float32))
        pool_k, pool_v = quant(pool_k), quant(pool_v)
    return q, pool_k, pool_v, tables, offs


_GROUP = 32   # positions a page group of the patched kernel spans

# name -> builder of (q, pool_k, pool_v, tables, offsets)
DECODE_CASES = {
    # the four-slot pool above, at the page group the shape rule gives it
    "adversarial": _adversarial_pool,
    # a length that ends on a page-group boundary, one short of it, one
    # past it, and a table full to its last position (a short last group)
    "group_edges": lambda rng: _grouped_pool(
        rng, [_GROUP - 1, _GROUP - 2, _GROUP, 79]),
    "two_groups_exact": lambda rng: _grouped_pool(
        rng, [2 * _GROUP - 1, 2 * _GROUP, 2 * _GROUP + 1]),
    # a slot of length 0 (one token) and an inactive slot beside a full one
    "empty_beside_full": lambda rng: _grouped_pool(rng, [0, -1, 79, -1, 5]),
    "shared_block": lambda rng: _grouped_pool(rng, [40, 17, 70],
                                              shared=True),
    "d64_g1": lambda rng: _grouped_pool(rng, [33, 79, 7], (4, 4, 64)),
    "d64_g2": lambda rng: _grouped_pool(rng, [33, 79, 7], (4, 2, 64)),
    "d64_g4": lambda rng: _grouped_pool(rng, [33, 79, 7], (8, 2, 64)),
    "d128_g1": lambda rng: _grouped_pool(rng, [33, 79, 7], (2, 2, 128)),
    "d128_g2": lambda rng: _grouped_pool(rng, [33, 79, 7], (4, 2, 128)),
    "d128_g4": lambda rng: _grouped_pool(rng, [33, 79, 7], (8, 2, 128)),
    "int8": lambda rng: _grouped_pool(rng, [_GROUP - 1, 79, 0, 45],
                                      int8=True),
    "int8_d128": lambda rng: _grouped_pool(rng, [64, 31, 9], (4, 2, 128),
                                           int8=True),
}


@pytest.fixture
def short_page_groups(monkeypatch):
    """Let the decode kernel's shape rule come out at 4 pages of 8 (it
    gives every table this small one whole group), so that 10-page tables
    loop, prefetch across groups and slots, and end on short groups."""
    from fault_tolerant_llm_training_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_DECODE_SPAN", _GROUP)
    assert pa._decode_pages_per_step(10, 2, 8, 16, 4) == 4


def _masked_bytes_rewritten(rng, pk, pv, tables, offs):
    """The pools with every byte no slot's query may see rewritten: all of
    a block that holds no live position (null block 0, stale, orphaned,
    never allocated — an int8 block's scales with it), and the positions
    past a slot's offset inside its last live block."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import QuantPool

    quantized = isinstance(pk, QuantPool)
    data = (pk.q, pv.q) if quantized else (pk, pv)
    n, _, bs, _ = data[0].shape
    live = np.zeros((n, bs), bool)
    for b in range(tables.shape[0]):
        for pos in range(int(offs[b]) + 1):
            live[tables[b, pos // bs], pos % bs] = True
    out = []
    for x in data:
        noise = rng.standard_normal(x.shape) * 9.0
        x2 = np.where(live[:, None, :, None], x,
                      np.clip(noise * 40, -127, 127).astype(x.dtype)
                      if quantized else noise.astype(x.dtype))
        out.append(x2)
    if not quantized:
        return tuple(out)
    dead = ~live.any(axis=1)
    scales = [np.where(dead[:, None],
                       rng.uniform(0.5, 50.0, p.scale.shape), p.scale)
              .astype(np.float32) for p in (pk, pv)]
    return tuple(QuantPool(q=x, scale=sc) for x, sc in zip(out, scales))


@pytest.mark.parametrize("case", DECODE_CASES)
def test_pallas_kernel_matches_gather_on_adversarial_pools(
        case, short_page_groups):
    rng = np.random.default_rng(7)
    q, pk, pv, tables, offs = DECODE_CASES[case](rng)
    ref = _attend(q, pk, pv, tables, offs, "gather")
    out = _attend(q, pk, pv, tables, offs, "pallas")
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=2e-6)


def test_lane_narrow_heads_take_the_page_grid(monkeypatch):
    """Compiled for the chip, a head narrower than the 128-lane tile cannot
    have its pages sliced out of HBM: the S=1 read then runs the chunk
    kernel's per-page grid, still in place. Interpreted, that route is
    steered here and must agree with the gather like the other."""
    from fault_tolerant_llm_training_tpu.ops import paged_attention as pa

    assert pa.decode_pages_whole(128) and pa.decode_pages_whole(256)
    assert not pa.decode_pages_whole(64) and not pa.decode_pages_whole(192)
    q, pk, pv, tables, offs = _adversarial_pool(np.random.default_rng(7))
    routed = []
    grid = pa._page_grid_attention
    monkeypatch.setattr(pa, "_page_grid_attention",
                        lambda *a: (routed.append(a[0].shape[1]),
                                    grid(*a[:-1], True))[1])
    out = np.asarray(pa.paged_decode_attention(q, pk, pv, tables, offs,
                                               interpret=False))
    assert routed == [1]
    np.testing.assert_allclose(
        out, _attend(q, pk, pv, tables, offs, "gather"),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("case", DECODE_CASES)
def test_pallas_kernel_output_invariant_to_masked_bytes(
        case, short_page_groups):
    """Rewrite every byte the masks are supposed to hide — the null block,
    the orphaned stale blocks, the positions past each offset inside live
    blocks — and the kernel output must not move by a single bit."""
    rng = np.random.default_rng(8)
    q, pk, pv, tables, offs = DECODE_CASES[case](rng)
    base = _attend(q, pk, pv, tables, offs, "pallas")
    pk2, pv2 = _masked_bytes_rewritten(rng, pk, pv, tables, offs)
    np.testing.assert_array_equal(
        _attend(q, pk2, pv2, tables, offs, "pallas"), base)


def test_pallas_kernel_rejects_multi_query():
    from fault_tolerant_llm_training_tpu.ops.paged_attention import (
        paged_decode_attention)

    rng = np.random.default_rng(9)
    q, pk, pv, tables, offs = _adversarial_pool(rng)
    q3 = np.repeat(q, 3, axis=1)
    with pytest.raises(ValueError, match="decode"):
        paged_decode_attention(q3, pk, pv, tables, offs)


def _adversarial_chunk_pool(rng, s_q=5, dtype=np.float32):
    """Four slots mid-prefill, each an adversarial S>1 chunk geometry.

    slot 0: chunk starts exactly ON a block boundary (offset == 2*bs)
    slot 1: chunk STRADDLES a block boundary (rows span blocks 0 and 1);
            the table tail entry is stale, aimed at an orphaned garbage
            block that starts past the LAST row — must be skipped wholesale
    slot 2: prefix-cache row — shares its first two blocks with slot 3
    slot 3: same shared prefix, but its final block is a copy-on-write
            private copy of slot 2's that diverges in the rows the chunk
            actually lands on
    """
    K, H, bs, NB, D = 2, 4, 8, 4, 16
    B = 4
    N = 16                                    # pool blocks incl. null block 0
    pool_k = rng.standard_normal((N, K, bs, D)).astype(dtype)
    pool_v = rng.standard_normal((N, K, bs, D)).astype(dtype)

    tables = np.zeros((B, NB), np.int32)
    tables[0] = [1, 2, 3, 0]
    tables[1] = [4, 5, 14, 0]                 # 14 stale: past the last row
    tables[2] = [6, 7, 8, 0]                  # shared prefix: blocks 6, 7
    tables[3] = [6, 7, 9, 0]                  # COW copy of block 8 -> block 9
    pool_k[9], pool_v[9] = pool_k[8].copy(), pool_v[8].copy()
    pool_k[9, :, -3:], pool_v[9, :, -3:] = 0.25, -0.5   # diverged tail

    offsets = np.array([2 * bs, bs - 2, 2 * bs + 1, 2 * bs + 3], np.int32)
    q = rng.standard_normal((B, s_q, H, D)).astype(dtype)
    return q, pool_k, pool_v, tables, offsets


@pytest.mark.parametrize("s_q", [2, 5])
def test_pallas_chunk_kernel_matches_gather_on_adversarial_pools(s_q):
    rng = np.random.default_rng(14)
    q, pk, pv, tables, offs = _adversarial_chunk_pool(rng, s_q=s_q)
    ref = _attend(q, pk, pv, tables, offs, "gather")
    out = _attend(q, pk, pv, tables, offs, "pallas")
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_pallas_chunk_kernel_output_invariant_to_masked_bytes():
    """Rewrite every pool byte outside the union of the rows' live sets —
    the null block, the orphaned stale block, every lane past each chunk's
    last row (k_pos > offsets[b] + S - 1 for the owning slot) — and the
    chunk kernel output must not move by a single bit. The live set is
    per-ROW: a lane is live iff SOME slot's boundary admits it, which is
    exactly the union the per-row causal mask protects."""
    rng = np.random.default_rng(15)
    q, pk, pv, tables, offs = _adversarial_chunk_pool(rng)
    base = _attend(q, pk, pv, tables, offs, "pallas")

    s_q = q.shape[1]
    n, _, bs, _ = pk.shape
    live = np.zeros((n, bs), bool)
    for b in range(tables.shape[0]):
        for i in range(tables.shape[1]):
            for lane in range(bs):
                if i * bs + lane <= int(offs[b]) + s_q - 1:
                    live[tables[b, i], lane] = True
    pk2 = np.where(live[:, None, :, None], pk,
                   rng.standard_normal(pk.shape).astype(pk.dtype))
    pv2 = np.where(live[:, None, :, None], pv,
                   rng.standard_normal(pv.shape).astype(pv.dtype))
    assert not np.array_equal(pk2, pk)       # the rewrite actually happened
    np.testing.assert_array_equal(
        _attend(q, pk2, pv2, tables, offs, "pallas"), base)


def test_pallas_chunk_kernel_rejects_single_query():
    from fault_tolerant_llm_training_tpu.ops.paged_attention import (
        paged_chunk_attention)

    rng = np.random.default_rng(16)
    q, pk, pv, tables, offs = _adversarial_pool(rng)    # S == 1 shapes
    with pytest.raises(ValueError, match="S > 1"):
        paged_chunk_attention(q, pk, pv, tables, offs)


# ------------------------------------------------------------------ 2. dispatch
def test_paged_attention_dispatch_routes_and_validates(monkeypatch):
    from fault_tolerant_llm_training_tpu.ops import (
        paged_attention as pa_mod)
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_cached_attention)

    rng = np.random.default_rng(10)
    q, pk, pv, tables, offs = _adversarial_pool(rng)
    import jax.numpy as jnp
    ref = np.asarray(paged_cached_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(tables), jnp.asarray(offs)))
    # "gather" IS paged_cached_attention, bitwise
    np.testing.assert_array_equal(_attend(q, pk, pv, tables, offs, "gather"),
                                  ref)
    # "pallas" dispatches on S: the decode kernel for S == 1, the chunk
    # kernel for S > 1 — no silent gather fallback. Prove the route (spy on
    # the kernel entry points) AND the result (fp32-close to gather; online
    # softmax reorders the reduction, so closeness, not bitwise).
    routed = []
    for name in ("paged_decode_attention", "paged_chunk_attention"):
        orig = getattr(pa_mod, name)
        monkeypatch.setattr(
            pa_mod, name,
            lambda *a, _orig=orig, _n=name, **k: (routed.append(_n),
                                                  _orig(*a, **k))[1])
    np.testing.assert_allclose(_attend(q, pk, pv, tables, offs, "pallas"),
                               ref, rtol=1e-5, atol=1e-6)
    qc, pkc, pvc, tablesc, offsc = _adversarial_chunk_pool(
        np.random.default_rng(17), s_q=3)
    np.testing.assert_allclose(
        _attend(qc, pkc, pvc, tablesc, offsc, "pallas"),
        _attend(qc, pkc, pvc, tablesc, offsc, "gather"),
        rtol=1e-5, atol=1e-6)
    assert routed == ["paged_decode_attention", "paged_chunk_attention"]
    with pytest.raises(ValueError, match="impl"):
        _attend(q, pk, pv, tables, offs, "vllm")
    # "auto" off the chip IS the gather, at every S: nothing routed, bitwise
    del routed[:]
    np.testing.assert_array_equal(_attend(q, pk, pv, tables, offs, "auto"),
                                  ref)
    np.testing.assert_array_equal(
        _attend(qc, pkc, pvc, tablesc, offsc, "auto"),
        _attend(qc, pkc, pvc, tablesc, offsc, "gather"))
    assert routed == []


@pytest.mark.parametrize("where,s_q,head_dim,want", [
    ("cpu", 1, 128, "gather"),            # off the chip: the oracle
    ("cpu", 5, 128, "gather"),
    ("tpu", 1, 128, "pallas"),            # the decode read, in place
    ("tpu", 1, 256, "pallas"),
    ("tpu", 5, 128, "gather"),            # S > 1 stays on the gather
    ("tpu", 1, 64, "gather"),             # heads narrower than a lane tile
    ("tpu_mesh", 1, 128, "gather"),       # Mosaic cannot be partitioned
    ("tpu_one_device_mesh", 1, 128, "pallas"),
])
def test_auto_paged_kernel_rule(monkeypatch, where, s_q, head_dim, want):
    """``auto`` reads the backend, the query length, the head size and the
    active mesh — and nothing a user sets. The explicit values pass."""
    import jax

    from fault_tolerant_llm_training_tpu.ops import attention as attn
    from fault_tolerant_llm_training_tpu.parallel.mesh import (
        make_mesh, use_mesh)

    if where != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = {"tpu_mesh": lambda: make_mesh(dp=2, devices=jax.devices()[:2]),
            "tpu_one_device_mesh": lambda: make_mesh(
                dp=1, devices=jax.devices()[:1])}.get(where, lambda: None)()
    with use_mesh(mesh):
        assert attn.resolve_paged_kernel("auto", s_q, head_dim) == want
        for explicit in ("gather", "pallas"):
            assert attn.resolve_paged_kernel(explicit, s_q,
                                             head_dim) == explicit
        # the server's start-up line states the resolution, not the option
        line = attn.describe_paged_kernel("auto", head_dim)
        decode = attn.resolve_paged_kernel("auto", 1, head_dim)
        mode = {"cpu": "interpret"}.get(where, "compiled")
        assert line == (f"auto: decode "
                        f"{f'pallas ({mode})' if decode == 'pallas' else decode}"
                        f", prefill gather")
        assert attn.describe_paged_kernel("gather", head_dim) == "gather"
        assert attn.describe_paged_kernel(
            "pallas", head_dim) == f"pallas ({mode})"


def test_auto_routes_the_decode_read_in_place_on_a_tpu(monkeypatch):
    """With the backend reading as a TPU (the kernels still interpreted),
    ``auto`` sends S = 1 through the decode kernel and S > 1 through the
    gather, and both agree with the oracle."""
    import jax

    from fault_tolerant_llm_training_tpu.ops import paged_attention as pa

    q, pk, pv, tables, offs = _grouped_pool(np.random.default_rng(3),
                                            [33, 79, 7], (4, 2, 128))
    qc = np.repeat(q, 3, axis=1)
    offsc = np.minimum(offs, 70)
    want = _attend(q, pk, pv, tables, offs, "gather")
    wantc = _attend(qc, pk, pv, tables, offsc, "gather")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "_interpret", lambda: True)
    routed = []
    for name in ("paged_decode_attention", "paged_chunk_attention"):
        orig = getattr(pa, name)
        monkeypatch.setattr(
            pa, name, lambda *a, _orig=orig, _n=name, **k: (
                routed.append(_n), _orig(*a, **k))[1])
    np.testing.assert_allclose(_attend(q, pk, pv, tables, offs, "auto"),
                               want, rtol=1e-5, atol=2e-6)
    np.testing.assert_array_equal(
        _attend(qc, pk, pv, tables, offsc, "auto"), wantc)
    assert routed == ["paged_decode_attention"]


def test_multihead_attention_ring_impl_routes_dense():
    """configs.py admits attention_impl='ring'; a direct single-device call
    must resolve to the equivalent dense kernel, not raise (satellite: the
    dispatch previously raised on the impl its own config admitted)."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.ops.attention import (
        multihead_attention, xla_attention)

    cfg = tiny_cfg(attention_impl="ring")    # admitted by __post_init__
    assert cfg.attention_impl == "ring"
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 16, 2, 8)), jnp.float32)
    out = multihead_attention(q, k, v, impl="ring")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(xla_attention(q, k, v)))


def test_config_validates_paged_kernel():
    assert tiny_cfg(paged_kernel="pallas").paged_kernel == "pallas"
    assert tiny_cfg(paged_kernel="gather").paged_kernel == "gather"
    assert tiny_cfg().paged_kernel == "auto"      # the default is the rule
    with pytest.raises(ValueError, match="paged_kernel"):
        tiny_cfg(paged_kernel="cuda")


# -------------------------------------------------------------------- 3. engine
@pytest.fixture(scope="module")
def paged_engines():
    """One param set, two paged engines: the gather reference kernel and the
    Pallas in-place kernel, same slots/blocks/buckets."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    gather = InferenceEngine(cfg, params, slots=2, max_len=32,
                             prefill_buckets=(8, 16), kv_block_size=8,
                             paged_kernel="gather")
    pallas = InferenceEngine(cfg, params, slots=2, max_len=32,
                             prefill_buckets=(8, 16), kv_block_size=8,
                             paged_kernel="pallas")
    return cfg, gather, pallas


def test_engine_rejects_bad_kernel_combinations():
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="paged_kernel"):
        InferenceEngine(cfg, params, slots=1, max_len=16,
                        prefill_buckets=(8,), paged_kernel="cuda")
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, params, slots=1, max_len=16,
                        prefill_buckets=(8, 16), kv_layout="ring",
                        paged_kernel="pallas")
    # the default is "auto": it asks for nothing, so the ring layout takes
    # it, and off the chip every program's reads resolve to the gather
    ring = InferenceEngine(cfg, params, slots=1, max_len=16,
                           prefill_buckets=(8, 16), kv_layout="ring")
    assert ring.paged_kernel == ring.cfg.paged_kernel == "auto"
    assert (ring.decode_read_kernel, ring.prefill_read_kernel) == (
        "gather", "gather")


def test_paged_read_dispatch_counter_states_the_resolved_kernel(
        paged_engines):
    """``paged_read_dispatches_total{kernel,phase}`` counts every dispatched
    program that reads the pool under the kernel its reads RESOLVED to: a
    prefill chunk and a decode round each move one series, the gather
    engine's under ``gather`` and the pallas engine's under ``inplace``."""
    from fault_tolerant_llm_training_tpu.obs.registry import (
        default_registry)

    cfg, gather, pallas = paged_engines
    reads = default_registry().counter("paged_read_dispatches_total")
    series = [(k, ph) for k in ("gather", "inplace")
              for ph in ("prefill", "decode")]

    def now():
        return {s: reads.labels(kernel=s[0], phase=s[1]).value
                for s in series}

    row = np.array([1, 2, 3, 4], np.int32)
    for eng, kernel in ((gather, "gather"), (pallas, "inplace")):
        assert (eng.decode_read_kernel, eng.prefill_read_kernel) == (
            kernel, kernel)
        eng.reset()
        before = now()
        # 20 tokens over buckets (8, 16): a chunk of 16, then one of 4
        tok = eng.prefill(0, list(range(3, 23)), block_row=row)
        eng.decode_step(np.array([tok, 0], np.int32),
                        np.array([True, False]), np.zeros(2, np.float32),
                        np.ones(2, np.float32), np.zeros(2, np.int32),
                        np.ones(2, np.int32),
                        block_tables=np.stack([row, np.zeros_like(row)]))
        moved = {s: v - before[s] for s, v in now().items() if v != before[s]}
        assert moved == {(kernel, "prefill"): 2, (kernel, "decode"): 1}


def test_fused_sampler_bitmatches_host_sampler(paged_engines):
    """Same engine, two regimes: (a) fused decode_step — sampling runs inside
    the decode program, 4 bytes/slot sync; (b) unfused decode_logits — the
    (slots, V) fp32 plane syncs to host and sample_slot_tokens picks there.
    Slot 0 greedy, slot 1 seeded top-p: streams must be bit-identical."""
    from fault_tolerant_llm_training_tpu.inference.sampler import (
        sample_slot_tokens)

    cfg, eng, _ = paged_engines
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


# ----------------------------------------------------------------- 4. scheduler
def _stream(engine, requests, eos=None, burst=1, registry=None):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    engine.reset()
    sched = Scheduler(engine, eos_token_id=eos, registry=registry,
                      decode_burst=burst)
    for r in requests:
        sched.submit(r)
    sched.run()
    return sched, {c.request_id: c.tokens for c in sched.completed}


def _requests(cfg, n=4):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    rng = np.random.default_rng(13)
    return [Request(id=f"r{i}",
                    prompt=rng.integers(3, cfg.vocab_size, size=pl).tolist(),
                    max_new_tokens=gen, temperature=t, top_p=0.9, seed=i)
            for i, (pl, gen, t) in enumerate(
                [(6, 13, 0.0), (12, 13, 0.8), (9, 13, 0.0), (11, 13, 0.7)]
                [:n])]


def test_burst_streams_bitmatch_sequential_across_kernels(paged_engines):
    """Burst n in {1, 4, 8} over both kernels: every emitted stream must be
    bit-identical to per-token decode (max_new_tokens=13 is deliberately not
    a burst multiple — _bank_burst truncates the budget overshoot), greedy
    slots must also bit-match ACROSS kernels, and the dispatch counters must
    show the 1/n amortization the fused path exists for."""
    from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

    cfg, gather, pallas = paged_engines
    reqs = _requests(cfg)
    _, seq = _stream(gather, list(reqs), burst=1)
    reg = MetricRegistry()
    s4, b4 = _stream(gather, list(reqs), burst=4, registry=reg)
    _, b8 = _stream(gather, list(reqs), burst=8)
    assert seq == b4 == b8

    ps, pseq = _stream(pallas, list(reqs), burst=1)
    _, pb4 = _stream(pallas, list(reqs), burst=4)
    assert pseq == pb4
    # under "pallas" EVERY prefill chunk is read in place, under "gather"
    # none (a nonzero gather count there = the S>1 fallback came back)
    pm, gm = ps.metrics(), s4.metrics()
    assert pm["prefill_inplace_chunks"] == pm["prefill_chunks"] > 0
    assert pm["prefill_gather_chunks"] == 0
    assert gm["prefill_gather_chunks"] == gm["prefill_chunks"] > 0
    assert gm["prefill_inplace_chunks"] == 0
    # greedy slots bit-match across kernels (sampled slots are only fp32-close
    # in logit space, so a top-p boundary may legitimately flip)
    for r in ("r0", "r2"):
        assert pseq[r] == seq[r]

    m = s4.metrics()
    assert m["decode_burst"] == 4
    assert m["decode_tokens"] == 4 * 12    # token 1 of 13 comes from prefill
    # 2 active slots per dispatch: amortization beats even the 1/n bar
    assert m["dispatches_per_token"] <= 1 / 4 + 0.05
    assert m["host_syncs_per_token"] <= 1 / 4 + 0.05
    rendered = reg.render()
    for name in ("decode_dispatches_total", "decode_host_syncs_total",
                 "decode_burst_tokens"):
        assert name in rendered


def test_burst_banking_truncates_at_eos(paged_engines):
    """Pick a token the greedy stream actually emits mid-sequence and rerun
    with it as EOS: burst decode overshoots it inside the device loop, and
    _bank_burst must truncate so the finished stream equals the sequential
    EOS stream exactly."""
    cfg, gather, _ = paged_engines
    reqs = _requests(cfg, n=2)
    _, free = _stream(gather, list(reqs), burst=1)
    eos = free["r0"][len(free["r0"]) // 2]    # mid-stream greedy token
    _, seq = _stream(gather, list(reqs), eos=eos, burst=1)
    _, b4 = _stream(gather, list(reqs), eos=eos, burst=4)
    assert seq == b4
    assert len(b4["r0"]) < len(free["r0"])    # EOS actually truncated it


def test_scheduler_validates_decode_burst(paged_engines):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    _, gather, _ = paged_engines
    with pytest.raises(ValueError, match="decode_burst"):
        Scheduler(gather, eos_token_id=None, decode_burst=0)
