"""On-chip kernel correctness checks (run on the real TPU).

Complements the interpret-mode CPU tests (tests/test_flash_attention.py,
tests/test_ring_attention.py) with checks where the kernels actually run
compiled, at the tuned production tiles (VERDICT round-1 weak spot #6: the
tuned D=64 shapes had no on-chip parity pin):

1. flash-vs-XLA allclose at the production shapes (D=64), forward AND
   gradients: the resident family (resident forward + FUSED backward) at
   S=2048, at S=4096 with GQA, and at S=16384 asking for more than the
   default scoped VMEM; and the streaming family (streamed forward +
   SPLIT backward) at S=16384, forced by telling the VMEM rule the chip
   has none to spare — the dispatch where the resident family does not
   fit.
2. A single-chip S=64k ring-carry check: the last ring position's work —
   its query block folded against all sp KV blocks through the carry
   kernels (ops/ring_flash.py) exactly as the per-device ring loop does —
   must match the corresponding rows of the streaming flash kernel's
   full-sequence output. This pins the carry kernels' numerics at the
   long-context scale they exist for, on one chip (the ring itself needs a
   multi-device 'sequence' axis; the per-step local math is what runs
   here). Peak HBM is reported to document memory parity with the
   streaming kernels (the round-1 einsum local math would need an
   (S/sp)^2 fp32 score tensor = 256 MB per kv-head-group at these shapes).

Prints one JSON line per check; exits non-zero on any failure.
"""

import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def _mem_peak():
    try:
        stats = jax.local_devices()[0].memory_stats()
        return int(stats.get("peak_bytes_in_use", 0))
    except Exception:
        return -1


@contextlib.contextmanager
def _streaming_family():
    """The streaming family (streamed forward, split backward) at any
    shape: the VMEM rule of ops/flash_attention.py told the chip has none
    to spare."""
    from fault_tolerant_llm_training_tpu.ops import flash_attention as fa

    capacity = fa.vmem_capacity_bytes
    fa.vmem_capacity_bytes = lambda: 0
    try:
        yield
    finally:
        fa.vmem_capacity_bytes = capacity


def check_flash_parity(s, h, kv, d, dtype=jnp.bfloat16):
    from fault_tolerant_llm_training_tpu.ops import flash_attention as fa
    from fault_tolerant_llm_training_tpu.ops.attention import xla_attention

    flash_attention = fa.flash_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((1, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((1, s, kv, d)), dtype)

    want = jax.jit(lambda q, k, v: xla_attention(q, k, v, causal=True))(
        q, k, v)
    got = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(q, k, v)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))

    def loss_x(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True).astype(
            jnp.float32) ** 2)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(
            jnp.float32) ** 2)

    gx = jax.jit(jax.grad(loss_x, argnums=(0, 1, 2)))(q, k, v)
    grad_f = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))
    gf = grad_f(q, k, v)
    calls, _ = fa.flash_calls(grad_f.trace(q, k, v).jaxpr)
    (forward, _), = calls["forward"].items()
    (backward, _), = calls["backward"].items()
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gx, gf))
    # bf16 inputs with fp32 accumulators: elementwise |max| error tracks
    # the bf16 ulp of the magnitudes involved.
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    gscale = max(float(jnp.max(jnp.abs(a.astype(jnp.float32))))
                 for a in gx) or 1.0
    ok = err / scale < 2e-2 and gerr / gscale < 5e-2
    print(json.dumps({
        "check": f"flash_vs_xla_onchip s={s} h={h} kv={kv} d={d} "
                 f"forward={forward} backward={backward}",
        "max_abs_err_out": err, "max_abs_err_grad": gerr,
        "rel_out": err / scale, "rel_grad": gerr / gscale, "ok": ok,
    }), flush=True)
    return ok


def check_rope_fused_parity(s, h, kv, d, dtype=jnp.bfloat16):
    """In-kernel rope (the rope_impl='fused' production default) vs
    XLA-side apply_rope + the same flash kernels, compiled on the chip."""
    from fault_tolerant_llm_training_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_rope,
    )
    from fault_tolerant_llm_training_tpu.ops.rope import (
        apply_rope,
        precompute_rope,
    )

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((1, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((1, s, kv, d)), dtype)
    cos, sin = precompute_rope(d, s, 10000.0)
    cos2 = jnp.repeat(cos, 2, axis=-1)
    sin2 = jnp.repeat(sin, 2, axis=-1)

    def f_ref(q, k, v):
        return flash_attention(apply_rope(q, cos, sin),
                               apply_rope(k, cos, sin), v, True)

    def f_rope(q, k, v):
        qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
        return jnp.transpose(
            flash_attention_rope(qt, kt, vt, cos2, sin2, True), (0, 2, 1, 3))

    want = jax.jit(f_ref)(q, k, v)
    got = jax.jit(f_rope)(q, k, v)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    gx = jax.jit(jax.grad(
        lambda *a: jnp.sum(f_ref(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(
        lambda *a: jnp.sum(f_rope(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gx, gf))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    gscale = max(float(jnp.max(jnp.abs(a.astype(jnp.float32))))
                 for a in gx) or 1.0
    ok = err / scale < 2e-2 and gerr / gscale < 5e-2
    print(json.dumps({
        "check": f"rope_fused_vs_xla_rope_onchip s={s} h={h} kv={kv} d={d}",
        "max_abs_err_out": err, "max_abs_err_grad": gerr,
        "rel_out": err / scale, "rel_grad": gerr / gscale, "ok": ok,
    }), flush=True)
    return ok


def check_ring_carry_64k(s=65536, sp=8, h=4, kv=2, d=64):
    """Last-ring-position carry-kernel math == streaming flash at S=64k."""
    from fault_tolerant_llm_training_tpu.ops.flash_attention import (
        _interpret,
        flash_attention,
    )
    from fault_tolerant_llm_training_tpu.ops.ring_flash import (
        carry_fwd,
        finalize_carry,
        fresh_carry,
    )

    itp = _interpret()  # CPU sanity runs use pallas interpret mode

    s_loc = s // sp
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, s, kv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, s, kv, d)), jnp.bfloat16)

    base = _mem_peak()
    full = jax.jit(lambda q, k, v: flash_attention(q, k, v, True))(q, k, v)
    full.block_until_ready()
    flash_peak = _mem_peak()

    my = sp - 1  # the position whose queries see every KV block

    @jax.jit
    def last_position(q, k, v):
        qt = jnp.transpose(q[:, my * s_loc:], (0, 2, 1, 3))
        m, l, acc = fresh_carry(1, h, s_loc, d)
        for t in range(sp):
            src = (my - t) % sp
            k_blk = jnp.transpose(
                k[:, src * s_loc:(src + 1) * s_loc], (0, 2, 1, 3))
            v_blk = jnp.transpose(
                v[:, src * s_loc:(src + 1) * s_loc], (0, 2, 1, 3))
            m, l, acc = carry_fwd(qt, k_blk, v_blk, m, l, acc,
                                  my * s_loc, src * s_loc, causal=True,
                                  interpret=itp)
        out, _ = finalize_carry(m, l, acc, q.dtype)
        return jnp.transpose(out, (0, 2, 1, 3))

    got = last_position(q, k, v)
    got.block_until_ready()
    ring_peak = _mem_peak()
    want = full[:, my * s_loc:]
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    ok = err / scale < 2e-2
    print(json.dumps({
        "check": f"ring_carry_vs_streaming_flash s={s} sp={sp} d={d}",
        "max_abs_err": err, "rel": err / scale,
        "peak_hbm_after_flash_mb": round((flash_peak - base) / 2**20, 1)
        if flash_peak > 0 else None,
        "peak_hbm_after_ring_mb": round((ring_peak - base) / 2**20, 1)
        if ring_peak > 0 else None,
        "einsum_score_tensor_would_be_mb": round(
            (s_loc * s_loc * 4 * (h // kv)) / 2**20, 1),
        "ok": ok,
    }), flush=True)
    return ok


def check_paged_decode_parity(slots=8, kv=2, h=4, bs=16, nb=16, d=64,
                              dtype=jnp.bfloat16):
    """Pallas paged-decode kernel vs the gather reference, compiled on the
    chip at serving shapes, over an adversarial pool: shuffled block order,
    garbage null block, freed tails fallen back to block 0, stale table
    entries aimed at orphaned blocks, two slots sharing prefix blocks, and
    offsets pinned to block boundaries. The CPU tests pin the same matrix
    in interpret mode (tests/test_paged_kernel.py); this pins the MOSAIC
    lowering at the tuned head widths: D=128 takes the kernel that moves
    whole pages (``nb`` past its 32-page group makes it loop, prefetch
    across groups and slots, and end on short groups), D=64 its per-page
    grid (``decode_pages_whole``). Compiled, the output must also not move
    by a bit when the masked bytes are rewritten."""
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_cached_attention,
    )
    from fault_tolerant_llm_training_tpu.ops.paged_attention import (
        paged_decode_attention,
    )

    rng = np.random.default_rng(3)
    n_pool = slots * nb + 4                 # null + spare orphan blocks
    pool_k = jnp.asarray(rng.standard_normal((n_pool, kv, bs, d)), dtype)
    pool_v = jnp.asarray(rng.standard_normal((n_pool, kv, bs, d)), dtype)
    perm = rng.permutation(np.arange(1, slots * nb + 1))
    tables = perm.reshape(slots, nb).astype(np.int32)
    offsets = rng.integers(1, nb * bs - 1, size=slots).astype(np.int32)
    offsets[0] = 2 * bs                     # decode lands ON a boundary
    offsets[1] = bs - 1                     # last position of block 0
    for b in range(slots):                  # free blocks past the live tail
        tables[b, int(offsets[b]) // bs + 1:] = 0
    tables[2, -1] = n_pool - 1              # stale entry at an orphan block
    tables[3, :2] = tables[2, :2]           # shared prefix rows
    q = jnp.asarray(rng.standard_normal((slots, 1, h, d)), dtype)
    tables = jnp.asarray(tables)
    offsets = jnp.asarray(offsets)

    want = jax.jit(paged_cached_attention)(q, pool_k, pool_v, tables,
                                           offsets)
    got = jax.jit(paged_decode_attention)(q, pool_k, pool_v, tables,
                                          offsets)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    # rewrite what the masks hide: every position no slot's query sees
    # (the null block, the orphan, freed blocks, live blocks' tails)
    live = np.zeros((n_pool, bs), bool)
    for b, off in enumerate(np.asarray(offsets)):
        pos = np.arange(int(off) + 1)
        live[np.asarray(tables)[b, pos // bs], pos % bs] = True
    hide = ~live[:, None, :, None]
    again = jax.jit(paged_decode_attention)(
        q, jnp.where(hide, 9.0, pool_k).astype(dtype),
        jnp.where(hide, -9.0, pool_v).astype(dtype), tables, offsets)
    invariant = bool(jnp.array_equal(got, again))
    ok = err / scale < 2e-2 and invariant
    print(json.dumps({
        "check": (f"paged_decode_vs_gather_onchip slots={slots} kv={kv} "
                  f"h={h} bs={bs} nb={nb} d={d}"),
        "max_abs_err": err, "rel": err / scale,
        "masked_bytes_invariant": invariant, "ok": ok,
    }), flush=True)
    return ok


def check_paged_chunk_parity(slots=8, kv=2, h=4, bs=16, nb=16, d=64, s_q=8,
                             dtype=jnp.bfloat16):
    """Pallas paged-chunk kernel (S > 1: chunked/packed prefill, chunk-mode
    spec-verify) vs the gather reference, compiled on the chip, over the
    same adversarial pool matrix as the decode check but with each slot's
    chunk STARTING at its offset — boundary-straddling chunks, stale table
    tails past the last row, shared prefix blocks. Also pins the masked-byte
    invariance compiled: rewriting every pool byte outside the rows' live
    sets must not move the output by a single bit."""
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_cached_attention,
    )
    from fault_tolerant_llm_training_tpu.ops.paged_attention import (
        paged_chunk_attention,
    )

    rng = np.random.default_rng(4)
    n_pool = slots * nb + 4
    np_k = rng.standard_normal((n_pool, kv, bs, d))
    np_v = rng.standard_normal((n_pool, kv, bs, d))
    perm = rng.permutation(np.arange(1, slots * nb + 1))
    tables = perm.reshape(slots, nb).astype(np.int32)
    # offsets are chunk STARTS; rows reach offsets[b] + s_q - 1
    offsets = rng.integers(0, nb * bs - s_q, size=slots).astype(np.int32)
    offsets[0] = 2 * bs                     # chunk starts ON a boundary
    offsets[1] = bs - s_q // 2              # chunk STRADDLES a boundary
    for b in range(slots):                  # free blocks past the last row
        tables[b, (int(offsets[b]) + s_q - 1) // bs + 1:] = 0
    tables[2, -1] = n_pool - 1              # stale entry at an orphan block
    tables[3, :2] = tables[2, :2]           # shared prefix rows
    q = jnp.asarray(rng.standard_normal((slots, s_q, h, d)), dtype)
    pool_k, pool_v = jnp.asarray(np_k, dtype), jnp.asarray(np_v, dtype)
    jtables, joffsets = jnp.asarray(tables), jnp.asarray(offsets)

    want = jax.jit(paged_cached_attention)(q, pool_k, pool_v, jtables,
                                           joffsets)
    got = jax.jit(paged_chunk_attention)(q, pool_k, pool_v, jtables,
                                         joffsets)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0

    live = np.zeros((n_pool, bs), bool)
    for b in range(slots):
        for i in range(nb):
            for lane in range(bs):
                if i * bs + lane <= int(offsets[b]) + s_q - 1:
                    live[tables[b, i], lane] = True
    mask = live[:, None, :, None]
    k2 = jnp.asarray(np.where(mask, np_k, rng.standard_normal(np_k.shape)),
                     dtype)
    v2 = jnp.asarray(np.where(mask, np_v, rng.standard_normal(np_v.shape)),
                     dtype)
    got2 = jax.jit(paged_chunk_attention)(q, k2, v2, jtables, joffsets)
    invariant = bool(jnp.array_equal(got, got2))

    ok = err / scale < 2e-2 and invariant
    print(json.dumps({
        "check": (f"paged_chunk_vs_gather_onchip slots={slots} kv={kv} "
                  f"h={h} bs={bs} nb={nb} d={d} s_q={s_q}"),
        "max_abs_err": err, "rel": err / scale,
        "masked_bytes_bitwise_invariant": invariant, "ok": ok,
    }), flush=True)
    return ok


def check_tree_verify_parity(slots=8, kv=2, h=4, bs=16, nb=16, d=64,
                             dtype=jnp.bfloat16):
    """Ancestor-masked tree-verify: pallas in-place kernel vs the gather
    reference, compiled on the chip, over the adversarial pool matrix
    (shuffled tables, window starting ON and STRADDLING block boundaries,
    stale table tails, an orphan-block entry, shared prefix rows). The
    tree window is a real TreeShape's flattened rows — the exact (S, S)
    visibility matrix the engine bakes into its verify programs. Also
    pins the masked-byte bitwise invariance: rewriting every pool byte
    outside the committed prefixes + tree windows must not move a bit."""
    from fault_tolerant_llm_training_tpu.inference.engine import TreeShape
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_tree_attention,
    )

    shape = TreeShape((2, 2, 1))
    s_q = shape.size
    anc = jnp.asarray(shape.anc_mask)
    rng = np.random.default_rng(6)
    n_pool = slots * nb + 4
    np_k = rng.standard_normal((n_pool, kv, bs, d))
    np_v = rng.standard_normal((n_pool, kv, bs, d))
    perm = rng.permutation(np.arange(1, slots * nb + 1))
    tables = perm.reshape(slots, nb).astype(np.int32)
    # offsets are committed lengths; tree row j sits at offsets[b] + j
    offsets = rng.integers(0, nb * bs - s_q, size=slots).astype(np.int32)
    offsets[0] = 2 * bs                     # window starts ON a boundary
    offsets[1] = bs - s_q // 2              # window STRADDLES a boundary
    for b in range(slots):                  # free blocks past the window
        tables[b, (int(offsets[b]) + s_q - 1) // bs + 1:] = 0
    tables[2, -1] = n_pool - 1              # stale entry at an orphan block
    tables[3, :2] = tables[2, :2]           # shared prefix rows
    q = jnp.asarray(rng.standard_normal((slots, s_q, h, d)), dtype)
    pool_k, pool_v = jnp.asarray(np_k, dtype), jnp.asarray(np_v, dtype)
    jtables, joffsets = jnp.asarray(tables), jnp.asarray(offsets)

    def ref(q, k, v, t, o):
        return paged_tree_attention(q, k, v, t, o, anc, impl="gather")

    def ker(q, k, v, t, o):
        return paged_tree_attention(q, k, v, t, o, anc, impl="pallas")

    want = jax.jit(ref)(q, pool_k, pool_v, jtables, joffsets)
    got = jax.jit(ker)(q, pool_k, pool_v, jtables, joffsets)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0

    live = np.zeros((n_pool, bs), bool)
    for b in range(slots):
        for i in range(nb):
            for lane in range(bs):
                if i * bs + lane <= int(offsets[b]) + s_q - 1:
                    live[tables[b, i], lane] = True
    mask = live[:, None, :, None]
    k2 = jnp.asarray(np.where(mask, np_k, rng.standard_normal(np_k.shape)),
                     dtype)
    v2 = jnp.asarray(np.where(mask, np_v, rng.standard_normal(np_v.shape)),
                     dtype)
    got2 = jax.jit(ker)(q, k2, v2, jtables, joffsets)
    invariant = bool(jnp.array_equal(got, got2))

    ok = err / scale < 2e-2 and invariant
    print(json.dumps({
        "check": (f"tree_verify_vs_gather_onchip slots={slots} kv={kv} "
                  f"h={h} bs={bs} nb={nb} d={d} "
                  f"shape={','.join(map(str, shape.fanouts))}"),
        "max_abs_err": err, "rel": err / scale,
        "masked_bytes_bitwise_invariant": invariant, "ok": ok,
    }), flush=True)
    return ok


def _quantize_pool(np_pool):
    """Per-(block, kv-head) symmetric int8, the same rule the paged write
    path applies at local position 0 (inference/kv_cache.py)."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KV_QUANT_QMAX,
        QuantPool,
    )

    a = np.asarray(np_pool, np.float32)
    amax = np.max(np.abs(a), axis=(2, 3))
    scale = np.where(amax > 0, amax / KV_QUANT_QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(a / scale[:, :, None, None]),
                -KV_QUANT_QMAX, KV_QUANT_QMAX).astype(np.int8)
    return QuantPool(q=jnp.asarray(q), scale=jnp.asarray(scale))


def check_quantized_decode_parity(slots=8, kv=2, h=4, bs=16, nb=16, d=64,
                                  dtype=jnp.bfloat16):
    """int8 KV pools, compiled: the fused-dequant pallas kernels (S=1
    decode, S>1 chunk, tree-verify) vs the int8 gather oracle must agree
    to kernel-numerics tolerance, and the int8 path vs the UNQUANTIZED
    bf16 gather reference must stay inside the per-block-scale
    quantization error bound — over the same adversarial pool matrix as
    the bf16 checks (garbage null block, freed tails at block 0, stale
    entries aimed at orphan blocks, shared/COW prefix rows, offsets ON
    and STRADDLING block boundaries)."""
    from fault_tolerant_llm_training_tpu.inference.engine import TreeShape
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_cached_attention,
        paged_tree_attention,
    )
    from fault_tolerant_llm_training_tpu.ops.paged_attention import (
        paged_chunk_attention,
        paged_decode_attention,
    )

    shape = TreeShape((2, 2, 1))
    s_q = shape.size
    anc = jnp.asarray(shape.anc_mask)
    rng = np.random.default_rng(7)
    n_pool = slots * nb + 4
    np_k = rng.standard_normal((n_pool, kv, bs, d))
    np_v = rng.standard_normal((n_pool, kv, bs, d))
    perm = rng.permutation(np.arange(1, slots * nb + 1))
    tables = perm.reshape(slots, nb).astype(np.int32)
    offsets = rng.integers(s_q, nb * bs - s_q, size=slots).astype(np.int32)
    offsets[0] = 2 * bs                     # ON a block boundary
    offsets[1] = bs - s_q // 2              # chunk/window STRADDLES one
    for b in range(slots):                  # freed tails back at block 0
        tables[b, (int(offsets[b]) + s_q - 1) // bs + 1:] = 0
    tables[2, -1] = n_pool - 1              # stale entry at an orphan block
    tables[3, :2] = tables[2, :2]           # shared (COW-parent) rows
    pool_k, pool_v = jnp.asarray(np_k, dtype), jnp.asarray(np_v, dtype)
    qk, qv = _quantize_pool(np_k), _quantize_pool(np_v)
    jtables, joffsets = jnp.asarray(tables), jnp.asarray(offsets)
    q1 = jnp.asarray(rng.standard_normal((slots, 1, h, d)), dtype)
    qs = jnp.asarray(rng.standard_normal((slots, s_q, h, d)), dtype)

    def rel(got, want):
        e = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                  - want.astype(jnp.float32))))
        s = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
        return e / s

    report, ok = {}, True
    # (path, fused-on-int8, oracle-on-int8, bf16 reference)
    paths = [
        ("decode",
         jax.jit(paged_decode_attention)(q1, qk, qv, jtables, joffsets),
         jax.jit(paged_cached_attention)(q1, qk, qv, jtables, joffsets),
         jax.jit(paged_cached_attention)(q1, pool_k, pool_v, jtables,
                                         joffsets)),
        ("chunk",
         jax.jit(paged_chunk_attention)(qs, qk, qv, jtables, joffsets),
         jax.jit(paged_cached_attention)(qs, qk, qv, jtables, joffsets),
         jax.jit(paged_cached_attention)(qs, pool_k, pool_v, jtables,
                                         joffsets)),
        ("tree",
         jax.jit(lambda *a: paged_tree_attention(*a, anc, impl="pallas"))(
             qs, qk, qv, jtables, joffsets),
         jax.jit(lambda *a: paged_tree_attention(*a, anc, impl="gather"))(
             qs, qk, qv, jtables, joffsets),
         jax.jit(lambda *a: paged_tree_attention(*a, anc, impl="gather"))(
             qs, pool_k, pool_v, jtables, joffsets)),
    ]
    for name, fused, oracle, ref16 in paths:
        r_oracle = rel(fused, oracle)   # kernel numerics, same int8 bytes
        r_quant = rel(fused, ref16)     # quantization error itself
        report[f"rel_{name}_vs_int8_oracle"] = r_oracle
        report[f"rel_{name}_vs_bf16_ref"] = r_quant
        ok &= r_oracle < 2e-2 and r_quant < 5e-2
    print(json.dumps({
        "check": (f"quantized_decode_parity slots={slots} kv={kv} h={h} "
                  f"bs={bs} nb={nb} d={d}"),
        **{k: round(v, 6) for k, v in report.items()}, "ok": ok,
    }), flush=True)
    return ok


def main():
    from fault_tolerant_llm_training_tpu.ops.flash_attention import _interpret
    if _interpret():
        sys.exit(f"kernel_checks: backend {jax.default_backend()!r} would run "
                 f"the Pallas kernels interpreted; this script checks the "
                 f"COMPILED kernels and runs on a TPU only (the CPU tests "
                 f"cover interpret mode)")
    ok = True
    if "--paged-only" not in sys.argv[1:]:
        ok &= _training_kernel_checks()
    ok &= check_paged_decode_parity()                       # serving, D=64
    ok &= check_paged_decode_parity(h=8, kv=4, d=128)       # flagship width
    # InternLM2's heads over tables past one page group: the loop
    ok &= check_paged_decode_parity(h=16, kv=8, d=128, nb=80)
    ok &= check_paged_chunk_parity()                        # S>1 chunk, D=64
    ok &= check_paged_chunk_parity(h=8, kv=4, d=128)        # flagship width
    ok &= check_tree_verify_parity()                        # tree spec, D=64
    ok &= check_tree_verify_parity(h=8, kv=4, d=128)        # flagship width
    ok &= check_quantized_decode_parity()                   # int8 KV, D=64
    ok &= check_quantized_decode_parity(h=8, kv=4, d=128)   # flagship width
    ok &= check_quantized_decode_parity(h=8, kv=4, d=128, nb=80)  # looped
    sys.exit(0 if ok else 1)


def _training_kernel_checks() -> bool:
    ok = True
    ok &= check_flash_parity(2048, 12, 12, 64)   # resident, bench shape
    ok &= check_flash_parity(4096, 4, 2, 64)     # resident past 2,048, GQA
    ok &= check_flash_parity(16384, 4, 2, 64)    # resident past 16 MiB, GQA
    with _streaming_family():
        ok &= check_flash_parity(16384, 4, 2, 64)    # streamed fwd, split bwd
    ok &= check_rope_fused_parity(2048, 12, 12, 64)  # in-kernel rope, bench
    ok &= check_rope_fused_parity(4096, 4, 2, 64)    # rope, resident, 4,096
    # D=128 (the flagship llama head width; VERDICT r4 next-step #7): the
    # tiles were calibrated at D=64 — these pin that the dispatch is
    # CORRECT at double the head width, resident at 2,048 and 4,096 rows,
    # and with the streaming family forced.
    ok &= check_flash_parity(2048, 4, 2, 128)    # resident, GQA
    ok &= check_flash_parity(4096, 4, 2, 128)    # resident past 2,048
    with _streaming_family():
        ok &= check_flash_parity(4096, 4, 2, 128)    # streamed fwd, split bwd
    ok &= check_rope_fused_parity(2048, 4, 2, 128)  # rope at its S*D bound
    ok &= check_ring_carry_64k()
    ok &= check_ring_carry_64k(s=32768, sp=4, h=2, kv=2, d=128)
    return ok


if __name__ == "__main__":
    main()
