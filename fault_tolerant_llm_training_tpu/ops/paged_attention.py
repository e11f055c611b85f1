"""Pallas paged-attention kernels: block-indexed KV reads in place.

The XLA-level paged path (ops/attention.py ``paged_cached_attention``)
gathers each slot's pool blocks into a transient contiguous (B, K, T, D)
copy and runs the ring kernel's einsum on it — correct by construction,
but the gather is an extra full-cache pass per layer per dispatch. This
module is the serving-side member of the repo's Pallas kernel family
(flash_attention.py prefill, ring_flash.py sequence-parallel): the block
table rides in as a scalar-prefetch operand and every DMA is aimed
straight at ``pool[tables[b, j]]`` — the pool is read THROUGH the table
with no gathered intermediate, vLLM's PagedAttention fused with
flash-decoding's split-KV online softmax.

Three kernels, dispatched by ``ops/attention.py paged_attention(impl=)``
on the query length (and ``paged_tree_attention``):

- :func:`paged_decode_attention` — S = 1 (the decode step; the query
  rows of a kv head are its GQA group, (G, D)). The round's hot read: the
  pools stay in HBM, the kernel's own DMAs move one whole ``(K, bs, D)``
  pool block each — all kv heads of ``bs`` positions, contiguous in the
  pool's layout — a few dozen pages a double-buffered step, and only the
  pages under each slot's length: bytes follow the LIVE tokens.
- :func:`paged_chunk_attention` — S > 1 (chunked prefill, chunk-mode
  spec-verify): a grid over (slot, kv head, table entry) whose BlockSpec
  index maps aim one ``(bs, D)`` page of one head a step, the q block the
  chunk's S*G rows, the causal boundary applied per row.
- :func:`paged_tree_chunk_attention` — S > 1 TREE-verify (tree
  speculative decoding): the chunk kernel with the speculative window's
  causal rule replaced by a per-row ancestor mask, dispatched by
  ``ops/attention.py paged_tree_attention(impl=)``.

MASKING (the single statement of the rationale, for both kernels and
for the gather reference that ops/attention.py keeps selectable):
everything is positional. A query at absolute position ``p`` attends
keys at ``k_pos <= p`` — decode has one position per slot
(``offsets[b]``), a chunk has ``offsets[b] + s`` for its s-th row.
Everything the gather path neutralizes with its additive ``finfo.min``
mask — null-block-0 garbage behind unallocated table entries, stale KV
in freed-and-reused blocks, the written-ahead tail of a COW'd final
block, the unwritten pad tail of a partial prefill chunk — sits past
that per-row boundary, so the same comparison excludes it here: masked
lanes get ``exp2(NEG_INF - m) == 0`` probability exactly, and blocks
that start past the LAST row's boundary are skipped wholesale
(``@pl.when``), never touching the accumulator. The output is therefore
bitwise invariant to the bytes in masked positions (asserted,
tests/test_paged_kernel.py). Shared prefix blocks need no handling at
all: a block referenced by several rows is simply DMA'd for each, same
bytes.

Numerics follow the house flash-decoding scheme (flash_attention.py):
base-2 online softmax with ``log2(e)`` folded into the q prescale, fp32
(m, l, acc) carried across the block axis (VMEM scratch in the grid
kernels, the page-group loop's carry in the decode kernel), one rescale +
normalize at the last block. Accumulation order therefore differs from
the gather path's full-row softmax — equality holds to fp32 accumulation
tolerance, not bitwise, which is why the engine keeps the gather program
selectable as the bit-exact reference (``--paged-kernel gather``).

QUANTIZED POOLS (``--kv-dtype int8``): when the pools arrive as
``kv_cache.QuantPool`` (int8 data + per-(block, kv-head) fp32 scales),
the scale pools ride along as two extra scalar-prefetch operands —
(N, K) fp32 in SMEM, looked up with the same dynamic scalar indexing as
the block table — and each kernel dequantizes the block right after its
DMA lands in VMEM, with exactly ``ops/attention.py dequant_kv``'s rule
(fp32 multiply, cast to q dtype). The gather reference dequantizes
after gather with the same rule, so the two paths still differ only by
online-softmax accumulation order; scripts/kernel_checks.py
``check_quantized_decode_parity`` pins the int8-vs-fp32 bound at D=64
and D=128 over the same adversarial pool matrix.

Runs under ``interpret=True`` off-TPU like every kernel here, so tier-1
asserts the equivalence on CPU (tests/test_paged_kernel.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.trace import scope
from .flash_attention import LOG2E, NEG_INF, _interpret

# m/l scratch rides full lanes: TPU VMEM tiles pad the trailing dim to
# 128 anyway, and a (G, 128) broadcast store beats a strided (G, 1) one.
_STAT_LANES = 128

# Mosaic tile knob of the S>1 chunk kernel (ROADMAP D=128 tile-tuning
# follow-up): how many kv heads one grid step processes. A tile of T fuses
# T heads' (bs, D) KV DMAs and dots into one step — fewer grid steps,
# larger VMEM tiles — at T× the scratch. 1 is the recorded
# CPU-interpret-safe default (the sweep found no CPU win above it). A tile
# that does not divide the pool's kv-head count falls back to 1.
CHUNK_HEAD_TILE = 1

# What the S=1 kernel sizes its page groups from (_decode_pages_per_step):
# the positions one compute block wants, and the VMEM its four page
# buffers may take (well inside the 16 MiB a v5e kernel may scope).
_DECODE_SPAN = 512
_DECODE_BUFFER_BYTES = 4 << 20


def _split_quant_pools(k_pool, v_pool):
    """Unpack possibly-quantized pools for the pallas_call plumbing.

    Returns ``(k_data, v_data, scale_ops)``: the raw (N, K, bs, D) data
    arrays plus the extra scalar-prefetch operands — ``(k_scale,
    v_scale)`` (each (N, K) fp32, ridden to SMEM like the block table)
    when the pools are int8 :class:`QuantPool`s, else ``()``. Mixed
    quantization of K vs V is rejected: the write path quantizes both
    or neither.
    """
    from ..inference.kv_cache import QuantPool  # lazy: avoid import cycle
    kq, vq = isinstance(k_pool, QuantPool), isinstance(v_pool, QuantPool)
    if kq != vq:
        raise TypeError(f"k/v pools must be quantized together, got "
                        f"k={type(k_pool).__name__} "
                        f"v={type(v_pool).__name__}")
    if not kq:
        return k_pool, v_pool, ()
    return k_pool.q, v_pool.q, (k_pool.scale, v_pool.scale)


def _dequant_block(blk, scale_ref, pool_blk, kv_head, out_dtype):
    """Fused dequant at the point the block DMA landed in VMEM.

    ``blk`` is the int8 (bs, D) slice just read through the table;
    ``scale_ref`` the scalar-prefetched (N, K) fp32 scale pool in SMEM,
    looked up at (pool block id, kv head) with the same dynamic scalar
    indexing the table ride-along already uses. MUST match ops/
    attention.py ``dequant_kv`` exactly — fp32 multiply, cast to the
    query dtype — so the gather oracle and the fused kernels disagree
    only by online-softmax accumulation order (the PR 8 tolerance),
    never by dequant rule.
    """
    return (blk.astype(jnp.float32)
            * scale_ref[pool_blk, kv_head]).astype(out_dtype)


def _decode_pages_per_step(nb: int, kv: int, bs: int, d: int,
                           itemsize: int) -> int:
    """How many pool blocks one step of the S=1 kernel moves and consumes.

    Read off the shapes, never set by a caller: the compute block wants
    ``_DECODE_SPAN`` positions (a few hundred keys a matmul, so a long
    slot is a few dozen steps and not one a page), cut down to the table's
    width and to what four page buffers (K and V, double-buffered) may
    take of VMEM. A page is counted as it lies in VMEM — lanes padded to
    128, sublanes to the dtype's tile.
    """
    sublanes = 8 * 4 // itemsize
    page = kv * -(-bs // sublanes) * sublanes * -(-d // 128) * 128 * itemsize
    return max(1, min(nb, _DECODE_SPAN // bs,
                      _DECODE_BUFFER_BYTES // (4 * page)))


def _decode_kernel(tables_ref, offs_ref, *args, block_size: int, pages: int,
                   nb: int, scale: float, quantized: bool = False):
    """One slot a grid step; inside it a loop over the slot's LIVE page
    groups, ``pages`` pool blocks each, all kv heads at once.

    The pools stay in HBM. A page is one contiguous ``(K, bs, D)`` pool
    block — every kv head's rows of ``bs`` positions — and one DMA lands
    it whole in slot ``buf`` of the double-buffered ``(2, pages, K, bs,
    D)`` VMEM scratch, aimed at ``tables[b, j]`` like the other kernels'
    index maps. While group ``g`` is contracted, group ``g + 1`` of the
    same slot — or group 0 of the next slot, across the grid step — is
    already in flight into the other buffer (the parity rides in SMEM
    scratch between steps).

    LIVE-SIZED: slot ``b`` runs ``ceil(n / pages)`` groups for its ``n =
    offsets[b] // bs + 1`` pages under its query position, a dynamic trip
    count, and the last group starts (and waits for) only its live pages'
    DMAs. Table entries past the slot's length are never followed and cost
    nothing; an inactive slot costs one page. What a short group leaves of
    the buffer is an older group's pool bytes or the zeros the first step
    wrote — finite either way, and masked below.

    Per group and kv head the arithmetic is the family's: (G, D) x (T, D)
    scores in fp32 over the group's ``T = pages * bs`` positions,
    positional mask ``k_pos <= offsets[b]``, base-2 online softmax on the
    fp32 (m, l, acc) the group loop carries, probabilities cast to the
    value dtype before ``p @ V``. The head loop is a static unroll, taken
    phase by phase (every head's scores, then every head's softmax, ...)
    so that the heads' dependent chains overlap: a third of the time of
    head-by-head over VMEM scratch, on the chip.

    ``quantized`` (static) reads int8 pool blocks with two extra
    scalar-prefetch operands — the (N, K) fp32 k/v scale pools — and
    dequantizes each page as it is taken out of the buffer
    (:func:`_dequant_block`). The positional mask is unchanged, so masked
    int8 garbage (null block, stale tails — including pool rows whose
    scale[0] entry holds junk from diverted null-row writes) still
    contributes exactly zero probability: dequant keeps every lane finite
    (finite int8 x finite fp32 scale), and finite lanes past the boundary
    underflow to 0.0.
    """
    if quantized:
        ksc_ref, vsc_ref, *args = args
    else:
        ksc_ref = vsc_ref = None
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, parity = args
    b = pl.program_id(0)
    _, kv, g, d = q_ref.shape
    span = pages * block_size
    pools = ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))

    def n_pages(slot):  # pages at or under the slot's query position
        return jnp.minimum(offs_ref[slot] // block_size + 1, nb)

    def for_live_pages(slot, grp, buf, act):
        """``act`` (start or wait) the DMAs of group ``grp`` of ``slot``."""
        base = slot * nb + grp * pages

        def _page(i, _):
            blk = tables_ref[base + i]
            for hbm, vmem, which in pools:
                act(pltpu.make_async_copy(hbm.at[blk], vmem.at[buf, i],
                                          sems.at[which, buf]))

        jax.lax.fori_loop(
            0, jnp.minimum(n_pages(slot) - grp * pages, pages), _page, None)

    def wait_group(grp, buf):
        live = n_pages(b) - grp * pages

        @pl.when(live >= pages)
        def _whole():
            # a group's pages all signal one semaphore: a whole group is
            # waited for at once, by the buffer's size
            for _, vmem, which in pools:
                pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                      sems.at[which, buf]).wait()

        @pl.when(live < pages)
        def _some():
            for_live_pages(b, grp, buf, lambda c: c.wait())

    @pl.when(b == 0)
    def _first():
        # p == 0 on a page no DMA has written must meet finite V
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        parity[0] = 0
        for_live_pages(0, 0, 0, lambda c: c.start())

    offset = offs_ref[b]  # this slot's decode position (committed length)
    groups = (n_pages(b) + pages - 1) // pages
    first_buf = parity[0]
    heads = range(kv)
    q2 = [(q_ref[0, h].astype(jnp.float32)
           * (scale * LOG2E)).astype(q_ref.dtype) for h in heads]  # (G, D)

    def _group(grp, carry):
        m, l, acc = carry  # per kv head: (G, 1), (G, 1), (G, D) fp32
        buf = (first_buf + grp) % 2
        more = grp + 1 < groups
        nxt_slot = jnp.where(more, b, b + 1)

        @pl.when(nxt_slot < pl.num_programs(0))
        def _prefetch():
            for_live_pages(nxt_slot, jnp.where(more, grp + 1, 0), 1 - buf,
                           lambda c: c.start())

        wait_group(grp, buf)
        if quantized:
            # a page this group did not load keeps older int8 bytes and
            # takes the scale of some entry of the slot's table: finite
            last = b * nb + nb - 1
            blks = [tables_ref[jnp.minimum(b * nb + grp * pages + i, last)]
                    for i in range(pages)]

            def dequant(ref, sc_ref, h):
                return jnp.concatenate([
                    _dequant_block(ref[buf, i, h], sc_ref, blks[i], h,
                                   q_ref.dtype) for i in range(pages)],
                    axis=0)
            k = [dequant(kbuf, ksc_ref, h) for h in heads]
            v = [dequant(vbuf, vsc_ref, h) for h in heads]
        else:
            k = [kbuf[buf, :, h].reshape(span, d) for h in heads]
            v = [vbuf[buf, :, h].reshape(span, d) for h in heads]
        visible = (grp * span + jax.lax.broadcasted_iota(
            jnp.int32, (g, span), 1)) <= offset
        s = [jnp.where(visible, jax.lax.dot_general(        # (G, T) fp32
            q2[h], k[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), NEG_INF) for h in heads]
        m_new = [jnp.maximum(m[h], jnp.max(s[h], axis=-1, keepdims=True))
                 for h in heads]
        p = [jnp.exp2(s[h] - m_new[h]) for h in heads]
        alpha = [jnp.exp2(m[h] - m_new[h]) for h in heads]
        l_new = [l[h] * alpha[h] + jnp.sum(p[h], axis=-1, keepdims=True)
                 for h in heads]
        acc_new = [acc[h] * alpha[h] + jax.lax.dot_general(
            p[h].astype(v[h].dtype), v[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for h in heads]
        return tuple(m_new), tuple(l_new), tuple(acc_new)

    _, l, acc = jax.lax.fori_loop(0, groups, _group, (
        tuple(jnp.full((g, 1), NEG_INF, jnp.float32) for _ in heads),
        tuple(jnp.zeros((g, 1), jnp.float32) for _ in heads),
        tuple(jnp.zeros((g, d), jnp.float32) for _ in heads)))
    parity[0] = (first_buf + groups) % 2
    # l >= exp2(0) always: position ``offset`` itself is in range (the
    # decode writes the query token's KV before attending).
    for h in heads:
        o_ref[0, h] = (acc[h] / l[h]).astype(o_ref.dtype)


@scope("kv_read")
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           offsets: jnp.ndarray,
                           interpret: bool = None) -> jnp.ndarray:
    """S=1 GQA paged attention reading pool blocks in place via the table.

    q:            (B, 1, H, D) decode queries (rope applied, KV written).
    k/v_pool:     (N, K, bs, D) global block pools (kv_cache.py layout).
    block_tables: (B, NB) int32 — slot b's logical block j is pool block
                  ``block_tables[b, j]``; 0 (the null block) for
                  unallocated entries.
    offsets:      (B,) int32 query positions; keys at ``k_pos <=
                  offsets[b]`` attend, everything else is masked (see
                  module docstring for why that alone covers every
                  adversarial pool state).

    Returns (B, 1, H, D), equal to ``paged_cached_attention`` on the same
    operands to fp32 accumulation tolerance. Bytes moved follow the live
    tokens, not the tables' width (:func:`_decode_kernel`).

    k/v_pool may be :class:`~..inference.kv_cache.QuantPool` (int8 data
    + (N, K) fp32 scales): the scales ride as two extra scalar-prefetch
    operands and the kernel dequantizes each block in place — same
    positional masking, same tolerance against the (dequantizing)
    gather oracle.
    """
    if q.shape[1] != 1:
        raise ValueError(f"paged_decode_attention is S=1-specialized, got "
                         f"S={q.shape[1]} (multi-token shapes take "
                         f"paged_chunk_attention — ops/attention.py "
                         f"paged_attention routes)")
    k_pool, v_pool, scale_ops = _split_quant_pools(k_pool, v_pool)
    interpret = _interpret() if interpret is None else interpret
    if not (interpret or decode_pages_whole(q.shape[3])):
        # the chunk kernel's per-page grid at S=1 (what this kernel was
        # before it moved whole pages): in place, and slow. The
        # interpreter has no such limit, so the CPU tests run the kernel
        # below at every head size.
        return _page_grid_attention(q, k_pool, v_pool, scale_ops,
                                    block_tables, offsets, interpret)
    _, kv, bs, d = k_pool.shape
    return _paged_decode(
        q, k_pool, v_pool, scale_ops, block_tables, offsets,
        pages=_decode_pages_per_step(block_tables.shape[1], kv, bs, d,
                                     k_pool.dtype.itemsize),
        interpret=interpret)


def decode_pages_whole(head_dim: int) -> bool:
    """Whether the S=1 kernel can move whole pool blocks at this head size.

    Its DMAs slice pages out of the HBM pool, and Mosaic (libtpu 0.0.34)
    lays an HBM ref out in 128-lane tiles and refuses a slice whose last
    dim is not a multiple of that — D = 64 pads to 128 in HBM and the
    (K, bs, 64) page no longer divides it. Such heads keep the per-page
    BlockSpec grid; ``paged_attention``'s ``"auto"`` rule reads this too.
    """
    return head_dim % 128 == 0


# Called once a layer with the same shapes: under its own jit the kernel
# body (a static unroll over the kv heads) is traced and lowered once a
# program, not once a layer; XLA inlines the calls.
@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def _paged_decode(q, k_pool, v_pool, scale_ops, block_tables, offsets, *,
                  pages: int, interpret: bool):
    b, _, h, d = q.shape
    n, kv, bs, _ = k_pool.shape
    g = h // kv
    nb = block_tables.shape[1]
    kernel = functools.partial(_decode_kernel, block_size=bs, pages=pages,
                               nb=nb, scale=1.0 / math.sqrt(d),
                               quantized=bool(scale_ops))
    slot_spec = pl.BlockSpec((1, kv, g, d),
                             lambda bi, *pref: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(scale_ops),
            grid=(b,),
            in_specs=[slot_spec,
                      pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((2, pages, kv, bs, d), k_pool.dtype),
                pltpu.VMEM((2, pages, kv, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),      # (k|v, buffer)
                pltpu.SMEM((1,), jnp.int32),          # buffer parity
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        # the prefetched group and the buffer parity cross grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), offsets.astype(jnp.int32),
      *scale_ops, q.reshape(b, kv, g, d), k_pool, v_pool)
    return out.reshape(b, 1, h, d)


def _chunk_kernel(tables_ref, offs_ref, *args, block_size: int, group: int,
                  s_q: int, scale: float, head_tile: int = 1,
                  quantized: bool = False):
    """One (slot b, kv-head tile h, logical block j) grid step, S > 1 rows.

    The q block is the chunk's S*G rows for each tiled kv head, s-major:
    row r is query position ``offsets[b] + r // group``, group member
    ``r % group``. Same online-softmax carry as :func:`_decode_kernel`
    (one rows-band per tiled head, statically unrolled), but the causal
    boundary is applied PER ROW — one iota-derived q_pos column against
    the block's k_pos row — and the wholesale block skip keys off the
    LAST row's boundary (a block any row can see must run; rows that
    can't see it get every lane masked, exp2 underflows to 0.0 exactly,
    their carry is untouched). ``quantized`` fuses the int8 block
    dequant exactly as in :func:`_decode_kernel`.
    """
    if quantized:
        (ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = args
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = args
        ksc_ref = vsc_ref = None
    b = pl.program_id(0)
    ht_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    offset = offs_ref[b]  # this slot's chunk start (first row's position)
    rows = s_q * group

    @pl.when(j * block_size <= offset + (s_q - 1))
    def _block():
        for hh in range(head_tile):
            lo, hi = hh * rows, (hh + 1) * rows
            kb, vb = k_ref[0, hh], v_ref[0, hh]
            if quantized:
                blk = tables_ref[b * pl.num_programs(2) + j]
                kvh = ht_i * head_tile + hh
                kb = _dequant_block(kb, ksc_ref, blk, kvh, q_ref.dtype)
                vb = _dequant_block(vb, vsc_ref, blk, kvh, q_ref.dtype)
            q2 = (q_ref[0, hh].astype(jnp.float32)
                  * (scale * LOG2E)).astype(q_ref.dtype)       # (rows, D)
            s = jax.lax.dot_general(                           # (rows, bs)
                q2, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            k_pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_size), 1)
            q_pos = offset + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_size), 0) // group
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            m_prev, l_prev = m_scr[lo:hi, 0], l_scr[lo:hi, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc_scr[lo:hi, :] = (acc_scr[lo:hi, :] * alpha[:, None]
                                 + jax.lax.dot_general(
                                     p.astype(vb.dtype), vb,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32))
            m_scr[lo:hi, :] = jnp.broadcast_to(
                m_new[:, None], (rows, m_scr.shape[1]))
            l_scr[lo:hi, :] = jnp.broadcast_to(
                l_new[:, None], (rows, l_scr.shape[1]))

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        # l >= exp2(0) for every row: k_pos = 0 satisfies the row's own
        # boundary (offset >= 0), and block 0 always runs.
        for hh in range(head_tile):
            lo, hi = hh * rows, (hh + 1) * rows
            o_ref[0, hh] = (acc_scr[lo:hi, :]
                            / l_scr[lo:hi, :1]).astype(o_ref.dtype)


@scope("kv_read")
def paged_chunk_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                          v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                          offsets: jnp.ndarray,
                          interpret: bool = None) -> jnp.ndarray:
    """S>1 GQA paged attention reading pool blocks in place via the table.

    The multi-token counterpart of :func:`paged_decode_attention` for
    chunked prefill and chunk-mode spec-verify: same scalar-prefetched
    (B, K, NB) grid, but the q block carries the chunk's S*G rows and the
    causal mask is per row (``k_pos <= offsets[b] + s`` for the chunk's
    s-th query — exactly ``cached_attention``'s additive mask, stated
    positionally; module docstring has the full equivalence argument).

    q:            (B, S, H, D) chunk queries (rope applied, KV written).
    k/v_pool:     (N, K, bs, D) global block pools.
    block_tables: (B, NB) int32 per-slot tables (0 = null block).
    offsets:      (B,) int32 — row s of slot b sits at absolute position
                  ``offsets[b] + s``.

    Returns (B, S, H, D), equal to ``paged_cached_attention`` on the same
    operands to fp32 accumulation tolerance (pad rows past a partial
    chunk's valid length read the same unwritten pool bytes both paths
    read — callers discard those rows).
    """
    if q.shape[1] < 2:
        raise ValueError(f"paged_chunk_attention wants S > 1, got "
                         f"S={q.shape[1]} (S=1 is paged_decode_attention's "
                         f"shape)")
    k_pool, v_pool, scale_ops = _split_quant_pools(k_pool, v_pool)
    return _page_grid_attention(
        q, k_pool, v_pool, scale_ops, block_tables, offsets,
        _interpret() if interpret is None else interpret)


def _page_grid_attention(q, k_pool, v_pool, scale_ops, block_tables,
                         offsets, interpret: bool):
    """The chunk kernel's call, at any S: grid (slot, kv-head tile, table
    entry), one (bs, D) page of one head a step."""
    b, s_q, h, d = q.shape
    n, kv, bs, _ = k_pool.shape
    g = h // kv
    nb = block_tables.shape[1]
    rows = s_q * g
    ht = CHUNK_HEAD_TILE if kv % CHUNK_HEAD_TILE == 0 else 1
    # s-major rows per kv head: (B, S, K, G, D) -> (B, K, S*G, D), so row
    # r is (position r // g, group member r % g) — what the kernel's
    # per-row q_pos iota assumes.
    qr = (q.reshape(b, s_q, kv, g, d)
          .transpose(0, 2, 1, 3, 4).reshape(b, kv, rows, d))
    tables = block_tables.reshape(-1).astype(jnp.int32)
    offs = offsets.astype(jnp.int32)
    kernel = functools.partial(_chunk_kernel, block_size=bs, group=g,
                               s_q=s_q, scale=1.0 / math.sqrt(d),
                               head_tile=ht, quantized=bool(scale_ops))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(scale_ops),
            grid=(b, kv // ht, nb),
            in_specs=[
                pl.BlockSpec((1, ht, rows, d),
                             lambda bi, hi, j, t, *pref: (bi, hi, 0, 0)),
                pl.BlockSpec((1, ht, bs, d),
                             lambda bi, hi, j, t, *pref: (t[bi * nb + j],
                                                          hi, 0, 0)),
                pl.BlockSpec((1, ht, bs, d),
                             lambda bi, hi, j, t, *pref: (t[bi * nb + j],
                                                          hi, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, ht, rows, d),
                lambda bi, hi, j, t, *pref: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((ht * rows, _STAT_LANES), jnp.float32),  # m
                pltpu.VMEM((ht * rows, _STAT_LANES), jnp.float32),  # l
                pltpu.VMEM((ht * rows, d), jnp.float32),            # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, d), q.dtype),
        interpret=interpret,
    )(tables, offs, *scale_ops, qr, k_pool, v_pool)
    return (out.reshape(b, kv, s_q, g, d)
            .transpose(0, 2, 1, 3, 4).reshape(b, s_q, h, d))


def _tree_kernel(tables_ref, offs_ref, *args, block_size: int, group: int,
                 s_q: int, scale: float, quantized: bool = False):
    """:func:`_chunk_kernel` with the causal rule swapped for the tree's
    ANCESTOR rule (tree-verify: the q rows are one flattened token tree).

    Row r (tree node ``r // group``) attends every committed key
    (``k_pos < offset``) and, inside the speculative window
    ``[offset, offset + s_q)``, exactly the keys of the nodes on its root
    path: ``anc_ref[r, j]`` gates window key ``offset + j`` (the wrapper
    hands the mask in already expanded to one row per q row — Mosaic has
    no layout for an in-kernel (s_q, group) -> (rows, 1) reshape).
    The mask is built by a static unroll over the s_q window nodes — an
    equality compare against each node's k_pos AND'd with that node's
    ancestor column — so sibling/cousin keys are NEG_INF'd and underflow
    to exact zero probability like every other masked lane; the block
    skip and the online-softmax carry are the chunk kernel's unchanged.
    Every row sees at least its own key (``anc[r, r]`` is set), so l > 0
    at emit. ``quantized`` fuses the int8 block dequant exactly as in
    :func:`_decode_kernel` (head_tile is 1 here: program_id(1) IS the
    kv head).
    """
    if quantized:
        (ksc_ref, vsc_ref, q_ref, anc_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = args
    else:
        q_ref, anc_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = args
        ksc_ref = vsc_ref = None
    b = pl.program_id(0)
    kvh = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    offset = offs_ref[b]  # committed length: the root row's position
    rows = s_q * group

    @pl.when(j * block_size <= offset + (s_q - 1))
    def _block():
        kb, vb = k_ref[0, 0], v_ref[0, 0]
        if quantized:
            blk = tables_ref[b * pl.num_programs(2) + j]
            kb = _dequant_block(kb, ksc_ref, blk, kvh, q_ref.dtype)
            vb = _dequant_block(vb, vsc_ref, blk, kvh, q_ref.dtype)
        q2 = (q_ref[0, 0].astype(jnp.float32)
              * (scale * LOG2E)).astype(q_ref.dtype)       # (rows, D)
        s = jax.lax.dot_general(                           # (rows, bs) fp32
            q2, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        vis = k_pos < offset                               # committed keys
        for t_node in range(s_q):
            col = anc_ref[:, t_node:t_node + 1]            # (rows, 1)
            vis = vis | ((k_pos == offset + t_node) & (col > 0))
        s = jnp.where(vis, s, NEG_INF)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(vb.dtype), vb,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@scope("kv_read")
def paged_tree_chunk_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray,
                               block_tables: jnp.ndarray,
                               offsets: jnp.ndarray, anc_mask: jnp.ndarray,
                               interpret: bool = None) -> jnp.ndarray:
    """Tree-verify paged attention reading pool blocks in place.

    The ancestor-masked sibling of :func:`paged_chunk_attention`: same
    scalar-prefetched (B, K, NB) grid and s-major q rows, but the per-row
    causal boundary is replaced by the tree's ancestor rule, carried as a
    dense (S, S) int32 visibility matrix rider (``anc_mask[r, j]`` != 0
    iff tree row j — cache position ``offsets[b] + j`` — is on row r's
    root path; include self and root). Committed keys below ``offsets[b]``
    attend unconditionally, keys past the window never do, so the gather
    reference (ops/attention.py ``tree_cached_attention``) and this
    kernel mask the identical position set — equal to fp32 accumulation
    tolerance, bitwise invariant to masked bytes (scripts/
    kernel_checks.py pins both at D=64 and D=128).

    q:        (B, S, H, D) flattened tree rows (rope at depth positions
              applied, KV written at ``offsets[b] + row``).
    anc_mask: (S, S) bool/int — static per tree shape; the engine bakes
              one per compiled tree program.
    """
    b, s_q, h, d = q.shape
    if s_q < 2:
        raise ValueError(f"paged_tree_chunk_attention wants S > 1, got "
                         f"S={s_q} (a one-node tree is plain decode)")
    if anc_mask.shape != (s_q, s_q):
        raise ValueError(f"anc_mask must be (S, S) = ({s_q}, {s_q}), got "
                         f"{anc_mask.shape}")
    k_pool, v_pool, scale_ops = _split_quant_pools(k_pool, v_pool)
    n, kv, bs, _ = k_pool.shape
    g = h // kv
    nb = block_tables.shape[1]
    rows = s_q * g
    qr = (q.reshape(b, s_q, kv, g, d)
          .transpose(0, 2, 1, 3, 4).reshape(b, kv, rows, d))
    tables = block_tables.reshape(-1).astype(jnp.int32)
    offs = offsets.astype(jnp.int32)
    # one mask row per q row (row r is tree node r // g)
    anc = jnp.repeat(anc_mask.astype(jnp.int32), g, axis=0)
    kernel = functools.partial(_tree_kernel, block_size=bs, group=g,
                               s_q=s_q, scale=1.0 / math.sqrt(d),
                               quantized=bool(scale_ops))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(scale_ops),
            grid=(b, kv, nb),
            in_specs=[
                pl.BlockSpec((1, 1, rows, d),
                             lambda bi, hi, j, t, *pref: (bi, hi, 0, 0)),
                pl.BlockSpec((rows, s_q),
                             lambda bi, hi, j, t, *pref: (0, 0)),
                pl.BlockSpec((1, 1, bs, d),
                             lambda bi, hi, j, t, *pref: (t[bi * nb + j],
                                                          hi, 0, 0)),
                pl.BlockSpec((1, 1, bs, d),
                             lambda bi, hi, j, t, *pref: (t[bi * nb + j],
                                                          hi, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, rows, d),
                lambda bi, hi, j, t, *pref: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, _STAT_LANES), jnp.float32),  # m
                pltpu.VMEM((rows, _STAT_LANES), jnp.float32),  # l
                pltpu.VMEM((rows, d), jnp.float32),            # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, d), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
    )(tables, offs, *scale_ops, qr, anc, k_pool, v_pool)
    return (out.reshape(b, kv, s_q, g, d)
            .transpose(0, 2, 1, 3, 4).reshape(b, s_q, h, d))
