"""Causal multi-head attention dispatch.

The reference delegates the attention kernel to the container's fused
``F.scaled_dot_product_attention(is_causal=True)`` (ref: model.py:212) after
expanding GQA KV heads with ``repeat_kv`` (ref: model.py:129-138,204-205).
On TPU the equivalents are:

- ``xla``    — einsum attention with fp32 softmax; XLA fuses it well and it is
               the portable (CPU-testable) reference semantics.
- ``pallas`` — the Pallas flash-attention kernel (ops/flash_attention.py),
               tiled for the MXU, O(S) memory.
- ``ring``   — sequence-parallel ring attention (ops/ring_attention.py) for
               long contexts sharded over the mesh's 'sequence' axis.
- ``auto``   — pallas on TPU, xla elsewhere.

GQA is handled *without* materializing repeated KV heads: the einsum reshapes
Q to (B, S, K, G, D) — K kv-groups of G = n_heads // n_kv_heads query heads —
so KV stay at their native head count (the repeat in the reference exists only
because SDPA requires matching head counts; on TPU it would waste HBM
bandwidth).
"""

import jax
import jax.numpy as jnp

from ..obs.trace import scope
from ..parallel.mesh import active_mesh, mesh_axis_size


def _causal_mask(s_q: int, s_k: int, dtype=jnp.float32) -> jnp.ndarray:
    """Additive causal mask (s_q, s_k); query i attends keys <= i (+ offset)."""
    q_pos = jnp.arange(s_q)[:, None] + (s_k - s_q)
    k_pos = jnp.arange(s_k)[None, :]
    return jnp.where(k_pos <= q_pos, 0.0, jnp.finfo(dtype).min).astype(dtype)


def xla_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """Grouped-query causal attention, fp32 softmax, einsum formulation.

    q: (B, S, H, D); k, v: (B, S, K, D) with H % K == 0.
    Matches the reference kernel semantics (model.py:204-212) — softmax over
    keys in fp32, scale 1/sqrt(D) — without the repeat_kv copy.
    """
    b, s_q, h, d = q.shape
    _, s_k, kv, _ = k.shape
    g = h // kv
    qg = q.reshape(b, s_q, kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # scores: (B, K, G, S_q, S_k)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        scores = scores + _causal_mask(s_q, s_k)[None, None, None, :, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, h, d).astype(q.dtype)


@scope("kv_read")
def cached_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     offsets: jnp.ndarray) -> jnp.ndarray:
    """Grouped-query attention against a per-slot KV cache (prefill/decode).

    q:                (B, S, H, D) — S new queries per slot at absolute
                      positions ``offsets[b] + [0, S)``.
    k_cache, v_cache: (B, K, T, D) head-major slot buffers; positions
                      ``[0, offsets[b] + S)`` must already hold this slot's
                      rotated keys/values (the caller writes before calling).
    offsets:          (B,) int32 tokens previously in each slot's cache.

    Numerics mirror :func:`xla_attention` exactly — same grouped einsum
    contraction, fp32 scores, additive ``finfo.min`` mask, fp32 softmax cast
    back to q.dtype, fp32 output accumulation — so a cached decode reproduces
    the full-forward logits bit-for-bit: masked positions (the cache tail
    beyond a slot's length) get ``exp(min) == 0`` probability exactly, and
    zero probabilities contribute exact zeros to the fp32 accumulation.
    """
    b, s_q, h, d = q.shape
    _, kv, t, _ = k_cache.shape
    g = h // kv
    qg = q.reshape(b, s_q, kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # scores: (B, K, G, S_q, T)
    scores = jnp.einsum("bqkgd,bktd->bkgqt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    q_pos = offsets[:, None] + jnp.arange(s_q)[None, :]          # (B, S_q)
    k_pos = jnp.arange(t)[None, None, :]                         # (1, 1, T)
    mask = jnp.where(k_pos <= q_pos[:, :, None], 0.0,
                     jnp.finfo(jnp.float32).min)                 # (B, S_q, T)
    scores = scores + mask[:, None, None, :, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqt,bktd->bqkgd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, h, d).astype(q.dtype)


@scope("kv_read")
def tree_cached_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                          v_cache: jnp.ndarray, offsets: jnp.ndarray,
                          anc_mask: jnp.ndarray) -> jnp.ndarray:
    """:func:`cached_attention` with a per-row ANCESTOR mask over the
    speculative window — the tree-verify attention rule.

    q:        (B, S, H, D) — the round's flattened token tree, row 0 the
              committed last token (root), rows 1..S-1 draft proposals in
              topological order; node i's KV sits at cache position
              ``offsets[b] + i`` (written contiguously, like any chunk).
    anc_mask: (S, S) bool — ``anc_mask[r, j]`` iff tree row j is on row
              r's root path (ancestors ∪ self ∪ root), so siblings and
              cousins never see each other's keys.

    The mask replaces the chunk kernel's pure causal rule: row r attends
    every COMMITTED key (``k_pos < offsets[b]``) exactly as before, plus
    the speculative-window keys ``offsets[b] + j`` with ``anc_mask[r, j]``
    set; keys past the window stay masked. Everything else — grouped
    einsum, fp32 softmax, additive ``finfo.min`` mask with exact-zero
    masked probabilities — is :func:`cached_attention` byte for byte, so
    a tree whose mask happens to be the causal chain reproduces the
    linear verify bit-for-bit.
    """
    b, s_q, h, d = q.shape
    _, kv, t, _ = k_cache.shape
    g = h // kv
    qg = q.reshape(b, s_q, kv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bktd->bkgqt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(t, dtype=jnp.int32)[None, None, :]        # (1, 1, T)
    node = k_pos - offsets[:, None, None]                        # (B, 1, T)
    committed = node < 0
    in_window = (node >= 0) & (node < s_q)
    tree_vis = jnp.transpose(
        anc_mask[:, jnp.clip(node[:, 0, :], 0, s_q - 1)],        # (S, B, T)
        (1, 0, 2))                                               # (B, S, T)
    visible = committed | (in_window & tree_vis)
    mask = jnp.where(visible, 0.0, jnp.finfo(jnp.float32).min)
    scores = scores + mask[:, None, None, :, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqt,bktd->bqkgd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, h, d).astype(q.dtype)


def dequant_kv(q_vals: jnp.ndarray, scale: jnp.ndarray,
               out_dtype) -> jnp.ndarray:
    """THE int8-KV dequant rule, shared verbatim by the gather reference
    and the Pallas kernels: int8 values times their per-(block, kv_head)
    fp32 scale, in fp32, cast ONCE to the compute dtype. The gather path
    applies it after the gather (:func:`gather_kv_blocks`); the Pallas
    kernels apply it to each block right where its DMA lands in VMEM
    (ops/paged_attention.py) — so quantized gather-vs-pallas parity
    reduces to the same online-softmax fp32-reordering tolerance as the
    bf16 lanes."""
    return (q_vals.astype(jnp.float32) * scale).astype(out_dtype)


@scope("kv_read")
def gather_kv_blocks(pool, block_tables: jnp.ndarray,
                     out_dtype=None) -> jnp.ndarray:
    """Assemble per-slot contiguous KV views from a paged block pool.

    pool:         (N, K, bs, D) global block pool (inference/kv_cache.py
                  ``PagedKVCache``); block 0 is the null/scratch block.
                  An int8 ``QuantPool`` is accepted too — see below.
    block_tables: (B, NB) int32 — slot b's logical block n lives in pool
                  block ``block_tables[b, n]``; unallocated entries are 0.

    Returns (B, K, NB*bs, D). One gather per layer: position ``p`` of slot
    ``b`` is ``pool[block_tables[b, p // bs], :, p % bs]`` — exactly the
    ring buffer's content for every written position, and null-block/stale
    content beyond a slot's length, which the caller's length mask zeroes.

    A quantized pool gathers its int8 blocks AND their per-(block, kv_head)
    scales through the same table, then dequantizes the gathered view via
    :func:`dequant_kv` into ``out_dtype`` (the attention compute dtype,
    default bf16) — dequantize-after-gather, the selectable correctness
    oracle the fused-dequant Pallas kernels are checked against.
    ``out_dtype`` is ignored for plain pools: their bytes pass through
    untouched, preserving the bf16 lanes' bit-exactness story.

    The gather is a pure READ of the tables, so the same pool block may
    appear in several slots' rows at once — that is how the prefix cache
    (inference/prefix_cache.py) serves shared prompt prefixes with zero
    kernel changes: hit blocks are simply referenced by more than one row.
    Writes never target a shared block (the scheduler copy-on-writes it
    into a private block first), so concurrent readers always see
    committed, immutable bytes.
    """
    from ..inference.kv_cache import QuantPool
    if isinstance(pool, QuantPool):
        g = pool.q[block_tables]               # (B, NB, K, bs, D) int8
        sc = pool.scale[block_tables]          # (B, NB, K)
        g = dequant_kv(g, sc[..., None, None],
                       jnp.bfloat16 if out_dtype is None else out_dtype)
    else:
        g = pool[block_tables]                 # (B, NB, K, bs, D)
    b, nb, k, bs, d = g.shape
    return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(b, k, nb * bs, d)


@scope("kv_read")
def paged_cached_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           offsets: jnp.ndarray) -> jnp.ndarray:
    """:func:`cached_attention` against block-paged KV pools.

    Gathers each slot's blocks into the (B, K, T, D) layout via the block
    table, then runs the EXACT :func:`cached_attention` math on it — same
    grouped einsum, fp32 softmax, additive ``finfo.min`` mask keyed on
    ``offsets`` — so on identical cached contents the two paths bit-match:
    masked gathered positions (null block, stale/freed blocks, positions
    beyond a slot's length) get ``exp(finfo.min + score) == 0`` probability
    exactly and contribute exact zeros to the fp32 accumulation, just like
    the ring buffer's masked tail. This is the portable XLA-level reference
    of vLLM's PagedAttention: the gather materializes a transient per-call
    contiguous view instead of a fused block-indexed kernel — the
    semantics the in-place Pallas kernel (ops/paged_attention.py)
    reproduces to fp32 accumulation tolerance, and the bit-exact
    reference it is tested against (:func:`paged_attention` dispatches
    between the two).

    Prefix sharing needs NO change here: a block referenced by several
    slots' table rows (prefix-cache hit) is gathered into each of their
    views with bit-identical contents, and since shared blocks are
    read-only (copy-on-write precedes any write into one), a cache-hit
    slot's gathered view equals what its own prefill would have produced —
    the root of the cached-stream bit-exactness tests.
    """
    return cached_attention(
        q, gather_kv_blocks(k_pool, block_tables, q.dtype),
        gather_kv_blocks(v_pool, block_tables, q.dtype), offsets)


@scope("kv_read")
def paged_tree_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                         v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                         offsets: jnp.ndarray, anc_mask: jnp.ndarray,
                         impl: str = "gather") -> jnp.ndarray:
    """:func:`tree_cached_attention` against block-paged KV pools — the
    tree-verify routing point, mirroring :func:`paged_attention`
    (``"auto"`` is the gather here: a tree is S > 1 rows).

    ``"gather"`` assembles each slot's blocks and runs the bit-exact
    reference above; ``"pallas"`` takes the ancestor-masked chunk kernel
    (ops/paged_attention.py ``paged_tree_chunk_attention``), which reads
    pool blocks in place through the table and carries the (S, S) mask as
    a packed per-row int32 bitmask — equal within fp32 accumulation
    tolerance and bitwise invariant to masked bytes, like every other
    pallas lane.
    """
    impl = resolve_paged_kernel(impl, q.shape[1], q.shape[3])
    if impl == "gather":
        return tree_cached_attention(
            q, gather_kv_blocks(k_pool, block_tables, q.dtype),
            gather_kv_blocks(v_pool, block_tables, q.dtype), offsets,
            anc_mask)
    if impl == "pallas":
        from .paged_attention import paged_tree_chunk_attention
        return paged_tree_chunk_attention(q, k_pool, v_pool, block_tables,
                                          offsets, anc_mask)
    raise ValueError(f"unknown paged attention impl: {impl!r} "
                     f"(want 'auto', 'gather' or 'pallas')")


@scope("kv_read")
def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                    offsets: jnp.ndarray, impl: str = "gather"
                    ) -> jnp.ndarray:
    """Paged-attention kernel dispatch — THE routing point for every read
    through the block tables (decode, chunked prefill, spec-verify; the
    former ``paged_verify_attention`` alias collapsed into this).

    - ``"gather"`` — :func:`paged_cached_attention`: gather-then-ring,
      the portable bit-exact reference (serving's ``--paged-kernel
      gather``).
    - ``"pallas"`` — the in-place block-indexed kernels
      (ops/paged_attention.py): pool blocks are DMA'd straight through
      the table, no gathered copy. S=1 takes the decode kernel, S>1
      (chunked prefill, chunk-mode spec-verify) the chunk kernel — every
      paged read is in place under this impl, no silent gather. Both are
      equal to gather within fp32 accumulation tolerance (online softmax
      reorders the reduction) and bitwise invariant to masked bytes; the
      single statement of the positional-masking equivalence lives in
      ops/paged_attention.py's module docstring.
    - ``"auto"`` — one of the two, by :func:`resolve_paged_kernel`'s rule
      on this call's shape and the backend (the serving default).
    """
    impl = resolve_paged_kernel(impl, q.shape[1], q.shape[3])
    if impl == "gather":
        return paged_cached_attention(q, k_pool, v_pool, block_tables,
                                      offsets)
    if impl == "pallas":
        if q.shape[1] == 1:
            from .paged_attention import paged_decode_attention
            return paged_decode_attention(q, k_pool, v_pool, block_tables,
                                          offsets)
        from .paged_attention import paged_chunk_attention
        return paged_chunk_attention(q, k_pool, v_pool, block_tables,
                                     offsets)
    raise ValueError(f"unknown paged attention impl: {impl!r} "
                     f"(want 'auto', 'gather' or 'pallas')")


def resolve_paged_kernel(impl: str, s_q: int, head_dim: int) -> str:
    """The one statement of the paged ``auto`` rule, read off the call:
    IN PLACE (``"pallas"``) iff the backend is a TPU, the query is one
    token (S = 1: decode, a draft's micro-step, exact-mode verify), the
    engine serves on one device, and the head fills the lane tile;
    otherwise the gather. ``"gather"`` and ``"pallas"`` pass through.

    Why each clause. Off the chip the kernels run interpreted, which is
    a test mode, not a way to serve. At S = 1 the gather moves every
    slot's whole table through HBM twice to read what is live once, and
    the decode kernel reads the live pages where they lie; at S > 1 the
    read is a small share of a prefill, and the chunk kernel's S x G-row
    q block wants a tiling of its own first (ROADMAP S9). A Mosaic call
    cannot be partitioned automatically, so a mesh of several devices
    keeps the gather until the call is wrapped per shard as
    ``flash_attention._per_shard`` is. And the decode kernel moves whole
    pages only where Mosaic can slice them out of HBM
    (``paged_attention.decode_pages_whole``); narrower heads would take
    its slow per-page grid.
    """
    if impl != "auto":
        return impl
    from .paged_attention import decode_pages_whole
    mesh = active_mesh()
    in_place = (jax.default_backend() == "tpu" and s_q == 1
                and (mesh is None or mesh.size == 1)
                and decode_pages_whole(head_dim))
    return "pallas" if in_place else "gather"


def describe_paged_kernel(impl: str, head_dim: int) -> str:
    """``impl`` as the server's start-up line states it, resolved for the
    two query shapes a server dispatches (call it under the serving
    mesh): ``gather``, ``pallas (compiled)``, or ``auto: decode pallas
    (compiled), prefill gather``."""
    if impl != "auto":
        return describe_kernel_mode(impl)
    decode, prefill = (describe_kernel_mode(
        resolve_paged_kernel(impl, s_q, head_dim)) for s_q in (1, 2))
    return f"auto: decode {decode}, prefill {prefill}"


def resolve_attention_impl(impl: str) -> str:
    """The one statement of the ``auto`` rule: pallas on a TPU backend, xla
    elsewhere. ``ring`` resolves the same way — it only exists under a >1
    'sequence' mesh axis, which the model layer checks first
    (models/llama.py); with no axis to ring over it is the dense kernel."""
    if impl in ("auto", "ring"):
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def ring_attention_active(impl: str) -> bool:
    """Ring attention runs iff it may be chosen AND the active mesh has a
    'sequence' axis to ring over."""
    return impl in ("auto", "ring") and mesh_axis_size("sequence") > 1


def describe_attention_impl(impl: str) -> str:
    """What ``impl`` resolves to under the active mesh, for the trainer's
    start-up line: ``xla``, ``pallas (compiled)``, ``ring pallas (...)``."""
    if ring_attention_active(impl):
        return "ring " + describe_kernel_mode("pallas")  # ops/ring_flash.py
    return describe_kernel_mode(resolve_attention_impl(impl))


def describe_kernel_mode(impl: str) -> str:
    """``impl`` as a start-up log states it: Pallas implementations say
    whether the kernels are compiled for the chip or run interpreted (the
    CPU test mode) — a run that quietly took interpret mode, or ``xla``,
    on a chip is then visible in its own log."""
    if impl != "pallas":
        return impl
    from .flash_attention import _interpret
    return f"pallas ({'interpret' if _interpret() else 'compiled'})"


def multihead_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        impl: str = "auto", causal: bool = True) -> jnp.ndarray:
    """Dispatch to the requested attention implementation.

    ``"ring"`` is accepted (models/configs.py admits it as an
    ``attention_impl``) but resolves like ``"auto"``: ring attention is
    the sequence-parallel collective form (ops/ring_attention.py) and
    only exists under a mesh with a >1 'sequence' axis — the model layer
    routes it there itself (models/llama.py). A direct single-device
    call has no axis to ring over, so it gets the equivalent dense
    kernel instead of an opaque raise.
    """
    impl = resolve_attention_impl(impl)
    if impl == "xla":
        return xla_attention(q, k, v, causal=causal)
    if impl == "pallas":
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl: {impl!r}")
