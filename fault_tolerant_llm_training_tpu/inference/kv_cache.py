"""Static-shape GQA-aware KV caches: per-slot ring buffers and paged blocks.

Two layouts share one contract (fixed-shape pytree in, pytree out, buffers
donatable by the jitted step):

**Ring** (:class:`KVCache`) — one pair of head-major buffers per layer,
``[slots, kv_heads, max_len, head_dim]``. ``slots`` is the
continuous-batching dimension: each slot holds one in-flight request's
prefix, and the per-slot ``lengths`` vector is both the decode position
offset and the attention-mask boundary (ops/attention.py
``cached_attention``). Simple, but every slot reserves ``max_len``
positions: long-context configs strand most of HBM on empty reservation.

**Paged** (:class:`PagedKVCache`, vLLM's PagedAttention layout, Kwon et al.
2023) — one GLOBAL block pool per layer, ``[num_blocks, kv_heads,
block_size, head_dim]``, plus a host-owned int32 block table per slot
mapping logical block position -> pool block. A request only occupies the
blocks its actual ``prompt + max_new_tokens`` needs, so at a fixed HBM
budget far more requests fit concurrently. Block 0 is the reserved
null/scratch block: free block-table entries point at it, and writes from
masked positions (bucket padding, inactive decode slots) are redirected
into it, so a static-shape step never scribbles on another request's
blocks. The block allocator lives host-side in the scheduler
(inference/scheduler.py ``BlockAllocator``); the device only ever sees the
pool and the tables.

Because the tables are plain indices, a pool block can appear in SEVERAL
slots' tables at once — that is the prefix cache
(inference/prefix_cache.py): requests sharing a committed prompt prefix
point their tables at the same blocks and skip the prefill compute for
them. Sharing is refcounted in the allocator and strictly READ-only: the
only write a shared block ever sees is :func:`copy_kv_block` — the
copy-on-write primitive that duplicates it into a private block before a
slot resumes prefill inside it.

Everything is a fixed-shape pytree argument (flax ``struct``), NOT a flax
mutable collection: the jitted decode step takes the cache in and returns it
out, which lets the engine donate the buffers (jax.jit ``donate_argnums``)
so XLA updates them in place — no per-token reallocation of the largest
serving tensor.

Sharding under the training mesh (parallel/mesh.py): ``kv_heads`` rides the
'tensor' axis exactly like the wk/wv projections that produce it
(parallel/sharding.py LOGICAL_RULES) in BOTH layouts (it is dim 1 of the
ring buffer and of the block pool alike); slots/blocks/positions stay
replicated.

**Quantized paged mode** (``init_paged_cache(dtype=jnp.int8)``) stores each
layer's pool as a :class:`QuantPool`: an int8 block pool plus a parallel
per-(block, kv_head) fp32 scale pool, vLLM/KIVI-style symmetric per-block
quantization. Halving bytes-per-position doubles ``kv_blocks_total`` at a
fixed HBM budget — which the paged admission gate converts directly into
concurrency. The scale invariant is deliberately simple (a block's scale is
owned by the row at its local position 0; see ``_quantized_write``) so
every write stays row-granular like the bf16 path and the within-dtype
bit-exactness contracts survive unchanged.
"""

import json
import os
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.configs import TransformerConfig
from ..obs.trace import scope


class KVCache(struct.PyTreeNode):
    """Per-layer (slots, kv_heads, max_len, head_dim) buffers + fill counts."""

    k: Tuple[jax.Array, ...]  # length n_layers
    v: Tuple[jax.Array, ...]
    lengths: jax.Array        # (slots,) int32 tokens written per slot

    @property
    def slots(self) -> int:
        return self.k[0].shape[0]

    @property
    def max_len(self) -> int:
        return self.k[0].shape[2]


def init_cache(cfg: TransformerConfig, slots: int, max_len: int,
               dtype=None) -> KVCache:
    """Zero-filled cache; ``dtype`` defaults to the model's activation dtype
    (bf16) so cached keys/values are bit-identical to the training forward's."""
    dtype = cfg.dtype if dtype is None else dtype
    shape = (slots, cfg.kv_heads, max_len, cfg.head_dim)
    zeros = tuple(jnp.zeros(shape, dtype) for _ in range(cfg.n_layers))
    return KVCache(k=zeros, v=tuple(jnp.zeros(shape, dtype)
                                    for _ in range(cfg.n_layers)),
                   lengths=jnp.zeros((slots,), jnp.int32))


class PagedKVCache(struct.PyTreeNode):
    """Per-layer (num_blocks, kv_heads, block_size, head_dim) pools + per-slot
    fill counts. The block tables stay HOST-side (scheduler) and are passed
    into each compiled step as a plain int32 argument — they are tiny
    (slots x blocks_per_slot) and change at admission/eviction, not per
    token, so shipping them per call costs nothing while keeping the donated
    device state to the pools themselves."""

    k: Tuple[jax.Array, ...]  # length n_layers
    v: Tuple[jax.Array, ...]
    lengths: jax.Array        # (slots,) int32 tokens written per slot

    @property
    def slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k[0].shape[0]

    @property
    def block_size(self) -> int:
        return self.k[0].shape[2]


class LatentKVCache(struct.PyTreeNode):
    """The serving cache of a model whose layers cache by KIND
    (models/latent_moe.py): full layers keep, a token, one latent row, one
    rope key and one index key in pools addressed by the SAME host-side
    block tables a :class:`PagedKVCache` uses (so the allocator, the prefix
    cache and copy-on-write see blocks as before) — the index keys by
    block, ``(N, 1, bs, d_i)``, read a table at a time; the latent rows and
    rope keys row by row, ``(N * bs, r)`` and ``(N * bs, d_r)``, block b
    being rows ``[b * bs, (b + 1) * bs)`` (ops/latent_attention.py says why
    they differ); sliding
    layers keep a ring of ``R >= window`` rows a slot, whatever the
    context. ``win_from[slot]`` is the first position whose row the slot's
    rings hold of the request in it (a resumed prefill starts there, not
    at 0: ops/latent_attention.py, ``InferenceEngine.prefill``)."""

    latent: Tuple[jax.Array, ...]   # a full layer: (N * bs, r), and
    rope: Tuple[jax.Array, ...]     # (N * bs, d_r): 16-bit rows packed as
    #                                 uint32 words
    index: Tuple[jax.Array, ...]    # a full layer: (N, 1, bs, d_i)
    window: Tuple[jax.Array, ...]   # a sliding layer: (slots, R, r + d_r)
    win_from: jax.Array             # (slots,) int32
    lengths: jax.Array              # (slots,) int32 tokens written per slot

    @property
    def slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.index[0].shape[0]

    @property
    def block_size(self) -> int:
        return self.index[0].shape[2]

    def copy_block(self, src, dst) -> "LatentKVCache":
        """Copy-on-write of one pool block, in every full layer's two
        pools (a sliding layer shares nothing)."""
        bs = self.block_size

        def rows(p):    # the block's bs rows of a latent pool
            return jax.lax.dynamic_update_slice_in_dim(
                p, jax.lax.dynamic_slice_in_dim(p, src * bs, bs, 0),
                dst * bs, 0)

        return self.replace(
            latent=tuple(rows(p) for p in self.latent),
            rope=tuple(rows(p) for p in self.rope),
            index=tuple(copy_kv_block(p, src, dst) for p in self.index))

    def resident_bytes(self) -> dict:
        """Bytes held, by layer kind."""
        size = lambda ps: int(sum(p.size * p.dtype.itemsize for p in ps))
        return {"full": (size(self.latent) + size(self.rope)
                         + size(self.index)),
                "sliding": size(self.window)}


def init_latent_cache(cfg, slots: int, block_size: int, num_blocks: int,
                      dtype=None) -> LatentKVCache:
    """Zero-filled pools and rings for a ``LatentMoEConfig``."""
    dtype = cfg.dtype if dtype is None else dtype
    if num_blocks < 2:
        raise ValueError(f"num_blocks {num_blocks} < 2: block 0 is the "
                         f"reserved null block")
    from ..ops.latent_attention import packed_width

    full, swa = cfg.mixer("full"), cfg.mixer("sliding")
    pool = lambda c: jnp.zeros((num_blocks, 1, block_size, c), dtype)
    # rows, not blocks, and a 16-bit row as uint32 words of whole lanes:
    # they are written and gathered one by one
    rows = lambda c: jnp.zeros(                               # noqa: E731
        (num_blocks * block_size, packed_width(c, dtype)),
        jnp.uint32 if jnp.dtype(dtype).itemsize == 2 else dtype)
    return LatentKVCache(
        latent=tuple(rows(full["kv_rank"]) for _ in cfg.full_layers),
        rope=tuple(rows(full["rope"]) for _ in cfg.full_layers),
        index=tuple(pool(cfg.index_head_dim) for _ in cfg.full_layers),
        window=tuple(jnp.zeros((slots, cfg.window_ring,
                                swa["kv_rank"] + swa["rope"]), dtype)
                     for _ in cfg.sliding_layers),
        win_from=jnp.zeros((slots,), jnp.int32),
        lengths=jnp.zeros((slots,), jnp.int32))


def blocks_per_slot(max_len: int, block_size: int) -> int:
    """Block-table row length covering ``max_len`` positions."""
    return -(-max_len // block_size)


KV_QUANT_QMAX = 127.0  # symmetric int8: q in [-127, 127], -128 unused


class QuantPool(struct.PyTreeNode):
    """One layer's int8 paged block pool plus its parallel scale pool.

    ``q`` keeps the bf16 pool's exact geometry at one byte per element;
    ``scale`` holds one fp32 dequant scale per (block, kv_head). The
    ``shape``/``dtype`` properties mirror a plain array pool so every
    shape-derived consumer (block table reach in models/llama.py, engine
    geometry, export manifests) reads a QuantPool without branching, and as
    a ``struct.PyTreeNode`` it is transparent to jit/donation/eval_shape —
    the int8-mode :class:`PagedKVCache` simply carries QuantPools in its
    ``k``/``v`` tuples.

    The dequant rule — ``q.astype(float32) * scale`` cast once to the
    compute dtype — is THE shared contract: the gather reference applies it
    after the gather (ops/attention.py ``gather_kv_blocks``) and the Pallas
    kernels apply it to the block right after its DMA lands in VMEM
    (ops/paged_attention.py), so the two impls differ only by the online
    softmax's fp32 reordering, same as the bf16 parity story."""

    q: jax.Array      # (num_blocks, kv_heads, block_size, head_dim) int8
    scale: jax.Array  # (num_blocks, kv_heads) fp32

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


def quantize_rows(rows: jax.Array, scale: jax.Array) -> jax.Array:
    """Symmetric round-to-nearest: (R, K, D) fp32 rows at per-(row, head)
    ``scale`` (R, K) into int8 [-127, 127]. Zero scales (a block whose
    position-0 row was exactly zero) degrade to divisor 1 so the result
    stays finite and deterministic — dequant then reproduces the zeros
    exactly."""
    safe = jnp.where(scale > 0, scale, 1.0)[:, :, None]
    return jnp.clip(jnp.round(rows / safe), -KV_QUANT_QMAX,
                    KV_QUANT_QMAX).astype(jnp.int8)


def init_paged_cache(cfg: TransformerConfig, slots: int, max_len: int,
                     block_size: int, num_blocks: Optional[int] = None,
                     dtype=None) -> PagedKVCache:
    """Zero-filled block pool. ``num_blocks`` defaults to full reservation
    parity with the ring layout (slots * ceil(max_len/block_size)) plus the
    null block — the interesting configs pass FEWER blocks than that and let
    the scheduler admit by actual per-request need instead."""
    dtype = cfg.dtype if dtype is None else dtype
    if num_blocks is None:
        num_blocks = slots * blocks_per_slot(max_len, block_size) + 1
    if num_blocks < 2:
        raise ValueError(f"num_blocks {num_blocks} < 2: block 0 is the "
                         f"reserved null block, at least one usable block "
                         f"is required")
    shape = (num_blocks, cfg.kv_heads, block_size, cfg.head_dim)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        # Quantized mode: int8 pools + per-(block, kv_head) fp32 scales.
        # Requesting the pool dtype IS the mode switch, so reset/rebuild
        # paths that thread ``cache.k[0].dtype`` round-trip for free.
        def pool():
            return QuantPool(
                q=jnp.zeros(shape, jnp.int8),
                scale=jnp.zeros((num_blocks, cfg.kv_heads), jnp.float32))
        return PagedKVCache(
            k=tuple(pool() for _ in range(cfg.n_layers)),
            v=tuple(pool() for _ in range(cfg.n_layers)),
            lengths=jnp.zeros((slots,), jnp.int32))
    return PagedKVCache(
        k=tuple(jnp.zeros(shape, dtype) for _ in range(cfg.n_layers)),
        v=tuple(jnp.zeros(shape, dtype) for _ in range(cfg.n_layers)),
        lengths=jnp.zeros((slots,), jnp.int32))


def _route_blocks(block_tables: jax.Array, logical: jax.Array,
                  live: jax.Array) -> jax.Array:
    """Pool block behind each logical block index of ``logical`` (B, X)
    through the slot's table row: the table's entry where ``live`` (B, X)
    holds and the index is within the table's reach, else null block 0."""
    nb = block_tables.shape[1]
    return jnp.where(
        live & (logical < nb),
        jnp.take_along_axis(block_tables, jnp.clip(logical, 0, nb - 1),
                            axis=1),
        0)


# A program calls this twice a layer with the same shapes: under its own jit
# it is traced and lowered once a program and called 48 times, not unrolled
# 48 times (1.2 s of tracing a program at 24 layers, 7 s of a server's
# set-up over its ladder of programs); XLA inlines the calls.
@jax.jit
def _write_block_rows(pool: jax.Array, new: jax.Array,
                      block_tables: jax.Array, start: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Land ``new`` (B, K, S, D) in a plain (N, K, bs, D) pool array at
    positions ``start[b] + [0, S)``: a block read-modify-write whose gather
    and scatter index the pool on dim 0 ONLY.

    The pool is stored with N outermost. A scatter of rows at ``[blk, :,
    off, :]`` indexes dims 0 and 2 around the K window, and the TPU scatter
    then takes the operand with K and bs swapped: every program that wrote
    the donated pool held two pool-sized ``copy`` relayouts a layer-pool
    (in and back out), ~0.5 ms each at 168 MB, to land a few 2 KiB rows.
    Whole blocks ``[blk]`` with window (K, bs, D) are the layout's own
    major dim, so the donated pool is updated in place.

    Per slot the S rows fall in at most ``nblk`` consecutive logical
    blocks. The rows are shifted into that window by ``start % bs`` (a
    barrel shifter of static rolls: a per-slot dynamic_update_slice becomes
    a scatter of its own, measured slower from 4 slots up), merged over the
    blocks' current content by a select, and scattered back as whole
    blocks. All of a call's rows are merged BEFORE the one scatter, so rows
    that share a block (every prefill) all land. A window block with no
    valid row is not written at all (its index is out of range and
    dropped), so a block another slot may share is never touched; valid
    rows behind a free table entry or past the table's reach land in null
    block 0, which stays the scratch it was.
    """
    n, k, bs, d = pool.shape
    b, _, s, _ = new.shape
    nblk = (s + bs - 2) // bs + 1
    w = nblk * bs
    off = start % bs

    def window(x, fill, axis):   # S -> W along ``axis``, rows at off[b]
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, w - s)
        x = jnp.pad(x, pad, constant_values=fill)
        sel = off.reshape((b,) + (1,) * (x.ndim - 1))
        # off < bs <= w - s + 1, so what a roll wraps round is padding
        for i in range((bs - 1).bit_length()):
            x = jnp.where(sel & (1 << i) != 0, jnp.roll(x, 1 << i, axis), x)
        return x

    mask = window(valid, False, 1).reshape(b, nblk, bs)
    live = jnp.any(mask, axis=2)
    logical = (start // bs)[:, None] + jnp.arange(nblk, dtype=jnp.int32)
    blk = _route_blocks(block_tables, logical, live).reshape(-1)
    rows = jnp.swapaxes(window(new, 0, 2).reshape(b, k, nblk, bs, d), 1, 2)
    merged = jnp.where(mask.reshape(-1, 1, bs, 1),
                       rows.reshape(-1, k, bs, d), pool[blk])
    dest = jnp.where(live.reshape(-1), blk, n)      # n: out of range
    return pool.at[dest].set(merged, mode="drop")


def _quantized_write(pool: QuantPool, new: jax.Array,
                     block_tables: jax.Array, start: jax.Array,
                     valid: jax.Array) -> QuantPool:
    """Land fp32 ``new`` (B, K, S, D) at positions ``start[b] + [0, S)`` of
    an int8 pool, maintaining the scale invariant:

    **A block's scale is owned by its local position 0.** A row landing at
    block-local offset 0 SETS the block's per-head scale to its own
    amax/127 — a plain overwrite, never a running max — and every row
    landing at offset > 0 quantizes at the scale already in the pool,
    clipped into [-127, 127]. Positions are committed in sequence order, so
    a block's position 0 is always written before its higher offsets, and
    existing content is NEVER requantized: a write stays row-granular
    exactly like the bf16 write. That is the property the within-dtype
    bit-exactness contracts (exact spec-verify, burst decode, packed
    prefill, COW resume) lean on — a rejected speculative row can disturb a
    scale only at an offset-0 position the committed stream's own next
    write deterministically resets with identical inputs. Clipping rows
    that outgrow their block's committed scale is the accuracy cost of that
    determinism; the parity check's adversarial matrix bounds it.

    Rows diverted to null block 0 (masked writes, and offset>0 rows' scale
    lane below) may scribble scale[0]; harmless — null-block lanes are
    additively masked to exactly zero attention weight, so scale[0] is
    never read live. The quantized rows land through
    :func:`_write_block_rows` like a plain pool's."""
    bs = pool.shape[2]
    b, k, s, d = new.shape
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # (B, S)
    blk = _route_blocks(block_tables, pos // bs, valid).reshape(-1)
    rows = jnp.transpose(new, (0, 2, 1, 3)).reshape(b * s, k, d)
    amax = jnp.max(jnp.abs(rows), axis=-1)            # (R, K)
    scale_blk = jnp.where((pos % bs).reshape(-1) == 0, blk, 0)
    new_scale = pool.scale.at[scale_blk, :].set(amax / KV_QUANT_QMAX)
    q_rows = quantize_rows(rows, new_scale[blk])      # post-update gather
    q_rows = jnp.transpose(q_rows.reshape(b, s, k, d), (0, 2, 1, 3))
    return QuantPool(
        q=_write_block_rows(pool.q, q_rows, block_tables, start, valid),
        scale=new_scale)


@scope("kv_write")
def write_paged_kv(pool: jax.Array, new: jax.Array, block_tables: jax.Array,
                   start: jax.Array, valid: jax.Array) -> jax.Array:
    """Write ``new`` (B, K, S, D) into the block ``pool`` (N, K, bs, D) at
    each slot's positions ``start[b] + [0, S)``, translated through
    ``block_tables`` (B, blocks_per_slot). Only the blocks the new tokens
    fall in move — one gather and one scatter of at most ``B * (S // bs +
    2)`` whole blocks per call (:func:`_write_block_rows`), never the whole
    cache. Positions with ``valid`` False — (B, S), or (B, 1) for a whole
    slot: bucket padding past the prompt, inactive decode slots — are never
    written to an allocated block: what they would touch is routed to null
    block 0, so a static-shape write can never land in another request's
    blocks. Positions past the table's reach (start + S can exceed
    blocks_per_slot * bs in a speculative verify round whose draft overruns
    a nearly-full slot) also divert to the null block — clipping them into
    the last table column would wrap the write onto the slot's OWN
    committed KV at ``pos % bs`` and silently corrupt it. Valid in-range
    positions of different slots fall in distinct blocks (the allocator
    hands each slot disjoint blocks), so the scatter is collision-free
    where it matters; every other byte of a written block is written back
    as it was read.

    A :class:`QuantPool` takes the identical routing; its rows quantize
    first (:func:`_quantized_write`: offset-0 rows set their block's scale,
    the rest quantize at it)."""
    valid = jnp.broadcast_to(valid, (new.shape[0], new.shape[2]))
    if isinstance(pool, QuantPool):
        return _quantized_write(pool, new.astype(jnp.float32), block_tables,
                                start, valid)
    return _write_block_rows(pool, new, block_tables, start, valid)


def remap_paged_path(pool: jax.Array, block_tables: jax.Array,
                     start: jax.Array, src_nodes: jax.Array,
                     accepted: jax.Array) -> jax.Array:
    """Commit a tree-verify round's WINNING path: move each accepted
    node's (kv_heads, head_dim) row from its tree-window position to its
    committed position, inside the slot's own blocks.

    A tree round writes node i's KV at position ``start[b] + i`` (row
    order), but the accepted path's nodes p_0 < p_1 < ... are generally
    non-contiguous rows; the committed stream needs them at
    ``start[b] + 1 + j``. ``src_nodes`` (B, depth) holds the path's node
    row indices, ``accepted`` (B,) how many are live. Moves with
    ``j >= accepted[b]`` are dropped (the discipline of
    :func:`write_paged_kv`, which lands the rows), so rejected branches
    simply rot as stale bytes past the new committed length — the
    linear-spec rejected-suffix story, no allocator traffic. Primary-chain
    moves (src == dst) are harmless bitwise no-ops: every source row is
    gathered before the one scatter writes. This runs as the tree-verify
    program's epilogue (inference/engine.py), one gather+scatter per layer
    per pool. A :class:`QuantPool`'s rows are dequantized at their SOURCE
    blocks' scales and requantized at the destination (a move crossing into
    a fresh block lands at local offset 0 and sets that block's scale, same
    as a sequential write would have).
    """
    bs = pool.shape[2]
    b, depth = src_nodes.shape
    steps = jnp.arange(depth, dtype=jnp.int32)[None, :]
    src_pos = start[:, None] + src_nodes                        # (B, depth)
    src_blk = jnp.take_along_axis(
        block_tables,
        jnp.clip(src_pos // bs, 0, block_tables.shape[1] - 1),
        axis=1).reshape(-1)
    src_off = (src_pos % bs).reshape(-1, 1, 1, 1)

    def rows_of(arr):   # whole source blocks on dim 0, then the row inside
        return jnp.take_along_axis(arr[src_blk], src_off, axis=2)[:, :, 0, :]

    if isinstance(pool, QuantPool):
        vals = (rows_of(pool.q).astype(jnp.float32)
                * pool.scale[src_blk][:, :, None])
    else:
        vals = rows_of(pool)                                # (B*depth, K, D)
    vals = jnp.swapaxes(vals.reshape(b, depth, *vals.shape[1:]), 1, 2)
    return write_paged_kv(pool, vals, block_tables, start + 1,
                          steps < accepted[:, None])


def copy_kv_block(pool: jax.Array, src: jax.Array, dst: jax.Array
                  ) -> jax.Array:
    """Copy one pool block's (kv_heads, block_size, head_dim) contents from
    row ``src`` to row ``dst`` — the copy-on-write primitive. A slot about
    to write INSIDE a block it shares with other requests (prefix-cache
    full-prompt hit resuming at the last prompt position) first duplicates
    the block into a private one and remaps its table entry; the shared
    original is never written. Bitwise copy of committed bytes, so the
    divergent stream stays bit-identical to an uncached run. A
    :class:`QuantPool` copies BOTH the int8 row and its scale row bitwise —
    the copy dequantizes to exactly the original's values, so COW resumes
    stay bit-identical within the quantized mode too."""
    if isinstance(pool, QuantPool):
        return QuantPool(q=pool.q.at[dst].set(pool.q[src]),
                         scale=pool.scale.at[dst].set(pool.scale[src]))
    return pool.at[dst].set(pool[src])


@scope("kv_write")
def write_slot_kv(buf: jax.Array, new: jax.Array,
                  start: jax.Array) -> jax.Array:
    """Write ``new`` (B, K, S, D) into ``buf`` (B, K, T, D) at each slot's
    ``start`` (B,) position along the T axis — a vmap'd dynamic_update_slice,
    so every slot writes at its own offset in one fused XLA op. Callers
    guarantee ``start + S <= T`` for multi-token (prefill) writes; the
    single-token decode write always fits (start is taken mod T)."""
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, p, 0)))(
        buf, new, start)


# ---------------------------------------------------------------------------
# Block export / import — the tiered-KV block-move primitive.
#
# A block artifact is a DIRECTORY: one payload file per exported pool block
# (``block_00000.bin`` = that row's bytes across every layer, K then V per
# layer) plus an ``integrity.json`` manifest recording geometry, the slot's
# committed KV length, per-file size + CRC32, and caller metadata (request
# id, committed tokens, row positions). The manifest is written atomic
# tmp+fsync+rename exactly like checkpoint/manager.py's checkpoint
# manifests, and import verifies every payload's size and CRC BEFORE any
# device write — a flipped byte, truncated file, or swapped manifest raises
# :class:`KVBlockIntegrityError` and the device pool is untouched, so every
# consumer (spill restore, handoff import) can fall back to the bit-exact
# committed-prefix replay instead of decoding garbage. The manifest file
# deliberately reuses the checkpoint manifest's name: the chaos injector's
# byte-flipper spares ``integrity.json``, so injected corruption always
# lands in a payload where the CRC must catch it.
# ---------------------------------------------------------------------------

BLOCK_MANIFEST_NAME = "integrity.json"
_BLOCK_ARTIFACT_VERSION = 1


class KVBlockIntegrityError(RuntimeError):
    """A KV block artifact failed verification (missing/torn manifest,
    size or CRC32 mismatch, or geometry that does not fit the live pool).
    Raised BEFORE any device write, so the pool is never half-imported."""


def _fsync_dir(path: str) -> None:
    """Flush directory metadata so a rename survives power loss (same
    best-effort semantics as checkpoint/manager.py)."""
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _block_file_name(i: int) -> str:
    return f"block_{i:05d}.bin"


def _cache_geometry(cache: PagedKVCache) -> Dict[str, object]:
    return {
        "n_layers": len(cache.k),
        "kv_heads": int(cache.k[0].shape[1]),
        "block_size": int(cache.block_size),
        "head_dim": int(cache.k[0].shape[3]),
        "dtype": str(np.dtype(cache.k[0].dtype)
                     if not hasattr(cache.k[0].dtype, "name")
                     else cache.k[0].dtype.name),
    }


def _np_dtype(arr) -> np.dtype:
    return np.dtype(arr.dtype.name if hasattr(arr.dtype, "name")
                    else arr.dtype)


def _pool_parts(field: str, pool):
    """The named device arrays one logical pool contributes to a block's
    payload: ``(field, array)`` for a plain pool, plus ``(field_scale,
    scales)`` for a :class:`QuantPool` — the scales ride INSIDE the
    per-block payload so the artifact CRC covers them like any other KV
    byte."""
    if isinstance(pool, QuantPool):
        return ((field, pool.q), (field + "_scale", pool.scale))
    return ((field, pool),)


def block_layout(cache: PagedKVCache) -> List[Dict[str, object]]:
    """THE per-block payload layout, shared by :func:`export_blocks`
    (payload assembly) and :func:`import_blocks` (payload slicing) so the
    two can never drift: an ordered segment list, one entry per pool array,
    layer-major with K before V (and each quantized pool's scale row
    directly after its int8 data). Each segment describes ONE block's slice
    of its array — ``array[j]`` — as ``{layer, field, array, shape, dtype,
    nbytes, offset}`` with ``offset`` its byte position inside the
    concatenated payload."""
    segs: List[Dict[str, object]] = []
    off = 0
    for layer in range(len(cache.k)):
        for field, base in (("k", cache.k[layer]), ("v", cache.v[layer])):
            for name, arr in _pool_parts(field, base):
                dt = _np_dtype(arr)
                shape = tuple(int(s) for s in arr.shape[1:])
                nbytes = int(np.prod(shape)) * dt.itemsize
                segs.append({"layer": layer, "field": name, "array": arr,
                             "shape": shape, "dtype": dt, "nbytes": nbytes,
                             "offset": off})
                off += nbytes
    return segs


def block_bytes(cache: PagedKVCache) -> int:
    """One pool block's payload bytes across every layer — K, V, and in
    the quantized layout their scale rows. Both the export payload size
    and the /metrics ``kv_bytes_per_block`` gauge."""
    return sum(int(seg["nbytes"]) for seg in block_layout(cache))


def bf16_block_bytes(cache: PagedKVCache) -> int:
    """What one block of the SAME geometry costs in the bf16 layout —
    the denominator of the [KV QUANT] capacity ratio. Data elements at
    2 bytes each, scale rows excluded (the bf16 layout has none). Equal
    to :func:`block_bytes` on a bf16 cache by construction."""
    return sum(
        (int(seg["nbytes"]) // seg["dtype"].itemsize) * 2
        for seg in block_layout(cache)
        if not str(seg["field"]).endswith("_scale"))


def export_blocks(cache: PagedKVCache, blocks: Sequence[int], out_dir: str,
                  *, length: int, meta: Optional[Dict] = None) -> Dict:
    """Serialize pool rows ``blocks`` device->host into artifact ``out_dir``.

    Payload file i holds block ``blocks[i]``'s bytes for every layer
    (layer-major, K before V). ``length`` is the slot's committed KV fill
    count (``cache.lengths[slot]`` at export) so import can restore the
    decode position exactly; ``meta`` is caller context carried verbatim
    (request id, committed tokens, row positions). Payloads are flushed and
    fsynced before the manifest commits via tmp+fsync+rename, so a torn
    artifact is detectable as missing-manifest, never as silent garbage.
    Returns the manifest dict."""
    if 0 in blocks:
        raise ValueError("refusing to export reserved null block 0")
    os.makedirs(out_dir, exist_ok=True)
    idx = np.asarray(list(blocks), np.int32)
    # One device->host gather per pool array, not per block; payload byte
    # order is block_layout()'s segment order, the same order import
    # slices by.
    hosts = [np.asarray(seg["array"][idx]) for seg in block_layout(cache)]
    files: Dict[str, Dict[str, int]] = {}
    for j in range(len(idx)):
        payload = b"".join(h[j].tobytes() for h in hosts)
        name = _block_file_name(j)
        path = os.path.join(out_dir, name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        files[name] = {"size": len(payload),
                       "crc32": zlib.crc32(payload) & 0xFFFFFFFF}
    manifest = {
        "version": _BLOCK_ARTIFACT_VERSION,
        "geometry": _cache_geometry(cache),
        "blocks": [int(b) for b in blocks],
        "length": int(length),
        "files": files,
        "meta": dict(meta or {}),
    }
    man_path = os.path.join(out_dir, BLOCK_MANIFEST_NAME)
    tmp = man_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, man_path)
    _fsync_dir(out_dir)
    return manifest


def verify_block_artifact(art_dir: str) -> Dict:
    """Read and CRC-verify a block artifact; returns the manifest.

    Checks, in order: manifest present and parseable, every payload file
    present, size match, CRC32 match. Any failure raises
    :class:`KVBlockIntegrityError` with the failing file named. No device
    state is involved — the router uses this to decide ship-vs-replay
    before a survivor ever sees the artifact."""
    man_path = os.path.join(art_dir, BLOCK_MANIFEST_NAME)
    try:
        with open(man_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise KVBlockIntegrityError(
            f"block artifact manifest unreadable: {man_path}: {e}") from e
    files = manifest.get("files", {})
    if len(files) != len(manifest.get("blocks", [])):
        raise KVBlockIntegrityError(
            f"block artifact manifest torn: {len(files)} file(s) for "
            f"{len(manifest.get('blocks', []))} block(s)")
    for name in sorted(files):
        want = files[name]
        path = os.path.join(art_dir, name)
        try:
            with open(path, "rb") as f:
                payload = f.read()
        except OSError as e:
            raise KVBlockIntegrityError(
                f"block payload missing: {name}: {e}") from e
        if len(payload) != int(want["size"]):
            raise KVBlockIntegrityError(
                f"block payload size mismatch: {name}: "
                f"{len(payload)} != {want['size']}")
        got = zlib.crc32(payload) & 0xFFFFFFFF
        if got != int(want["crc32"]):
            raise KVBlockIntegrityError(
                f"block payload CRC mismatch: {name}: "
                f"{got:#010x} != {int(want['crc32']):#010x}")
    return manifest


def import_block_batch(cache: PagedKVCache,
                       parts: Sequence[Tuple[str, Sequence[int]]],
                       allow_partial: bool = False
                       ) -> Tuple[PagedKVCache, List[Dict]]:
    """Verify EVERY artifact in ``parts`` (``(art_dir, dest_blocks)``
    pairs, payload i of each artifact -> its ``dest_blocks[i]``) and land
    them all with ONE gather-scatter per pool array — a request's
    multi-chunk shipment train costs a single pool copy instead of one
    per artifact, which is what keeps a decode engine's admission stall
    off its decode-round tail. ALL verification — CRC of every payload,
    geometry vs the live pool, destination-row counts — happens before
    the first device write; on any mismatch
    :class:`KVBlockIntegrityError` is raised and ``cache`` is returned
    unmodified by the caller's contract. ``lengths`` is NOT touched here
    (the destination slot differs between spill-restore, handoff-import
    and shipment-import); callers set it from the manifests' ``length``.

    Under ``allow_partial=True`` a part may name FEWER destination rows
    than its artifact has blocks: payload files
    ``0..len(dest_blocks)-1`` land and the tail is left on disk —
    sub-train addressability, the store's partial prefix hit (a train
    published at depth N serves any prompt sharing its first
    ``len(dest_blocks)`` blocks; chain-hash keys make position
    content-determined, so a prefix of the payload files IS a prefix of
    the prompt). Verification still covers the WHOLE artifact. By
    default a count mismatch in EITHER direction is a caller bug
    (``ValueError``) — only the store's prefix-addressed fetch opts in.
    Returns ``(new_cache, manifests)`` in ``parts`` order."""
    live = _cache_geometry(cache)
    manifests: List[Dict] = []
    dests: List[int] = []
    for art_dir, dest_blocks in parts:
        manifest = verify_block_artifact(art_dir)
        geo = manifest["geometry"]
        if geo != live:
            raise KVBlockIntegrityError(
                f"block artifact geometry {geo} does not fit pool {live}")
        n = len(manifest["blocks"])
        if (len(dest_blocks) > n
                or (not allow_partial and len(dest_blocks) != n)):
            raise ValueError(
                f"artifact has {n} block(s) but {len(dest_blocks)} "
                f"destination row(s) given")
        if 0 in dest_blocks:
            raise ValueError("refusing to import into reserved null "
                             "block 0")
        manifests.append(manifest)
        dests.extend(int(b) for b in dest_blocks)
    n_layers = len(cache.k)
    layout = block_layout(cache)
    total = sum(int(seg["nbytes"]) for seg in layout)
    hosts = {(seg["layer"], seg["field"]):
             np.empty((len(dests),) + seg["shape"], seg["dtype"])
             for seg in layout}
    row = 0
    for (art_dir, dest_blocks), manifest in zip(parts, manifests):
        for j in range(len(dest_blocks)):
            with open(os.path.join(art_dir, _block_file_name(j)),
                      "rb") as f:
                payload = f.read()
            if len(payload) != total:
                raise KVBlockIntegrityError(
                    f"block payload {j} has {len(payload)} byte(s), "
                    f"geometry needs {total}")
            for seg in layout:
                off = int(seg["offset"])
                hosts[(seg["layer"], seg["field"])][row] = np.frombuffer(
                    payload[off:off + int(seg["nbytes"])],
                    seg["dtype"]).reshape(seg["shape"])
            row += 1
    idx = jnp.asarray(np.asarray(dests, np.int32))

    # Import is rare (restore/handoff/shipment admission, not per token),
    # so plain .at[].set per pool array is fine — no AOT program, no
    # donation games; the batching above keeps it to one set per array.
    def rebuild(pool, layer, field):
        if isinstance(pool, QuantPool):
            return QuantPool(
                q=pool.q.at[idx].set(
                    jnp.asarray(hosts[(layer, field)])),
                scale=pool.scale.at[idx].set(
                    jnp.asarray(hosts[(layer, field + "_scale")])))
        return pool.at[idx].set(jnp.asarray(hosts[(layer, field)]))

    new_k = tuple(rebuild(cache.k[layer], layer, "k")
                  for layer in range(n_layers))
    new_v = tuple(rebuild(cache.v[layer], layer, "v")
                  for layer in range(n_layers))
    return cache.replace(k=new_k, v=new_v), manifests


def import_blocks(cache: PagedKVCache, art_dir: str,
                  dest_blocks: Sequence[int]
                  ) -> Tuple[PagedKVCache, Dict]:
    """Single-artifact :func:`import_block_batch` — same
    verify-everything-before-any-device-write contract; returns
    ``(new_cache, manifest)``."""
    new_cache, manifests = import_block_batch(
        cache, [(art_dir, dest_blocks)])
    return new_cache, manifests[0]


def artifact_bytes(manifest: Dict) -> int:
    """Total payload bytes recorded in a block-artifact manifest."""
    return sum(int(f["size"]) for f in manifest.get("files", {}).values())


def block_payload(cache: PagedKVCache, block: int) -> bytes:
    """One pool block's host-side payload bytes, in :func:`block_layout`
    segment order — byte-identical to what :func:`export_blocks` writes
    for that block, which is what lets tests assert a store/ship
    roundtrip bitwise without re-exporting."""
    return b"".join(
        np.asarray(seg["array"][int(block)]).tobytes()
        for seg in block_layout(cache))


def cache_pspec() -> P:
    """(slots|blocks, kv_heads, positions, head_dim): slots/blocks replicated
    — every device decodes every request — only the heads shard: kv_heads
    on 'tensor', matching the wk/wv kernels that fill the buffer. The spec
    serves BOTH layouts because the paged pool keeps kv_heads at dim 1."""
    return P(None, "tensor", None, None)


def cache_shardings(cache, mesh):
    """NamedSharding pytree for a :class:`KVCache` or :class:`PagedKVCache`
    on ``mesh`` (None -> None), with the same divisibility degrade as the
    param shardings."""
    if mesh is None:
        return None
    from ..parallel.sharding import _fit_spec

    def shard(a):
        return NamedSharding(mesh, _fit_spec(cache_pspec(), a.shape, mesh))

    def shard_pool(p):
        if isinstance(p, QuantPool):
            # scale pools are (blocks, kv_heads): same head sharding as
            # the int8 data, one axis shorter.
            return QuantPool(
                q=shard(p.q),
                scale=NamedSharding(
                    mesh, _fit_spec(P(None, "tensor"), p.scale.shape, mesh)))
        return shard(p)

    return type(cache)(
        k=tuple(shard_pool(a) for a in cache.k),
        v=tuple(shard_pool(a) for a in cache.v),
        lengths=NamedSharding(mesh, P(None)),
    )
