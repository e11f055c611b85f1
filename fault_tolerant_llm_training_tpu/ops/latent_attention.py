"""Reads of the latent-attention caches (models/latent_moe.py): the learned
indexer's scores and its top-k, attention over the rows it picked, and
attention over a sliding window. Plain XLA, no Pallas kernel yet.

Three caches by layer kind (inference/kv_cache.py ``LatentKVCache``):

- a **latent pool** ``(N * bs, r)`` and a **rope-key pool** ``(N * bs,
  d_r)`` a full layer, one row a token each: its normed latent ``c_kv`` and
  its one roped key ``k_r``. Block b of the slot's block table is rows ``[b
  * bs, (b + 1) * bs)``; rows are written and gathered ONE BY ONE, so the
  pools are indexed on dim 0 alone, a 16-bit row is packed as uint32 words
  (``pack_rows``), and the two parts have a pool each because a row of r +
  d_r = 576 values is 288 words, no multiple of the 128 lanes: the compiler
  then stores such a pool column-major and every row access copies it whole
  (as does a pool stored by block, ``(N, 1, bs, C)``: six 1.4 GB copies, 29
  of a 61 ms decode round, measured). 256 and 32 words it stores as given;
- an **index-key pool** ``(N, 1, bs, d_i)`` a full layer, through the same
  table;
- a **window ring** ``(slots, R, r + d_r)`` a sliding layer: position p of
  a slot lives at row ``p % R``, R >= the window, so a slot holds its last
  R rows whatever its context.

**The selected set.** ``A(t)`` = the ``k`` positions ``s <= t`` of largest
indexer score, ties to the lower position (``jax.lax.top_k``'s order), all
of them while ``t + 1 <= k``. A one-token query (decode) takes the indices
and gathers those rows; a chunk (prefill) needs the set as a mask over the
slot's rows, and gets it without a sort: the k-th largest score a row by 32
counting passes over the scores' bit patterns (:func:`kth_largest_key`),
members = above it, and of those equal to it the first by position.

**Two forms of one attention.** With ``[k_n | v]_h = c_kv W_kvb`` the score
``q_n . k_n`` equals ``(q_n W_kvb_k^T) . c_kv``: decode *absorbs* the
up-projection into the query and the output (the cache is read in latent
space, r + d_r values a row whatever the head count); a chunk *expands* the
rows it reads to per-head keys and values once a key block, which costs a
third of the absorbed form's FLOPs at 2,048 queries.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.trace import scope
from .flash_attention import LOG2E, NEG_INF, _interpret

_STAT_LANES = 128

_MASKED = float(jnp.finfo(jnp.float32).min)


# ------------------------------------------------------------ row packing
# A 16-bit row is stored two to a 32-bit sublane on the TPU, so one row of a
# bfloat16 pool cannot be addressed alone: an XLA gather of rows first
# copies the WHOLE pool to an unpacked layout (4.9 ms a 1.4 GB pool,
# measured). The latent pool therefore stores a 16-bit row as half as many
# uint32 words — the same bytes, every row its own sublane — and a float32
# pool (the CPU tests) as it is.
def pack_rows(x: jax.Array) -> jax.Array:
    """(..., C) of a 16-bit type -> (..., C / 2) uint32; others unchanged."""
    if x.dtype.itemsize != 2:
        return x
    return jax.lax.bitcast_convert_type(
        x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2), jnp.uint32)


def unpack_rows(x: jax.Array, dtype, width: int = None) -> jax.Array:
    """The inverse of :func:`pack_rows` for rows of ``dtype``; the first
    ``width`` values of each where the pool's rows are padded."""
    if x.dtype != jnp.uint32 or jnp.dtype(dtype).itemsize != 2:
        return x
    y = jax.lax.bitcast_convert_type(x, dtype)          # (..., C / 2, 2)
    return y.reshape(*x.shape[:-1], x.shape[-1] * 2)[..., :width]


# A packed pool whose rows are narrower than the 128 lanes is stored at 128
# words a row: the 32-word rope keys gathered at 4.7 ms a layer where the
# 256-word latents beside them took 1.7 (a decode round of 64 x 2,048 rows,
# measured); padded they cost 0.96 GB more and gather like the latents.
LANE_WORDS = 128


def packed_width(values: int, dtype) -> int:
    """Columns of a row pool for rows of ``values`` of ``dtype``."""
    if jnp.dtype(dtype).itemsize != 2:
        return values
    return -(-(values // 2) // LANE_WORDS) * LANE_WORDS


# ---------------------------------------------------------------- selection
def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    b = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """Per row of ``keys`` (..., T) uint32: the k-th largest value, built
    bit by bit from the top (the largest threshold that at least k entries
    reach). 32 compare-and-count passes, no sort. k <= T."""
    def body(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    return jax.lax.fori_loop(0, 32, body,
                             jnp.zeros(keys.shape[:-1], jnp.uint32))


def topk_members(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The selected set as a mask: ``scores`` (S, T) float32, ``valid``
    (S, T) (the positions a query may see). True at the ``k`` valid
    positions of largest score, ties to the lower position; every valid
    position where a row has at most ``k``."""
    k = min(k, scores.shape[-1])
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    thr = kth_largest_key(keys, k)[..., None]
    above = (keys > thr) & valid
    equal = (keys == thr) & valid
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32, keepdims=True)

    def with_ties(_):
        # of the entries AT the threshold, the first ``room`` by position
        return above | (equal & (jnp.cumsum(equal, axis=-1,
                                            dtype=jnp.int32) <= room))

    # scores of distinct positions are equal only by accident (or, at toy
    # widths, as exact zeros of the ReLU): the running count is paid for
    # only by a call that holds such a row
    tied = jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32,
                           keepdims=True) > room)
    return jax.lax.cond(tied, with_ties, lambda _: above | equal, None)


def _block_of(n: int, want: int) -> int:
    """The largest divisor of ``n`` that is at most ``want`` (and >= 1)."""
    b = max(1, min(n, want))
    while n % b:
        b -= 1
    return b


def _key_block(nb: int, bs: int, want: int = 256) -> int:
    """Table entries a key block of the chunk loops holds: whole blocks,
    a divisor of the table, within ``want`` rows."""
    return _block_of(nb, want // bs)


def _latent_rows(pool: jax.Array, entries: jax.Array, bs: int) -> jax.Array:
    """Rows of the blocks ``entries`` (G,) of a latent pool (N * bs, C), in
    order: (G * bs, C), a gather on dim 0."""
    at = entries[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)[None, :]
    return jnp.take(pool, at.reshape(-1), axis=0)


@scope("kv_write")
def write_latent_rows(pool, rows, block_tables, start, valid, bs: int):
    """Land ``rows`` (B, S, C) in the latent pool (N * bs, C'): row i of
    batch row b is position ``start[b] + i`` and lands in row ``table[b,
    p // bs] * bs + p % bs``, packed as the pool stores it. Rows with
    ``valid`` (B, S) False, and positions past the table's reach, land
    nowhere (so a block another slot shares is never touched, and null
    block 0 stays scratch). One scatter on dim 0."""
    b, s, _ = rows.shape
    nb = block_tables.shape[1]
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(pos // bs, 0, nb - 1), axis=1)
    at = jnp.where(valid & (pos // bs < nb), blk * bs + pos % bs,
                   pool.shape[0])                     # past the end: dropped
    packed = pack_rows(rows) if pool.dtype == jnp.uint32 else rows
    packed = packed.reshape(b * s, -1).astype(pool.dtype)
    # a packed pool may be wider than its rows (LANE_WORDS)
    packed = jnp.pad(packed, ((0, 0), (0, pool.shape[1] - packed.shape[1])))
    return pool.at[at.reshape(-1)].set(packed, mode="drop")


def _slot_rows(pool: jax.Array, entries: jax.Array) -> jax.Array:
    """Rows of pool blocks ``entries`` (G,), in order: (G * bs, C)."""
    g = pool[entries]                                  # (G, 1, bs, C)
    return g.reshape(g.shape[0] * g.shape[2], g.shape[3])


@scope("index_select")
def index_scores_decode(q_i, w, index_pool, block_tables, pos):
    """Indexer scores of one query a slot over the slot's rows.

    q_i (B, Hi, di) roped index queries, w (B, Hi) float32 head weights
    (already scaled), index_pool (N, 1, bs, di), block_tables (B, NB),
    pos (B,) the query's position. Returns (B, T) float32 with -inf at
    positions past ``pos``: ``I[b, s] = sum_j w[b, j] relu(q_i[b, j] .
    k_i[s])``."""
    g = index_pool[block_tables]                       # (B, NB, 1, bs, di)
    b, nb, _, bs, di = g.shape
    keys = g.reshape(b, nb * bs, di)
    sc = jnp.einsum("bhd,btd->bht", q_i, keys,
                    preferred_element_type=jnp.float32)
    # (float32 x float32 at the default precision is one bfloat16 pass on
    # the chip: the few terms of this sum decide a threshold, so "highest")
    scores = jnp.einsum("bht,bh->bt", jax.nn.relu(sc), w,
                        precision=jax.lax.Precision.HIGHEST)
    live = jnp.arange(nb * bs, dtype=jnp.int32)[None, :] <= pos[:, None]
    return jnp.where(live, scores, -jnp.inf)


@scope("index_select")
def select_topk(scores, k: int):
    """(indices (B, K), chosen (B, K)) of the K = min(k, T) largest of
    ``scores`` (B, T) a row; ``chosen`` is False where a row has fewer
    live positions than K (-inf entries)."""
    vals, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


@scope("index_select")
def index_members_chunk(q_i, w, index_pool, table_row, pos, n_keys, k: int):
    """The selected sets of a chunk's queries, as a mask over the slot's
    rows. q_i (S, Hi, di), w (S, Hi) float32, table_row (NB,), pos (S,)
    query positions, n_keys the rows written so far (a traced scalar: key
    blocks past it are not visited). Returns (S, T) bool."""
    nb, bs = table_row.shape[0], index_pool.shape[2]
    g = _key_block(nb, bs)
    kb = g * bs
    s = q_i.shape[0]

    def block(j, scores):
        keys = _slot_rows(index_pool, jax.lax.dynamic_slice_in_dim(
            table_row, j * g, g))
        sc = jnp.einsum("shd,td->sht", q_i, keys,
                        preferred_element_type=jnp.float32)
        blk = jnp.einsum("sht,sh->st", jax.nn.relu(sc), w,
                         precision=jax.lax.Precision.HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(scores, blk, j * kb, 1)

    scores = jax.lax.fori_loop(
        0, (n_keys + kb - 1) // kb, block,
        jnp.zeros((s, nb * bs), jnp.float32))
    valid = (jnp.arange(nb * bs, dtype=jnp.int32)[None, :] <= pos[:, None])
    return topk_members(scores, valid, k)


# ---------------------------------------------------- full layers: the read
@scope("kv_read")
def latent_decode_attention(q_lat, q_r, latent_pool, rope_pool,
                            block_tables, idx, chosen, scale: float,
                            bs: int):
    """Absorbed attention of one query a slot over the rows ``idx`` picked.

    q_lat (B, H, r) = ``q_n W_kvb_k^T``, q_r (B, H, d_r) roped; the two
    pools (N * bs, r) / (N * bs, d_r) or their packed forms; idx / chosen
    (B, K) positions and their validity. Returns the attended latents (B,
    H, r) float32 — the caller applies ``W_kvb_v``."""
    at = jnp.take_along_axis(block_tables, idx // bs, axis=1) * bs + idx % bs
    rows = unpack_rows(jnp.take(latent_pool, at, axis=0), q_lat.dtype)
    keys = unpack_rows(jnp.take(rope_pool, at, axis=0), q_lat.dtype,
                       q_r.shape[-1])
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, rows,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bkd->bhk", q_r, keys,
                      preferred_element_type=jnp.float32)) * scale
    s = jnp.where(chosen[:, None, :], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32)


def _masked_flash_kernel(nk_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                         keep_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         scale: float, bk: int):
    """One (head, query block, key block) step of attention under an
    explicit mask: ``keep_ref`` (bq, bk) int8 says which keys each query
    sees. Running max / sum / accumulator in VMEM scratch across the key
    blocks (the innermost, sequential grid axis); key blocks at or past
    ``nk_ref[0]`` rows are skipped (their operands are not fetched again:
    the index maps clamp to the last live block)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * bk < nk_ref[0])
    def _block():
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[...], dims,
                                   preferred_element_type=jnp.float32))
        keep = keep_ref[...] != 0
        s = jnp.where(keep, s * (scale * LOG2E), NEG_INF)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(keep, jnp.exp2(s - m_new[:, None]), 0.0)
        alpha = jnp.exp2(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(
            o_ref.dtype)


def masked_flash_attention(q_n, q_r, k_n, k_r, v, keep, n_keys,
                           scale: float, interpret: bool = None):
    """Attention of every head under one explicit (S, T) mask, scores
    never leaving VMEM. q_n (H, S, dn), q_r (H, S, dr), k_n (H, T, dn),
    k_r (T, dr) shared by the heads, v (H, T, dv), keep (S, T) int8,
    n_keys a traced scalar (key blocks past it are skipped). Returns
    (H, S, dv) float32."""
    h, s_q, dn = q_n.shape
    t, dr = k_r.shape
    dv = v.shape[-1]
    bq, bk = _block_of(s_q, 512), _block_of(t, 512)
    last = lambda n: jnp.maximum(n[0] - 1, 0) // bk       # noqa: E731
    kernel = functools.partial(_masked_flash_kernel, scale=scale, bk=bk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, s_q // bq, t // bk),
            in_specs=[
                pl.BlockSpec((1, bq, dn), lambda hi, i, j, n: (hi, i, 0)),
                pl.BlockSpec((1, bq, dr), lambda hi, i, j, n: (hi, i, 0)),
                pl.BlockSpec((1, bk, dn), lambda hi, i, j, n: (
                    hi, jnp.minimum(j, last(n)), 0)),
                pl.BlockSpec((bk, dr), lambda hi, i, j, n: (
                    jnp.minimum(j, last(n)), 0)),
                pl.BlockSpec((1, bk, dv), lambda hi, i, j, n: (
                    hi, jnp.minimum(j, last(n)), 0)),
                pl.BlockSpec((bq, bk), lambda hi, i, j, n: (
                    i, jnp.minimum(j, last(n)))),
            ],
            out_specs=pl.BlockSpec((1, bq, dv),
                                   lambda hi, i, j, n: (hi, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, _STAT_LANES), jnp.float32),   # m
                pltpu.VMEM((bq, _STAT_LANES), jnp.float32),   # l
                pltpu.VMEM((bq, dv), jnp.float32),            # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((h, s_q, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="latent_chunk_attention",
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.reshape(n_keys, (1,)).astype(jnp.int32), q_n, q_r, k_n, k_r, v,
      keep)


def chunk_kernel_fits(s_q: int, nope: int, rope: int, v_dim: int) -> bool:
    """The rule for a chunk's full-layer read: the Mosaic kernel where a
    TPU runs it and the widths fill its tiles (128-lane heads, a rope part
    of whole sublane tiles, query blocks of whole int8 mask tiles), the XLA
    key-block loop elsewhere (the CPU, toy widths)."""
    return (jax.default_backend() == "tpu" and nope % 128 == 0
            and v_dim % 128 == 0 and rope % 64 == 0 and s_q % 32 == 0)


@scope("kv_read")
def latent_chunk_attention(q, members, latent_pool, rope_pool, table_row,
                           w_kvb, n_keys, nope: int, scale: float, bs: int,
                           kernel: bool = None):
    """Expanded attention of a chunk's queries over the slot's rows under
    the mask ``members``, a key block at a time with a running softmax.

    q (S, H, nope + d_r) roped; members (S, T) bool; w_kvb (r, H, nope +
    v). Returns (S, H, v) float32. Key blocks past ``n_keys`` rows are not
    visited. ``kernel`` (default: :func:`chunk_kernel_fits`) takes the
    Mosaic kernel :func:`masked_flash_attention` over keys and values
    expanded once for the whole table, in place of the key-block loop."""
    nb = table_row.shape[0]
    g = _key_block(nb, bs)
    kb = g * bs
    s_q, h, _ = q.shape
    v_dim = w_kvb.shape[-1] - nope
    q_n, q_r = q[..., :nope], q[..., nope:]
    if kernel is None:
        kernel = chunk_kernel_fits(s_q, nope, q_r.shape[-1], v_dim)
    if kernel:
        rows = unpack_rows(_latent_rows(latent_pool, table_row, bs), q.dtype)
        keys = unpack_rows(_latent_rows(rope_pool, table_row, bs), q.dtype,
                           q_r.shape[-1])
        out = masked_flash_attention(
            jnp.transpose(q_n, (1, 0, 2)), jnp.transpose(q_r, (1, 0, 2)),
            jnp.einsum("kr,rhe->hke", rows, w_kvb[..., :nope]), keys,
            jnp.einsum("kr,rhe->hke", rows, w_kvb[..., nope:]),
            members.astype(jnp.int8), n_keys, scale)
        return jnp.transpose(out, (1, 0, 2))

    def block(j, carry):
        m, l, acc = carry
        entries = jax.lax.dynamic_slice_in_dim(table_row, j * g, g)
        rows = unpack_rows(_latent_rows(latent_pool, entries, bs), q.dtype)
        keys = unpack_rows(_latent_rows(rope_pool, entries, bs), q.dtype,
                           q_r.shape[-1])
        kv = jnp.einsum("kr,rhe->khe", rows, w_kvb)
        sc = (jnp.einsum("shd,khd->hsk", q_n, kv[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("shd,kd->hsk", q_r, keys,
                           preferred_element_type=jnp.float32)) * scale
        keep = jax.lax.dynamic_slice_in_dim(members, j * kb, kb, 1)[None]
        sc = jnp.where(keep, sc, _MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(keep, jnp.exp(sc - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        l = l * fix + jnp.sum(p, axis=-1)
        acc = acc * fix[..., None] + jnp.einsum(
            "hsk,khv->hsv", p.astype(kv.dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, (n_keys + kb - 1) // kb, block,
        (jnp.full((h, s_q), _MASKED, jnp.float32),
         jnp.zeros((h, s_q), jnp.float32),
         jnp.zeros((h, s_q, v_dim), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (1, 0, 2))


# ------------------------------------------------ sliding layers: the window
@scope("kv_write")
def write_window_rows(ring, rows, slot_of_row, start, valid):
    """Land ``rows`` (B, S, C) in the window ring (slots, R, C): row i of
    batch row b is position ``start[b] + i`` of slot ``slot_of_row[b]`` and
    lands at ``position % R``. Of a chunk longer than the ring only the
    last R valid rows land; rows with ``valid`` (B, S) False land nowhere."""
    r = ring.shape[1]
    b, s, _ = rows.shape
    i = jnp.arange(s, dtype=jnp.int32)[None, :]
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32, keepdims=True)
    land = valid & (i >= n_valid - r)       # valid rows are a prefix
    at = jnp.where(land, (start[:, None] + i) % r, r)   # r: dropped
    slot = jnp.broadcast_to(slot_of_row[:, None], (b, s))
    return ring.at[slot, at].set(rows, mode="drop")


def _window_positions(pos, ring_rows: int):
    """The position each ring row holds when the newest is ``pos`` (B,):
    (B, R), the latest position <= pos congruent to the row."""
    r = jnp.arange(ring_rows, dtype=jnp.int32)[None, :]
    return pos[:, None] - (pos[:, None] - r) % ring_rows


@scope("kv_read")
def window_decode_attention(q_abs, ring, pos, win_from, window: int,
                            rank: int, scale: float):
    """Absorbed attention of one query a slot over its window ring.

    q_abs (B, H, r + d_r), ring (B, R, r + d_r) with the query's own row
    already written, pos (B,), win_from (B,) the first position the ring
    holds of this request. Returns (B, H, r) float32."""
    held = _window_positions(pos, ring.shape[1])
    seen = ((held > pos[:, None] - window) & (held >= win_from[:, None])
            & (held >= 0))
    s = jnp.einsum("bhc,bkc->bhk", q_abs, ring,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen[:, None, :], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(ring.dtype),
                      ring[..., :rank],
                      preferred_element_type=jnp.float32)


@scope("kv_read")
def window_chunk_attention(q, rows_new, ring_slot, start, seq_from, w_kvb,
                           window: int, rank: int, nope: int, scale: float):
    """Expanded attention of a chunk over its own rows and the
    ``window - 1`` before it in the slot's ring.

    q (S, H, nope + d_r) roped; rows_new (S, r + d_r) the chunk's own
    cache rows (positions ``start + [0, S)``); ring_slot (R, r + d_r) the
    slot's ring BEFORE the chunk is written; seq_from the first position
    this request computed (earlier ring rows are another request's).
    Returns (S, H, v) float32. Queries are banded in blocks of
    ``window - 1`` where the chunk is a multiple of that, so a block reads
    two blocks of keys."""
    s_q, h, _ = q.shape
    back = window - 1
    r = ring_slot.shape[0]
    prev_pos = start - back + jnp.arange(back, dtype=jnp.int32)
    prev = jnp.take(ring_slot, prev_pos % r, axis=0)    # (back, C)
    rows = jnp.concatenate([prev, rows_new], axis=0) if back else rows_new
    key_pos = jnp.concatenate(
        [prev_pos, start + jnp.arange(s_q, dtype=jnp.int32)])
    qb = back if (back and s_q > back and s_q % back == 0) else s_q
    nblk = s_q // qb
    kv = jnp.einsum("kr,rhe->khe", rows[:, :rank], w_kvb)
    k_r = rows[:, rank:]
    q_pos = start + jnp.arange(s_q, dtype=jnp.int32)

    def band(x):       # keys of query block j: [j * qb, j * qb + back + qb)
        return jnp.stack([x[j * qb:j * qb + back + qb] for j in range(nblk)])

    qn = q[..., :nope].reshape(nblk, qb, h, nope)
    qr = q[..., nope:].reshape(nblk, qb, h, -1)
    qp = q_pos.reshape(nblk, qb)

    def one_band(args):     # a band at a time: the scores of one fit easily
        qn_j, qr_j, qp_j, kv_j, kr_j, kp_j = args
        sc = (jnp.einsum("qhd,khd->hqk", qn_j, kv_j[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("qhd,kd->hqk", qr_j, kr_j,
                           preferred_element_type=jnp.float32)) * scale
        seen = ((kp_j[None, :] <= qp_j[:, None])
                & (kp_j[None, :] > qp_j[:, None] - window)
                & (kp_j[None, :] >= seq_from))
        p = jax.nn.softmax(jnp.where(seen[None], sc, _MASKED), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p.astype(kv_j.dtype),
                          kv_j[..., nope:],
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(one_band, (qn, qr, qp, band(kv), band(k_r),
                                 band(key_pos)))
    return out.reshape(s_q, h, -1)
