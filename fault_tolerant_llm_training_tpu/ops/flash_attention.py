"""Pallas TPU flash attention (causal, GQA-aware), forward + backward.

TPU-native replacement for the reference's fused-kernel dependency
``F.scaled_dot_product_attention(is_causal=True)`` (ref: model.py:212), which
on CUDA comes from the NGC container. Here the kernel is first-party:
an online-softmax tiled forward that never materializes the (S, S) score
matrix — O(S) memory, q-tiles streamed through VMEM, scores computed on the
MXU in fp32 — plus Pallas backward kernels that recompute scores per tile
from the saved logsumexp, so the backward is O(S) memory too (the
flash-attention-2 recomputation scheme; the resident family fuses dq and
dk/dv into one kernel, the streaming family keeps them split).

GQA: the kernels map query head ``h`` to KV head ``h // (H // K)`` in the
BlockSpec index map — KV are never repeated in memory (the reference's
``repeat_kv`` at model.py:129-138 materializes the expansion). dk/dv are
written at native KV-head granularity: the resident fused backward emits
its full-row scratch once per KV-head span, and the streaming dk/dv
kernel runs one grid step per *KV* head, accumulating its query-head
group in-kernel.

VPU economy (attention at head_dim 64 is VPU-bound on TPU, not MXU-bound):

- The causal mask (two iotas + compare + select per (bq, bk) tile) is applied
  only to *diagonal* k-blocks; the k-loop is split into a full-block phase
  with no masking and a masked tail. For bq == bk that is one masked block
  per q-tile instead of all of them.
- Softmax runs in base 2: ``log2(e)`` is folded into the per-tile q scaling
  (one (bq, D) multiply) so the inner loop's only transcendental is a bare
  ``exp2`` — no per-element score scaling at all. The saved logsumexp is
  base-2 as well; it is a kernel-internal residual, consumed only by the
  backward kernels which recompute probabilities as ``exp2(s2 - lse2)``.
  Backward accumulators run unscaled and are rescaled once per tile at the
  final write (exact: the accumulation is linear).

lse is carried padding-free in both families (see _lse_layout): the
streaming family as (B, H, 1, S) — q positions on the LANE dim — and the
resident family as (B, H, S/128, 128) — the lse vector wrapped into full
(8, 128) tiles; the family that writes it is the family that reads it.
The Pallas TPU lowering requires a block's last two dims
to be (8k, 128m)-tileable or full, and the TPU (8, 128) tile pads
whatever lands on the trailing dims: the legacy (B, H, S, 1) residual
(kept for unaligned shapes) pads its singleton lane 128x (measured
95.25 MB per layer at the bench shape, seen in HBM dumps), where (1, S)
pads the singleton sublane only 8x and (S/128, 128) pads nothing.
Kernels read the (1, block_q) row / (block_q/128, 128) block and restore
the (block_q, 1) orientation the tile math uses — once per q tile
(cached in scratch where the k loop is the grid).
delta (rowwise dO . O) is computed inside the backward kernels
from the do/o tiles (see _delta) — an XLA-side delta materializes fp32
casts of the full dO and O with layout-change copies at the custom-call
boundary.

Two kernel families, and ONE rule picks the family at a shape, for the
forward and the backward alike: the resident family wherever the fused
backward's VMEM residency fits the chip (_fused_bwd_vmem_limit; the
forward holds a subset of those blocks), the streaming family elsewhere.

- **Resident**: the non-grid operand (K/V, and the dk/dv gradient
  accumulators) sits whole in VMEM and an in-kernel fori_loop walks it —
  no per-block pipeline boundaries, no K/V tile fetched twice — but
  VMEM-bound: the resident rows grow linearly with S and the padded
  widths. The forward is _fwd_kernel on a (b, h, q-tile) grid; the
  backward is ONE fused kernel (_bwd_fused_kernel) producing dq, dk and
  dv from a single pass over the causal tile triangle — the split FA2
  scheme recomputes the softmax core (scores, exp2, dO @ V^T, dS) twice
  per tile, once in dq and once in dk/dv, 7 matmuls and 2 exp passes
  where the fused kernel does 5 and 1 (+10.9% on the round-3 headline
  bench, BASELINE.md). Both calls ask the compiler for the family's VMEM
  (vmem_limit_bytes) where that is more than XLA's default scoped limit.
- **Streaming**: the loop moves into the grid's innermost dimension; the
  online-softmax / gradient accumulators live in VMEM scratch that
  persists across grid steps, and every operand is a fixed-size tile.
  O(1) VMEM in S — this is what makes 32k+ contexts compile on a single
  chip (beyond that, ring attention shards S over the mesh's 'sequence'
  axis, ops/ring_attention.py). Its backward is split: a dq kernel and a
  dk/dv kernel.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes tuned on TPU v5e at S=2048, D=64 (see BASELINE.md); each kernel
# has its own operating point because the blocks play different roles: the
# q-tile is the grid unit in fwd/dq but the loop chunk in dkv, and vice
# versa. FWD retuned again in round 4 after the in-kernel rope shifted the
# balance (512x512: 122.1k vs 120.5k at the round-3 512x1024, and best at
# bs 16 too; round-3's sweep history: the bs-8 peak 256x1024 collapses 26x
# at bs 16 — BASELINE.md). Swept again on v5e past 2,048 rows, one
# resident forward call: at S=8192, 32 heads of 192 / 128, batch 4 — 512x512
# 28.8 ms, 512x1024 29.1, 256x512 29.7, 1024x1024 29.8, 1024x512 30.6; at
# S=4096, 32:8 heads of 128, batch 3 — 512x512 4.53, 256x512 4.61,
# 512x1024 4.71, 1024x1024 4.85, 1024x512 5.07.
FWD_BLOCK_Q, FWD_BLOCK_K = 512, 512
# The fused backward walks the dq tiles. Swept on v5e at S=8192, 32 heads of
# 192 / 128, batch 4 (one backward call): 512x512 57.9 ms, 512x1024 58.3,
# 1024x1024 58.4, 1024x512 59.9, 256x512 63.4.
DQ_BLOCK_Q, DQ_BLOCK_K = 512, 512
DKV_BLOCK_Q, DKV_BLOCK_K = 512, 1024
# The STREAMING family's forward below LONG_STREAM_THRESHOLD (the resident
# family uses FWD_BLOCK_*) keeps the round-3 streamed tiles: its A/B there
# (S=8192: -13%, S=16384: -10% vs the older 1024x256) was measured with
# the 1024-wide k-tile, and the round-4 resident retune does not transfer
# (the grid-streamed pipeline amortizes differently).
MID_FWD_BLOCK_Q, MID_FWD_BLOCK_K = 512, 1024
# Very long sequences, streaming family, get their own operating point
# (tuned at S=32k/64k, B1/H12/D64: -6.6% at 32k, -14.5% at 64k vs the
# resident tiles — the grid-streamed pipeline prefers larger k-tiles in
# fwd/dq and a larger q-tile in dkv there). Below LONG_STREAM_THRESHOLD
# the resident dq/dkv tile sizes measured equal (8k) or clearly better
# (16k: 132 vs 179 ms), so the streaming backward keeps them.
LONG_STREAM_THRESHOLD = 32768
STREAM_FWD_BLOCK_Q, STREAM_FWD_BLOCK_K = 1024, 512
STREAM_DQ_BLOCK_Q, STREAM_DQ_BLOCK_K = 512, 1024
STREAM_DKV_BLOCK_Q, STREAM_DKV_BLOCK_K = 1024, 512
# The family is decided by VMEM, not by a row count: the resident family
# runs wherever the fused backward's residency (_fused_bwd_residency:
# whole K/V rows and dk/dv outputs, two fp32 (S, D) scratches, the q-side
# tiles and the score tiles' temporaries) fits the chip, and both of its
# calls ask the compiler for that much (vmem_limit_bytes) where XLA's
# default scoped limit is too small. The D=64 tile constants transfer to
# D=128 unchanged: a 10-combo resident fwd/dq/dkv sweep at S=2048/D=128
# (scripts/d128_tile_sweep.py) put the defaults first, every variant
# 8-11% slower.
#
# XLA's default --xla_tpu_scoped_vmem_limit_kib: what a Mosaic kernel may
# hold unless its call asks for more. It is not the physical VMEM, which
# jax's chip table gives (pltpu.get_tpu_info(): 128 MiB on v5e).
DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20
# v5e's VMEM as that table gives it: the chip the kernels are tuned on, and
# what a backend with no TPU table is taken to be — the CPU the tests
# interpret on, and the described v5e they compile for.
CALIBRATION_VMEM_BYTES = 128 * 2**20
# In-kernel rope is profitable up to this S*D (rope_fused_profitable).
ROPE_FUSED_SD_BUDGET = 4096 * 64


def rope_fused_profitable(s: int, d: int) -> bool:
    """Whether in-kernel rope (flash_attention_rope) beats XLA-side rope
    at this shape — the dispatch the model's rope_impl='fused' uses.

    Measured on v5e (BASELINE.md round 4): +3.7% headline at S=2048 and
    −2.6% attention time at S=4096 (where K is roped ONCE per span into
    the fused backward's scratch), but +2.1% at S=8192 and +3.7% at
    S=16384, measured when every kernel streamed there. That cost is the
    STREAMING family's: its kernels re-fetch
    each K tile per (q-tile, k-step) grid visit and the rotation rides
    every fetch, so the redundant k-rope grows with S while XLA-side rope
    stays O(S); the resident kernels rope K once per span, and their rope
    tables add 2 x (S, D) fp32 rows to the residency. The boundary is a
    measurement of its own (ROPE_FUSED_SD_BUDGET), not the family's VMEM
    rule, and was not re-measured with the resident forward past 2,048."""
    return s * d <= ROPE_FUSED_SD_BUDGET


def vmem_capacity_bytes() -> int:
    """VMEM of one core of the TPU the kernels run on, from jax's chip
    table (keyed on the device kind; a TPU the table does not know raises
    there); CALIBRATION_VMEM_BYTES where the backend is not a TPU."""
    if jax.default_backend() != "tpu":
        return CALIBRATION_VMEM_BYTES
    return pltpu.get_tpu_info().vmem_capacity_bytes


def _lanes(n: int) -> int:
    """A VMEM row's width: whole 128-lane tiles."""
    return -(-n // 128) * 128


def _fused_bwd_residency(s: int, d: int, dv: int, rope: bool,
                         itemsize: int) -> int:
    """Bytes of VMEM the fused backward's call holds, counted from the
    blocks it builds (widths padded to 128 lanes; q/k width ``d`` and the
    value's ``dv`` apart):

    - pipeline blocks, each double-buffered: the q, dO, O and dq tiles,
      whole K and V rows and the dk/dv outputs of one KV head, the lse
      block (counted as the largest of its layouts, legacy's padded
      (block_q, 128) column), and with ``rope`` the (block_q, D) and
      (S, D) fp32 tables;
    - scratch, single: the fp32 dk/dv accumulators, and with ``rope`` the
      rotated K;
    - temporaries of one k step: two fp32 score tiles live at once (P and
      dP, then dS) and the two cast to the input dtype, the fp32 dq
      accumulator, and the dk/dv row slices read and written back.

    The GQA group does not enter: a span's blocks are one KV head's rows
    whatever the group, revisited across it. Against what Mosaic
    allocates for a described v5e (bf16, the smallest vmem_limit_bytes
    that compiles): kanana-2's S=8192, 192 / 128 counts 43 MiB, needs
    41-42; mistral-7b's S=4096, 128 counts 17.75, needs 15-16; below 128
    lanes the count is high (S=2048, 64: 11.75 counted, 5-6 needed — a
    64-wide row is not padded there)."""
    bq, bk = _blocks(s, *_active_tiles(s, True)[1])
    dp, dvp = _lanes(d), _lanes(dv)
    f32 = 4
    blocks = (2 * bq * dp + 2 * bq * dvp + 2 * s * dp + 2 * s * dvp) * itemsize
    blocks += bq * 128 * f32 + (2 * bq * dp + 2 * s * dp) * f32 * rope
    scratch = (s * dp + s * dvp) * f32 + s * dp * itemsize * rope
    temps = (bq * bk * (2 * f32 + 2 * itemsize) + bq * dp * f32
             + 2 * bk * (dp + dvp) * f32)
    return 2 * blocks + scratch + temps


def _fused_bwd_vmem_limit(s: int, d: int, dv: int, rope: bool,
                          itemsize: int):
    """The resident family's VMEM request in bytes, or None where it does
    not fit and the streaming family runs — the ONE predicate of the
    family, forward and backward (the resident forward holds a subset of
    the fused backward's blocks, so the backward's fit implies its own).

    It asks for the fused backward's residency and a quarter more (what
    the count does not see: Mosaic's internal scratch and relayout
    copies), in whole MiB and never below XLA's default scoped limit, and
    the family is resident where that is at most three quarters of the
    chip's VMEM (the rest left to the compiler). On v5e's 128 MiB, bf16:
    S=8192 at 192 / 128 wide (kanana-2) asks 54 MiB, S=4096 at 128
    (mistral-7b) 23 MiB; S=65536 at 128 does not fit. Measured on v5e at
    those two shapes, one backward call: fused 57.9 ms against the split
    pair's 124.7 (kanana-2, batch 4, 32 heads), 6.8 against 18.3
    (mistral-7b, batch 3, 32:8 heads). The resident forward's own blocks
    need about 18 MiB at kanana-2's shape (a compile for a described v5e:
    16 MiB, XLA's default scoped limit, is too little) and under 8 at
    mistral-7b's."""
    need = _fused_bwd_residency(s, d, dv, rope, itemsize) * 5 // 4
    need = -(-need // 2**20) * 2**20
    if need > vmem_capacity_bytes() * 3 // 4:
        return None
    return max(need, DEFAULT_SCOPED_VMEM_BYTES)


NEG_INF = -1e30
LOG2E = math.log2(math.e)
LN2 = math.log(2.0)


def _prescale_q(q_ref_slice, scale):
    """Pre-scale a q tile by scale*log2(e) (base-2 softmax, see module doc).

    Single source of truth for the rounding: the backward's exp2(s - lse) is
    exact only if every kernel scales (and rounds) q identically.
    """
    return (q_ref_slice.astype(jnp.float32) * (scale * LOG2E)).astype(
        q_ref_slice.dtype)


def _rope_j(d: int):
    """The (D, D) pair-rotation matrix J of the interleaved RoPE convention:
    ``(x @ J)[2j] = -x[2j+1]`` and ``(x @ J)[2j+1] = x[2j]``.

    Lets the kernels apply RoPE as ``x*cos2 + (x@J)*sin2`` — one tiny MXU
    matmul instead of even/odd lane shuffles (which Mosaic lowers poorly)
    or an XLA-side rope whose strided-pair reshapes force the fp32
    relayout-copy family at the custom-call boundary (BASELINE.md round-4
    profile). Entries are exactly +-1, so the product is exact in fp32.
    """
    r = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    plus = (c == r + 1) & (r % 2 == 0)
    minus = (c == r - 1) & (r % 2 == 1)
    return (jnp.where(plus, 1.0, 0.0)
            + jnp.where(minus, -1.0, 0.0)).astype(jnp.float32)


def _rope_rot(x, c, s, scale_const=None):
    """Interleaved-pair RoPE rotation of a (rows, D) tile, fp32 internal.

    ``c``/``s`` are (rows, D) fp32 interleave-duplicated tables
    (``c[r, 2j] == c[r, 2j+1] == cos(angle_j(r))``). With ``scale_const``
    the softmax prescale (scale * log2(e), see _prescale_q) is folded in.
    Rounds back to ``x.dtype`` ONCE at the end; the XLA-side chain rounds
    twice on q (``apply_rope`` -> dtype, then ``_prescale_q`` -> dtype),
    so under bf16 the two paths can differ by that one extra rounding —
    fp32 is bit-identical (ADVICE r4). Within THIS path the forward and
    backward recompute the rotation identically, so ``exp2(s - lse)``
    stays exact regardless."""
    xf = x.astype(jnp.float32)
    xj = jax.lax.dot_general(xf, _rope_j(x.shape[-1]), (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    out = xf * c + xj * s
    if scale_const is not None:
        out = out * scale_const
    return out.astype(x.dtype)


def _rope_rot_t(g, c, s):
    """Transpose (= inverse) rotation applied to an fp32 cotangent tile:
    ``rot^T(g) = g*c - (g*s) @ J`` (J^T = -J; the duplicated-halves
    structure of the tables makes s commute with the pair swap). The
    backward kernels emit dq/dk through this — gradients w.r.t. the RAW
    pre-rope q/k, so no XLA-side rope backward exists at all."""
    return g * c - jax.lax.dot_general(
        g * s, _rope_j(g.shape[-1]), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _causal_select(s, q_start, k_start):
    """Apply the causal mask to a (bq, bk) score tile in place."""
    bq, bk = s.shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _scores(q2, k, q_start, k_start, masked):
    """q2 @ k^T base-2 scores (fp32); q2 is pre-scaled by scale*log2(e).

    Applies the causal select only when ``masked``: statically elided for
    full blocks when ``masked`` is a Python bool (resident kernels), or a
    runtime lax.cond when it is a traced predicate (streaming kernels,
    where the diagonal/full distinction is a grid position).
    q2: (bq, D), k: (bk, D) -> (bq, bk).
    """
    s = jax.lax.dot_general(
        q2, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if isinstance(masked, bool):
        return _causal_select(s, q_start, k_start) if masked else s
    return jax.lax.cond(
        masked, lambda x: _causal_select(x, q_start, k_start), lambda x: x, s)


def _online_softmax_step(q2, k, v, carry, q_start, k_start, masked):
    """One online-softmax accumulation over a (bq, bk) tile.

    carry = (m, l, acc) running rowwise max (base-2), normalizer, and fp32
    PV accumulator. Shared by the resident and streaming forward kernels so
    their math can never diverge."""
    m_prev, l_prev, acc_prev = carry
    s = _scores(q2, k, q_start, k_start, masked)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp2(s - m_new[:, None])
    alpha = jnp.exp2(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_new = acc_prev * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _active_tiles(s: int, resident: bool):
    """The (fwd, dq, dkv) (block_q, block_k) pairs the kernel family
    (``resident``, see _fused_bwd_vmem_limit) uses at sequence length
    ``s`` — the single source of truth for the tile-set dispatch, shared
    by _flash_fwd_t, _flash_bwd_t and _lse_layout (which must validate
    lane alignment against the SAME q-tiles). The resident family has one
    tile set; the streaming family's forward takes the mid or the long
    set by LONG_STREAM_THRESHOLD, its backward the long set past it."""
    if resident:
        return ((FWD_BLOCK_Q, FWD_BLOCK_K),
                (DQ_BLOCK_Q, DQ_BLOCK_K),
                (DKV_BLOCK_Q, DKV_BLOCK_K))
    if s >= LONG_STREAM_THRESHOLD:
        return ((STREAM_FWD_BLOCK_Q, STREAM_FWD_BLOCK_K),
                (STREAM_DQ_BLOCK_Q, STREAM_DQ_BLOCK_K),
                (STREAM_DKV_BLOCK_Q, STREAM_DKV_BLOCK_K))
    return ((MID_FWD_BLOCK_Q, MID_FWD_BLOCK_K),
            (DQ_BLOCK_Q, DQ_BLOCK_K),
            (DKV_BLOCK_Q, DKV_BLOCK_K))


def _lse_layout(s: int, resident: bool) -> str:
    """The lse residual's memory layout at sequence length ``s`` in the
    kernel family ``resident`` (the family writes it and the same family
    reads it):

    - ``"packed"`` — (B, H, 1, S), q positions on the lane dim. Streaming
      family, where the legacy layout's padding is the point — e.g.
      384 MB at S=64k — and every q-tile is 128-aligned (odd sequence
      lengths degrade tiles below 128 rows, making the packed blocks
      illegal). Consumers (via _read_lse): the streaming backward
      kernels.
    - ``"blocked"`` — (B, H, S/128, 128): the resident family's packed
      form (VERDICT r4 weak #3, the one variant the r2/r3 rejection
      sweeps never built). The forward's (block_q,) lse vector wraps to
      (block_q/128, 128) — a lane-preserving reshape, unlike the r3
      relayout/transpose variants (−1.4 to −3%) — and the fused backward
      unwraps it once per q-tile. Zero padding: the fp32 (S/128, 128)
      plane tiles natively. Requires the resident q-tiles (and so s) to
      be 128-multiples; FTL_LSE_RESIDENT=legacy opts out (A/B knob).
    - ``"legacy"`` — (B, H, S, 1), whose singleton lane pads 128x
      (~1.1 GB at the bs-8 bench shape). Kept for unaligned shapes.
    """
    if not all(_fit_block(s, bq) % 128 == 0
               for bq, _ in _active_tiles(s, resident)):
        return "legacy"
    if not resident:
        return "packed"
    if os.environ.get("FTL_LSE_RESIDENT", "blocked") == "legacy":
        return "legacy"
    return "blocked"


def _read_lse(ref, g, layout):
    """(block_q, 1) column lse from a kernel ref; ``g`` is the GQA group
    row (0 for per-head refs). Streaming-family layouts only — the
    resident family's fused backward reads its own layouts inline (the
    "blocked" unwrap needs the grid's q-tile index)."""
    if layout == "packed":
        return jnp.transpose(ref[0, g])  # (1, bq) -> (bq, 1)
    return ref[0, g]


def _delta(do, o):
    """Rowwise dO . O — the softmax-normalization term, (bq, 1) fp32.

    Computed in-kernel from tiles already resident in VMEM: an XLA-side
    delta materializes fp32 casts of the full (B, H, S, D) dO and O with
    layout-change copies around the custom-call boundary (profiled at
    several ms/step, BASELINE.md breakdown).
    """
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)


def _dq_tile(q2, k, v, do, lse, delta, q_start, k_start, masked):
    """Unscaled dq contribution of one (bq, bk) tile (caller scales once)."""
    s = _scores(q2, k, q_start, k_start, masked)
    p = jnp.exp2(s - lse)  # exact probabilities; lse is (bq, 1), base-2
    dp = jax.lax.dot_general(  # dO @ V^T: (bq, bk)
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dkv_tile(q2, k, v, do, lse, delta, q_start, k_start, masked):
    """(dk, dv) contributions of one (bq, bk) tile for one GQA query head.

    dk is unscaled: dk_true = (ds*scale)^T @ q_raw = (ds^T @ q2) * ln(2)
    since q2 = q_raw * scale * log2(e); the caller rescales once."""
    s = _scores(q2, k, q_start, k_start, masked)
    p = jnp.exp2(s - lse)
    dv_c = jax.lax.dot_general(  # P^T @ dO
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(  # dO @ V^T
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_c = jax.lax.dot_general(  # dS^T @ Q2
        ds.astype(q2.dtype), q2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return dk_c, dv_c


def _k_block_bounds(q_start, block_q, s_k, block_k, causal):
    """(n_full, n_total) k-block counts for a q-tile at ``q_start``.

    Blocks [0, n_full) are fully attended (no mask needed); blocks
    [n_full, n_total) straddle the diagonal and need the causal select.
    A k-block [ks, ks+bk) is full iff ks + bk - 1 <= q_start (its every key
    is visible to the tile's *first* row, hence to all rows).
    """
    n_blocks = s_k // block_k
    if not causal:
        return n_blocks, n_blocks
    n_total = jnp.minimum(
        (q_start + block_q + block_k - 1) // block_k, n_blocks)
    n_full = jnp.minimum(q_start // block_k, n_total)
    return n_full, n_total


def _fwd_kernel(*refs, block_k: int, scale: float, causal: bool,
                rope: bool = False, group: int = 1,
                lse_blocked: bool = False):
    # q_ref/o_ref: (1, 1, block_q, D); k_ref/v_ref: (1, 1, S, D);
    # lse_ref: (1, 1, block_q/128, 128) in the blocked layout (the
    # resident default — the (block_q,) lse vector wraps lane-preserving,
    # see _lse_layout), else (1, 1, block_q, 1) legacy.
    # rope=True adds (cq, sq) q-row and (ck, sk) full-row table refs plus a
    # (S, D) scratch holding this KV head's rotated K (computed once per
    # GQA span — see _rope_rot; q is rotated per tile with the softmax
    # prescale folded into the tables' scalar).
    if rope:
        (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, k2_scr) = refs

        @pl.when((pl.program_id(2) == 0) & (pl.program_id(1) % group == 0))
        def _rope_k():
            k2_scr[...] = _rope_rot(k_ref[0, 0], ck_ref[...], sk_ref[...])

        q2 = _rope_rot(q_ref[0, 0], cq_ref[...], sq_ref[...], scale * LOG2E)

        def k_at(start):
            return k2_scr[pl.ds(start, block_k), :]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        q2 = _prescale_q(q_ref[0, 0], scale)

        def k_at(start):
            return k_ref[0, 0, pl.ds(start, block_k), :]
    block_q, d = q2.shape
    s_k = k_ref.shape[2]
    q_start = pl.program_id(2) * block_q
    n_full, n_total = _k_block_bounds(q_start, block_q, s_k, block_k, causal)

    def body(j, carry, masked):
        k_start = j * block_k
        k = k_at(k_start)
        v = v_ref[0, 0, pl.ds(k_start, block_k), :]
        return _online_softmax_step(q2, k, v, carry, q_start, k_start, masked)

    init = (jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32),
            jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32))
    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(body, masked=False), init)
    m, l, acc = jax.lax.fori_loop(
        n_full, n_total, functools.partial(body, masked=causal), carry)
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log2(l)  # base-2, internal only
    if lse_blocked:
        # Full (S/128, 128) plane revisited across q-tiles (Mosaic wants
        # block dims 8/128-divisible or full; block_q/128 rows is neither
        # at the production tiles) — each tile stores its wrapped rows.
        rows = block_q // 128
        lse_ref[0, 0, pl.ds(pl.program_id(2) * rows, rows), :] = (
            lse.reshape(rows, 128))
    else:
        lse_ref[0, 0] = lse[:, None]


def _bwd_fused_kernel(*refs, block_k: int, scale: float, causal: bool,
                      group: int, lse_blocked: bool, rope: bool = False):
    """Fused resident backward: dq, dk and dv from ONE pass over the score
    tiles.

    The split FA2 kernels each recompute the tile's scores, probabilities
    (exp2) and dP = dO @ V^T — i.e. the whole VPU-bound softmax core runs
    twice per (q, k) tile. Here the grid walks q tiles (like the dq
    kernel); dq accumulates per grid step, while dk/dv accumulate into
    full-row fp32 VMEM scratch that persists across the (GQA group x
    q-tile) span of one KV head and is emitted once at the span's last
    step. Per tile: 5 matmuls + 1 exp pass, vs the split kernels' 7 + 2.
    Resident family only — the scratch is (S, D) fp32, which is exactly
    the full-row VMEM residency that defines the family.

    Grid (b, h, qi), qi innermost. q/do/o/dq: (1, 1, block_q, D) at qi;
    k/v: (1, 1, S, D) and dk/dv out: (1, 1, S, D) at KV head h // group
    (their blocks are revisited across the span, written back on the last
    step); lse: the whole (1, 1, S/128, 128) plane with ``lse_blocked``,
    else (1, 1, block_q, 1) legacy — as the resident forward wrote it.

    rope=True adds (cq, sq) q-row and (ck, sk) full-row RAW table refs plus
    a (S, D) rotated-K scratch: scores recompute the forward's exact
    rotation; dq/dk are emitted through the transpose rotation
    (_rope_rot_t) so the kernel's outputs are gradients w.r.t. the raw
    pre-rope q/k — no XLA-side rope backward exists.
    """
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, cq_ref, sq_ref,
         ck_ref, sk_ref, dq_ref, dk_ref, dv_ref,
         dk_scr, dv_scr, k2_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
         dq_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    n_qi = pl.num_programs(2)

    @pl.when((qi == 0) & (hi % group == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if rope:
            k2_scr[...] = _rope_rot(k_ref[0, 0], ck_ref[...], sk_ref[...])

    if rope:
        q2 = _rope_rot(q_ref[0, 0], cq_ref[...], sq_ref[...], scale * LOG2E)
    else:
        q2 = _prescale_q(q_ref[0, 0], scale)
    do = do_ref[0, 0]
    # lse is read once per grid step: the "blocked" plane (the resident
    # default) unwraps this tile's rows of the full (S/128, 128) plane back
    # to the (block_q, 1) column; "legacy" is that column already.
    if lse_blocked:
        # Mosaic cannot shape-cast (rows, 128) -> (block_q, 1) directly;
        # per-row (1, 128) -> (128, 1) transposes + a sublane concat
        # restore the column.
        rows = q2.shape[0] // 128
        band = lse_ref[0, 0, pl.ds(qi * rows, rows), :]
        lse = jnp.concatenate(
            [jnp.transpose(band[r:r + 1, :]) for r in range(rows)], axis=0)
    else:
        lse = lse_ref[0, 0]
    delta = _delta(do, o_ref[0, 0])
    block_q, d = q2.shape
    s_k = k_ref.shape[2]
    q_start = qi * block_q
    n_full, n_total = _k_block_bounds(q_start, block_q, s_k, block_k, causal)

    def body(j, dq_acc, masked):
        k_start = j * block_k
        if rope:
            k = k2_scr[pl.ds(k_start, block_k), :]
        else:
            k = k_ref[0, 0, pl.ds(k_start, block_k), :]
        v = v_ref[0, 0, pl.ds(k_start, block_k), :]
        s = _scores(q2, k, q_start, k_start, masked)
        p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(  # dO @ V^T
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dv_scr[pl.ds(k_start, block_k), :] = (
            dv_scr[pl.ds(k_start, block_k), :]
            + jax.lax.dot_general(  # P^T @ dO
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dk_scr[pl.ds(k_start, block_k), :] = (
            dk_scr[pl.ds(k_start, block_k), :]
            + jax.lax.dot_general(  # dS^T @ Q2
                ds.astype(q2.dtype), q2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return dq_acc + jax.lax.dot_general(  # dS @ K
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_full, functools.partial(body, masked=False),
                           jnp.zeros((block_q, d), jnp.float32))
    dq = jax.lax.fori_loop(n_full, n_total,
                           functools.partial(body, masked=causal), dq)
    if rope:
        dq = _rope_rot_t(dq, cq_ref[...], sq_ref[...])
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)

    @pl.when((qi == n_qi - 1) & (hi % group == group - 1))
    def _emit():
        dk = dk_scr[...]
        if rope:
            dk = _rope_rot_t(dk, ck_ref[...], sk_ref[...])
        dk_ref[0, 0] = (dk * LN2).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _stream_bounds(ki, q_start, block_q, n_k, block_k, causal):
    """(useful, masked, n_total) for streamed k-step ``ki`` of a q-tile.

    Single source of truth for the causal grid bounds shared by the fwd and
    dq streaming kernels (the dkv kernel streams the transposed geometry and
    has its own bounds).
    """
    if not causal:
        return True, False, n_k
    n_full, n_total = _k_block_bounds(q_start, block_q, n_k * block_k,
                                      block_k, causal)
    return ki < n_total, ki >= n_full, n_total


def _fwd_stream_kernel(*refs, block_q: int, block_k: int,
                       scale: float, causal: bool, lse_layout: str,
                       rope: bool = False):
    # grid (b, h, qi, ki), ki innermost/sequential. q_ref/o_ref:
    # (1, 1, block_q, D) at qi; k_ref/v_ref: (1, 1, block_k, D) at ki;
    # lse_ref: (1, 1, 1, block_q). Scratch (fp32, persists across ki):
    # m/l (block_q, 1), acc (block_q, D).
    # rope=True adds (cq, sq) q-row tables at qi and (ck, sk) k-row
    # tables at ki (same clamped index map as k/v); q and the k tile are
    # rotated per step — the tile is re-fetched per (qi, ki) anyway, so
    # there is no span to cache across.
    if rope:
        (q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_start = pl.program_id(2) * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    useful, masked, n_total = _stream_bounds(ki, q_start, block_q, n_k,
                                             block_k, causal)

    @pl.when(useful)
    def _step():
        if rope:
            q2 = _rope_rot(q_ref[0, 0], cq_ref[...], sq_ref[...],
                           scale * LOG2E)
            k = _rope_rot(k_ref[0, 0], ck_ref[...], sk_ref[...])
        else:
            q2 = _prescale_q(q_ref[0, 0], scale)
            k = k_ref[0, 0]
        carry = (m_scr[...][:, 0], l_scr[...][:, 0], acc_scr[...])
        m, l, acc = _online_softmax_step(q2, k, v_ref[0, 0], carry,
                                         q_start, k_start, masked)
        m_scr[...] = m[:, None]
        l_scr[...] = l[:, None]
        acc_scr[...] = acc

    @pl.when(ki == n_total - 1)
    def _emit():
        l = l_scr[...][:, 0]
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)
        lse = m_scr[...][:, 0] + jnp.log2(l)
        lse_ref[0, 0] = (lse[None, :] if lse_layout == "packed"
                         else lse[:, None])


def _dq_stream_kernel(*refs, block_q: int,
                      block_k: int, scale: float, causal: bool,
                      lse_layout: str, rope: bool = False):
    # grid (b, h, qi, ki), ki innermost. Same tiling as _fwd_stream_kernel
    # plus do/o at qi; lse: (1, 1, 1, block_q). Scratch: dq (block_q, D)
    # fp32, delta and column-oriented lse (block_q, 1) fp32, all persisting
    # across ki (delta/lse depend only on the q tile, so they are computed
    # once at ki == 0).
    # rope=True adds (cq, sq) / (ck, sk) table refs plus a rotated-q2
    # scratch (cached at ki == 0 — the rotation depends only on the q
    # tile); k tiles rotate per step; dq emits through _rope_rot_t.
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, cq_ref, sq_ref,
         ck_ref, sk_ref, dq_ref, dq_scr, delta_scr, lse_scr, q2_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
         dq_ref, dq_scr, delta_scr, lse_scr) = refs
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    q_start = pl.program_id(2) * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        delta_scr[...] = _delta(do_ref[0, 0], o_ref[0, 0])
        lse_scr[...] = _read_lse(lse_ref, 0, lse_layout)
        if rope:
            q2_scr[...] = _rope_rot(q_ref[0, 0], cq_ref[...], sq_ref[...],
                                    scale * LOG2E)

    useful, masked, n_total = _stream_bounds(ki, q_start, block_q, n_k,
                                             block_k, causal)

    @pl.when(useful)
    def _step():
        if rope:
            q2 = q2_scr[...]
            k = _rope_rot(k_ref[0, 0], ck_ref[...], sk_ref[...])
        else:
            q2 = _prescale_q(q_ref[0, 0], scale)
            k = k_ref[0, 0]
        dq_scr[...] = dq_scr[...] + _dq_tile(
            q2, k, v_ref[0, 0], do_ref[0, 0], lse_scr[...],
            delta_scr[...], q_start, k_start, masked)

    @pl.when(ki == n_total - 1)
    def _emit():
        dq = dq_scr[...]
        if rope:
            dq = _rope_rot_t(dq, cq_ref[...], sq_ref[...])
        dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_stream_kernel(*refs, block_q: int,
                       block_k: int, scale: float, causal: bool,
                       lse_layout: str, rope: bool = False):
    # grid (b, kv_head, ki, qi), qi innermost. k/v/dk/dv: (1, 1, block_k, D)
    # at ki; q/do/o: (1, G, block_q, D) at qi; lse: (1, G, 1, block_q).
    # delta is recomputed per (g, qi) step — negligible next to the tile's
    # matmuls, and qi is the INNER grid axis so a single-tile cache cannot
    # hold it across the k rows.
    # Scratch dk/dv (block_k, D) fp32, persists across qi.
    # rope=True adds (cq, sq) q-row tables at qi and (ck, sk) k-row tables
    # at ki, plus a rotated-k scratch cached at qi == 0 (the k tile is
    # this grid row's constant); q rotates per (g, step) — the tables are
    # head-independent; dk emits through _rope_rot_t.
    if rope:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, cq_ref, sq_ref,
         ck_ref, sk_ref, dk_ref, dv_ref, dk_scr, dv_scr, k2_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    qi = pl.program_id(3)
    n_q = pl.num_programs(3)
    k_start = pl.program_id(2) * block_k
    q_start = qi * block_q
    group = q_ref.shape[1]
    v = v_ref[0, 0]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if rope:
            k2_scr[...] = _rope_rot(k_ref[0, 0], ck_ref[...], sk_ref[...])

    if causal:
        j_start = k_start // block_q
        j_full = (k_start + block_k - 1 + block_q - 1) // block_q
        useful = qi >= j_start
        masked = qi < j_full
    else:
        useful, masked = True, False

    @pl.when(useful)
    def _step():
        k = k2_scr[...] if rope else k_ref[0, 0]
        dk_acc, dv_acc = dk_scr[...], dv_scr[...]
        for g in range(group):  # static loop: accumulate the GQA group
            if rope:
                q2 = _rope_rot(q_ref[0, g], cq_ref[...], sq_ref[...],
                               scale * LOG2E)
            else:
                q2 = _prescale_q(q_ref[0, g], scale)
            dk_c, dv_c = _dkv_tile(q2, k, v, do_ref[0, g],
                                   _read_lse(lse_ref, g, lse_layout),
                                   _delta(do_ref[0, g], o_ref[0, g]),
                                   q_start, k_start, masked)
            dk_acc, dv_acc = dk_acc + dk_c, dv_acc + dv_c
        dk_scr[...], dv_scr[...] = dk_acc, dv_acc

    @pl.when(qi == n_q - 1)
    def _emit():
        dk = dk_scr[...]
        if rope:
            dk = _rope_rot_t(dk, ck_ref[...], sk_ref[...])
        dk_ref[0, 0] = (dk * LN2).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _fit_block(s, block):
    """Largest usable tile size <= ``block`` for a sequence of length ``s``.

    The tuned defaults are large (up to 1024); a sequence length they don't
    divide (e.g. 1536) degrades to a smaller tile instead of failing. Tiles
    must divide ``s`` and satisfy the TPU tiling rule from the module doc —
    a multiple of 8 sublanes, or the full dim; if no such divisor exists
    (e.g. prime ``s``), the whole sequence becomes one tile."""
    block = min(block, s)
    if s % block == 0:
        return block
    best = s  # "full" is always a legal tile
    for b in range(8, block + 1, 8):
        if s % b == 0:
            best = b
    if best < block // 4 or best > block * 4:
        import logging
        logging.getLogger(__name__).warning(
            "flash attention: seq len %d forces a %d-row tile far from the "
            "tuned %d; expect degraded throughput (pad the sequence length "
            "to a multiple of a large power of two to avoid this)",
            s, best, block)
    return best


def _blocks(s, block_q, block_k):
    return _fit_block(s, block_q), _fit_block(s, block_k)


def _vmem_params(vmem_limit):
    """A resident call's compiler params: the family's VMEM request where
    XLA's default scoped limit is too small, else the default."""
    if vmem_limit > DEFAULT_SCOPED_VMEM_BYTES:
        return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
    return None


def _flash_fwd(q, k, v, causal, interpret):
    # (B, S, H, D) -> (B, H, S, D) so heads become a grid axis.
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out, lse = _flash_fwd_t(qt, kt, vt, causal, interpret)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _flash_fwd_t(qt, kt, vt, causal, interpret, rope_tables=None):
    # Head-major (B, H, S, D) operands — heads are a grid axis. The value
    # (and so the output) may be narrower than the query/key: D is q/k's
    # width, dv v's (latent attention: 192 / 128).
    # rope_tables: optional (cos2, sin2) interleave-duplicated (S, D) fp32
    # tables — the kernels then apply RoPE to q/k tiles in VMEM
    # (flash_attention_rope); q/k arrive RAW.
    b, h, s, d = qt.shape
    dv = vt.shape[-1]
    kv_heads = kt.shape[1]
    group = h // kv_heads
    scale = 1.0 / (d ** 0.5)
    rope = rope_tables is not None
    vmem_limit = _fused_bwd_vmem_limit(s, d, dv, rope, kt.dtype.itemsize)
    resident = vmem_limit is not None
    block_q, block_k = _blocks(s, *_active_tiles(s, resident)[0])
    layout = _lse_layout(s, resident)
    if layout == "packed":
        lse_shape = (b, h, 1, s)
        lse_spec = pl.BlockSpec((1, 1, 1, block_q),
                                lambda bi, hi, qi, *_: (bi, hi, 0, qi))
    elif layout == "blocked":
        lse_shape = (b, h, s // 128, 128)
        lse_spec = pl.BlockSpec((1, 1, s // 128, 128),
                                lambda bi, hi, qi, *_: (bi, hi, 0, 0))
    else:
        lse_shape = (b, h, s, 1)
        lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda bi, hi, qi, *_: (bi, hi, qi, 0))
    out_shape = [
        jax.ShapeDtypeStruct((b, h, s, dv), qt.dtype),
        jax.ShapeDtypeStruct(lse_shape, jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, dv),
                     lambda bi, hi, qi, *_: (bi, hi, qi, 0)),
        lse_spec,
    ]

    if resident:
        kernel = functools.partial(_fwd_kernel, block_k=block_k, scale=scale,
                                   causal=causal, rope=rope, group=group,
                                   lse_blocked=(layout == "blocked"))
        in_specs = [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
            pl.BlockSpec((1, 1, s, dv), lambda bi, hi, qi: (bi, hi // group, 0, 0)),
        ]
        operands = (qt, kt, vt)
        scratch = []
        if rope:
            cq_spec = pl.BlockSpec((block_q, d), lambda bi, hi, qi: (qi, 0))
            ck_spec = pl.BlockSpec((s, d), lambda bi, hi, qi: (0, 0))
            in_specs += [cq_spec, cq_spec, ck_spec, ck_spec]
            operands += (*rope_tables, *rope_tables)
            scratch = [pltpu.VMEM((s, d), kt.dtype)]
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, h, s // block_q),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=_vmem_params(vmem_limit),
            interpret=interpret,
        )(*operands)
    else:
        kernel = functools.partial(_fwd_stream_kernel, block_q=block_q,
                                   block_k=block_k, scale=scale,
                                   causal=causal, lse_layout=layout,
                                   rope=rope)
        # Causal: grid steps past the diagonal are no-ops in the kernel, so
        # clamp their K/V block index to the last useful one — an unchanged
        # index makes the pipeline skip the HBM fetch entirely.
        if causal:
            def kv_idx(bi, hi, qi, ki):
                last = (qi * block_q + block_q - 1) // block_k
                return (bi, hi // group, jnp.minimum(ki, last), 0)

            def ck_idx(bi, hi, qi, ki):
                last = (qi * block_q + block_q - 1) // block_k
                return (jnp.minimum(ki, last), 0)
        else:
            def kv_idx(bi, hi, qi, ki):
                return (bi, hi // group, ki, 0)

            def ck_idx(bi, hi, qi, ki):
                return (ki, 0)
        in_specs = [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_idx),
            pl.BlockSpec((1, 1, block_k, dv), kv_idx),
        ]
        operands = (qt, kt, vt)
        if rope:
            cq_spec = pl.BlockSpec((block_q, d),
                                   lambda bi, hi, qi, ki: (qi, 0))
            ck_spec = pl.BlockSpec((block_k, d), ck_idx)
            in_specs += [cq_spec, cq_spec, ck_spec, ck_spec]
            operands += (*rope_tables, *rope_tables)
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, h, s // block_q, s // block_k),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, dv), jnp.float32),
            ],
            interpret=interpret,
        )(*operands)
    return out, lse


def _flash_bwd(q, k, v, o, lse, g, causal, interpret):
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = jnp.transpose(o, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    dq, dk, dv = _flash_bwd_t(qt, kt, vt, ot, lse, dot, causal, interpret)
    return (jnp.transpose(dq, (0, 2, 1, 3)),
            jnp.transpose(dk, (0, 2, 1, 3)),
            jnp.transpose(dv, (0, 2, 1, 3)))


def _flash_bwd_t(qt, kt, vt, ot, lse, dot, causal, interpret,
                 rope_tables=None):
    """Pallas backward on head-major operands. Where its VMEM fits the chip
    (_fused_bwd_vmem_limit): ONE fused kernel on a (b, h, q-tile) grid
    producing dq, dk and dv per pass (_bwd_fused_kernel). Elsewhere split
    streaming kernels — dq via a (head, q-tile, k-step) grid, dk/dv via a
    (kv-head, k-tile, q-step) grid that accumulates the GQA group
    in-kernel.

    rope_tables: optional (cos2, sin2) (S, D) fp32 — in-kernel RoPE mode
    (q/k and the saved residuals are RAW; dq/dk come back w.r.t. raw).
    v, o, do and dv take the value's own width dv (D is q/k's)."""
    b, h, s, d = qt.shape
    dv = vt.shape[-1]
    kv_heads = kt.shape[1]
    group = h // kv_heads
    scale = 1.0 / (d ** 0.5)
    rope = rope_tables is not None
    vmem_limit = _fused_bwd_vmem_limit(s, d, dv, rope, kt.dtype.itemsize)
    resident = vmem_limit is not None
    (_, __), (dq_q, dq_k), (dkv_q, dkv_k) = _active_tiles(s, resident)
    dq_bq, dq_bk = _blocks(s, dq_q, dq_k)
    dkv_bq, dkv_bk = _blocks(s, dkv_q, dkv_k)
    layout = _lse_layout(s, resident)
    # delta (rowwise dO . O) is computed inside the kernels from the do/o
    # tiles (see _delta) — no fp32 materialization at the XLA level.

    if resident:
        # Fused single-pass backward (see _bwd_fused_kernel): dq, dk, dv
        # from one walk of the causal tile triangle, reading the lse the
        # resident forward wrote ("blocked", or "legacy" where unaligned).
        q_spec = pl.BlockSpec((1, 1, dq_bq, d), lambda bi, hi, qi: (bi, hi, qi, 0))
        kv_full = pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi // group, 0, 0))
        o_spec = pl.BlockSpec((1, 1, dq_bq, dv),
                              lambda bi, hi, qi: (bi, hi, qi, 0))
        v_full = pl.BlockSpec((1, 1, s, dv),
                              lambda bi, hi, qi: (bi, hi // group, 0, 0))
        if layout == "blocked":
            row_spec = pl.BlockSpec((1, 1, s // 128, 128),
                                    lambda bi, hi, qi: (bi, hi, 0, 0))
        else:
            row_spec = pl.BlockSpec((1, 1, dq_bq, 1),
                                    lambda bi, hi, qi: (bi, hi, qi, 0))
        in_specs = [q_spec, kv_full, v_full, o_spec, row_spec, o_spec]
        operands = (qt, kt, vt, dot, lse, ot)
        scratch = [pltpu.VMEM((s, d), jnp.float32),
                   pltpu.VMEM((s, dv), jnp.float32)]
        if rope:
            cq_spec = pl.BlockSpec((dq_bq, d), lambda bi, hi, qi: (qi, 0))
            ck_spec = pl.BlockSpec((s, d), lambda bi, hi, qi: (0, 0))
            in_specs += [cq_spec, cq_spec, ck_spec, ck_spec]
            operands += (*rope_tables, *rope_tables)
            scratch.append(pltpu.VMEM((s, d), kt.dtype))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, block_k=dq_bk, scale=scale,
                              causal=causal, group=group,
                              lse_blocked=(layout == "blocked"), rope=rope),
            grid=(b, h, s // dq_bq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, dq_bq, d),
                                    lambda bi, hi, qi: (bi, hi, qi, 0)),
                       kv_full, v_full],
            out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                       jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                       jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
            scratch_shapes=scratch,
            compiler_params=_vmem_params(vmem_limit),
            interpret=interpret,
        )(*operands)
    else:
        q_spec = pl.BlockSpec((1, 1, dq_bq, d),
                              lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        if causal:  # same fetch-elision clamp as the fwd streaming kernel
            def dq_kv_idx(bi, hi, qi, ki):
                last = (qi * dq_bq + dq_bq - 1) // dq_bk
                return (bi, hi // group, jnp.minimum(ki, last), 0)
        else:
            def dq_kv_idx(bi, hi, qi, ki):
                return (bi, hi // group, ki, 0)
        kv_spec = pl.BlockSpec((1, 1, dq_bk, d), dq_kv_idx)
        v_spec = pl.BlockSpec((1, 1, dq_bk, dv), dq_kv_idx)
        o_spec = pl.BlockSpec((1, 1, dq_bq, dv),
                              lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        if layout == "packed":
            row_spec = pl.BlockSpec((1, 1, 1, dq_bq),
                                    lambda bi, hi, qi, ki: (bi, hi, 0, qi))
        else:
            row_spec = pl.BlockSpec((1, 1, dq_bq, 1),
                                    lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        in_specs = [q_spec, kv_spec, v_spec, o_spec, row_spec, o_spec]
        operands = (qt, kt, vt, dot, lse, ot)
        scratch = [pltpu.VMEM((dq_bq, d), jnp.float32),
                   pltpu.VMEM((dq_bq, 1), jnp.float32),
                   pltpu.VMEM((dq_bq, 1), jnp.float32)]
        if rope:
            if causal:
                def dq_ck_idx(bi, hi, qi, ki):
                    last = (qi * dq_bq + dq_bq - 1) // dq_bk
                    return (jnp.minimum(ki, last), 0)
            else:
                def dq_ck_idx(bi, hi, qi, ki):
                    return (ki, 0)
            cq_spec = pl.BlockSpec((dq_bq, d),
                                   lambda bi, hi, qi, ki: (qi, 0))
            ck_spec = pl.BlockSpec((dq_bk, d), dq_ck_idx)
            in_specs += [cq_spec, cq_spec, ck_spec, ck_spec]
            operands += (*rope_tables, *rope_tables)
            scratch.append(pltpu.VMEM((dq_bq, d), qt.dtype))
        dq = pl.pallas_call(
            functools.partial(_dq_stream_kernel, block_q=dq_bq, block_k=dq_bk,
                              scale=scale, causal=causal, lse_layout=layout,
                              rope=rope),
            grid=(b, h, s // dq_bq, s // dq_bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, dq_bq, d),
                                   lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)

        # Grid over KV heads: block index maps pick up this head's group
        # of G query heads ((1, G, ...) blocks); dk/dv land at KV-head
        # granularity — no (B, H, S, D) expansion buffer. (Streaming
        # only: the resident family's fused kernel produced dk/dv above.)
        kv_spec = pl.BlockSpec((1, 1, dkv_bk, d),
                               lambda bi, hi, ki, qi: (bi, hi, ki, 0))
        v_spec = pl.BlockSpec((1, 1, dkv_bk, dv),
                              lambda bi, hi, ki, qi: (bi, hi, ki, 0))
        if causal:  # steps before the diagonal are no-ops: pin their q fetch
            def dkv_q_idx(bi, hi, ki, qi):
                return (bi, hi, jnp.maximum(qi, ki * dkv_bk // dkv_bq), 0)

            def dkv_row_idx(bi, hi, ki, qi):
                return (bi, hi, 0, jnp.maximum(qi, ki * dkv_bk // dkv_bq))
        else:
            def dkv_q_idx(bi, hi, ki, qi):
                return (bi, hi, qi, 0)

            def dkv_row_idx(bi, hi, ki, qi):
                return (bi, hi, 0, qi)
        qgrp_spec = pl.BlockSpec((1, group, dkv_bq, d), dkv_q_idx)
        ogrp_spec = pl.BlockSpec((1, group, dkv_bq, dv), dkv_q_idx)
        rowgrp_spec = (
            pl.BlockSpec((1, group, 1, dkv_bq), dkv_row_idx)
            if layout == "packed"
            else pl.BlockSpec((1, group, dkv_bq, 1), dkv_q_idx))
        in_specs = [qgrp_spec, kv_spec, v_spec, ogrp_spec, rowgrp_spec,
                    ogrp_spec]
        operands = (qt, kt, vt, dot, lse, ot)
        scratch = [pltpu.VMEM((dkv_bk, d), jnp.float32),
                   pltpu.VMEM((dkv_bk, dv), jnp.float32)]
        if rope:
            if causal:
                def dkv_cq_idx(bi, hi, ki, qi):
                    return (jnp.maximum(qi, ki * dkv_bk // dkv_bq), 0)
            else:
                def dkv_cq_idx(bi, hi, ki, qi):
                    return (qi, 0)
            cq_spec = pl.BlockSpec((dkv_bq, d), dkv_cq_idx)
            ck_spec = pl.BlockSpec((dkv_bk, d),
                                   lambda bi, hi, ki, qi: (ki, 0))
            in_specs += [cq_spec, cq_spec, ck_spec, ck_spec]
            operands += (*rope_tables, *rope_tables)
            scratch.append(pltpu.VMEM((dkv_bk, d), kt.dtype))
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_stream_kernel, block_q=dkv_bq,
                              block_k=dkv_bk, scale=scale, causal=causal,
                              lse_layout=layout, rope=rope),
            grid=(b, kv_heads, s // dkv_bk, s // dkv_bq),
            in_specs=in_specs,
            out_specs=[kv_spec, v_spec],
            out_shape=[
                jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                jax.ShapeDtypeStruct(vt.shape, vt.dtype),
            ],
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)
    return dq, dk, dv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_shard(local, head_dim: int, q, k, v, *tables):
    """Run the single-device kernel ``local`` on each device's (batch,
    head) shard of the active mesh.

    Mosaic kernels cannot be partitioned by the compiler: issued bare
    under ``jit`` with sharded operands they fail to lower on any mesh of
    more than one device. Attention is independent per batch row and per
    head, so a ``shard_map`` over the batch axes ('data', 'fsdp') and the
    head axis ('tensor') is exact, with no collective inside. Every other
    mesh axis not already manual (the pipeline trunk is manual over
    'pipe') is made manual with the operands replicated along it — the
    kernel needs ALL axes manual. A dim its axes do not divide (the
    batch-1 dummy of ``model.init``) is replicated instead, as
    ops/ring_attention.py does. A 1-device mesh keeps the bare call.

    ``head_dim``: index of the head dim (2 canonical, 1 head-major);
    ``tables``: the replicated rope tables of the fused variant."""
    from ..parallel.mesh import active_mesh

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return local(q, k, v, *tables)
    from jax.sharding import PartitionSpec as P

    dp_total = mesh.shape["data"] * mesh.shape["fsdp"]
    tp = mesh.shape["tensor"]
    spec = [None] * q.ndim
    if q.shape[0] % dp_total == 0:
        spec[0] = ("data", "fsdp")
    if q.shape[head_dim] % tp == 0 and k.shape[head_dim] % tp == 0:
        spec[head_dim] = "tensor"
    spec = P(*spec)
    # nested in a partial-manual region (the pipeline trunk), shard_map
    # must be handed that context's mesh, not the all-auto concrete one
    ctx = jax.sharding.get_abstract_mesh()
    fn = jax.shard_map(
        local, mesh=ctx if ctx.manual_axes else mesh,
        in_specs=(spec, spec, spec) + (P(),) * len(tables), out_specs=spec,
        axis_names=set(mesh.axis_names) - set(ctx.manual_axes),
        check_vma=False)
    return fn(q, k, v, *tables)


def flash_attention(q, k, v, causal=True):
    """Causal flash attention; q (B,S,H,D), k/v (B,S,K,D) -> (B,S,H,D)."""
    return _per_shard(
        lambda q, k, v: _flash_attention(q, k, v, causal), 2, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_attention(q, k, v, causal):
    out, _ = _flash_fwd(q, k, v, causal, _interpret())
    return out


def _flash_attention_fwd(q, k, v, causal):
    out, lse = _flash_fwd(q, k, v, causal, _interpret())
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(causal, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_bwd(q, k, v, o, lse, g, causal, _interpret())


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention_bhsd(q, k, v, causal=True):
    """Head-major entry: q/k (B,H,S,D) / (B,K,S,D), v (B,K,S,Dv) ->
    (B,H,S,Dv). The value's width may differ from the query/key's (latent
    attention, models/latent_moe.py: 192 / 128); nothing is padded.

    Identical kernels and math to :func:`flash_attention`, minus the
    (B,S,H,D) <-> (B,H,S,D) transposes at entry and exit — the caller
    (models/llama.py ``qkv_layout="bhsd"``) already holds operands in the
    kernel-native layout, so rope's elementwise fusion writes exactly
    the layout the custom call consumes and the backward's dq/dk/dv come
    out in the layout the rope backward wants. This is what eliminates
    the fp32 relayout-copy family at the custom-call boundary
    (BASELINE.md round-4)."""
    return _per_shard(
        lambda q, k, v: _flash_attention_bhsd(q, k, v, causal), 1, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_attention_bhsd(q, k, v, causal):
    out, _ = _flash_fwd_t(q, k, v, causal, _interpret())
    return out


def _flash_attention_bhsd_fwd(q, k, v, causal):
    out, lse = _flash_fwd_t(q, k, v, causal, _interpret())
    return out, (q, k, v, out, lse)


def _flash_attention_bhsd_bwd(causal, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_bwd_t(q, k, v, o, lse, g, causal, _interpret())


_flash_attention_bhsd.defvjp(_flash_attention_bhsd_fwd,
                             _flash_attention_bhsd_bwd)


def flash_attention_rope(q, k, v, cos2, sin2, causal=True):
    """Flash attention with RoPE applied INSIDE the kernels.

    q (B,H,S,D) and k/v (B,K,S,D) are RAW (pre-rope) head-major
    projections; ``cos2``/``sin2`` are (S, D) fp32 interleave-duplicated
    tables (``cos2[t, 2j] == cos2[t, 2j+1] == cos(t * theta^(-2j/D))`` —
    build with ``jnp.repeat(cos, 2, axis=-1)`` from the (S, D/2) tables of
    ops/rope.py). Rotation happens on VMEM tiles via the J-matrix matmul
    (see _rope_j) with the softmax prescale folded into the q-side pass,
    and the backward kernels emit dq/dk through the transpose rotation —
    so NO rotated q/k, fp32 rope intermediate, or rope backward ever
    exists at the XLA level. That eliminates the rope-adjacent relayout
    copies and convert fusions that an XLA-side rope pays at the Pallas
    custom-call boundary (~11 ms/step at the bench shape, BASELINE.md
    round-4 profile).

    Numerics: the rotation runs in fp32 with a single rounding to the
    input dtype. In fp32 (where astype is a no-op) scores, lse and the
    probability recomputation are bit-identical to the non-fused kernels
    fed pre-rotated inputs (tested in tests/test_flash_attention.py);
    under bf16 the q side agrees to one rounding — the fused path rounds
    once where the XLA rope + prescale chain rounds twice (ADVICE r4)."""
    return _per_shard(
        lambda q, k, v, c, s: _flash_attention_rope(q, k, v, c, s, causal),
        1, q, k, v, cos2, sin2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_attention_rope(q, k, v, cos2, sin2, causal):
    out, _ = _flash_fwd_t(q, k, v, causal, _interpret(), (cos2, sin2))
    return out


def _flash_attention_rope_fwd(q, k, v, cos2, sin2, causal):
    out, lse = _flash_fwd_t(q, k, v, causal, _interpret(), (cos2, sin2))
    return out, (q, k, v, out, lse, cos2, sin2)


def _flash_attention_rope_bwd(causal, residuals, g):
    q, k, v, o, lse, cos2, sin2 = residuals
    dq, dk, dv = _flash_bwd_t(q, k, v, o, lse, g, causal, _interpret(),
                              (cos2, sin2))
    # The tables are position constants — zero cotangents (DCE'd).
    return dq, dk, dv, jnp.zeros_like(cos2), jnp.zeros_like(sin2)


_flash_attention_rope.defvjp(_flash_attention_rope_fwd,
                             _flash_attention_rope_bwd)


# the kernel that stands for one attention forward or backward in a traced
# program, by direction and family (the split family's dk/dv kernel runs
# beside its dq kernel)
_KERNEL_CALLS = {"_fwd_kernel": ("forward", "resident"),
                 "_fwd_stream_kernel": ("forward", "stream"),
                 "_bwd_fused_kernel": ("backward", "fused"),
                 "_dq_stream_kernel": ("backward", "split")}


def flash_calls(closed_jaxpr):
    """``({"forward": {"resident"|"stream": calls}, "backward":
    {"fused"|"split": calls}}, vmem)``: the attention calls a traced
    program makes each time it runs (``jax.jit(f).trace(...).jaxpr``; a
    scan's body counts its length times, and a forward that remat runs
    again in the backward counts twice), and the most VMEM a call asks
    the compiler for (XLA's default scoped limit where none asks more).
    Read from the program, so what the rule picked at each shape is what
    is counted."""
    from jax.extend import core as jcore

    calls = {"forward": {}, "backward": {}}
    vmem = DEFAULT_SCOPED_VMEM_BYTES

    def walk(jaxpr, times):
        nonlocal vmem
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                info = eqn.params["jaxpr"].debug_info
                kernel = _KERNEL_CALLS.get(
                    info.func_src_info.split(" ")[0] if info else None)
                if kernel:
                    direction, family = kernel
                    tally = calls[direction]
                    tally[family] = tally.get(family, 0) + times
                    for params in eqn.params["compiler_params"].values():
                        vmem = max(vmem, params.vmem_limit_bytes or 0)
                continue
            n = times * eqn.params["length"] if (
                eqn.primitive.name == "scan") else times
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else (value,)):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        walk(sub.jaxpr, n)
                    elif isinstance(sub, jcore.Jaxpr):
                        walk(sub, n)

    walk(closed_jaxpr.jaxpr, 1)
    return calls, vmem

