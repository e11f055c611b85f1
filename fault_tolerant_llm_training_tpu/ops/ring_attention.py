"""Sequence-parallel causal ring attention (long-context support).

The reference has no long-context machinery (SURVEY.md §5.7) — full causal
SDPA bounded by one GPU's memory. This is the TPU-native scale-out path: the
sequence is sharded over the mesh's 'sequence' axis, each device computes
online-softmax partial attention for its query block while KV blocks rotate
around the ring via ``lax.ppermute`` over ICI, overlapping compute with
neighbor exchange. Memory per device is O(S / sp); no (S, S) score matrix
ever exists.

The local block math runs on the Pallas carry kernels (ops/ring_flash.py):
each ring step is one k-phase of the flash forward/backward with the
online-softmax (fwd) or gradient (bwd) state threaded between pallas calls,
so per-step memory is O(tile) VMEM — never an (S/sp, S/sp) score tensor.
The backward is a second ring pass under a custom VJP: dq accumulates on
the query's device while (dk, dv) travel with their KV block and take one
extra hop home. ``impl="xla"`` keeps the original plain-einsum local math
as an independent oracle for parity tests.

Causality without wasted work: device ``i`` starts with its own KV block
(the diagonal, causal-masked), then receives blocks ``i-1, i-2, ...``; blocks
from the future are fully masked and contribute nothing to the softmax
accumulators.

Two sequence layouts are supported:

- ``contiguous`` — shard ``i`` holds global positions ``[i*S/sp, (i+1)*S/sp)``.
  Simple, but causal work is imbalanced: device 0's queries attend one block
  while device sp-1's attend all of them, and since every ring step is gated
  by the lockstep ``ppermute``, the busiest device sets the pace (a per-step
  ``lax.cond`` skip of fully-masked blocks was tried and reverted — it saves
  FLOPs but zero wall-clock).
- ``zigzag`` — the sequence is split into ``2*sp`` chunks and shard ``i``
  holds chunks ``(i, 2*sp-1-i)``: one early chunk plus its mirrored late
  chunk. Then at every ring step ``t>0`` each device has exactly half a
  block of *unmasked* work — either all its queries against the visiting
  early chunk (KV from an earlier device) or its late queries against both
  visiting chunks (KV from a later device) — two equal-FLOP ``lax.cond``
  branches, so the ring stays in lockstep while doing ~2x fewer FLOPs than
  contiguous, evenly. The layout permutation is applied once to the token
  stream by the train step (training/step.py) — RoPE and the causal mask see
  true global positions; the summed CE loss is permutation-invariant.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import active_mesh
from .flash_attention import LN2, _interpret
from .ring_flash import (
    carry_dkv,
    carry_dq,
    carry_fwd,
    delta_rows,
    finalize_carry,
    fresh_carry,
)

NEG_INF = -1e30


def _local_update(qg, k_blk, v_blk, m, l, acc, q_pos, k_pos, scale):
    """One online-softmax accumulation of q against a single KV block.

    ``q_pos``/``k_pos`` of None means the caller guarantees every (q, k)
    pair in the block is causally valid — no mask is applied."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk,
                   preferred_element_type=jnp.float32) * scale
    if q_pos is not None:
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None, :, :]
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _unmasked_update(qg, k_blk, v_blk, m, l, acc, scale):
    return _local_update(qg, k_blk, v_blk, m, l, acc, None, None, scale)


def zigzag_ok(seq_len: int, sp: int) -> bool:
    """Whether the zigzag layout applies: needs 2*sp even chunks."""
    return sp > 1 and seq_len % (2 * sp) == 0


def zigzag_perm(seq_len: int, sp: int) -> np.ndarray:
    """Global sequence permutation for the zigzag layout.

    ``permuted[j] = original[perm[j]]``: split the sequence into ``2*sp``
    chunks; contiguous shard ``i`` of the permuted sequence holds chunks
    ``(i, 2*sp-1-i)``. Static (trace-time) data."""
    c = seq_len // (2 * sp)
    chunks = np.arange(seq_len, dtype=np.int32).reshape(2 * sp, c)
    order = [x for i in range(sp) for x in (i, 2 * sp - 1 - i)]
    return chunks[order].reshape(-1)


def zigzag_layout_active(cfg, seq_len: int, sp: int) -> bool:
    """The single predicate deciding whether the train step permutes tokens
    into the zigzag layout — must mirror the model's attention dispatch
    (models/llama.py: ring is used iff impl is auto|ring and sp > 1) plus
    the ring op's own ``zigzag_ok`` divisibility fallback, or masking and
    layout would disagree."""
    return (sp > 1 and cfg.attention_impl in ("auto", "ring")
            and cfg.sp_layout == "zigzag" and zigzag_ok(seq_len, sp))


def _zigzag_pos(idx, sp: int, c: int):
    """(2c,) true global positions of the shard holding chunks
    ``(idx, 2*sp-1-idx)``."""
    lo = idx * c + jnp.arange(c)
    hi = (2 * sp - 1 - idx) * c + jnp.arange(c)
    return jnp.concatenate([lo, hi])


def _ring_local_zigzag(q, k, v, *, sp: int, axis_name: str):
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    kv_heads = k.shape[2]
    g = h // kv_heads
    c = s_loc // 2
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, s_loc, kv_heads, g, d)
    q_pos = _zigzag_pos(my, sp, c)

    m = jnp.full((b, kv_heads, g, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kv_heads, g, s_loc), jnp.float32)
    acc = jnp.zeros((b, kv_heads, g, s_loc, d), jnp.float32)

    perm = _ring_perm(sp)
    k_blk, v_blk = k, v
    for t in range(sp):
        if t == 0:
            # Diagonal: both chunk pairs are our own — positional causal mask.
            m, l, acc = _local_update(qg, k_blk, v_blk, m, l, acc, q_pos,
                                      q_pos, scale)
        else:
            src = (my - t) % sp

            def from_earlier(ops, kb=k_blk, vb=v_blk):
                # Visiting KV came from an earlier ring slot: chunk src is
                # entirely in our past, chunk 2*sp-1-src entirely in our
                # future — so every query attends exactly the early half.
                m, l, acc = ops
                return _unmasked_update(qg, kb[:, :c], vb[:, :c], m, l, acc,
                                        scale)

            def from_later(ops, kb=k_blk, vb=v_blk):
                # Visiting KV came from a later slot: our early chunk sees
                # nothing, our late chunk (2*sp-1-my) sees both visiting
                # chunks in full. Same FLOPs as the other branch.
                m, l, acc = ops
                m2, l2, acc2 = _unmasked_update(
                    qg[:, c:], kb, vb, m[..., c:], l[..., c:],
                    acc[..., c:, :], scale)
                return (jnp.concatenate([m[..., :c], m2], axis=-1),
                        jnp.concatenate([l[..., :c], l2], axis=-1),
                        jnp.concatenate([acc[..., :c, :], acc2], axis=-2))

            m, l, acc = jax.lax.cond(src < my, from_earlier, from_later,
                                     (m, l, acc))
        if t + 1 < sp:
            k_blk, v_blk = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)

    out = acc / l[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s_loc, h, d)
    return out.astype(q.dtype)


def _ring_local(q, k, v, *, sp: int, axis_name: str):
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    kv_heads = k.shape[2]
    g = h // kv_heads
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, s_loc, kv_heads, g, d)
    q_pos = my * s_loc + jnp.arange(s_loc)

    m = jnp.full((b, kv_heads, g, s_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kv_heads, g, s_loc), jnp.float32)
    acc = jnp.zeros((b, kv_heads, g, s_loc, d), jnp.float32)

    perm = _ring_perm(sp)
    k_blk, v_blk = k, v
    for t in range(sp):
        src = (my - t) % sp  # which global block this device holds at step t
        k_pos = src * s_loc + jnp.arange(s_loc)
        # Future blocks (src > my) are fully masked and mathematically
        # no-ops. Skipping their compute would save FLOPs but no wall-clock:
        # every ring step is gated by the slowest device through the
        # lockstep ppermute, and some device always computes at every step.
        # The fix is the zigzag layout above, which balances causal work.
        m, l, acc = _local_update(qg, k_blk, v_blk, m, l, acc, q_pos, k_pos,
                                  scale)
        if t + 1 < sp:
            k_blk, v_blk = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)

    out = acc / l[..., None]  # (b, kv, g, s_loc, d)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s_loc, h, d)
    return out.astype(q.dtype)


def _ring_perm(sp):
    return [(i, (i + 1) % sp) for i in range(sp)]


def _flash_fwd_impl(q, k, v, sp, axis_name, zigzag):
    """Ring forward with Pallas carry kernels: O(block) VMEM per step, no
    (S/sp, S/sp) score tensor (the round-1 einsum path materialized one).

    Per-device shards: q (b, s_loc, h, d), k/v (b, s_loc, kv, d). Internally
    (B, H, S, D) — transposed once here, not per ring step. Returns the
    attention output in the input layout plus the base-2 lse residual."""
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    itp = _interpret()
    m, l, acc = fresh_carry(b, h, s_loc, d)
    c = s_loc // 2
    k_blk, v_blk = kt, vt
    for t in range(sp):
        src = (my - t) % sp
        if not zigzag:
            # One causal kernel per step; global offsets make the diagonal
            # mask itself, past blocks run unmasked, and future blocks
            # degenerate to carry pass-through (compute and fetch elided
            # tile-by-tile inside the kernel).
            m, l, acc = carry_fwd(qt, k_blk, v_blk, m, l, acc,
                                  my * s_loc, src * s_loc,
                                  causal=True, interpret=itp)
        elif t == 0:
            # Diagonal in the zigzag layout: our chunks are (my, 2sp-1-my).
            # lo x lo and hi x hi are causal at their true global offsets;
            # hi x lo is fully visible; lo x hi is fully future (skipped).
            lo_off, hi_off = my * c, (2 * sp - 1 - my) * c
            m_lo, l_lo, acc_lo = carry_fwd(
                qt[:, :, :c], k_blk[:, :, :c], v_blk[:, :, :c],
                m[:, :, :c], l[:, :, :c], acc[:, :, :c],
                lo_off, lo_off, causal=True, interpret=itp)
            m_hi, l_hi, acc_hi = carry_fwd(
                qt[:, :, c:], k_blk[:, :, c:], v_blk[:, :, c:],
                m[:, :, c:], l[:, :, c:], acc[:, :, c:],
                hi_off, hi_off, causal=True, interpret=itp)
            m_hi, l_hi, acc_hi = carry_fwd(
                qt[:, :, c:], k_blk[:, :, :c], v_blk[:, :, :c],
                m_hi, l_hi, acc_hi, 0, 0, causal=False, interpret=itp)
            m = jnp.concatenate([m_lo, m_hi], axis=2)
            l = jnp.concatenate([l_lo, l_hi], axis=2)
            acc = jnp.concatenate([acc_lo, acc_hi], axis=2)
        else:
            # Equal-FLOP branches (module doc): earlier visitor -> all our
            # queries see its early chunk; later visitor -> our late chunk
            # sees both its chunks. All updates are unmasked.
            def from_earlier(ops, kb=k_blk, vb=v_blk):
                m, l, acc = ops
                return carry_fwd(qt, kb[:, :, :c], vb[:, :, :c], m, l, acc,
                                 0, 0, causal=False, interpret=itp)

            def from_later(ops, kb=k_blk, vb=v_blk):
                m, l, acc = ops
                m2, l2, acc2 = carry_fwd(
                    qt[:, :, c:], kb, vb, m[:, :, c:], l[:, :, c:],
                    acc[:, :, c:], 0, 0, causal=False, interpret=itp)
                return (jnp.concatenate([m[:, :, :c], m2], axis=2),
                        jnp.concatenate([l[:, :, :c], l2], axis=2),
                        jnp.concatenate([acc[:, :, :c], acc2], axis=2))

            m, l, acc = jax.lax.cond(src < my, from_earlier, from_later,
                                     (m, l, acc))
        if t + 1 < sp:
            k_blk, v_blk = jax.lax.ppermute((k_blk, v_blk), axis_name,
                                            _ring_perm(sp))
    out, lse = finalize_carry(m, l, acc, q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _flash_bwd_impl(sp, axis_name, zigzag, res, g):
    """Ring backward: dq accumulates locally; (dk, dv) travel with their KV
    block and take one extra rotation home after the last step. The masking
    geometry mirrors the forward exactly, via the same carry kernels."""
    q, k, v, out, lse = res
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = jnp.transpose(out, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    delta = delta_rows(dot, ot)
    itp = _interpret()
    scale = 1.0 / (d ** 0.5)
    c = s_loc // 2
    dq = jnp.zeros((b, h, s_loc, d), jnp.float32)
    k_blk, v_blk = kt, vt
    dk_blk = jnp.zeros(kt.shape, jnp.float32)
    dv_blk = jnp.zeros(vt.shape, jnp.float32)
    for t in range(sp):
        src = (my - t) % sp
        if not zigzag:
            q_off, k_off = my * s_loc, src * s_loc
            dq = carry_dq(qt, k_blk, v_blk, dot, lse, delta, dq,
                          q_off, k_off, causal=True, interpret=itp)
            dk_blk, dv_blk = carry_dkv(qt, k_blk, v_blk, dot, lse, delta,
                                       dk_blk, dv_blk, q_off, k_off,
                                       causal=True, interpret=itp)
        elif t == 0:
            lo_off, hi_off = my * c, (2 * sp - 1 - my) * c
            q_lo, q_hi = qt[:, :, :c], qt[:, :, c:]
            do_lo, do_hi = dot[:, :, :c], dot[:, :, c:]
            lse_lo, lse_hi = lse[:, :, :c], lse[:, :, c:]
            dl_lo, dl_hi = delta[:, :, :c], delta[:, :, c:]
            k_lo, v_lo = k_blk[:, :, :c], v_blk[:, :, :c]
            k_hi, v_hi = k_blk[:, :, c:], v_blk[:, :, c:]
            dq_lo = carry_dq(q_lo, k_lo, v_lo, do_lo, lse_lo, dl_lo,
                             dq[:, :, :c], lo_off, lo_off, causal=True,
                             interpret=itp)
            dq_hi = carry_dq(q_hi, k_hi, v_hi, do_hi, lse_hi, dl_hi,
                             dq[:, :, c:], hi_off, hi_off, causal=True,
                             interpret=itp)
            dq_hi = carry_dq(q_hi, k_lo, v_lo, do_hi, lse_hi, dl_hi,
                             dq_hi, 0, 0, causal=False, interpret=itp)
            dq = jnp.concatenate([dq_lo, dq_hi], axis=2)
            dk_lo, dv_lo = carry_dkv(q_lo, k_lo, v_lo, do_lo, lse_lo, dl_lo,
                                     dk_blk[:, :, :c], dv_blk[:, :, :c],
                                     lo_off, lo_off, causal=True,
                                     interpret=itp)
            dk_lo, dv_lo = carry_dkv(q_hi, k_lo, v_lo, do_hi, lse_hi, dl_hi,
                                     dk_lo, dv_lo, 0, 0, causal=False,
                                     interpret=itp)
            dk_hi, dv_hi = carry_dkv(q_hi, k_hi, v_hi, do_hi, lse_hi, dl_hi,
                                     dk_blk[:, :, c:], dv_blk[:, :, c:],
                                     hi_off, hi_off, causal=True,
                                     interpret=itp)
            dk_blk = jnp.concatenate([dk_lo, dk_hi], axis=2)
            dv_blk = jnp.concatenate([dv_lo, dv_hi], axis=2)
        else:
            def from_earlier(ops, kb=k_blk, vb=v_blk):
                dq, dkb, dvb = ops
                dq = carry_dq(qt, kb[:, :, :c], vb[:, :, :c], dot, lse,
                              delta, dq, 0, 0, causal=False, interpret=itp)
                dk_lo, dv_lo = carry_dkv(qt, kb[:, :, :c], vb[:, :, :c],
                                         dot, lse, delta, dkb[:, :, :c],
                                         dvb[:, :, :c], 0, 0, causal=False,
                                         interpret=itp)
                return (dq,
                        jnp.concatenate([dk_lo, dkb[:, :, c:]], axis=2),
                        jnp.concatenate([dv_lo, dvb[:, :, c:]], axis=2))

            def from_later(ops, kb=k_blk, vb=v_blk):
                dq, dkb, dvb = ops
                dq_hi = carry_dq(qt[:, :, c:], kb, vb, dot[:, :, c:],
                                 lse[:, :, c:], delta[:, :, c:],
                                 dq[:, :, c:], 0, 0, causal=False,
                                 interpret=itp)
                dq = jnp.concatenate([dq[:, :, :c], dq_hi], axis=2)
                dkb, dvb = carry_dkv(qt[:, :, c:], kb, vb, dot[:, :, c:],
                                     lse[:, :, c:], delta[:, :, c:],
                                     dkb, dvb, 0, 0, causal=False,
                                     interpret=itp)
                return dq, dkb, dvb

            dq, dk_blk, dv_blk = jax.lax.cond(
                src < my, from_earlier, from_later, (dq, dk_blk, dv_blk))
        if t + 1 < sp:
            k_blk, v_blk, dk_blk, dv_blk = jax.lax.ppermute(
                (k_blk, v_blk, dk_blk, dv_blk), axis_name, _ring_perm(sp))
    # After sp-1 rotations the traveling gradients sit one hop short of
    # their owner; one more ppermute completes the circle.
    dk_blk, dv_blk = jax.lax.ppermute((dk_blk, dv_blk), axis_name,
                                      _ring_perm(sp))
    dq_out = jnp.transpose(dq * scale, (0, 2, 1, 3)).astype(q.dtype)
    dk_out = jnp.transpose(dk_blk * LN2, (0, 2, 1, 3)).astype(k.dtype)
    dv_out = jnp.transpose(dv_blk, (0, 2, 1, 3)).astype(v.dtype)
    return dq_out, dk_out, dv_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, sp, axis_name, zigzag):
    out, _ = _flash_fwd_impl(q, k, v, sp, axis_name, zigzag)
    return out


def _ring_flash_fwd(q, k, v, sp, axis_name, zigzag):
    out, lse = _flash_fwd_impl(q, k, v, sp, axis_name, zigzag)
    return out, (q, k, v, out, lse)


_ring_flash.defvjp(_ring_flash_fwd, _flash_bwd_impl)


def _ring_local_flash(q, k, v, *, sp: int, axis_name: str):
    return _ring_flash(q, k, v, sp, axis_name, False)


def _ring_local_flash_zigzag(q, k, v, *, sp: int, axis_name: str):
    return _ring_flash(q, k, v, sp, axis_name, True)


def ring_attention(q, k, v, axis_name: str = "sequence", mesh=None,
                   zigzag: bool = False, impl: str = "flash") -> jax.Array:
    """Causal GQA attention with the sequence dim sharded over ``axis_name``.

    q: (B, S, H, D); k/v: (B, S, K, D) — global (jit) view; internally a
    shard_map over the active mesh rotates KV blocks around the ring.
    With ``zigzag=True`` the inputs must already be in the zigzag sequence
    layout (``zigzag_perm``; the train step applies it) — the op then does
    ~2x fewer, evenly balanced FLOPs per device.
    """
    mesh = mesh or active_mesh()
    if mesh is None or mesh.shape[axis_name] == 1:
        from .attention import xla_attention
        return xla_attention(q, k, v, causal=True)
    sp = mesh.shape[axis_name]
    use_zigzag = zigzag and zigzag_ok(q.shape[1], sp)
    if impl == "flash":
        local = _ring_local_flash_zigzag if use_zigzag else _ring_local_flash
    elif impl == "xla":  # plain-einsum reference path (parity oracle)
        local = _ring_local_zigzag if use_zigzag else _ring_local
    else:
        raise ValueError(f"unknown ring attention impl: {impl!r}")
    # Degrade per-axis when a dim is not divisible by its mesh axes (e.g. the
    # batch-1 dummy used by model.init): shard_map then replicates that dim,
    # which is always semantically valid.
    dp_total = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    tp = mesh.shape.get("tensor", 1)
    batch_axes = ("data", "fsdp") if q.shape[0] % dp_total == 0 else None
    head_axis = ("tensor"
                 if q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None)
    spec = P(batch_axes, axis_name, head_axis, None)
    fn = _shard_map(
        functools.partial(local, sp=sp, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
