"""Vocab-blocked cross-entropy: CE at large vocab without fp32 logits.

The reference computes sum-reduced fp32 CE over flattened (B*S, V) logits
(ref: train.py:101-102). At its 131k vocab the fp32 cast of the logits is
the single largest tensor in the step — (B, S, V) fp32 is ~2x the bf16
logits the model already produced, and the softmax residuals double it
again in the backward (VERDICT round-1 weak spot #5).

This module computes the same quantity vocab-block by vocab-block:

- **Forward** keeps three (B, S) fp32 running stats — rowwise max ``m``,
  shifted normalizer ``l``, and the picked (label) logit — and folds one
  (B, S, block) fp32 slice at a time via an online-logsumexp update (the
  same algebra as the flash-attention online softmax, over the vocab axis
  instead of keys). Peak extra memory is one block slice, not V.
- **Backward** is a custom VJP: softmax probabilities are recomputed per
  block from the saved (bf16 logits, fp32 logsumexp) — exactly the
  flash-attention recomputation scheme — and written straight into the
  dlogits buffer in the logits dtype. No fp32 (B, S, V) tensor and no
  stored softmax residuals.

Numerics match ``optax.softmax_cross_entropy_with_integer_labels`` to fp32
tolerance: both compute lse(logits_f32) - picked_f32 per token; the online
update is an exact reassociation of the same sum (tested in
tests/test_train_step.py).

The vocab tail (V % block) is handled as one separate static slice — no
padding copy, no masked lanes.
"""

import functools

import jax
import jax.numpy as jnp

from ..obs.trace import scope

# Vocab sizes at or above this use the blocked path automatically; below it
# the dense optax-style CE is faster (one fused reduction, no loop carries).
# 131072 (the reference's Mistral-Nemo vocab) is the motivating case.
AUTO_THRESHOLD = 65536
DEFAULT_BLOCK = 8192


def _block_update(sl, labels, v0, m, l, picked):
    """Fold one fp32 logits slice ``sl`` (B, S, Vb) starting at vocab index
    ``v0`` into the running (m, l, picked) stats."""
    vb = sl.shape[-1]
    bm = jnp.max(sl, axis=-1)
    m_new = jnp.maximum(m, bm)
    l = l * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(sl - m_new[..., None]), axis=-1)
    loc = labels - v0
    hit = (loc >= 0) & (loc < vb)
    pick = jnp.take_along_axis(
        sl, jnp.clip(loc, 0, vb - 1)[..., None], axis=-1)[..., 0]
    picked = jnp.where(hit, pick, picked)
    return m_new, l, picked


@scope("loss_head")
def _lse_and_picked(logits, labels, block):
    b, s, v = logits.shape
    m = jnp.full((b, s), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, s), jnp.float32)
    picked = jnp.zeros((b, s), jnp.float32)

    def body(j, carry):
        sl = jax.lax.dynamic_slice_in_dim(
            logits, j * block, block, axis=2).astype(jnp.float32)
        return _block_update(sl, labels, j * block, *carry)

    m, l, picked = jax.lax.fori_loop(0, v // block, body, (m, l, picked))
    if v % block:  # static tail slice — no padding copy
        tail = logits[:, :, (v // block) * block:].astype(jnp.float32)
        m, l, picked = _block_update(tail, labels, (v // block) * block,
                                     m, l, picked)
    return m + jnp.log(l), picked


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def chunked_softmax_xent(logits, labels, block: int = DEFAULT_BLOCK):
    """Per-token -log_softmax(logits)[label], fp32 (B, S).

    ``labels`` must already be in-range (callers mask ignore positions
    before/after, as cross_entropy_loss in training/step.py does)."""
    lse, picked = _lse_and_picked(logits, labels, block)
    return lse - picked


def _xent_fwd(logits, labels, block):
    lse, picked = _lse_and_picked(logits, labels, block)
    return lse - picked, (logits, labels, lse)


@scope("loss_head")
def _xent_bwd(block, res, g):
    logits, labels, lse = res
    b, s, v = logits.shape
    gf = g.astype(jnp.float32)

    def block_grad(sl, v0):
        # d nll / d logit_j = softmax_j - 1[label == j]
        p = jnp.exp(sl.astype(jnp.float32) - lse[..., None])
        loc = labels - v0
        hit = (loc >= 0) & (loc < sl.shape[-1])
        onehot = (jax.lax.broadcasted_iota(jnp.int32, sl.shape, 2)
                  == loc[..., None]) & hit[..., None]
        return (gf[..., None] * (p - onehot.astype(jnp.float32))
                ).astype(logits.dtype)

    def body(j, dlogits):
        sl = jax.lax.dynamic_slice_in_dim(logits, j * block, block, axis=2)
        return jax.lax.dynamic_update_slice_in_dim(
            dlogits, block_grad(sl, j * block), j * block, axis=2)

    dlogits = jax.lax.fori_loop(0, v // block, body,
                                jnp.zeros_like(logits))
    if v % block:
        v0 = (v // block) * block
        dlogits = jax.lax.dynamic_update_slice_in_dim(
            dlogits, block_grad(logits[:, :, v0:], v0), v0, axis=2)
    return dlogits, None


chunked_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
