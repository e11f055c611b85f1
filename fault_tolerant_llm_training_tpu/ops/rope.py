"""Rotary position embeddings, real-arithmetic interleaved form.

The reference computes RoPE with complex arithmetic: it views the head dim as
``head_dim/2`` complex numbers formed from *adjacent* element pairs
``(x[2j], x[2j+1])`` and multiplies by ``exp(i * t * theta^(-2j/d))`` in fp32
(ref: model.py:51-126, esp. ``view_as_complex`` of a ``(..., -1, 2)`` reshape
at model.py:121-122). Complex view tricks lower poorly on TPU, so we express
the identical rotation with real cos/sin pairs — the *interleaved* convention
(NOT the half-split "rotate_half" convention, which permutes differently):

    out[2j]   = x[2j] * cos(a) - x[2j+1] * sin(a)
    out[2j+1] = x[2j] * sin(a) + x[2j+1] * cos(a)

with ``a = t * theta^(-2j/d)``. Computed in fp32, cast back to the input
dtype, exactly like the reference (model.py:121-126 casts via ``.float()`` /
``.type_as``).
"""

from typing import Tuple

import jax.numpy as jnp

from ..obs.trace import scope


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """(D/2,) inverse frequencies theta^(-2j/d) (ref: model.py:67-69)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def precompute_rope(head_dim: int, seq_len: int, theta: float = 10000.0
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables of shape (seq_len, head_dim // 2), fp32.

    Equivalent to the modulus/argument of the reference's complex table
    (ref: model.py:67-71), precomputed once — the reference keeps it as a
    non-persistent buffer (model.py:342-344); here it is a constant folded
    into the jitted step.
    """
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, rope_freqs(head_dim, theta))  # (S, D/2)
    return jnp.cos(angles), jnp.sin(angles)


def rope_cos_sin(head_dim: int, theta: float, positions: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S, D/2) cos/sin computed directly from ``positions`` (B, S).

    An outer product instead of a table gather: under sequence parallelism
    the positions array is sharded along S, and XLA shards this elementwise
    compute with it — whereas a ``table[positions]`` gather forces an
    involuntary full rematerialization when the table's sharding does not
    match the activations' (observed in the SPMD partitioner on the
    dp/fsdp/sp/tp dryrun mesh).
    """
    angles = positions.astype(jnp.float32)[..., None] * rope_freqs(head_dim, theta)
    return jnp.cos(angles), jnp.sin(angles)


@scope("rope")
def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray = None) -> jnp.ndarray:
    """Rotate ``x`` of shape (B, S, H, D) by the interleaved-pair convention.

    ``cos``/``sin`` are (S_table, D/2) — the first S rows are used (the
    reference slices its table to the runtime seqlen, model.py:91-97) — or
    per-token (B, S, D/2) from :func:`rope_cos_sin` (needed under sequence
    parallelism, where each shard holds a non-prefix slice of the sequence).
    ``positions`` (B, S) selects table rows explicitly via gather; prefer
    :func:`rope_cos_sin` inside sharded code (see its docstring).
    """
    orig_dtype = x.dtype
    b, s, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x_even, x_odd = xf[..., 0], xf[..., 1]
    if positions is not None:
        c = cos[positions][:, :, None, :]  # (B, S, 1, D/2)
        si = sin[positions][:, :, None, :]
    elif cos.ndim == 3:
        c = cos[:, :, None, :]  # (B, S, 1, D/2) per-token form
        si = sin[:, :, None, :]
    else:
        c = cos[:s][None, :, None, :]  # (1, S, 1, D/2)
        si = sin[:s][None, :, None, :]
    out_even = x_even * c - x_odd * si
    out_odd = x_even * si + x_odd * c
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(b, s, h, d)
    return out.astype(orig_dtype)


@scope("rope")
def apply_rope_bhsd(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
                    ) -> jnp.ndarray:
    """:func:`apply_rope` for head-major ``x`` of shape (B, H, S, D).

    Same math, same fp32 internal precision — only the broadcast axes
    move. Used by the ``qkv_layout="bhsd"`` attention path, where q/k are
    transposed to the flash kernel's native layout *before* rope so the
    rope fusion's output layout is exactly what the Pallas custom call
    consumes (no fp32 relayout copies at the boundary; BASELINE.md round-4
    copy-family breakdown). Prefix positions only — the sequence-parallel
    paths (which need per-token positions) keep the (B, S, H, D) form.
    """
    orig_dtype = x.dtype
    b, h, s, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, h, s, d // 2, 2)
    x_even, x_odd = xf[..., 0], xf[..., 1]
    c = cos[:s][None, None, :, :]  # (1, 1, S, D/2)
    si = sin[:s][None, None, :, :]
    out_even = x_even * c - x_odd * si
    out_odd = x_even * si + x_odd * c
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(b, h, s, d)
    return out.astype(orig_dtype)
