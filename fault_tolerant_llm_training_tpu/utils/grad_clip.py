"""Global-norm gradient clipping with a non-finite guard (ref: utils.py:58-63).

The reference uses ``torch.nn.utils.get_total_norm(error_if_nonfinite=True)``
followed by ``clip_grads_with_norm_`` — i.e. a NaN/Inf global gradient norm
*raises*, feeding the fault-handler path, and the clip coefficient is
``min(max_norm / (total_norm + 1e-6), 1.0)``.

In JAX the clip happens inside the jitted step (pure function of the grads);
the non-finite *raise* is a host-side decision made by the training loop when
it pulls the ``grad_norm`` metric (you cannot raise from inside ``jit``).
"""

import jax
import jax.numpy as jnp

from ..obs.trace import scope


class NonFiniteGradientError(RuntimeError):
    """Host-side equivalent of torch's ``error_if_nonfinite`` (ref: utils.py:61)."""


def global_norm(tree) -> jax.Array:
    """L2 norm over the concatenation of every leaf (torch ``get_total_norm``)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves)
    )


@scope("grad_clip")
def clip_grads_with_norm(grads, max_norm: float):
    """Scale ``grads`` by ``min(max_norm / (norm + 1e-6), 1.0)``.

    Returns ``(clipped_grads, total_norm)``; matches torch's
    ``clip_grads_with_norm_`` coefficient exactly (ref: utils.py:62).
    """
    total_norm = global_norm(grads)
    clip_coef = jnp.minimum(max_norm / (total_norm + 1e-6), 1.0)
    clipped = jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * clip_coef).astype(g.dtype), grads
    )
    return clipped, total_norm
