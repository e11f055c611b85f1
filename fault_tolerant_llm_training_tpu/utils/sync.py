"""Value-dependent device synchronization.

``jax.block_until_ready`` waits on buffer *readiness events*. That is the
documented barrier and it is exact on the PJRT TPU and CPU clients; a host
*read* of a value is a barrier by construction on every backend — the
device->host transfer cannot start before the producing computation ends.
(This also means correctness of downstream consumers that read values,
e.g. Orbax checkpoint serialization, never depends on this barrier; it
exists to drain dispatched work at a known point and to delimit timing
measurements.)

``hard_sync`` does both, so a timing anchored on it does not depend on how
a backend implements readiness events: it materializes every scalar (0-d)
leaf — all outputs of one XLA executable complete together, so for a tree
produced by a single jitted step (TrainState with its ``step`` counter, a
metrics dict) fetching one scalar output is an exact barrier for the whole
tree — and then calls ``block_until_ready`` on the rest, which covers
leaves produced by other dispatches.
"""

import jax


def hard_sync(tree) -> None:
    """Drain the computation(s) producing ``tree`` (see module docstring)."""
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "ndim") and getattr(x, "size", 0) > 0]
    scalars = [x for x in leaves if x.ndim == 0]
    if scalars:
        jax.device_get(scalars)
    elif leaves:
        # No scalar outputs: fetch one element of every leaf (leaves may come
        # from different dispatches) — still a value-dependent barrier,
        # unlike block_until_ready alone; one batched transfer.
        jax.device_get([x[(0,) * x.ndim] for x in leaves])
    jax.block_until_ready(tree)
