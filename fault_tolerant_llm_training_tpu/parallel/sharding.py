"""Sharding rules: logical axes -> mesh axes, and param-path -> logical axes.

This is the build's FSDP/TP layer (SURVEY.md §2.3: the reference has none; the
BASELINE.json north star requires DP psum + pjit/NamedSharding FSDP). Instead
of boxing Flax params in metadata, shardings are derived from the parameter
tree *path* with regex rules — transparent, testable, and Orbax-friendly.

Logical activation/parameter axes:

- batch -> ('data', 'fsdp')   (FSDP also shards the batch)
- seq   -> 'sequence'         (ring attention shards)
- vocab -> 'tensor'
- embed -> 'fsdp'             (FSDP shards params along their embed dim)
- heads -> 'tensor'           (Megatron: split attention heads)
- mlp   -> 'tensor'           (Megatron: split SwiGLU hidden)
- norm  -> None               (tiny vectors, replicated)

With this single rule set, FSDP-only meshes (tp=1) shard every matrix over
'fsdp' on its embed dim, TP-only meshes split heads/mlp/vocab, and combined
meshes do both — XLA inserts all-gathers / reduce-scatters / psums from the
NamedShardings (the scaling-book recipe).
"""

import contextlib
import re
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import active_mesh

_CONSTRAINTS_SUSPENDED = False


@contextlib.contextmanager
def suspend_constraints():
    """Disable ``constrain`` for the dynamic extent of a trace.

    Needed while tracing the pipeline's partial-manual shard_map body
    (parallel/pipeline.py): inside ``lax.scan`` the Manual-context query
    below is unreliable, and a constraint stamped on the all-auto mesh
    inside the manual region breaks the shard_map transpose."""
    global _CONSTRAINTS_SUSPENDED
    prev = _CONSTRAINTS_SUSPENDED
    _CONSTRAINTS_SUSPENDED = True
    try:
        yield
    finally:
        _CONSTRAINTS_SUSPENDED = prev

LOGICAL_RULES: Dict[str, object] = {
    "batch": ("data", "fsdp"),
    "seq": "sequence",
    # vocab shards over pipe AND tensor: on a pp mesh every stage stores
    # only its vocab slice of the embed table / head weight and computes
    # only its slice of the (B, S, V) logits — one head matmul total
    # across the mesh instead of P replicated ones (the round-1 pipeline
    # recomputed the model's largest matmul on every stage). The CE is
    # gather-free (training/step.py) so vocab-sharded logits reduce with
    # small (B, S) collectives, never an all-gather of logits. 'pipe'
    # MAJOR: the 1F1B pipeline's in-loop head (parallel/pipeline.py) views
    # the weight as (D, P, V/P) under a partial-manual shard_map, which is
    # a reshard-free reshape only when each stage's slice is contiguous
    # (pipe outermost); the tensor sub-sharding stays inside each slice.
    "vocab": ("pipe", "tensor"),
    "embed": "fsdp",
    # activations keep their feature dim replicated (FSDP shards params, not
    # activations; 'embed' -> fsdp applies to parameter matrices only)
    "act_embed": None,
    "heads": "tensor",
    "kv_heads": "tensor",
    "mlp": "tensor",
    "norm": None,
    # leading layer-stack axis of scan-form params (models/llama.py
    # layer_impl="scan"): sharded by pipeline stage, so each stage stores
    # only its own layers (parallel/pipeline.py); on meshes without a pipe
    # axis (size 1) this resolves to replicated
    "layers": "pipe",
    # leading expert axis of MoE expert stacks and activations
    # (models/moe.py): each device on the 'expert' axis stores and computes
    # only its experts; XLA inserts the dispatch/combine all-to-all
    "expert_stack": "expert",
}

# Parameter-path (joined with '/') -> logical axes of that parameter.
PARAM_AXIS_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r"tok_embeddings/embedding$", ("vocab", "embed")),
    (r"wq/kernel$", ("embed", "heads")),
    (r"wk/kernel$", ("embed", "kv_heads")),
    (r"wv/kernel$", ("embed", "kv_heads")),
    (r"wo/kernel$", ("heads", "embed")),
    (r"w1/kernel$", ("embed", "mlp")),
    (r"w3/kernel$", ("embed", "mlp")),
    (r"w2/kernel$", ("mlp", "embed")),
    (r"output/kernel$", ("embed", "vocab")),
    (r"router/kernel$", ("embed", None)),  # MoE router (models/moe.py)
    (r"(scale|norm)[^/]*$", ("norm",)),
)


def _resolve(logical_axes, rules=None) -> P:
    rules = LOGICAL_RULES if rules is None else rules
    return P(*(rules.get(a) if a is not None else None for a in logical_axes))


_FIT_WARNED = set()


def _fit_spec(spec: P, shape, mesh) -> P:
    """Drop mesh axes a dimension cannot actually be sharded over.

    An indivisible dim (e.g. the byte tokenizer's 259-entry vocab over a
    ('pipe', 'tensor') product) would be a hard pjit error; degrading that
    dim to the divisible prefix of its axes (possibly replicated) is always
    semantically valid — the same per-axis degrade the ring attention op
    applies to its batch axes. Dropping an axis on a non-trivial dim is
    logged once per (dim, axes) pair: silent replication of a large param
    or batch is a real capacity/compute cost the operator should see."""
    if mesh is None:
        return spec
    fitted = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            fitted.append(None)
            continue
        keep, dropped, prod = [], [], 1
        for a in axes if isinstance(axes, tuple) else (axes,):
            n = mesh.shape.get(a, 1)
            if dim % (prod * n) == 0:
                keep.append(a)
                prod *= n
            elif n > 1:
                dropped.append(a)
        if dropped and dim >= 64 and (dim, tuple(dropped)) not in _FIT_WARNED:
            _FIT_WARNED.add((dim, tuple(dropped)))
            import logging
            logging.getLogger(__name__).warning(
                "sharding: dim %d is not divisible by mesh axes %s "
                "(sizes %s); that dim degrades to %s — replicated work/"
                "storage where sharding was requested",
                dim, dropped, [mesh.shape.get(a, 1) for a in dropped],
                keep or "replicated")
        fitted.append(tuple(keep) if len(keep) > 1
                      else (keep[0] if keep else None))
    return P(*fitted)


def shard_size(dim: int, logical_axis: str, mesh=None) -> int:
    """How many ways ``dim`` would actually shard over ``logical_axis`` on
    the active mesh, after the :func:`_fit_spec` divisibility degrade.

    The dispatch predicate for layout-sensitive implementation choices
    (e.g. embed gather-vs-one_hot, dense-vs-blocked CE): axis size alone
    lies when the dim is indivisible and silently degrades to replication.
    """
    mesh = mesh or active_mesh()
    if mesh is None:
        return 1
    spec = _fit_spec(_resolve((logical_axis,)), (dim,), mesh)
    axes = spec[0]
    if axes is None:
        return 1
    prod = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        prod *= mesh.shape.get(a, 1)
    return prod


def logical_pspec(*logical_axes) -> P:
    return _resolve(logical_axes)


def vocab_shard_axes(w_shape, mesh) -> Tuple[str, ...]:
    """Mesh axes that actually shard the vocab dim of a (D, V) weight on
    ``mesh`` (after the :func:`_fit_spec` divisibility degrade), in
    sharding-major order. The single source of truth for every consumer
    that hand-schedules over the vocab sharding (the fused sharded CE in
    ops/fused_ce.py and the 1F1B pipeline's in-loop head) — their offset
    math must agree or labels land in the wrong shard."""
    fitted = _fit_spec(logical_pspec("embed", "vocab"), w_shape, mesh)
    axes = fitted[1]
    return axes if isinstance(axes, tuple) else ((axes,) if axes else ())


def batch_pspec() -> P:
    """Batches: (B, S) sharded batch->data+fsdp, seq->sequence."""
    return _resolve(("batch", "seq"))


def constrain(x: jax.Array, *logical_axes) -> jax.Array:
    """``with_sharding_constraint`` against the active mesh; no-op without one.

    Axes whose mesh axis has size 1 still resolve fine (XLA treats them as
    unsharded), so the same model code traces identically on a laptop CPU and
    a v5p-64 mesh. Inside a partial-manual ``shard_map`` (the pipeline
    trunk, parallel/pipeline.py) the constraint must be built on the
    context's abstract mesh — whose manual axes (e.g. 'pipe') may not be
    referenced — not on the all-auto concrete mesh."""
    mesh = active_mesh()
    if mesh is None or len(logical_axes) != x.ndim or _CONSTRAINTS_SUSPENDED:
        return x
    if jax.sharding.get_abstract_mesh().manual_axes:
        # Inside a partial-manual shard_map (the pipeline trunk,
        # parallel/pipeline.py) constraints built on the all-auto
        # concrete mesh clash with the Manual context (and rebuilt ones
        # still break under autodiff replay); the auto axes' shardings
        # propagate from the body's inputs, so skip the hint here.
        return x
    spec = _fit_spec(_resolve(logical_axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_pspecs(params) -> dict:
    """PartitionSpec pytree for a param pytree, from PARAM_AXIS_RULES paths."""

    def spec_for(path: str, leaf) -> P:
        for pattern, axes in PARAM_AXIS_RULES:
            if re.search(pattern, path):
                axes = tuple(axes)
                # stacked-param prefixes, outermost first: the scan-form
                # layer axis, then the MoE expert axis (both optional)
                if re.search(r"(^|/)experts/", path) and leaf.ndim > len(axes):
                    axes = ("expert_stack",) + axes
                if (re.search(r"(^|/)layers/block/", path)
                        and leaf.ndim > len(axes)):
                    axes = ("layers",) + axes
                if len(axes) != leaf.ndim:
                    raise ValueError(
                        f"rule {pattern!r} gives {len(axes)} axes for {path} "
                        f"with ndim {leaf.ndim}")
                return _fit_spec(_resolve(axes), leaf.shape, active_mesh())
        return P(*([None] * leaf.ndim))  # replicate unknown params

    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = {}
    for keypath, leaf in flat[0]:
        path = "/".join(_key_str(k) for k in keypath)
        specs[path] = spec_for(path, leaf)
    return jax.tree_util.tree_unflatten(
        flat[1], [specs["/".join(_key_str(k) for k in kp)] for kp, _ in flat[0]])


def replicated_pspecs(tree) -> dict:
    """Every leaf whole on every device: the latent / expert class's state
    (models/latent_moe.py), which trains on one device (training/loop.py
    refuses a mesh of more for it)."""
    return jax.tree_util.tree_map(lambda leaf: P(*([None] * leaf.ndim)),
                                  tree)


def param_shardings(params, mesh=None):
    """NamedSharding pytree for ``params`` on ``mesh`` (default: active mesh)."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return None
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_pspecs(params),
        is_leaf=lambda x: isinstance(x, P))


def _key_str(k) -> str:
    if hasattr(k, "key"):  # DictKey
        return str(k.key)
    if hasattr(k, "name"):  # GetAttrKey (e.g. TrainState fields)
        return str(k.name)
    if hasattr(k, "idx"):  # SequenceKey (e.g. optax chain tuples)
        return str(k.idx)
    return str(k)
