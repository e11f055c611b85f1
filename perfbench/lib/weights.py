"""Weights from the seed, made by the benchmark and by nothing else.

One leaf is a pure function of (seed key, leaf path, shape): the program's
parameter tree and the plain reference both call :func:`make_leaf`, so the
reference takes nothing the program has made. Values are drawn in float32
and rounded to the served/trained type (bfloat16) once; the reference
upcasts those same bfloat16 values.

Which leaves a model has, and what its sizes are, is its family's business
(``perfbench/families/<family>.py``, found by the name the configuration
file gives): :func:`dims_of` returns the family's sizes with the family's
name among them (``d["family"]``), and everything here that takes ``d``
asks that family. So sizes carry their family wherever they travel (the
training child's spec, a reader's ``ctx["dims"]``).
"""

import math
import zlib

from . import manifest


def family_of(d: dict):
    """The family file of sizes that :func:`dims_of` made."""
    return manifest.load_family(d["family"])


def dims_of(config: dict) -> dict:
    """The family's sizes for a configuration file, and the family's name.
    The harness itself reads ``vocab`` (token ids are drawn under it); every
    other key is the family's own."""
    name = manifest.family_name(config)
    return dict(manifest.load_family(name).dims_of(config), family=name)


def preset_kwargs(config: dict) -> dict:
    """Keyword arguments of the program's model configuration for a
    configuration file."""
    return manifest.load_family(manifest.family_name(config)).preset_kwargs(
        config)


def all_leaves(d: dict) -> dict:
    """Every leaf of the model: full path -> (shape, kind)."""
    return family_of(d).all_leaves(d)


def path_id(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def make_leaf(key, path: str, shape, kind: str, dtype, family: str = None):
    """One parameter leaf. ``key`` is ``jax.random.PRNGKey(seed)``; works
    traced (inside one jitted init) and eagerly (the reference, leaf by
    leaf). Three kinds are drawn here; any other is the named family's:
    its ``draw_leaf(z, shape, kind)`` makes the float32 values from the
    standard normal draw ``z`` (a leaf stacked over experts has its fan-in
    on axis 1; a decay or a bias is neither a scale nor an embedding)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, path_id(path))
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "scale":
        w = 1.0 + 0.05 * z
    elif kind == "embed":
        w = 0.02 * z
    elif kind == "dense":
        # (fan_in, fan_out): lecun normal, the program's own scale
        w = z / math.sqrt(shape[0])
    elif family is None:
        raise ValueError(f"leaf {path}: kind {kind!r} is no kind of "
                         f"weights.make_leaf and no family was named")
    else:
        w = manifest.load_family(family).draw_leaf(z, shape, kind)
    return w.astype(dtype)


def nest(flat: dict) -> dict:
    """{'a/b/c': x} -> {'a': {'b': {'c': x}}}."""
    out = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def make_param_tree(key, d: dict, dtype):
    """The whole tree, nested as the program's ``params`` collection."""
    return nest({p: make_leaf(key, p, shape, kind, dtype, d["family"])
                 for p, (shape, kind) in all_leaves(d).items()})


def param_count(d: dict) -> int:
    return sum(math.prod(s) for s, _ in all_leaves(d).values())
