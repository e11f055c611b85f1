"""Weights from the seed, made by the benchmark and by nothing else.

One leaf is a pure function of (seed key, leaf path, shape): the program's
parameter tree and the plain reference both call :func:`make_leaf`, so the
reference takes nothing the program has made. Values are drawn in float32
and rounded to the served/trained type (bfloat16) once; the reference
upcasts those same bfloat16 values.

Paths follow the checkpoint layout of the program's Llama-family model
(``layers_<i>/attention/wq/kernel`` ...): that layout is the interface the
benchmark feeds, exactly as a converted public checkpoint would be fed.
"""

import math
import zlib


def dims_of(config: dict) -> dict:
    """The sizes a Llama-family block needs, from a HF-style config dict."""
    h = config["hidden_size"]
    n_heads = config["num_attention_heads"]
    return {
        "dim": h,
        "n_layers": config["num_hidden_layers"],
        "n_heads": n_heads,
        "n_kv_heads": config.get("num_key_value_heads", n_heads),
        "head_dim": config.get("head_dim", h // n_heads),
        "hidden": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
    }


def preset_kwargs(config: dict) -> dict:
    """Keyword arguments of the program's ``TransformerConfig`` for a
    configuration file (its ``program`` group carries the two numbers the
    program derives the feed-forward width from)."""
    d = dims_of(config)
    return dict(dim=d["dim"], n_layers=d["n_layers"], n_heads=d["n_heads"],
                n_kv_heads=d["n_kv_heads"],
                ffn_dim_multiplier=config["program"]["ffn_dim_multiplier"],
                multiple_of=config["program"]["multiple_of"],
                norm_eps=d["norm_eps"], rope_theta=d["rope_theta"],
                vocab_size=d["vocab"])


def layer_leaves(d: dict) -> dict:
    """path (inside one block) -> (shape, kind)."""
    nq, nkv = d["n_heads"] * d["head_dim"], d["n_kv_heads"] * d["head_dim"]
    return {
        "attention/wq/kernel": ((d["dim"], nq), "dense"),
        "attention/wk/kernel": ((d["dim"], nkv), "dense"),
        "attention/wv/kernel": ((d["dim"], nkv), "dense"),
        "attention/wo/kernel": ((nq, d["dim"]), "dense"),
        "attention_norm/scale": ((d["dim"],), "scale"),
        "feed_forward/w1/kernel": ((d["dim"], d["hidden"]), "dense"),
        "feed_forward/w2/kernel": ((d["hidden"], d["dim"]), "dense"),
        "feed_forward/w3/kernel": ((d["dim"], d["hidden"]), "dense"),
        "ffn_norm/scale": ((d["dim"],), "scale"),
    }


def all_leaves(d: dict) -> dict:
    """Every leaf of the model: full path -> (shape, kind)."""
    out = {"tok_embeddings/embedding": ((d["vocab"], d["dim"]), "embed")}
    for i in range(d["n_layers"]):
        for p, v in layer_leaves(d).items():
            out[f"layers_{i}/{p}"] = v
    out["norm/scale"] = ((d["dim"],), "scale")
    out["output/kernel"] = ((d["dim"], d["vocab"]), "dense")
    return out


def path_id(path: str) -> int:
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


def make_leaf(key, path: str, shape, kind: str, dtype):
    """One parameter leaf. ``key`` is ``jax.random.PRNGKey(seed)``; works
    traced (inside one jitted init) and eagerly (the reference, leaf by
    leaf)."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, path_id(path))
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "scale":
        w = 1.0 + 0.05 * z
    elif kind == "embed":
        w = 0.02 * z
    else:  # dense (fan_in, fan_out): lecun normal, the program's own scale
        w = z / math.sqrt(shape[0])
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
    return nest({p: make_leaf(key, p, shape, kind, dtype)
                 for p, (shape, kind) in all_leaves(d).items()})


def param_count(d: dict) -> int:
    return sum(math.prod(s) for s, _ in all_leaves(d).values())
