"""A model family that is not Llama's, for ``test_perfbench_family.py``: the
test copies this file to ``<bench>/families/toy.py`` and the harness finds
it by the name a configuration gives. It has what the Llama family has not:
layers of two kinds by index (a leading dense layer with a bias, then
layers that keep a decayed running state — no keys and values to page — and
mix experts stacked in one leaf), kinds of leaf of its own, a model class
and a preset that are not the program's ``Transformer``, a loss and
gradients of its own under the shared optimizer half, and counts of its own
(no attention kernel and no paged read: a cell of such a family lists
neither roofline). No JAX at import."""

import math


def dims_of(config: dict) -> dict:
    return {"dim": config["width"], "n_layers": config["depth"],
            "experts": config["experts"], "vocab": config["vocab_size"]}


def check_dims(d: dict) -> list:
    """Its own statement, not Llama's: no heads to multiply out; an expert
    layer needs a layer after the leading dense one and an expert in it."""
    return [f"{k} {d[k]} < {least}" for k, least in (("n_layers", 2),
                                                     ("experts", 1))
            if d[k] < least]


# ------------------------------------------------------- the program's side
class ToyModel:
    """Stands where a program's model class would: ``init`` makes a tree
    with the leaves table's paths and shapes."""

    def __init__(self, cfg: dict):
        self.cfg = cfg

    def init(self, key):
        import jax.numpy as jnp

        from perfbench.lib import weights as W

        return {"params": W.nest({
            p: jnp.zeros(shape, jnp.float32)
            for p, (shape, _) in all_leaves(self.cfg["d"]).items()})}


def preset_kwargs(config: dict) -> dict:
    return {"d": dims_of(config)}


def preset(config: dict, **over):
    return dict(preset_kwargs(config), **over)


def model_class():
    return ToyModel


# ------------------------------------------------------------------- leaves
def all_leaves(d: dict) -> dict:
    dim, e = d["dim"], d["experts"]
    out = {"embed/table": ((d["vocab"], dim), "embed")}
    for i in range(d["n_layers"]):
        if i == 0:
            out["layer_0/w"] = ((dim, dim), "dense")
            out["layer_0/b"] = ((dim,), "bias")
        else:
            out[f"layer_{i}/decay"] = ((dim,), "decay")
            out[f"layer_{i}/experts"] = ((e, dim, dim), "expert_dense")
    out["head/w"] = ((dim, d["vocab"]), "dense")
    return out


def draw_leaf(z, shape, kind: str):
    """The kinds ``weights.make_leaf`` does not know."""
    if kind == "expert_dense":      # (experts, fan_in, fan_out)
        return z / math.sqrt(shape[1])
    if kind == "bias":
        return 0.01 * z
    if kind == "decay":
        return 1.0 + 0.1 * z
    raise ValueError(f"toy family: no kind {kind!r}")


# ---------------------------------------------------------------- reference
def _forward(params: dict, tokens, d: dict, mm):
    """tokens (S,) -> logits (S, vocab)."""
    import jax
    import jax.numpy as jnp

    x = params["embed/table"][tokens]
    for i in range(d["n_layers"]):
        if i == 0:
            x = x + jnp.tanh(mm(x, params["layer_0/w"])
                             + params["layer_0/b"])
            continue
        keep = jax.nn.sigmoid(params[f"layer_{i}/decay"])

        def step(state, x_t, keep=keep):
            state = keep * state + (1.0 - keep) * x_t
            return state, state

        _, states = jax.lax.scan(step, jnp.zeros_like(x[0]), x)
        w = params[f"layer_{i}/experts"]
        x = x + sum(mm(states, w[e]) for e in range(d["experts"])) / d[
            "experts"]
    return mm(x, params["head/w"])


def _params(key, d: dict, dtype) -> dict:
    import jax.numpy as jnp

    from perfbench.lib import weights as W

    return {p: W.make_leaf(key, p, shape, kind, dtype, d["family"]).astype(
        jnp.float32) for p, (shape, kind) in all_leaves(d).items()}


def forward_logits(key, d: dict, tokens, positions_wanted, mm, dtype):
    import jax.numpy as jnp

    return _forward(_params(key, d, dtype), jnp.asarray(tokens), d, mm)[
        jnp.asarray(positions_wanted)]


def batch_logits(key, d: dict, seqs, wanted, mm, dtype) -> list:
    import numpy as np

    return [np.asarray(forward_logits(key, d, s, pos, mm, dtype))
            for s, pos in zip(seqs, wanted)]


class LossAndGrads:
    def __init__(self, d: dict, mm):
        import jax
        import jax.numpy as jnp

        def loss(params, inputs, labels):
            logits = jax.vmap(lambda t: _forward(params, t, d, mm))(inputs)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[..., None],
                                         axis=-1)[..., 0]
            return jnp.mean(lse - picked)

        self._fn = jax.jit(jax.value_and_grad(loss))

    def __call__(self, params: dict, inputs, labels):
        import jax.numpy as jnp

        loss, grads = self._fn(params, jnp.asarray(inputs),
                               jnp.asarray(labels))
        return float(loss), dict(grads)


# ------------------------------------------------------------------- counts
def matmul_params(d: dict) -> int:
    dim = d["dim"]
    return (dim * dim + (d["n_layers"] - 1) * d["experts"] * dim * dim
            + dim * d["vocab"])


def train_flops_per_token(d: dict, seq_len: int) -> float:
    return 3.0 * 2.0 * matmul_params(d)     # no attention: no seq term


def serve_flops(d: dict, new_tokens: int, ctx_token_pairs: int) -> float:
    return 2.0 * matmul_params(d) * new_tokens
