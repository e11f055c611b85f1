"""The Llama family (Mistral-7B, InternLM2): everything the benchmark knows
of one family of models, in one file found by the name a configuration
gives (``program.family``; ``lib/manifest.load_family``).

A family file holds five things, and the harness asks for them by these
names (``PERF.md`` section 3, "how a family comes in"):

- sizes: ``dims_of(config)``; the harness itself reads only ``vocab``;
  optionally ``check_dims(d)``, what has to hold between them;
- the program's side: ``preset_kwargs`` / ``preset`` build the program's
  model configuration, ``model_class`` names the class whose ``init`` the
  training child wraps;
- leaves: ``all_leaves(d)`` is the table path -> (shape, kind) that
  ``lib/weights.py`` draws from the seed. The kinds here are the three
  ``weights.make_leaf`` draws itself; a family with another kind adds
  ``draw_leaf(z, shape, kind)``;
- the plain reference: ``forward_logits``, ``batch_logits`` (serving) and
  ``LossAndGrads`` (training, under ``reference.TrainReference``'s
  optimizer half): float32 ``jax.numpy``, no kernels, no cache. It imports
  nothing of the program and takes nothing the program has made;
- the counts: operations and bytes the *algorithm* needs, from shapes
  alone (``train_flops_per_token``, ``serve_flops``, ``paged_read_bytes``,
  ``flash_attn_flops`` / ``flash_attn_bytes``). What an implementation
  moves beyond that (a gathered copy, a recompute) is not counted, so a
  share built on these cannot pass 100% by construction of the count.

Nothing here needs JAX at import (the training cells' parent process stays
off JAX): the reference's functions import it when they run.

Paths follow the checkpoint layout of the program's Llama-family model
(``layers_<i>/attention/wq/kernel`` ...): that layout is the interface the
benchmark feeds, exactly as a converted public checkpoint would be fed.

Departures from the published descriptions, each noted in the configuration
files under ``assumed``: RoPE rotates adjacent pairs (the original
Llama/Mistral formulation; the HF port's half-split form is the same
equations under a fixed permutation of q/k columns).

Count conventions: one multiply-add = 2 FLOPs; causal attention counts the
lower triangle (half of S x S); the embedding gather is a lookup, not a
matmul.
"""

import functools
import math

Q_CHUNK = 1024  # queries per attention block


# -------------------------------------------------------------------- sizes
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


def check_dims(d: dict) -> list:
    """What has to hold between this family's sizes (tests call this over
    every configuration that names the family): the problems, as text."""
    if d["head_dim"] * d["n_heads"] != d["dim"]:
        return [f"head_dim {d['head_dim']} x n_heads {d['n_heads']} is not "
                f"dim {d['dim']}"]
    return []


# ------------------------------------------------------- the program's side
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


def preset(config: dict, **over):
    """The program's model configuration for this file, held to the
    file's own sizes (the program derives two of them)."""
    from fault_tolerant_llm_training_tpu.models import configs as mc

    d = dims_of(config)
    cfg = mc.TransformerConfig(**preset_kwargs(config), **over)
    assert cfg.ffn_hidden_dim == d["hidden"], (cfg.ffn_hidden_dim, d)
    assert cfg.head_dim == d["head_dim"], (cfg.head_dim, d)
    return cfg


def model_class():
    """The program's model class: the training child replaces its ``init``
    by the seed's weights."""
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    return Transformer


# ------------------------------------------------------------------- leaves
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


# ------------------------------------------------- the reference: equations
def rope(x, positions, theta):
    """x (S, H, D), positions (S,): rotate adjacent pairs (x[2j], x[2j+1])
    by positions * theta^(-2j/D)."""
    import jax.numpy as jnp

    s, h, dd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dd, 2, dtype=jnp.float32) / dd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x.reshape(s, h, dd // 2, 2)
    a, b = xr[..., 0], xr[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(s, h, dd)


def causal_attention(q, k, v):
    """q (S, H, D), k/v (S, K, D), grouped queries; softmax in float32,
    in blocks of queries so that the scores fit."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import HIGHEST

    s, h, dd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for lo in range(0, s, Q_CHUNK):
        hi = min(s, lo + Q_CHUNK)
        sc = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi],
                        precision=HIGHEST) / math.sqrt(dd)
        mask = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi],
                               precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def block(w: dict, x, d: dict, mm):
    """One decoder block on one row: x (S, dim) -> (S, dim)."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import rmsnorm

    s = x.shape[0]
    pos = jnp.arange(s)
    h = rmsnorm(x, w["attention_norm/scale"], d["norm_eps"])
    q = mm(h, w["attention/wq/kernel"]).reshape(s, d["n_heads"],
                                               d["head_dim"])
    k = mm(h, w["attention/wk/kernel"]).reshape(s, d["n_kv_heads"],
                                               d["head_dim"])
    v = mm(h, w["attention/wv/kernel"]).reshape(s, d["n_kv_heads"],
                                               d["head_dim"])
    q, k = rope(q, pos, d["rope_theta"]), rope(k, pos, d["rope_theta"])
    o = causal_attention(q, k, v).reshape(s, -1)
    x = x + mm(o, w["attention/wo/kernel"])
    h = rmsnorm(x, w["ffn_norm/scale"], d["norm_eps"])
    gate = mm(h, w["feed_forward/w1/kernel"])
    up = mm(h, w["feed_forward/w3/kernel"])
    return x + mm(jax.nn.silu(gate) * up, w["feed_forward/w2/kernel"])


def head_logits(x, norm_scale, w_out, d, mm):
    from perfbench.lib.reference import rmsnorm

    return mm(rmsnorm(x, norm_scale, d["norm_eps"]), w_out)


# --------------------------------------------------- the reference: weights
def layer_weights(key, d: dict, i: int, dtype) -> dict:
    """Block i's weights, made from the seed in the served type and upcast."""
    import jax.numpy as jnp

    from perfbench.lib import weights as W

    return {p: W.make_leaf(key, f"layers_{i}/{p}", shape, kind,
                           dtype).astype(jnp.float32)
            for p, (shape, kind) in layer_leaves(d).items()}


def top_weights(key, d: dict, dtype) -> dict:
    import jax.numpy as jnp

    from perfbench.lib import weights as W

    leaves = all_leaves(d)
    return {p: W.make_leaf(key, p, *leaves[p], dtype).astype(jnp.float32)
            for p in ("tok_embeddings/embedding", "norm/scale",
                      "output/kernel")}


# --------------------------------------------------- the reference: serving
def forward_logits(key, d: dict, tokens, positions_wanted, mm, dtype):
    """Logits (len(positions_wanted), vocab) of one sequence ``tokens``
    (S,) at the given positions, layer by layer."""
    import jax
    import jax.numpy as jnp

    top = top_weights(key, d, dtype)
    x = top["tok_embeddings/embedding"][jnp.asarray(tokens)]
    blk = jax.jit(functools.partial(block, d=d, mm=mm))
    for i in range(d["n_layers"]):
        x = blk(layer_weights(key, d, i, dtype), x)
    x = x[jnp.asarray(positions_wanted)]
    return jax.jit(functools.partial(head_logits, d=d, mm=mm))(
        x, top["norm/scale"], top["output/kernel"])


def batch_logits(key, d: dict, seqs, wanted, mm, dtype) -> list:
    """Logits of several padded sequences ``seqs`` at each one's ``wanted``
    positions, layer by layer (one layer's weights live at a time)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    top = top_weights(key, d, dtype)
    xs = [top["tok_embeddings/embedding"][jnp.asarray(s)] for s in seqs]
    blk = jax.jit(functools.partial(block, d=d, mm=mm))
    for i in range(d["n_layers"]):
        w = layer_weights(key, d, i, dtype)
        xs = [blk(w, x) for x in xs]
        del w
    head = jax.jit(functools.partial(head_logits, d=d, mm=mm))
    return [np.asarray(head(x[jnp.asarray(pos)], top["norm/scale"],
                            top["output/kernel"]))
            for x, pos in zip(xs, wanted)]


# -------------------------------------------------- the reference: training
def _row_nll_sum(x, norm_scale, w_out, labels, d, mm):
    import jax
    import jax.numpy as jnp

    logits = head_logits(x, norm_scale, w_out, d, mm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


class LossAndGrads:
    """The family's half of ``reference.TrainReference``: the mean loss of
    a batch and its gradient by leaf, from float32 parameters. It runs layer
    by layer and row by row (a ``vjp`` per block per row), so that float32
    parameters and gradients of a 1.1 B model fit one 16 GB chip beside one
    block's activations. All labels are valid in the benchmark's corpus (no
    padding)."""

    def __init__(self, d: dict, mm):
        import jax

        self.d = d
        self._blk = jax.jit(functools.partial(block, d=d, mm=mm))

        def blk_vjp(w, x, g):
            _, vjp = jax.vjp(lambda w_, x_: block(w_, x_, d, mm), w, x)
            return vjp(g)

        self._blk_vjp = jax.jit(blk_vjp)
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(_row_nll_sum, d=d, mm=mm), argnums=(0, 1, 2)))

    @staticmethod
    def layer(params: dict, i: int) -> dict:
        pre = f"layers_{i}/"
        return {p[len(pre):]: v for p, v in params.items()
                if p.startswith(pre)}

    def __call__(self, params: dict, inputs, labels):
        import jax.numpy as jnp

        d = self.d
        b, s = inputs.shape
        n = float(b * s)
        emb = params["tok_embeddings/embedding"]
        xs = [[emb[jnp.asarray(inputs[r])]] for r in range(b)]
        for i in range(d["n_layers"]):
            w = self.layer(params, i)
            for r in range(b):
                xs[r].append(self._blk(w, xs[r][-1]))
        grads = {}
        loss = 0.0
        dxs = []
        for r in range(b):
            nll, (dx, dscale, dwout) = self._head(
                xs[r][-1], params["norm/scale"],
                params["output/kernel"], jnp.asarray(labels[r]))
            loss += float(nll) / n
            dxs.append(dx / n)
            for p, g in (("norm/scale", dscale), ("output/kernel", dwout)):
                grads[p] = g / n if p not in grads else grads[p] + g / n
            xs[r].pop()
        for i in reversed(range(d["n_layers"])):
            w = self.layer(params, i)
            for r in range(b):
                dw, dx = self._blk_vjp(w, xs[r].pop(), dxs[r])
                dxs[r] = dx
                for p, g in dw.items():
                    full = f"layers_{i}/{p}"
                    grads[full] = g if full not in grads else grads[full] + g
        demb = jnp.zeros_like(emb)
        for r in range(b):
            demb = demb.at[jnp.asarray(inputs[r])].add(dxs[r])
        grads["tok_embeddings/embedding"] = demb
        return loss, grads


# --------------------------------------------------------------- the counts
def matmul_params(d: dict) -> int:
    """Parameters that take part in a matmul for every token: all but the
    embedding table and the norm scales."""
    nq = d["n_heads"] * d["head_dim"]
    nkv = d["n_kv_heads"] * d["head_dim"]
    per_layer = (d["dim"] * (nq + 2 * nkv) + nq * d["dim"]
                 + 3 * d["dim"] * d["hidden"])
    return d["n_layers"] * per_layer + d["dim"] * d["vocab"]


def attn_flops_fwd(d: dict, q_len: int, kv_len: int, causal: bool) -> float:
    """QK^T and PV for ``q_len`` queries over ``kv_len`` keys, all layers.
    Causal with q_len == kv_len counts the triangle."""
    full = 2.0 * 2.0 * d["n_heads"] * d["head_dim"] * q_len * kv_len
    if causal and q_len == kv_len:
        full *= (q_len + 1) / (2.0 * q_len)
    return d["n_layers"] * full


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward (3x forward), causal attention, no recompute."""
    fwd = 2.0 * matmul_params(d) + attn_flops_fwd(
        d, seq_len, seq_len, causal=True) / seq_len
    return 3.0 * fwd


def flash_attn_flops(d: dict, batch: int, seq_len: int) -> float:
    """Causal attention forward + backward of one step: the backward makes
    two matmuls for each of the forward's (dQ, dK, dV and dP), 2x forward;
    the kernel's own recompute of P is not counted."""
    fwd = attn_flops_fwd(d, seq_len, seq_len, causal=True) * batch
    return 3.0 * fwd


def flash_attn_bytes(d: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv. Once each."""
    q = batch * seq_len * d["n_heads"] * d["head_dim"] * itemsize
    kv = batch * seq_len * d["n_kv_heads"] * d["head_dim"] * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return float(d["n_layers"] * (fwd + bwd))


def kv_bytes_per_token(d: dict, itemsize: int = 2) -> int:
    return d["n_layers"] * 2 * d["n_kv_heads"] * d["head_dim"] * itemsize


def paged_read_bytes(d: dict, live_tokens: int, itemsize: int = 2) -> float:
    """The live keys and values read once: what a decode step's attention
    needs whatever implements it."""
    return float(kv_bytes_per_token(d, itemsize) * live_tokens)


def serve_flops(d: dict, new_tokens: int, ctx_token_pairs: int) -> float:
    """Forward FLOPs of serving: 2 x matmul params for each token processed
    (prefill or decode) plus attention over its context;
    ``ctx_token_pairs`` is the sum over processed tokens of the context
    length each attended to."""
    attn = 2.0 * 2.0 * d["n_heads"] * d["head_dim"] * d["n_layers"]
    return 2.0 * matmul_params(d) * new_tokens + attn * ctx_token_pairs
