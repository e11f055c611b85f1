"""The dots3 family (``dots3-note-prev``, ``model_type`` ``dots3_note``): a
decoder whose layers follow ``layer_types`` — latent attention behind a
learned top-k indexer on ``full_attention`` layers, latent attention of
other sizes behind a sliding window on the rest, a head-wise sigmoid gate on
both — with one leading dense SwiGLU layer and sigmoid-routed experts after
it, of which a chip holds a stated range. Only the language model: the
published vision and audio towers and the MTP module are not in the
catalog row's ``config``.

The interface is ``families/llama.py``'s (``PERF.md`` section 3). Nothing
here needs JAX at import.

**Equations** (u a sub-layer's RMS-normed input; pre-norm residual blocks,
final RMSNorm, untied head, no embedding scale):

- latent attention with sizes (H, r_q, r_kv, d_n, d_r, d_v, theta):
  ``c_q = a_q RMSNorm(u W_qa)``; ``[q_n | q_r]_h = c_q W_qb``, RoPE on q_r;
  ``[c | k_r] = u W_kva``, ``c_kv = a_kv RMSNorm(c)``, RoPE on the one k_r
  all heads share; ``[k_n | v]_h = c_kv W_kvb``; ``a = softmax_{s in A(t)}
  ((q_n . k_n + q_r . k_r) / sqrt(d_n + d_r))``; ``out = concat_h(
  sigmoid(u W_g)_h o_h) W_o``.
- the indexer (full layers, after DeepSeek-V3.2-Exp's ``Indexer``):
  ``q^I_j = c_q W^I_qb`` (RoPE on the first d_r of each head), ``k^I =
  LayerNorm(u W^I_k)`` (RoPE on its first d_r), ``w = (u W^I_w) H_I^-1/2
  d_I^-1/2``, ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``; A(t) =
  the ``index_topk`` positions s <= t of largest I, ties to the lower
  position, all of them while t + 1 <= index_topk.
- sliding layers: A(t) = {s : t - sliding_window < s <= t}.
- the expert layer: ``s = sigmoid(u W_r)`` over all published experts; the
  chosen are the top ``num_experts_per_tok`` of ``s + b``; ``w_e = s_e /
  sum_chosen s`` x ``routed_scaling_factor``; the sum runs over chosen ∩
  held (this chip's share), plus the shared expert.

Departures from the published files are the configuration file's
``assumed`` (the variance alignment ``a = sqrt(hidden / rank)``; the gate's
form and place; the window counting the query's own position; no Hadamard
rotation and bfloat16 index keys; adjacent-pair RoPE).

**Counts**: one multiply-add = 2 FLOPs; the embedding is a lookup; a
routed expert counts at its expected share ``k x held / routed``.
"""

import functools
import math

Q_BLOCK = 128           # queries a block of the reference's attention
INDEX_NORM_EPS = 1e-6   # the indexer's LayerNorm


# -------------------------------------------------------------------- sizes
def dims_of(config: dict) -> dict:
    published = config.get("published", {})
    program = config.get("program", {})
    types = [{"full_attention": "full", "sliding_attention": "sliding"}[t]
             for t in config["layer_types"]]
    return {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "layer_types": types,
        "vocab": config["vocab_size"],
        "norm_eps": float(config["rms_norm_eps"]),
        "rescale": bool(config.get("apply_mla_qkv_lora_rescale", False)),
        "full": {"heads": config["num_attention_heads"],
                 "q_rank": config["q_lora_rank"],
                 "kv_rank": config["kv_lora_rank"],
                 "nope": config["qk_nope_head_dim"],
                 "rope": config["qk_rope_head_dim"],
                 "v": config["v_head_dim"],
                 "theta": float(config["rope_theta"])},
        "sliding": {"heads": config["swa_num_attention_heads"],
                    "q_rank": config["swa_q_lora_rank"],
                    "kv_rank": config["swa_kv_lora_rank"],
                    "nope": config["swa_qk_nope_head_dim"],
                    "rope": config["swa_qk_rope_head_dim"],
                    "v": config["swa_v_head_dim"],
                    "theta": float(config["swa_rope_theta"])},
        "window": config["sliding_window_size"],
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "first_dense": config["first_k_dense_replace"],
        "dense_hidden": config["intermediate_size"],
        "moe_hidden": config["moe_intermediate_size"],
        # the router keeps its published width; the file's own number is
        # what this chip holds of it
        "routed": published.get("n_routed_experts",
                                config["n_routed_experts"]),
        "held": config["n_routed_experts"],
        "held_first": int(program.get("held_first", 0)),
        "top_k": config["num_experts_per_tok"],
        "shared": config["n_shared_experts"],
        "route_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
    }


def check_dims(d: dict) -> list:
    bad = []
    if len(d["layer_types"]) != d["n_layers"]:
        bad.append(f"{len(d['layer_types'])} layer_types for "
                   f"{d['n_layers']} layers")
    if d["held_first"] + d["held"] > d["routed"]:
        bad.append(f"held experts {d['held_first']}+{d['held']} past the "
                   f"{d['routed']} routed")
    if d["top_k"] > d["routed"]:
        bad.append("num_experts_per_tok over the routed experts")
    for kind in ("full", "sliding"):
        m = d[kind]
        if m["rope"] % 2:
            bad.append(f"{kind}: odd rope width {m['rope']}")
    if d["index_dim"] < d["full"]["rope"]:
        bad.append("index_head_dim under the rope width")
    if d["first_dense"] > d["n_layers"]:
        bad.append("first_k_dense_replace over the layers")
    return bad


# ------------------------------------------------------- the program's side
def preset_kwargs(config: dict) -> dict:
    d = dims_of(config)
    f, s = d["full"], d["sliding"]
    return dict(
        dim=d["dim"], n_layers=d["n_layers"],
        layer_types=tuple(d["layer_types"]), norm_eps=d["norm_eps"],
        vocab_size=d["vocab"],
        n_heads=f["heads"], q_lora_rank=f["q_rank"],
        kv_lora_rank=f["kv_rank"], qk_nope_head_dim=f["nope"],
        qk_rope_head_dim=f["rope"], v_head_dim=f["v"],
        rope_theta=f["theta"],
        index_n_heads=d["index_heads"], index_head_dim=d["index_dim"],
        index_topk=d["index_topk"],
        swa_n_heads=s["heads"], swa_q_lora_rank=s["q_rank"],
        swa_kv_lora_rank=s["kv_rank"], swa_qk_nope_head_dim=s["nope"],
        swa_qk_rope_head_dim=s["rope"], swa_v_head_dim=s["v"],
        swa_rope_theta=s["theta"], sliding_window=d["window"],
        lora_rescale=d["rescale"], first_dense_layers=d["first_dense"],
        dense_hidden_dim=d["dense_hidden"], moe_hidden_dim=d["moe_hidden"],
        n_routed_experts=d["routed"],
        held_experts=(d["held_first"], d["held"]),
        num_experts_per_tok=d["top_k"], n_shared_experts=d["shared"],
        routed_scaling_factor=d["route_scale"],
        norm_topk_prob=d["norm_topk"])


def preset(config: dict, **over):
    from fault_tolerant_llm_training_tpu.models import configs as mc

    return mc.LatentMoEConfig(**preset_kwargs(config), **over)


def model_class():
    from fault_tolerant_llm_training_tpu.models.latent_moe import (
        LatentMoETransformer,
    )

    return LatentMoETransformer


# ------------------------------------------------------------------- leaves
def mixer_leaves(d: dict, kind: str) -> dict:
    m, dim = d[kind], d["dim"]
    h = m["heads"]
    # An up-projection that reads a rescaled latent is drawn as if its
    # fan-in were the hidden size: ``a = sqrt(hidden / rank)`` aligns the
    # latent's variance to the hidden state's, so at lecun's 1 / rank the
    # queries and keys would come out a times too large each — attention
    # logits of deviation 5.9 on the full layers, a near-argmax softmax
    # under which bfloat16 rounding decides whole outputs (measured: logits
    # off by 2 to 3 at a deviation of 1; configuration file, ``assumed``).
    up = f"aligned:{dim}" if d["rescale"] else "dense"
    out = {
        "wq_a/kernel": ((dim, m["q_rank"]), "dense"),
        "q_norm/scale": ((m["q_rank"],), "scale"),
        "wq_b/kernel": ((m["q_rank"], h * (m["nope"] + m["rope"])), up),
        "wkv_a/kernel": ((dim, m["kv_rank"] + m["rope"]), "dense"),
        "kv_norm/scale": ((m["kv_rank"],), "scale"),
        "wkv_b/kernel": ((m["kv_rank"], h * (m["nope"] + m["v"])), up),
        "wg/kernel": ((dim, h), "dense"),
        "wo/kernel": ((h * m["v"], dim), "dense"),
    }
    if kind == "full":
        hi, di = d["index_heads"], d["index_dim"]
        out.update({
            "indexer/wq_b/kernel": ((m["q_rank"], hi * di), up),
            "indexer/wk/kernel": ((dim, di), "dense"),
            "indexer/k_norm/scale": ((di,), "scale"),
            "indexer/k_norm/bias": ((di,), "norm_bias"),
            "indexer/weights_proj/kernel": ((dim, hi), "dense"),
        })
    return out


def ffn_leaves(d: dict, layer: int) -> dict:
    dim = d["dim"]
    if layer < d["first_dense"]:
        hdn = d["dense_hidden"]
        return {"w1/kernel": ((dim, hdn), "dense"),
                "w2/kernel": ((hdn, dim), "dense"),
                "w3/kernel": ((dim, hdn), "dense")}
    hdn, e = d["moe_hidden"], d["held"]
    out = {"router/kernel": ((dim, d["routed"]), "dense"),
           "router/bias": ((d["routed"],), "router_bias"),
           "experts/w1/kernel": ((e, dim, hdn), "stacked"),
           "experts/w2/kernel": ((e, hdn, dim), "stacked"),
           "experts/w3/kernel": ((e, dim, hdn), "stacked")}
    if d["shared"]:
        sh = hdn * d["shared"]
        out.update({"shared/w1/kernel": ((dim, sh), "dense"),
                    "shared/w2/kernel": ((sh, dim), "dense"),
                    "shared/w3/kernel": ((dim, sh), "dense")})
    return out


def layer_leaves(d: dict, layer: int) -> dict:
    """path (inside block ``layer``) -> (shape, kind)."""
    out = {"attention_norm/scale": ((d["dim"],), "scale"),
           "ffn_norm/scale": ((d["dim"],), "scale")}
    for p, v in mixer_leaves(d, d["layer_types"][layer]).items():
        out["attention/" + p] = v
    for p, v in ffn_leaves(d, layer).items():
        out["feed_forward/" + p] = v
    return out


def all_leaves(d: dict) -> dict:
    out = {"tok_embeddings/embedding": ((d["vocab"], d["dim"]), "embed")}
    for i in range(d["n_layers"]):
        for p, v in layer_leaves(d, i).items():
            out[f"layers_{i}/{p}"] = v
    out["norm/scale"] = ((d["dim"],), "scale")
    out["output/kernel"] = ((d["dim"], d["vocab"]), "dense")
    return out


def draw_leaf(z, shape, kind: str):
    """This family's own kinds, from the standard-normal draw ``z``."""
    if kind == "stacked":        # (experts, fan_in, fan_out): lecun, each
        return z / math.sqrt(shape[1])
    if kind == "router_bias":    # moves near-ties of the choice, no more
        return 0.1 * z
    if kind == "norm_bias":
        return 0.05 * z
    if kind.startswith("aligned:"):     # lecun at the stated fan-in
        return z / math.sqrt(int(kind.split(":", 1)[1]))
    raise ValueError(f"dots3 draws no leaf of kind {kind!r}")


# ------------------------------------------------- the reference: equations
def rope(x, positions, theta):
    """x (S, H, D): rotate adjacent pairs by positions * theta^(-2j/D)."""
    import jax.numpy as jnp

    s, h, dd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dd, 2, dtype=jnp.float32) / dd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x.reshape(s, h, dd // 2, 2)
    a, b = xr[..., 0], xr[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(s, h, dd)


def rope_front(x, positions, theta, width):
    """RoPE on the first ``width`` values of each head of x (S, H, D)."""
    import jax.numpy as jnp

    return jnp.concatenate([rope(x[..., :width], positions, theta),
                            x[..., width:]], axis=-1)


def layernorm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale + bias


def masked_attention(q_n, q_r, k_n, k_r, v, visible):
    """Dense softmax attention in blocks of queries. q_n (S, H, dn), q_r
    (S, H, dr), k_n (S, H, dn), k_r (S, dr), v (S, H, dv); ``visible(lo)``
    gives the (Q_BLOCK, S) mask of queries [lo, lo + Q_BLOCK)."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import HIGHEST

    s, h, dn = q_n.shape
    scale = 1.0 / math.sqrt(dn + q_r.shape[-1])
    qb = min(Q_BLOCK, s)
    assert s % qb == 0, (s, qb)

    def block(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_n, lo, qb, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, lo, qb, 0)
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", qr, k_r, precision=HIGHEST))
        sc = jnp.where(visible(lo)[None], sc * scale, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, s, qb))
    return out.reshape(s, h, v.shape[-1])


def indexer_visible(w: dict, u, c_q, d: dict, mm):
    """The full layers' visible sets: ``visible(lo)`` -> (Q_BLOCK, S)."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import HIGHEST

    s = u.shape[0]
    hi, di, dr = d["index_heads"], d["index_dim"], d["full"]["rope"]
    theta, k = d["full"]["theta"], min(d["index_topk"], s)
    pos = jnp.arange(s)
    q_i = rope_front(mm(c_q, w["indexer/wq_b/kernel"]).reshape(s, hi, di),
                     pos, theta, dr)
    k_i = layernorm(mm(u, w["indexer/wk/kernel"]),
                    w["indexer/k_norm/scale"], w["indexer/k_norm/bias"],
                    INDEX_NORM_EPS)
    k_i = rope_front(k_i[:, None, :], pos, theta, dr)[:, 0]
    w_i = mm(u, w["indexer/weights_proj/kernel"]) * (hi ** -0.5 * di ** -0.5)
    qb = min(Q_BLOCK, s)

    def visible(lo):
        qi = jax.lax.dynamic_slice_in_dim(q_i, lo, qb, 0)
        wi = jax.lax.dynamic_slice_in_dim(w_i, lo, qb, 0)
        sc = jnp.einsum("qhd,kd->qhk", qi, k_i, precision=HIGHEST)
        score = jnp.einsum("qhk,qh->qk", jax.nn.relu(sc), wi,
                           precision=HIGHEST)
        causal = (lo + jnp.arange(qb))[:, None] >= pos[None, :]
        score = jnp.where(causal, score, -jnp.inf)
        _, idx = jax.lax.top_k(score, k)    # ties: the lower position
        picked = jnp.zeros((qb, s), jnp.bool_).at[
            jnp.arange(qb)[:, None], idx].set(True)
        return picked & causal

    return visible


def window_visible(s: int, window: int):
    import jax.numpy as jnp

    qb = min(Q_BLOCK, s)
    pos = jnp.arange(s)

    def visible(lo):
        t = (lo + jnp.arange(qb))[:, None]
        return (pos[None, :] <= t) & (pos[None, :] > t - window)

    return visible


def mixer(w: dict, u, d: dict, mm, kind: str):
    """One latent-attention mixer on one sequence: u (S, dim) -> (S, dim).
    ``w`` holds the mixer's leaves by their path under ``attention/``."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import rmsnorm

    m, s = d[kind], u.shape[0]
    h, dn, dr, dv, r = m["heads"], m["nope"], m["rope"], m["v"], m["kv_rank"]
    a_q = math.sqrt(d["dim"] / m["q_rank"]) if d["rescale"] else 1.0
    a_kv = math.sqrt(d["dim"] / r) if d["rescale"] else 1.0
    pos = jnp.arange(s)
    c_q = a_q * rmsnorm(mm(u, w["wq_a/kernel"]), w["q_norm/scale"],
                        d["norm_eps"])
    q = mm(c_q, w["wq_b/kernel"]).reshape(s, h, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, m["theta"])
    ckr = mm(u, w["wkv_a/kernel"])
    c_kv = a_kv * rmsnorm(ckr[:, :r], w["kv_norm/scale"], d["norm_eps"])
    k_r = rope(ckr[:, None, r:], pos, m["theta"])[:, 0]
    kv = mm(c_kv, w["wkv_b/kernel"]).reshape(s, h, dn + dv)
    visible = (indexer_visible(w, u, c_q, d, mm) if kind == "full"
               else window_visible(s, d["window"]))
    o = masked_attention(q_n, q_r, kv[..., :dn], k_r, kv[..., dn:], visible)
    gate = jax.nn.sigmoid(mm(u, w["wg/kernel"]))
    return mm((o * gate[..., None]).reshape(s, h * dv), w["wo/kernel"])


def swiglu(u, w1, w2, w3, mm):
    import jax

    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def expert_layer(w: dict, u, d: dict, mm, held_first=None, shared=True):
    """The expert layer's share on one sequence: the held experts' part of
    the routed sum (``w``'s stacked leaves are experts ``held_first`` ...)
    plus, with ``shared``, the shared expert. Every held expert runs over
    every token and its weight is zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    first = d["held_first"] if held_first is None else held_first
    score = jax.nn.sigmoid(mm(u, w["router/kernel"]))           # (S, E)
    _, choice = jax.lax.top_k(score + w["router/bias"], d["top_k"])
    weight = jnp.take_along_axis(score, choice, axis=-1)
    if d["norm_topk"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * d["route_scale"]
    def one(out, held):     # a held expert at a time, over every token
        e, w1, w2, w3 = held
        w_e = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1)
        return out + w_e[:, None] * swiglu(u, w1, w2, w3, mm), None

    n_held = w["experts/w1/kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n_held), w["experts/w1/kernel"], w["experts/w2/kernel"],
        w["experts/w3/kernel"]))
    if shared and d["shared"]:
        out = out + swiglu(u, w["shared/w1/kernel"], w["shared/w2/kernel"],
                           w["shared/w3/kernel"], mm)
    return out


def sub(w: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in w.items() if p.startswith(prefix)}


def block(w: dict, x, d: dict, mm, layer: int):
    """Block ``layer`` on one sequence: x (S, dim) -> (S, dim)."""
    from perfbench.lib.reference import rmsnorm

    u = rmsnorm(x, w["attention_norm/scale"], d["norm_eps"])
    x = x + mixer(sub(w, "attention/"), u, d, mm, d["layer_types"][layer])
    u = rmsnorm(x, w["ffn_norm/scale"], d["norm_eps"])
    f = sub(w, "feed_forward/")
    if layer < d["first_dense"]:
        return x + swiglu(u, f["w1/kernel"], f["w2/kernel"], f["w3/kernel"],
                          mm)
    return x + expert_layer(f, u, d, mm)


def head_logits(x, norm_scale, w_out, d, mm):
    from perfbench.lib.reference import rmsnorm

    return mm(rmsnorm(x, norm_scale, d["norm_eps"]), w_out)


# --------------------------------------------------- the reference: weights
def layer_weights(key, d: dict, i: int, dtype) -> dict:
    import jax.numpy as jnp

    from perfbench.lib import weights as W

    return {p: W.make_leaf(key, f"layers_{i}/{p}", shape, kind, dtype,
                           "dots3").astype(jnp.float32)
            for p, (shape, kind) in layer_leaves(d, i).items()}


def top_weights(key, d: dict, dtype) -> dict:
    import jax.numpy as jnp

    from perfbench.lib import weights as W

    leaves = all_leaves(d)
    return {p: W.make_leaf(key, p, *leaves[p], dtype).astype(jnp.float32)
            for p in ("tok_embeddings/embedding", "norm/scale",
                      "output/kernel")}


def _pad_to_blocks(n: int) -> int:
    """Rows to add so that a sequence over one query block is whole blocks."""
    return (-n) % Q_BLOCK if n > Q_BLOCK else 0


# --------------------------------------------------- the reference: serving
def batch_logits(key, d: dict, seqs, wanted, mm, dtype) -> list:
    """Logits of several sequences at each one's ``wanted`` positions,
    layer by layer (one layer's weights live at a time). A sequence longer
    than a query block is padded to whole blocks (causal: the padding
    follows every wanted position)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    top = top_weights(key, d, dtype)
    xs = []
    for s in seqs:
        s = np.asarray(s, np.int32)
        s = np.concatenate([s, np.zeros((_pad_to_blocks(len(s)),),
                                        np.int32)])
        xs.append(top["tok_embeddings/embedding"][jnp.asarray(s)])
    for i in range(d["n_layers"]):
        blk = jax.jit(functools.partial(block, d=d, mm=mm, layer=i))
        w = layer_weights(key, d, i, dtype)
        xs = [blk(w, x) for x in xs]
        del w
    head = jax.jit(functools.partial(head_logits, d=d, mm=mm))
    return [np.asarray(head(x[jnp.asarray(pos)], top["norm/scale"],
                            top["output/kernel"]))
            for x, pos in zip(xs, wanted)]


def forward_logits(key, d: dict, tokens, positions_wanted, mm, dtype):
    """Logits (len(positions_wanted), vocab) of one sequence."""
    return batch_logits(key, d, [tokens], [positions_wanted], mm, dtype)[0]


# -------------------------------------------------- the reference: training
def forward_from(params: dict, tokens, d: dict, mm):
    """All positions' logits of one sequence from a flat {path: leaf}."""
    import jax.numpy as jnp

    x = params["tok_embeddings/embedding"][jnp.asarray(tokens)]
    for i in range(d["n_layers"]):
        x = block(sub(params, f"layers_{i}/"), x, d, mm, i)
    return head_logits(x, params["norm/scale"], params["output/kernel"], d,
                       mm)


class LossAndGrads:
    """``reference.TrainReference``'s family half, from the reference's own
    forward by ``jax.value_and_grad``: the mean next-token loss of a batch
    and its gradient by leaf. Whole-model autodiff: for sizes a test holds,
    no training cell runs this family."""

    def __init__(self, d: dict, mm):
        import jax
        import jax.numpy as jnp

        def loss(params, inputs, labels):
            total = 0.0
            for r in range(inputs.shape[0]):
                logits = forward_from(params, inputs[r], d, mm)
                lse = jax.nn.logsumexp(logits, axis=-1)
                picked = jnp.take_along_axis(
                    logits, labels[r][:, None], axis=-1)[:, 0]
                total = total + jnp.sum(lse - picked)
            return total / (inputs.shape[0] * inputs.shape[1])

        self._vg = jax.jit(jax.value_and_grad(loss))

    def __call__(self, params: dict, inputs, labels):
        import jax.numpy as jnp

        loss, grads = self._vg(params, jnp.asarray(inputs),
                               jnp.asarray(labels))
        return float(loss), grads


# --------------------------------------------------------------- the counts
def mixer_params(d: dict, kind: str) -> int:
    return sum(math.prod(s) for p, (s, k) in mixer_leaves(d, kind).items()
               if p.endswith("/kernel"))


def expert_params(d: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * d["dim"] * d["moe_hidden"]


def active_matmul_params(d: dict) -> float:
    """Parameters a token's forward multiplies by on this chip: both
    mixers' projections and the indexer's, the dense FFN, the router, the
    shared expert, the expected chosen ∩ held experts, the head."""
    total = 0.0
    for i, kind in enumerate(d["layer_types"]):
        total += mixer_params(d, kind)
        if i < d["first_dense"]:
            total += 3 * d["dim"] * d["dense_hidden"]
        else:
            total += (d["dim"] * d["routed"]
                      + d["shared"] * expert_params(d)
                      + d["top_k"] * d["held"] / d["routed"]
                      * expert_params(d))
    return total + d["dim"] * d["vocab"]


def _attended_flops_per_token(d: dict, ctx: float) -> float:
    """Indexer over every visible position, attention over at most
    ``index_topk`` / the window of them, a token at context ``ctx``."""
    out = 0.0
    for kind in d["layer_types"]:
        m = d[kind]
        pair = 2.0 * m["heads"] * (m["nope"] + m["rope"] + m["v"])
        if kind == "full":
            out += 2.0 * d["index_heads"] * d["index_dim"] * ctx
            out += pair * min(ctx, d["index_topk"])
        else:
            out += pair * min(ctx, d["window"])
    return out


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) at the mean context of a causal
    sequence; no training cell runs this family."""
    return 3.0 * (2.0 * active_matmul_params(d)
                  + _attended_flops_per_token(d, (seq_len + 1) / 2.0))


def serve_flops(d: dict, new_tokens: int, ctx_token_pairs: int) -> float:
    """Forward FLOPs of serving ``new_tokens`` whose contexts sum to
    ``ctx_token_pairs``: the sets a token attends to are capped (top-k,
    window), so the attention term is taken at the mean context."""
    if not new_tokens:
        return 0.0
    return new_tokens * (2.0 * active_matmul_params(d)
                         + _attended_flops_per_token(
                             d, ctx_token_pairs / new_tokens))


def moe_expert_bytes(d: dict, touched: int, itemsize: int = 2) -> float:
    """The weights of the held experts a round touched, read once each
    (``touched`` summed over expert layers)."""
    return float(touched) * expert_params(d) * itemsize


def moe_expert_flops(d: dict, pairs: int) -> float:
    """The (token, held expert) pairs' three matmuls."""
    return 2.0 * float(pairs) * expert_params(d)


def latent_read_bytes(d: dict, index_keys: int, latent_rows: int,
                      window_rows: int, itemsize: int = 2) -> float:
    """What the reads need whatever implements them: each index key
    scanned, each selected latent row and each window row, once."""
    f, s = d["full"], d["sliding"]
    return float(itemsize) * (
        index_keys * d["index_dim"]
        + latent_rows * (f["kv_rank"] + f["rope"])
        + window_rows * (s["kv_rank"] + s["rope"]))
