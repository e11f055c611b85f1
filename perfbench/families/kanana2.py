"""The kanana2 family (``kanana-2-30b-a3b``, ``model_type`` ``deepseek_v3``):
a decoder of latent-attention layers with no query latent, one leading
dense SwiGLU layer and sigmoid-routed experts after it, of which a chip
holds a stated range, plus two shared experts. Every layer attends densely
and causally (no indexer, no window, no head gate, no latent rescale).

The interface is ``families/llama.py``'s (``PERF.md`` section 3). Nothing
here needs JAX at import, and nothing here imports the program.

**Equations** (u a sub-layer's RMS-normed input; pre-norm residual blocks,
final RMSNorm, untied head, no embedding scale; H heads, widths d_n, d_r,
d_v, latent r):

- attention: ``[q_n | q_r]_h = u W_q`` (no query latent), RoPE on q_r;
  ``[c | k_r] = u W_kva``, ``c_kv = RMSNorm(c)``, RoPE on the one k_r all
  heads share; ``[k_n | v]_h = c_kv W_kvb``; ``a = softmax_{s <= t}((q_n .
  k_n + q_r . k_r) / sqrt(d_n + d_r))``; ``out = concat_h(a v_h) W_o``.
- the expert layer: ``s = sigmoid(u W_r)`` over all published experts; the
  chosen are the top ``num_experts_per_tok`` of ``s + b`` (``noaux_tc``
  with ``n_group = topk_group = 1``: nothing group-limited); ``w_e = s_e /
  sum_chosen s`` x ``routed_scaling_factor``; the sum runs over chosen ∩
  held (this chip's share), plus the shared SwiGLU of width ``n_shared x
  moe_intermediate_size``.

Departures from the published files are the configuration file's
``assumed`` (adjacent-pair RoPE, which ``rope_interleave: true`` states;
the router bias a drawn leaf).

**Counts**: one multiply-add = 2 FLOPs; the embedding is a lookup; a
routed expert counts at its expected share ``k x held / routed``; causal
attention counts the lower triangle; a backward is two matmuls for each of
the forward's (3x forward), recompute not counted.
"""

import functools
import math

Q_BLOCK = 256           # queries a block of the reference's attention
# Kanana-2-30B-A3B's published widths (the catalog row's ``config``): a
# configuration at the published scale may cut depth, the experts held and
# the vocabulary, never one of these
WIDTHS = {"dim": 2048, "heads": 32, "nope": 128, "rope": 64, "v": 128,
          "kv_rank": 512, "dense_hidden": 6144, "moe_hidden": 768,
          "top_k": 6, "shared": 2, "routed": 128}


# -------------------------------------------------------------------- sizes
def dims_of(config: dict) -> dict:
    published = config.get("published", {})
    program = config.get("program", {})
    return {
        "dim": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "vocab": config["vocab_size"],
        "norm_eps": float(config["rms_norm_eps"]),
        "q_rank": config["q_lora_rank"],
        "heads": config["num_attention_heads"],
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
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
        "groups": (config.get("n_group", 1), config.get("topk_group", 1)),
        # "published": the widths are the model's (a cell); "tiny": a CPU
        # stand-in at sizes of its own
        "scale": program.get("scale", "published"),
    }


def check_dims(d: dict) -> list:
    bad = []
    if d["q_rank"] is not None:
        bad.append("a query latent (q_lora_rank): this family has none")
    if d["held_first"] + d["held"] > d["routed"]:
        bad.append(f"held experts {d['held_first']}+{d['held']} past the "
                   f"{d['routed']} routed")
    if d["top_k"] > d["routed"]:
        bad.append("num_experts_per_tok over the routed experts")
    if d["rope"] % 2:
        bad.append(f"odd rope width {d['rope']}")
    if d["first_dense"] > d["n_layers"]:
        bad.append("first_k_dense_replace over the layers")
    if tuple(d["groups"]) != (1, 1):
        bad.append(f"group-limited routing {d['groups']} is not written")
    if d["scale"] == "published":
        bad += [f"width {k} is {d[k]}, published {v}: a width is never cut"
                for k, v in WIDTHS.items() if d[k] != v]
    return bad


# ------------------------------------------------------- the program's side
def preset_kwargs(config: dict) -> dict:
    d = dims_of(config)
    return dict(
        dim=d["dim"], n_layers=d["n_layers"],
        layer_types=("full",) * d["n_layers"], norm_eps=d["norm_eps"],
        vocab_size=d["vocab"], n_heads=d["heads"], q_lora_rank=None,
        kv_lora_rank=d["kv_rank"], qk_nope_head_dim=d["nope"],
        qk_rope_head_dim=d["rope"], v_head_dim=d["v"], rope_theta=d["theta"],
        index_n_heads=0, lora_rescale=False, head_gate=False,
        first_dense_layers=d["first_dense"],
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
def mixer_leaves(d: dict) -> dict:
    dim, h = d["dim"], d["heads"]
    return {
        "wq/kernel": ((dim, h * (d["nope"] + d["rope"])), "dense"),
        "wkv_a/kernel": ((dim, d["kv_rank"] + d["rope"]), "dense"),
        "kv_norm/scale": ((d["kv_rank"],), "scale"),
        "wkv_b/kernel": ((d["kv_rank"], h * (d["nope"] + d["v"])), "dense"),
        "wo/kernel": ((h * d["v"], dim), "dense"),
    }


def ffn_leaves(d: dict, layer: int) -> dict:
    dim = d["dim"]
    if layer < d["first_dense"]:
        hdn = d["dense_hidden"]
        return {"w1/kernel": ((dim, hdn), "dense"),
                "w2/kernel": ((hdn, dim), "dense"),
                "w3/kernel": ((dim, hdn), "dense")}
    hdn, e, sh = d["moe_hidden"], d["held"], d["moe_hidden"] * d["shared"]
    return {"router/kernel": ((dim, d["routed"]), "dense"),
            "router/bias": ((d["routed"],), "router_bias"),
            "experts/w1/kernel": ((e, dim, hdn), "stacked"),
            "experts/w2/kernel": ((e, hdn, dim), "stacked"),
            "experts/w3/kernel": ((e, dim, hdn), "stacked"),
            "shared/w1/kernel": ((dim, sh), "dense"),
            "shared/w2/kernel": ((sh, dim), "dense"),
            "shared/w3/kernel": ((dim, sh), "dense")}


def layer_leaves(d: dict, layer: int) -> dict:
    """path (inside block ``layer``) -> (shape, kind)."""
    out = {"attention_norm/scale": ((d["dim"],), "scale"),
           "ffn_norm/scale": ((d["dim"],), "scale")}
    out.update({"attention/" + p: v for p, v in mixer_leaves(d).items()})
    out.update({"feed_forward/" + p: v
                for p, v in ffn_leaves(d, layer).items()})
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
    raise ValueError(f"kanana2 draws no leaf of kind {kind!r}")


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


def causal_attention(q_n, q_r, k_n, k_r, v):
    """Dense causal softmax attention in blocks of queries, each block
    recomputed in the backward (``jax.checkpoint``): q_n (S, H, dn), q_r
    (S, H, dr), k_n (S, H, dn), k_r (S, dr), v (S, H, dv)."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib.reference import HIGHEST

    s, h, dn = q_n.shape
    scale = 1.0 / math.sqrt(dn + q_r.shape[-1])
    qb = min(Q_BLOCK, s)
    assert s % qb == 0, (s, qb)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_n, lo, qb, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, lo, qb, 0)
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_n, precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", qr, k_r, precision=HIGHEST))
        keep = (lo + jnp.arange(qb))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(keep[None], sc * scale, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, s, qb))
    return out.reshape(s, h, v.shape[-1])


def mixer(w: dict, u, d: dict, mm):
    """One attention mixer on one sequence: u (S, dim) -> (S, dim). ``w``
    holds its leaves by their path under ``attention/``."""
    import jax.numpy as jnp

    from perfbench.lib.reference import rmsnorm

    s = u.shape[0]
    h, dn, dr, dv, r = d["heads"], d["nope"], d["rope"], d["v"], d["kv_rank"]
    pos = jnp.arange(s)
    q = mm(u, w["wq/kernel"]).reshape(s, h, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, d["theta"])
    ckr = mm(u, w["wkv_a/kernel"])
    c_kv = rmsnorm(ckr[:, :r], w["kv_norm/scale"], d["norm_eps"])
    k_r = rope(ckr[:, None, r:], pos, d["theta"])[:, 0]
    kv = mm(c_kv, w["wkv_b/kernel"]).reshape(s, h, dn + dv)
    o = causal_attention(q_n, q_r, kv[..., :dn], k_r, kv[..., dn:])
    return mm(o.reshape(s, h * dv), w["wo/kernel"])


def swiglu(u, w1, w2, w3, mm):
    import jax

    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


def route(w: dict, u, d: dict, mm):
    """(choice (S, k) expert ids, weight (S, k)) of the router."""
    import jax
    import jax.numpy as jnp

    score = jax.nn.sigmoid(mm(u, w["router/kernel"]))           # (S, E)
    _, choice = jax.lax.top_k(score + w["router/bias"], d["top_k"])
    weight = jnp.take_along_axis(score, choice, axis=-1)
    if d["norm_topk"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return choice, weight * d["route_scale"]


def expert_layer(w: dict, u, d: dict, mm, held_first=None, shared=True):
    """The expert layer's share on one sequence: the held experts' part of
    the routed sum (``w``'s stacked leaves are experts ``held_first`` ...)
    plus, with ``shared``, the shared experts. Every held expert runs over
    every token (a ``lax.scan``, each step recomputed in the backward) and
    its weight is zero where it was not chosen."""
    import jax
    import jax.numpy as jnp

    first = d["held_first"] if held_first is None else held_first
    choice, weight = route(w, u, d, mm)

    @jax.checkpoint
    def one(out, held):
        e, w1, w2, w3 = held
        w_e = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1)
        return out + w_e[:, None] * swiglu(u, w1, w2, w3, mm), None

    n_held = w["experts/w1/kernel"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n_held), w["experts/w1/kernel"], w["experts/w2/kernel"],
        w["experts/w3/kernel"]))
    if shared:
        out = out + swiglu(u, w["shared/w1/kernel"], w["shared/w2/kernel"],
                           w["shared/w3/kernel"], mm)
    return out


def held_pairs(w: dict, u, d: dict, mm) -> int:
    """(token, held expert) pairs of one expert layer on one sequence: the
    reference's own count of what the program's ``moe_pairs`` counts."""
    import jax.numpy as jnp

    choice, _ = route(w, u, d, mm)
    local = choice - d["held_first"]
    return int(jnp.sum((local >= 0) & (local < d["held"])))


def sub(w: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in w.items() if p.startswith(prefix)}


def block(w: dict, x, d: dict, mm, layer: int):
    """Block ``layer`` on one sequence: x (S, dim) -> (S, dim)."""
    from perfbench.lib.reference import rmsnorm

    u = rmsnorm(x, w["attention_norm/scale"], d["norm_eps"])
    x = x + mixer(sub(w, "attention/"), u, d, mm)
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
                           "kanana2").astype(jnp.float32)
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


# ---------------------------------------------------- the reference: logits
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
def _row_nll_sum(x, norm_scale, w_out, labels, d, mm):
    import jax
    import jax.numpy as jnp

    logits = head_logits(x, norm_scale, w_out, d, mm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


class LossAndGrads:
    """``reference.TrainReference``'s family half: the mean next-token loss
    of a batch and its gradient by leaf, ``jax.value_and_grad`` of the
    equations above under ``jax.default_matmul_precision("highest")``,
    taken layer by layer and row by row (a ``vjp`` per block per row) so
    that float32 parameters and gradients of the cell's 687.5 M parameters
    fit one 16 GB chip beside one block's activations. All labels are
    valid in the benchmark's corpus (no padding)."""

    def __init__(self, d: dict, mm):
        import jax

        self.d = d
        self._blk = {}
        self._blk_vjp = {}
        for kind in {min(i, d["first_dense"]) for i in range(d["n_layers"])}:
            f = functools.partial(block, d=d, mm=mm, layer=kind)
            self._blk[kind] = jax.jit(f)
            self._blk_vjp[kind] = jax.jit(
                lambda w, x, g, f=f: jax.vjp(f, w, x)[1](g))
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(_row_nll_sum, d=d, mm=mm), argnums=(0, 1, 2)))

    def _kind(self, i: int) -> int:
        """Block i's program: the dense one, or the expert one (equal
        shapes from ``first_dense`` on)."""
        return min(i, self.d["first_dense"])

    @staticmethod
    def layer(params: dict, i: int) -> dict:
        return sub(params, f"layers_{i}/")

    def __call__(self, params: dict, inputs, labels):
        import jax
        import jax.numpy as jnp

        with jax.default_matmul_precision("highest"):
            return self._run(params, inputs, labels, jnp)

    def _run(self, params, inputs, labels, jnp):
        d = self.d
        b, s = inputs.shape
        n = float(b * s)
        emb = params["tok_embeddings/embedding"]
        xs = [[emb[jnp.asarray(inputs[r])]] for r in range(b)]
        for i in range(d["n_layers"]):
            w, f = self.layer(params, i), self._blk[self._kind(i)]
            for r in range(b):
                xs[r].append(f(w, xs[r][-1]))
        grads, loss, dxs = {}, 0.0, []
        for r in range(b):
            nll, (dx, dscale, dwout) = self._head(
                xs[r].pop(), params["norm/scale"], params["output/kernel"],
                jnp.asarray(labels[r]))
            loss += float(nll) / n
            dxs.append(dx / n)
            for p, g in (("norm/scale", dscale), ("output/kernel", dwout)):
                grads[p] = g / n if p not in grads else grads[p] + g / n
        for i in reversed(range(d["n_layers"])):
            w, f = self.layer(params, i), self._blk_vjp[self._kind(i)]
            for r in range(b):
                dw, dxs[r] = f(w, xs[r].pop(), dxs[r])
                for p, g in dw.items():
                    full = f"layers_{i}/{p}"
                    grads[full] = g if full not in grads else grads[full] + g
        demb = jnp.zeros_like(emb)
        for r in range(b):
            demb = demb.at[jnp.asarray(inputs[r])].add(dxs[r])
        grads["tok_embeddings/embedding"] = demb
        return loss, grads


# --------------------------------------------------------------- the counts
def mixer_params(d: dict) -> int:
    return sum(math.prod(s) for p, (s, k) in mixer_leaves(d).items()
               if p.endswith("/kernel"))


def expert_params(d: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * d["dim"] * d["moe_hidden"]


def active_matmul_params(d: dict) -> float:
    """Parameters a token's forward multiplies by on this chip: the
    attention projections, the dense FFN, the router, the shared experts,
    the expected chosen ∩ held experts, the head."""
    total = d["dim"] * d["vocab"]
    for i in range(d["n_layers"]):
        total += mixer_params(d)
        if i < d["first_dense"]:
            total += 3 * d["dim"] * d["dense_hidden"]
        else:
            total += (d["dim"] * d["routed"] + d["shared"] * expert_params(d)
                      + d["top_k"] * d["held"] / d["routed"]
                      * expert_params(d))
    return float(total)


def attn_flops_fwd(d: dict, batch: int, seq_len: int) -> float:
    """Scores (width d_n + d_r) and values (d_v) of causal attention over
    the lower triangle, all layers, ``batch`` rows of ``seq_len``."""
    pair = 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"])
    return d["n_layers"] * batch * pair * seq_len * (seq_len + 1) / 2.0


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward + backward (3x forward), causal attention, no recompute."""
    return 3.0 * (2.0 * active_matmul_params(d)
                  + attn_flops_fwd(d, 1, seq_len) / seq_len)


def serve_flops(d: dict, new_tokens: int, ctx_token_pairs: int) -> float:
    """Forward FLOPs of ``new_tokens`` whose contexts sum to
    ``ctx_token_pairs`` (no cell serves this family)."""
    pair = 2.0 * d["heads"] * (d["nope"] + d["rope"] + d["v"])
    return (2.0 * active_matmul_params(d) * new_tokens
            + d["n_layers"] * pair * ctx_token_pairs)


def flash_attn_flops(d: dict, batch: int, seq_len: int) -> float:
    """The attention kernels of one step, forward + backward (the backward
    two matmuls for each of the forward's; the kernels' own recompute of
    P not counted)."""
    return 3.0 * attn_flops_fwd(d, batch, seq_len)


def flash_attn_bytes(d: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> float:
    """Forward reads q, k (192 wide) and v and writes o (128 wide);
    backward reads q, k, v, o, do and writes dq, dk, dv. Once each."""
    rows = batch * seq_len * d["heads"] * itemsize
    qk, v = rows * (d["nope"] + d["rope"]), rows * d["v"]
    fwd = 2 * qk + 2 * v
    bwd = 4 * qk + 4 * v
    return float(d["n_layers"] * (fwd + bwd))


def moe_train_expert_flops(d: dict, pairs: int) -> float:
    """The three matmuls of each (token, held expert) pair, forward and
    backward (3x forward)."""
    return 3.0 * 2.0 * float(pairs) * expert_params(d)


def moe_train_expert_bytes(d: dict, touched: int, itemsize: int = 2) -> float:
    """The weights of each held expert a step touched (``touched`` summed
    over expert layers): read by the forward and by the backward, its
    gradient written once."""
    return 3.0 * float(touched) * expert_params(d) * itemsize
