"""The plain reference of the Llama family of equations (Mistral-7B,
InternLM2): float32 ``jax.numpy``, matmuls at ``highest`` precision, no
kernels, no cache, no batching tricks. It imports nothing of the program
and takes nothing the program has made: its weights come from
``weights.make_leaf`` and the seed.

Departures from the published descriptions, each noted in the configuration
files under ``assumed``: RoPE rotates adjacent pairs (the original
Llama/Mistral formulation; the HF port's half-split form is the same
equations under a fixed permutation of q/k columns).

``mm`` is the matmul every projection goes through. The *control* swaps in
:func:`mm_int8` — the nearest precision below the bfloat16 the
configurations state, the W8A8 step that would tempt a later PR.

Memory: the training step runs layer by layer and row by row (a ``vjp``
per block per row), so that float32 parameters and gradients of a 1.1 B
model fit one 16 GB chip beside one block's activations.
"""

import functools
import math

import jax
import jax.numpy as jnp

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 1024  # queries per attention block


def mm_f32(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s), s


@jax.custom_vjp
def mm_int8(x, w):
    """Symmetric int8 x int8 (per-token activations, per-channel weights),
    integer values carried in float32."""
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * sx * sw


def _mm_int8_fwd(x, w):
    return mm_int8(x, w), (x, w)


def _mm_int8_bwd(res, g):
    x, w = res
    gq_r, sg_r = _q8(g, -1)
    wq, sw = _q8(w, 1)
    dx = jnp.matmul(gq_r, wq.T, precision=HIGHEST) * sg_r * sw.T
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    xq, sx = _q8(x2, 0)
    gq_c, sg_c = _q8(g2, 0)
    dw = jnp.matmul(xq.T, gq_c, precision=HIGHEST) * sx.T * sg_c
    return dx, dw


mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)

MATMULS = {"float32": mm_f32, "int8": mm_int8}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, positions, theta):
    """x (S, H, D), positions (S,): rotate adjacent pairs (x[2j], x[2j+1])
    by positions * theta^(-2j/D)."""
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
    return mm(rmsnorm(x, norm_scale, d["norm_eps"]), w_out)


# ------------------------------------------------------------------ weights
def layer_weights(key, d: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Block i's weights, made from the seed in the served type and upcast."""
    return {p: W.make_leaf(key, f"layers_{i}/{p}", shape, kind,
                           dtype).astype(jnp.float32)
            for p, (shape, kind) in W.layer_leaves(d).items()}


def top_weights(key, d: dict, dtype=jnp.bfloat16) -> dict:
    leaves = W.all_leaves(d)
    return {p: W.make_leaf(key, p, *leaves[p], dtype).astype(jnp.float32)
            for p in ("tok_embeddings/embedding", "norm/scale",
                      "output/kernel")}


# ------------------------------------------------------------------ serving
def forward_logits(key, d: dict, tokens, positions_wanted, mm=mm_f32,
                   dtype=jnp.bfloat16):
    """Logits (len(positions_wanted), vocab) of one sequence ``tokens``
    (S,) at the given positions, layer by layer."""
    top = top_weights(key, d, dtype)
    x = top["tok_embeddings/embedding"][jnp.asarray(tokens)]
    blk = jax.jit(functools.partial(block, d=d, mm=mm))
    for i in range(d["n_layers"]):
        x = blk(layer_weights(key, d, i, dtype), x)
    x = x[jnp.asarray(positions_wanted)]
    return jax.jit(functools.partial(head_logits, d=d, mm=mm))(
        x, top["norm/scale"], top["output/kernel"])


# ----------------------------------------------------------------- training
def _row_nll_sum(x, norm_scale, w_out, labels, d, mm):
    logits = head_logits(x, norm_scale, w_out, d, mm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


class TrainReference:
    """Float32 AdamW training of the whole model, one optimizer step at a
    time, with the program's hyper-parameters (torch AdamW defaults, global
    norm clip with the 1e-6 in the denominator, constant or warm-up rate).
    All labels are valid in the benchmark's corpus (no padding)."""

    def __init__(self, key, d: dict, lr: float, warmup: int,
                 clip: float = 1.0, mm=mm_f32, dtype=jnp.bfloat16):
        self.d, self.mm, self.key, self.dtype = d, mm, key, dtype
        self.lr, self.warmup, self.clip = lr, warmup, clip
        self.params = {p: W.make_leaf(key, p, shape, kind, dtype).astype(
            jnp.float32) for p, (shape, kind) in W.all_leaves(d).items()}
        self.count = 0
        self.prev_grads = []  # clipped grads of earlier steps, on the host
        self._blk = jax.jit(functools.partial(block, d=d, mm=mm))

        def blk_vjp(w, x, g):
            _, vjp = jax.vjp(lambda w_, x_: block(w_, x_, d, mm), w, x)
            return vjp(g)

        self._blk_vjp = jax.jit(blk_vjp)
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(_row_nll_sum, d=d, mm=mm), argnums=(0, 1, 2)))

    def layer(self, i: int) -> dict:
        pre = f"layers_{i}/"
        return {p[len(pre):]: v for p, v in self.params.items()
                if p.startswith(pre)}

    def loss_and_grads(self, inputs, labels):
        d = self.d
        b, s = inputs.shape
        n = float(b * s)
        emb = self.params["tok_embeddings/embedding"]
        xs = [[emb[jnp.asarray(inputs[r])]] for r in range(b)]
        for i in range(d["n_layers"]):
            w = self.layer(i)
            for r in range(b):
                xs[r].append(self._blk(w, xs[r][-1]))
        grads = {}
        loss = 0.0
        dxs = []
        for r in range(b):
            nll, (dx, dscale, dwout) = self._head(
                xs[r][-1], self.params["norm/scale"],
                self.params["output/kernel"], jnp.asarray(labels[r]))
            loss += float(nll) / n
            dxs.append(dx / n)
            for p, g in (("norm/scale", dscale), ("output/kernel", dwout)):
                grads[p] = g / n if p not in grads else grads[p] + g / n
            xs[r].pop()
        for i in reversed(range(d["n_layers"])):
            w = self.layer(i)
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

    def step(self, inputs, labels) -> dict:
        """One optimizer step; returns the step's loss and the per-leaf
        norms of the gradient as the optimizer gets it (after the clip)."""
        loss, grads = self.loss_and_grads(inputs, labels)
        total = math.sqrt(sum(float(jnp.sum(g * g)) for g in grads.values()))
        coef = min(self.clip / (total + 1e-6), 1.0)
        t = self.count + 1
        lr = self.lr * min((self.count + 1.0) / (self.warmup + 1.0), 1.0)
        b1, b2, eps, wd = 0.9, 0.999, 1e-8, 0.01
        norms = {}
        import numpy as np

        for p in list(grads):
            g = grads.pop(p) * coef
            norms[p] = float(jnp.sqrt(jnp.sum(g * g)))
            mu, nu = (1 - b1) * g, (1 - b2) * g * g
            for age, old in enumerate(reversed(self.prev_grads), start=1):
                og = jnp.asarray(old[p])
                mu = mu + (1 - b1) * (b1 ** age) * og
                nu = nu + (1 - b2) * (b2 ** age) * og * og
            mhat, vhat = mu / (1 - b1 ** t), nu / (1 - b2 ** t)
            w = self.params[p]
            self.params[p] = w - lr * (mhat / (jnp.sqrt(vhat) + eps)
                                       + wd * w)
            grads[p] = np.asarray(g) if self.keep_grads else None
        if self.keep_grads:
            self.prev_grads.append(grads)
        self.count = t
        return {"loss": loss, "grad_norms": norms, "grad_norm_raw": total}

    keep_grads = True

    def change_norms(self) -> tuple:
        """Per leaf: the norm of its change since the seed's values, and
        the share of that change (squared) that sits on elements whose mean
        step is under half a bfloat16 ulp of the element's own value —
        stored in bfloat16, as the configuration states, such an element
        rounds back to where it was after every step. 0 everywhere for
        float32 storage."""
        norms, stuck = {}, {}
        steps = max(self.count, 1)

        @jax.jit
        def one(p, p0):
            delta = p - p0
            sq = jnp.square(delta)
            a = jnp.maximum(jnp.abs(p0), jnp.finfo(jnp.float32).tiny)
            half_ulp = jnp.exp2(jnp.floor(jnp.log2(a)) - 8.0)
            held = jnp.abs(delta) / steps < half_ulp
            return jnp.sum(sq), jnp.sum(jnp.where(held, sq, 0.0))

        for p, (shape, kind) in W.all_leaves(self.d).items():
            p0 = W.make_leaf(self.key, p, shape, kind, self.dtype).astype(
                jnp.float32)
            total, held = (float(x) for x in one(self.params[p], p0))
            norms[p] = math.sqrt(total)
            stuck[p] = (held / total if total > 0 and
                        self.dtype == jnp.bfloat16 else 0.0)
        return norms, stuck


def run_train_reference(key, d, lr, warmup, batches, mm=mm_f32,
                        dtype=jnp.bfloat16) -> dict:
    """Follow ``batches`` = [(inputs, labels), ...] optimizer steps."""
    ref = TrainReference(key, d, lr, warmup, mm=mm, dtype=dtype)
    out = {"loss": [], "grad_norm_raw": []}
    for i, (inputs, labels) in enumerate(batches):
        ref.keep_grads = i < len(batches) - 1
        r = ref.step(inputs, labels)
        out["loss"].append(r["loss"])
        out["grad_norm_raw"].append(r["grad_norm_raw"])
        if i == 0:
            out["grad_norms"] = r["grad_norms"]
    out["change_norms"], out["stuck_share"] = ref.change_norms()
    return out
