"""The plain reference's shared half: float32 ``jax.numpy``, matmuls at
``highest`` precision, no kernels, no cache, no batching tricks. It imports
nothing of the program and takes nothing the program has made: its weights
come from ``weights.make_leaf`` and the seed.

A model's equations — its block, its forward pass, its loss and gradients —
are its family's (``perfbench/families/<family>.py``). Here is what no
family owns: the matmul every projection goes through, the norm, and the
optimizer's half of a training step (the clip, AdamW, the change each leaf
made), which the program states for every model alike.

``mm`` is the matmul every projection goes through. The *control* swaps in
:func:`mm_int8` — the nearest precision below the bfloat16 the
configurations state, the W8A8 step that would tempt a later PR.
"""

import math

import jax
import jax.numpy as jnp

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


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


class TrainReference:
    """Float32 AdamW training of the whole model, one optimizer step at a
    time, with the program's hyper-parameters (torch AdamW defaults, global
    norm clip with the 1e-6 in the denominator, constant or warm-up rate).
    All labels are valid in the benchmark's corpus (no padding)."""

    def __init__(self, key, d: dict, lr: float, warmup: int,
                 clip: float = 1.0, mm=mm_f32, dtype=jnp.bfloat16):
        self.d, self.mm, self.key, self.dtype = d, mm, key, dtype
        self.lr, self.warmup, self.clip = lr, warmup, clip
        self.params = {p: W.make_leaf(key, p, shape, kind, dtype,
                                      d["family"]).astype(jnp.float32)
                       for p, (shape, kind) in W.all_leaves(d).items()}
        self.count = 0
        self.prev_grads = []  # clipped grads of earlier steps, on the host
        self._loss_and_grads = W.family_of(d).LossAndGrads(d, mm)

    def loss_and_grads(self, inputs, labels):
        """The family's half: (mean loss, {leaf path: gradient})."""
        return self._loss_and_grads(self.params, inputs, labels)

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
            p0 = W.make_leaf(self.key, p, shape, kind, self.dtype,
                             self.d["family"]).astype(jnp.float32)
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
