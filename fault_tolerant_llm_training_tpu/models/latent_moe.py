"""A decoder with a per-layer schedule of mixer and FFN kinds
(``LatentMoEConfig``): latent attention of two widths — full layers behind a
learned top-k indexer, sliding layers behind a window — a head-wise output
gate on both, a dense SwiGLU in the leading layers and a sigmoid-routed
expert layer, told which experts it holds, in the rest.

The equations, residual and pre-norm as the Llama class
(``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, final RMSNorm,
untied head), with u a mixer's normed input:

- latent attention: ``c_q = a_q RMSNorm(u W_qa)``; ``[q_n | q_r]_h = c_q
  W_qb`` with RoPE on ``q_r``; ``[c | k_r] = u W_kva``, ``c_kv = a_kv
  RMSNorm(c)``, RoPE on the one ``k_r`` all heads share; ``[k_n | v]_h =
  c_kv W_kvb``; softmax over the layer's visible set of ``(q_n . k_n + q_r
  . k_r) / sqrt(d_n + d_r)``; ``out = concat_h(sigmoid(u W_g)_h o_h) W_o``.
  ``a = sqrt(dim / rank)`` (``lora_rescale``). The cache holds ``c_kv`` and
  ``k_r`` a token. With ``q_lora_rank=None`` the query is ``u W_q`` (no
  latent); with ``head_gate=False`` there is no gate.
- the indexer of a full layer: ``q^I_j = c_q W^I_qb`` and ``k^I =
  LayerNorm(u W^I_k)``, RoPE on the first ``d_r`` values of each, ``w = (u
  W^I_w) / sqrt(H_I d_I)``, score ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] .
  k^I[s])``; a query sees the ``index_topk`` positions ``s <= t`` of
  largest score. The cache holds ``k^I`` a token.
- a sliding layer's query at t sees ``(t - sliding_window, t]``.
- the expert layer: ``s = sigmoid(u W_r)`` in float32 over ALL routed
  experts, the chosen are the top ``num_experts_per_tok`` of ``s + b``,
  weights ``s_e / sum_chosen s`` times ``routed_scaling_factor``; the layer
  computes chosen ∩ held (``cfg.held_experts``) — every such pair, at any
  imbalance, by grouped matmuls over the held experts' stacked kernels —
  and adds the shared expert. No exchange: what the absent experts would
  add is the other chips' part.

Two forwards. Serving: :meth:`LatentMoETransformer.forward_with_cache`,
through ``inference/kv_cache.py`` ``LatentKVCache``: a one-token query a
slot (decode: the indexer's indices, rows gathered, the up-projection
absorbed) or one slot's chunk (prefill: the same sets as masks, rows
expanded a key block at a time). ``ops/latent_attention.py`` holds the reads.
Training (a schedule of full layers with no indexer): ``__call__``, batched
and uncached, the attention ``ops/flash_attention.py``
``flash_attention_bhsd`` (query/key and value of their own widths), the
held experts' grouped matmuls differentiated through ``ragged_dot``.
"""

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..obs.trace import scope
from ..ops import latent_attention as la
from ..ops.rope import apply_rope, rope_cos_sin
from .configs import LatentMoEConfig
from .llama import _DENSE_INIT, RMSNorm, TokenEmbed, _Kernel
from .moe import _StackedKernel

# what a program returns beside its tokens: what the round did, known only
# on the device. name of the count (a stats span's arg) -> (the counter the
# engine adds it to, by phase; its help text), in the order of the array
STAT_COUNTERS = {
    "moe_pairs": ("moe_pairs_total", "(token, held expert) pairs the expert "
                  "layers computed"),
    "moe_touched": ("moe_experts_touched_total", "held experts with at "
                    "least one token, summed over expert layers and "
                    "programs"),
    "index_keys": ("index_keys_scanned_total", "index keys the indexer "
                   "scored, summed over full layers"),
    "latent_rows": ("latent_rows_read_total", "latent rows the full layers "
                    "attended to (at most index_topk a query)"),
    "window_rows": ("window_rows_read_total", "window rows the sliding "
                    "layers attended to (at most the window a query)"),
}
STATS = tuple(STAT_COUNTERS)
INDEX_NORM_EPS = 1e-6


class _LayerNorm(nn.Module):
    """Mean-and-variance norm with scale and bias, float32 inside."""

    dim: int
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (self.dim,),
                           self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (self.dim,),
                          self.param_dtype)
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + INDEX_NORM_EPS)
        return (xf * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)


class SwiGLU(nn.Module):
    """``w2(silu(w1 x) * w3 x)`` at a stated hidden width."""

    dim: int
    hidden: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        dense = dict(use_bias=False, dtype=self.dtype,
                     param_dtype=self.param_dtype, kernel_init=_DENSE_INIT)
        gate = nn.Dense(self.hidden, name="w1", **dense)(x)
        up = nn.Dense(self.hidden, name="w3", **dense)(x)
        return nn.Dense(self.dim, name="w2", **dense)(jax.nn.silu(gate) * up)


def _rope_head(x, cos, sin, width: int):
    """RoPE on the first ``width`` values of each head of x (B, S, H, D)."""
    if width == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate([apply_rope(x[..., :width], cos, sin),
                            x[..., width:]], axis=-1)


class Indexer(nn.Module):
    """The learned selector of a full layer: its projections. Returns the
    index queries (B, S, Hi, di), the index key (B, S, di) and the head
    weights (B, S, Hi) float32."""

    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, u, c_q, cos, sin):
        cfg = self.cfg
        hi, di, dr = (cfg.index_n_heads, cfg.index_head_dim,
                      cfg.qk_rope_head_dim)
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, kernel_init=_DENSE_INIT)
        b, s = u.shape[:2]
        with scope("index_select"):
            q_i = nn.Dense(hi * di, name="wq_b", **dense)(c_q).reshape(
                b, s, hi, di)
            q_i = _rope_head(q_i, cos, sin, dr)
            w = nn.Dense(hi, name="weights_proj", **dense)(u).astype(
                jnp.float32) * (hi ** -0.5 * di ** -0.5)
        k_i = _LayerNorm(di, cfg.param_dtype, name="k_norm")(
            nn.Dense(di, name="wk", **dense)(u))
        k_i = _rope_head(k_i[:, :, None, :], cos, sin, dr)[:, :, 0]
        return q_i, k_i, w


class LatentAttention(nn.Module):
    """Latent attention of one layer kind against its cache."""

    cfg: LatentMoEConfig
    kind: str

    @nn.compact
    def __call__(self, u, offsets, cache, block_tables, write_valid,
                 pool_valid, slot_ids, seq_from):
        """u (B, S, dim) at positions ``offsets[b] + [0, S)``. ``cache`` is
        this layer's part: (latent_pool, rope_pool, index_pool) or (ring,).
        ``write_valid`` (B, S): real rows; ``pool_valid`` (B, S): of those,
        the rows a full layer writes (a resumed prefill recomputes rows the
        pools already hold and must not write them: they may be shared).
        Returns (out (B, S, dim), new cache part). ``cache`` None is the
        uncached training forward of a full layer without an indexer:
        whole rows from position 0, dense causal attention, any B; the
        cache part returned is None."""
        from ..inference.kv_cache import write_paged_kv

        cfg, m = self.cfg, self.cfg.mixer(self.kind)
        h, rq, r = m["heads"], m["q_rank"], m["kv_rank"]
        dn, dr, dv = m["nope"], m["rope"], m["v"]
        b, s = u.shape[:2]
        if cache is not None and s > 1 and b != 1:
            raise ValueError("a chunk (S > 1) is one slot's: packed "
                             "prefill is not written for this model")
        dense = dict(use_bias=False, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, kernel_init=_DENSE_INIT)
        a_q = math.sqrt(cfg.dim / rq) if cfg.lora_rescale else 1.0
        a_kv = math.sqrt(cfg.dim / r) if cfg.lora_rescale else 1.0
        pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        cos, sin = rope_cos_sin(dr, m["theta"], pos)

        if rq is None:      # no query latent: q straight from u
            c_q = None
            q = nn.Dense(h * (dn + dr), name="wq", **dense)(u).reshape(
                b, s, h, dn + dr)
        else:
            c_q = RMSNorm(rq, cfg.norm_eps, cfg.param_dtype, name="q_norm")(
                nn.Dense(rq, name="wq_a", **dense)(u)) * a_q
            q = nn.Dense(h * (dn + dr), name="wq_b", **dense)(c_q).reshape(
                b, s, h, dn + dr)
        q_n, q_r = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
        ckr = nn.Dense(r + dr, name="wkv_a", **dense)(u)
        c_kv = RMSNorm(r, cfg.norm_eps, cfg.param_dtype, name="kv_norm")(
            ckr[..., :r]) * a_kv
        k_r = apply_rope(ckr[..., None, r:], cos, sin)[:, :, 0]
        c_kv, k_r = c_kv.astype(cfg.dtype), k_r.astype(cfg.dtype)
        w_kvb = _Kernel((r, h * (dn + dv)), cfg.param_dtype,
                        name="wkv_b")().astype(cfg.dtype).reshape(
            r, h, dn + dv)
        gate = None
        if cfg.head_gate:
            gate = jax.nn.sigmoid(nn.Dense(h, name="wg", **dense)(u).astype(
                jnp.float32))
        scale = (dn + dr) ** -0.5

        def absorbed():     # one query a slot: q_n W_kvb_k^T
            return jnp.einsum("bhn,rhn->bhr", q_n[:, 0], w_kvb[..., :dn])

        def expand_out(o_lat):      # (B, H, r) -> (B, 1, H, dv)
            return jnp.einsum("bhr,rhv->bhv", o_lat.astype(cfg.dtype),
                              w_kvb[..., dn:],
                              preferred_element_type=jnp.float32)[:, None]

        if cache is None:
            o = causal_latent_attention(q_n, q_r, c_kv, k_r, w_kvb, dn,
                                        cfg.attention_impl)
            new_cache = None
        elif self.kind == "full":
            latent_pool, rope_pool, index_pool = cache
            q_i, k_i, w_i = Indexer(cfg, name="indexer")(u, c_q, cos, sin)
            bs = index_pool.shape[2]
            latent_pool = la.write_latent_rows(
                latent_pool, c_kv, block_tables, offsets, pool_valid, bs)
            rope_pool = la.write_latent_rows(
                rope_pool, k_r, block_tables, offsets, pool_valid, bs)
            index_pool = write_paged_kv(
                index_pool, k_i[:, None].astype(cfg.dtype), block_tables,
                offsets, pool_valid)
            if s == 1:
                scores = la.index_scores_decode(
                    q_i[:, 0], w_i[:, 0], index_pool, block_tables, offsets)
                idx, chosen = la.select_topk(scores, cfg.index_topk)
                o = expand_out(la.latent_decode_attention(
                    absorbed(), q_r[:, 0], latent_pool, rope_pool,
                    block_tables, idx, chosen, scale, bs))
            else:
                t = block_tables.shape[1] * bs
                n_keys = jnp.minimum(offsets[0] + s, t)
                members = la.index_members_chunk(
                    q_i[0], w_i[0], index_pool, block_tables[0], pos[0],
                    n_keys, cfg.index_topk)
                o = la.latent_chunk_attention(
                    jnp.concatenate([q_n, q_r], axis=-1)[0], members,
                    latent_pool, rope_pool, block_tables[0], w_kvb, n_keys,
                    dn, scale, bs)[None]
            new_cache = (latent_pool, rope_pool, index_pool)
        else:
            (ring,) = cache
            row = jnp.concatenate([c_kv, k_r], axis=-1)
            if s == 1:
                ring = la.write_window_rows(ring, row, slot_ids, offsets,
                                            write_valid)
                o = expand_out(la.window_decode_attention(
                    jnp.concatenate([absorbed(), q_r[:, 0]], axis=-1), ring,
                    offsets, seq_from, cfg.sliding_window, r, scale))
            else:
                o = la.window_chunk_attention(
                    jnp.concatenate([q_n, q_r], axis=-1)[0], row[0],
                    ring[slot_ids[0]], offsets[0], seq_from[0], w_kvb,
                    cfg.sliding_window, r, dn, scale)[None]
                ring = la.write_window_rows(ring, row, slot_ids, offsets,
                                            write_valid)
            new_cache = (ring,)
        if gate is not None:
            o = o * gate[..., None]
        out = o.astype(cfg.dtype).reshape(b, s, h * dv)
        return nn.Dense(cfg.dim, name="wo", **dense)(out), new_cache


def causal_latent_attention(q_n, q_r, c_kv, k_r, w_kvb, dn: int,
                            impl: str = "auto"):
    """The uncached causal attention of a full layer without an indexer:
    q_n (B, S, H, dn), q_r (B, S, H, dr) roped, c_kv (B, S, r), k_r (B, S,
    dr) roped, w_kvb (r, H, dn + dv). Each head's key is ``[k_n | k_r]``,
    one 192-wide contraction at Kanana's widths with the one rope key all
    heads share; the value keeps its own width. Returns (B, S, H, dv).
    ``impl``: "auto" runs the Pallas kernel on a TPU and XLA elsewhere."""
    from ..ops.flash_attention import flash_attention_bhsd

    b, s, h, _ = q_n.shape
    dr = q_r.shape[-1]
    kv = jnp.einsum("bsr,rhe->bhse", c_kv, w_kvb)         # (B, H, S, dn+dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, None], (b, h, s, dr)).astype(kv.dtype)], axis=-1)
    v = kv[..., dn:]
    q = jnp.transpose(jnp.concatenate([q_n, q_r.astype(q_n.dtype)], axis=-1),
                      (0, 2, 1, 3))                        # (B, H, S, dn+dr)
    if impl == "pallas" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        o = flash_attention_bhsd(q, k, v)
    else:
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * (
            q.shape[-1] ** -0.5)
        keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32).astype(v.dtype)
    return jnp.transpose(o, (0, 2, 1, 3))


class ExpertLayer(nn.Module):
    """Sigmoid-routed experts of which this chip holds
    ``cfg.held_experts``, plus the shared expert. Dropless: every (token,
    chosen ∩ held expert) pair is computed, whatever the imbalance."""

    cfg: LatentMoEConfig

    def setup(self):
        cfg = self.cfg
        e, d, hdn = cfg.held_experts[1], cfg.dim, cfg.moe_hidden_dim
        self.router = _Router(d, cfg.n_routed_experts, cfg.param_dtype)
        self.experts = _HeldExperts(e, d, hdn, cfg.param_dtype)
        if cfg.n_shared_experts:
            self.shared = SwiGLU(d, hdn * cfg.n_shared_experts, cfg.dtype,
                                 cfg.param_dtype)

    def parts(self, x, token_valid, train: bool = False):
        """(routed (B, S, dim), shared (B, S, dim), pairs, touched): the
        held experts' part of the layer, the shared expert's, the (token,
        held expert) pairs computed and the held experts with a token.

        ``train``: the layer is differentiated. On a TPU ``ragged_dot``
        leaves the rows past its groups undefined, in the forward and in
        the transposes the backward runs alike; the backward would scatter
        the undefined rows of the activations' gradient into the tokens'
        own. So the training path zeroes the rows past the held pairs after
        the gather (its transpose zeroes them in that gradient) and after
        the first two grouped matmuls (no undefined row enters the products
        the backward's transposes read). The last one's rows past the
        groups need no select: the combine's select drops them in the
        forward, and its transpose gives them exact zeros."""
        cfg = self.cfg
        first, held_n = cfg.held_experts
        k = cfg.num_experts_per_tok
        b, s, d = x.shape
        n = b * s
        x_flat = x.reshape(n, d)
        with scope("moe_route"):
            w_r, b_r = self.router()
            logits = jnp.matmul(x_flat.astype(jnp.float32),
                                w_r.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            score = jax.nn.sigmoid(logits)                       # (N, E)
            _, choice = jax.lax.top_k(score + b_r.astype(jnp.float32), k)
            weight = jnp.take_along_axis(score, choice, axis=-1)
            if cfg.norm_topk_prob:
                weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
            weight = weight * cfg.routed_scaling_factor
            local = choice - first
            held = ((local >= 0) & (local < held_n)
                    & token_valid.reshape(n, 1))
            group = jnp.where(held, local, held_n).reshape(n * k)
            order = jnp.argsort(group)             # stable: held pairs first
            back = jnp.argsort(order)
            token_of = order // k
            sizes = jnp.bincount(group, length=held_n + 1)[:held_n].astype(
                jnp.int32)
            pair_w = jnp.where(held, weight, 0.0)               # (N, k)
        with scope("moe_experts"):
            w1, w3, w2 = self.experts()
            defined = lambda a: a                          # noqa: E731
            if train:
                rows = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
                defined = lambda a: jnp.where(rows, a, 0)  # noqa: E731
            xs = defined(jnp.take(x_flat, token_of, axis=0))
            gate = defined(jax.lax.ragged_dot(xs, w1.astype(cfg.dtype),
                                              sizes))
            up = defined(jax.lax.ragged_dot(xs, w3.astype(cfg.dtype), sizes))
            out = jax.lax.ragged_dot(
                (jax.nn.silu(gate) * up).astype(cfg.dtype),
                w2.astype(cfg.dtype), sizes)
            # back to token order; rows past the held groups belong to no
            # expert, and what ragged_dot leaves there is not defined
            out = jnp.take(out, back, axis=0).reshape(n, k, d)
            routed = jnp.sum(
                jnp.where(pair_w[..., None] != 0,
                          out.astype(jnp.float32) * pair_w[..., None], 0.0),
                axis=1).reshape(b, s, d).astype(x.dtype)
        shared = jnp.zeros_like(x)
        if cfg.n_shared_experts:
            with scope("moe_shared"):
                shared = self.shared(x)
        return (routed, shared, jnp.sum(sizes),
                jnp.sum(sizes > 0, dtype=jnp.int32))

    def __call__(self, x, token_valid, train: bool = False):
        routed, shared, pairs, touched = self.parts(x, token_valid, train)
        return routed + shared, pairs, touched


class _Router(nn.Module):
    """The router's two leaves: ``router/kernel`` (dim, all routed experts)
    and the selection bias ``router/bias``, which moves which experts are
    chosen and never their weights."""

    dim: int
    width: int
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return (self.param("kernel", _DENSE_INIT, (self.dim, self.width),
                           self.param_dtype),
                self.param("bias", nn.initializers.zeros, (self.width,),
                           self.param_dtype))


class _HeldExperts(nn.Module):
    """The held experts' stacked SwiGLU kernels (``experts/w{1,2,3}/kernel``,
    leading axis the held experts in order)."""

    held: int
    dim: int
    hidden: int
    param_dtype: Any

    @nn.compact
    def __call__(self):
        e, d, h = self.held, self.dim, self.hidden
        return (_StackedKernel((e, d, h), self.param_dtype, name="w1")(),
                _StackedKernel((e, d, h), self.param_dtype, name="w3")(),
                _StackedKernel((e, h, d), self.param_dtype, name="w2")())


class LatentMoEBlock(nn.Module):
    cfg: LatentMoEConfig
    index: int

    @nn.compact
    def __call__(self, x, offsets, cache, block_tables, write_valid,
                 pool_valid, slot_ids, seq_from):
        cfg = self.cfg
        normed = RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype,
                         name="attention_norm")(x)
        attn, new_cache = LatentAttention(
            cfg, cfg.layer_types[self.index], name="attention")(
            normed, offsets, cache, block_tables, write_valid, pool_valid,
            slot_ids, seq_from)
        h = x + attn
        normed = RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype,
                         name="ffn_norm")(h)
        if self.index < cfg.first_dense_layers:
            ffn = SwiGLU(cfg.dim, cfg.dense_hidden_dim, cfg.dtype,
                         cfg.param_dtype, name="feed_forward")(normed)
            pairs = touched = jnp.int32(0)
        else:
            ffn, pairs, touched = ExpertLayer(cfg, name="feed_forward")(
                normed, write_valid, cache is None)
        return h + ffn, new_cache, pairs, touched


class LatentMoETransformer(nn.Module):
    """embed -> the scheduled blocks -> final norm -> untied head."""

    cfg: LatentMoEConfig

    def setup(self):
        cfg = self.cfg
        self.tok_embeddings = TokenEmbed(cfg)
        # a block's activations are recomputed in the backward under remat
        block = nn.remat(LatentMoEBlock) if cfg.remat else LatentMoEBlock
        self.layers = [block(cfg, i) for i in range(cfg.n_layers)]
        self.norm = RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype)
        self.output = nn.Dense(cfg.vocab_size, use_bias=False,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=_DENSE_INIT)

    def forward_with_cache(self, tokens, cache, offsets, block_tables,
                           write_valid, slot_ids, seq_from, write_from=None,
                           logits_at=None):
        """``tokens`` (B, S) at positions ``offsets[b] + [0, S)`` through
        the ``LatentKVCache`` ``cache``: S = 1 is a decode round (row b is
        slot ``slot_ids[b]``), S > 1 one slot's prefill chunk (B = 1).
        ``write_valid`` (B, S) marks the real rows; ``seq_from`` (B,) is the
        first position the slot's window rings hold of its request;
        ``write_from`` (B,), where given, keeps positions before it out of
        the full layers' pools (a resumed prefill recomputes them only to
        rebuild the windows); ``logits_at`` (B,), where given, is the one
        row of each batch row whose logits are wanted (B, 1, vocab).
        Returns ``(logits, cache with its pools,
        rings and win_from updated — lengths are the caller's —, stats)``,
        stats the int32 counts named by :data:`STATS`."""
        cfg = self.cfg
        b, s = tokens.shape
        pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        pool_valid = write_valid
        if write_from is not None:
            pool_valid = write_valid & (pos >= write_from[:, None])
        x = self.tok_embeddings(tokens)
        latent, rope, index, window = (
            list(cache.latent), list(cache.rope), list(cache.index),
            list(cache.window))
        full_at = {l: i for i, l in enumerate(cfg.full_layers)}
        slide_at = {l: i for i, l in enumerate(cfg.sliding_layers)}
        pairs = touched = jnp.int32(0)
        for l, layer in enumerate(self.layers):
            if l in full_at:
                part = (latent[full_at[l]], rope[full_at[l]],
                        index[full_at[l]])
            else:
                part = (window[slide_at[l]],)
            x, part, p, t = layer(x, offsets, part, block_tables,
                                  write_valid, pool_valid, slot_ids,
                                  seq_from)
            if l in full_at:
                latent[full_at[l]], rope[full_at[l]], index[full_at[l]] = part
            else:
                (window[slide_at[l]],) = part
            pairs, touched = pairs + p, touched + t
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        logits = self.output(self.norm(x))
        # what the round read, as the algorithm counts it: a query at p
        # scans p + 1 index keys a full layer and reads the rows it picks;
        # a sliding layer's reads stop at the window and at seq_from
        real = write_valid.astype(jnp.int32)
        seen = pos + 1
        stats = jnp.stack([
            pairs, touched,
            len(full_at) * jnp.sum(real * seen),
            len(full_at) * jnp.sum(real * jnp.minimum(seen, cfg.index_topk)),
            len(slide_at) * jnp.sum(real * jnp.clip(
                seen - seq_from[:, None], 0, cfg.sliding_window))]).astype(
            jnp.int32)
        win_from = cache.win_from.at[slot_ids].set(
            jnp.where(jnp.any(write_valid, axis=1), seq_from,
                      cache.win_from[slot_ids]))
        return logits, cache.replace(
            latent=tuple(latent), rope=tuple(rope), index=tuple(index),
            window=tuple(window), win_from=win_from), stats

    def __call__(self, tokens):
        """Logits (B, S, vocab) of whole sequences from position 0.

        Where the configuration :attr:`~LatentMoEConfig.trains`: the
        batched, uncached training forward — every block over all B rows
        at once, dense causal attention, the expert layers dropless over
        the held experts — differentiable, each block rematerialised under
        ``cfg.remat``. It sows ``stats/moe`` = (pairs, touched), the
        (token, held expert) pairs computed and the held experts with a
        token, summed over expert layers (``training/step.py`` reads them
        with ``mutable=["stats"]``). The head matmul is read with the loss
        (``loss_head``), as the Llama class's is.

        Otherwise (an indexer or sliding layers): each row through a
        scratch cache of its own as one chunk from position 0, for
        ``init`` and for tests; serving goes through
        :meth:`forward_with_cache`."""
        from ..inference.kv_cache import init_latent_cache

        if self.cfg.trains:
            return self._train_forward(tokens)
        b, s = tokens.shape
        bs = 16
        nb = -(-s // bs)
        rows = []
        for r in range(b):
            cache = init_latent_cache(self.cfg, 1, bs, nb + 1)
            logits, _, _ = self.forward_with_cache(
                tokens[r:r + 1], cache, jnp.zeros((1,), jnp.int32),
                jnp.arange(1, nb + 1, dtype=jnp.int32)[None, :],
                jnp.ones((1, s), jnp.bool_), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32))
            rows.append(logits)
        return jnp.concatenate(rows, axis=0)

    def _train_forward(self, tokens):
        b, s = tokens.shape
        offsets = jnp.zeros((b,), jnp.int32)
        valid = jnp.ones((b, s), jnp.bool_)
        x = self.tok_embeddings(tokens)
        pairs = touched = jnp.int32(0)
        for layer in self.layers:
            x, _, p, t = layer(x, offsets, None, None, valid, valid, None,
                               None)
            pairs, touched = pairs + p, touched + t
        self.sow("stats", "moe", jnp.stack([pairs, touched]))
        x = self.norm(x)
        with scope("loss_head"):
            return self.output(x)
