"""Llama-3-style decoder-only transformer, Flax linen (ref: model.py:9-380).

Architecture parity with the reference:
- RMSNorm with fp32 internal math, cast back, learnable scale (model.py:24-48)
- interleaved-pair RoPE, fp32, precomputed table (model.py:51-126,342-344)
- GQA with separate bias-free wq/wk/wv/wo (model.py:170-177); the reference's
  ``repeat_kv`` copy (model.py:129-138) is replaced by a grouped einsum that
  keeps KV at their native head count (no HBM-bandwidth waste on TPU)
- SwiGLU ``w2(silu(w1 x) * w3 x)`` with the reference's hidden-dim rounding
  (model.py:243-254)
- pre-norm residual blocks, final RMSNorm, untied output head
  (model.py:310-312,350-352,373-380)

TPU-first differences: the RoPE table is a constant folded into the jitted
step (not a buffer); attention dispatches to XLA-einsum / Pallas-flash / ring
(sequence-parallel) kernels; activations carry logical sharding constraints
so the same module traces on 1 CPU device or a v5p pod mesh; optional
``jax.checkpoint`` rematerialization per block.
"""

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..obs.trace import scope
from ..ops.attention import cached_attention, multihead_attention
from ..ops.rope import (
    apply_rope,
    apply_rope_bhsd,
    precompute_rope,
    rope_cos_sin,
)
from ..parallel.sharding import constrain
from .configs import TransformerConfig

_DENSE_INIT = nn.initializers.lecun_normal()
_EMBED_INIT = nn.initializers.normal(stddev=0.02)


class RMSNorm(nn.Module):
    """ref: model.py:24-48 — x * rsqrt(mean(x^2) + eps) in fp32, then scale."""

    dim: int
    eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (self.dim,), self.param_dtype)
        xf = x.astype(jnp.float32)
        normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return normed.astype(x.dtype) * scale.astype(x.dtype)


class TokenEmbed(nn.Module):
    """Token embedding (ref: model.py:340 ``nn.Embedding``).

    Two lookups behind ``cfg.embed_impl``: a plain gather, or an iota
    one-hot matmul. The matmul form matters under tensor parallelism where
    the (vocab, embed) table is vocab-sharded: contracting the vocab axis is
    a clean MXU matmul + psum, whereas a gather from a vocab-sharded table
    forces the SPMD partitioner into an involuntary full rematerialization
    (observed on the dp/fsdp/sp/tp dryrun mesh)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        emb = self.param("embedding", _EMBED_INIT,
                         (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        impl = cfg.embed_impl
        if impl == "auto":
            # one_hot only when the VOCAB dim actually shards ('tensor' /
            # 'pipe' after the divisibility degrade, parallel/sharding.py):
            # there a gather would force the partitioner into involuntary
            # full rematerialization, while contracting vocab is a clean
            # MXU matmul + psum. With the vocab dim replicated (fsdp-only
            # meshes shard the table's FEATURE dim; dp-only meshes nothing)
            # gather stays the impl: the one_hot form was measured to
            # deadlock XLA's in-process CPU collectives on an fsdp-sharded
            # table under sustained multi-step load (2/3 runs on the
            # 8-virtual-device mesh), and gather is cheapest anyway.
            from ..parallel.sharding import shard_size
            impl = ("one_hot" if shard_size(cfg.vocab_size, "vocab") > 1
                    else "gather")
        if impl == "one_hot":
            one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
            # Pin the one-hot to the table's vocab sharding: the iota
            # compare generates each device's slice for free, so no
            # full-V (B, S, V) tensor exists per device.
            one_hot = constrain(one_hot, "batch", "seq", "vocab")
            return one_hot @ emb.astype(cfg.dtype)
        # Gather from a feature-sharded table computes a feature-sharded
        # output the partitioner cannot reshard to the batch-sharded
        # activation layout directly; its last resort is replicate-then-
        # partition plus an involuntary-full-rematerialization warning
        # (fsdp/ep meshes). When the (B, S, D) output is genuinely small,
        # stage that same reshard explicitly (replicate, then the
        # activation constraint re-slices) — identical data movement,
        # voluntary and warning-free. For large global shapes (long
        # context, big batch) forcing full replication would defeat the
        # batch/sequence sharding budget, so the partitioner keeps the
        # choice. (A feature-replicated TABLE constraint was tried
        # instead and deadlocks the in-process CPU collectives — see
        # ROUND_NOTES.md.)
        out = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
        if out.size * out.dtype.itemsize <= 64 * 2**20:
            out = constrain(out, None, None, None)
        return constrain(out, "batch", "seq", "act_embed")


class _Kernel(nn.Module):
    """Declares a Dense-compatible kernel param (``<name>/kernel``) and
    returns it raw — the fused projection paths (``cfg.fused_qkv`` /
    ``cfg.fused_w13``) contract several projections' kernels in ONE
    matmul while keeping the param tree byte-identical to the separate
    ``nn.Dense`` modules (checkpoints, shardings and the torch converter
    see no difference; init RNG folds over the same module path, so
    initial values match too)."""

    shape: tuple
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", _DENSE_INIT, self.shape, self.param_dtype)


class Attention(nn.Module):
    """GQA causal self-attention (ref: model.py:129-215)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions=None, cache=None, adapter=None):
        cfg = self.cfg
        dh = cfg.head_dim
        nq, nkv = cfg.n_heads * dh, cfg.kv_heads * dh
        dense = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_DENSE_INIT)
        b, s = x.shape[0], x.shape[1]
        # Attention-path resolution BEFORE the projections: the head-major
        # einsum form only pays where a head-major consumer follows (the
        # fused-rope / bhsd kernel branches). On the streaming/ring/XLA
        # paths the canonical transpose-back costs more than the Dense it
        # replaced (S=8192: 39.7k vs 40.3k tokens/s, −1.4% — BASELINE.md
        # round 5), so those keep the Dense projections.
        impl = cfg.attention_impl
        from ..ops.attention import (
            resolve_attention_impl,
            ring_attention_active,
        )
        ring = ring_attention_active(impl)
        resolved = resolve_attention_impl(impl)
        from ..ops.flash_attention import rope_fused_profitable
        fused_rope_branch = (not ring and resolved == "pallas"
                             and positions is None and cache is None
                             and cfg.rope_impl == "fused"
                             and rope_fused_profitable(s, dh))
        bhsd_branch = (not fused_rope_branch and not ring
                       and resolved == "pallas" and positions is None
                       and cache is None
                       and cfg.qkv_layout == "bhsd")
        head_major = None  # (qt, kt, vt) in (B, H, S, D) when qkv_einsum
        if cfg.qkv_einsum and (fused_rope_branch or bhsd_branch):
            # Head-major projections: contract x against the (D, H, dh)
            # views so q/k/v land directly in the flash kernels'
            # (B, H, S, D) layout — no activation-side transpose between
            # projection and kernel (pairs with fused_wo on the output
            # side). Only taken when the selected branch consumes
            # head_major natively (see the gate above).
            def proj(name, heads):
                w = _Kernel((cfg.dim, heads * dh), cfg.param_dtype,
                            name=name)()
                return jnp.einsum(
                    "bsd,dhe->bhse", x,
                    w.reshape(cfg.dim, heads, dh).astype(cfg.dtype))
            head_major = (proj("wq", cfg.n_heads), proj("wk", cfg.kv_heads),
                          proj("wv", cfg.kv_heads))
            # Canonical (B, S, H, D) views are built lazily in the branches
            # that consume them (ADVICE r4: materializing them here made
            # the fused branch's correctness depend on XLA DCE, and a
            # later accidental use would silently double-compute).
            q = k = v = None
        elif cfg.fused_qkv:
            # One (D, (H+2K)*dh) matmul over the concatenated kernels:
            # x is read once instead of three times, and the backward's
            # dx / dW each collapse to one dot (autodiff of the concat is
            # a slice). Weight-side concat cost: ~3 MB/layer, negligible.
            wq = _Kernel((cfg.dim, nq), cfg.param_dtype, name="wq")()
            wk = _Kernel((cfg.dim, nkv), cfg.param_dtype, name="wk")()
            wv = _Kernel((cfg.dim, nkv), cfg.param_dtype, name="wv")()
            qkv = x @ jnp.concatenate([wq, wk, wv], axis=1).astype(cfg.dtype)
            q, k, v = (qkv[..., :nq].reshape(b, s, cfg.n_heads, dh),
                       qkv[..., nq:nq + nkv].reshape(b, s, cfg.kv_heads, dh),
                       qkv[..., nq + nkv:].reshape(b, s, cfg.kv_heads, dh))
        else:
            q = nn.Dense(nq, name="wq", **dense)(x).reshape(
                b, s, cfg.n_heads, dh)
            k = nn.Dense(nkv, name="wk", **dense)(x).reshape(
                b, s, cfg.kv_heads, dh)
            v = nn.Dense(nkv, name="wv", **dense)(x).reshape(
                b, s, cfg.kv_heads, dh)

        if cache is not None:
            # Prefill/decode against a KV cache: q/k/v come from the SAME
            # projection impl the training forward selects (fused_qkv or
            # Dense — the fused_rope/bhsd branches are gated off above, so
            # canonical q/k/v always exist here), RoPE gathers from the same
            # precomputed table at absolute positions, and the einsum
            # attention mirrors xla_attention's numerics — cached decode
            # logits bit-match the uncached forward (tests/test_inference.py,
            # tests/test_paged_kv.py). Two cache layouts, dispatched on the
            # tuple arity (inference/kv_cache.py):
            #   (k, v, offsets)                 per-slot ring buffers
            #   (k, v, tables, offsets, valid)  paged block pool
            #   (k, v, tables, offsets, valid, positions, anc)
            #       paged TREE-verify: per-row rope positions + ancestor
            #       visibility over the speculative window
            from ..inference.kv_cache import write_paged_kv, write_slot_kv
            if adapter is not None:
                # Per-slot LoRA delta on the q/v projections (S-LoRA style
                # multi-tenant serving, inference/adapters.py): each batch
                # row carries ITS OWN low-rank factors — gathered from the
                # paged adapter pool by the caller — so one dispatch serves
                # slots bound to different adapters. The batch dim is a
                # PARALLEL dim of both einsums (each row's contraction is
                # independent of its neighbours), and a row whose scale is
                # 0 (the null adapter) selects the base activations through
                # jnp.where BITWISE — adapter-0 streams are bit-identical
                # to a no-adapter engine, and concurrent heterogeneous
                # streams bit-match sequential single-adapter runs.
                # Applied BEFORE RoPE/cache writes: the delta is part of
                # the projection, y = Wx + B(Ax) * (alpha/r).
                a_q, b_q, a_v, b_v, a_scale = adapter
                xf = x.astype(jnp.float32)
                gate = (a_scale > 0.0)[:, None, None, None]
                dq = jnp.einsum("bsd,bdr->bsr", xf, a_q)
                dq = (jnp.einsum("bsr,brn->bsn", dq, b_q)
                      * a_scale[:, None, None])
                q = jnp.where(gate, q + dq.reshape(q.shape).astype(q.dtype),
                              q)
                dv = jnp.einsum("bsd,bdr->bsr", xf, a_v)
                dv = (jnp.einsum("bsr,brn->bsn", dv, b_v)
                      * a_scale[:, None, None])
                v = jnp.where(gate, v + dv.reshape(v.shape).astype(v.dtype),
                              v)
            if len(cache) == 7:
                # Tree-verify: the S rows are one flattened token tree.
                # Node i's KV lands at cache position ``offsets[b] + i``
                # (contiguous — write_paged_kv unchanged) but its ROPE
                # position is ``offsets[b] + depth(i)``: rope encodes the
                # node's distance down its root path, not its row index,
                # so an accepted path's keys are rotated exactly as the
                # sequential decode would have rotated them. Attention
                # swaps the causal rule for the (S, S) ancestor mask.
                (k_pool, v_pool, block_tables, offsets, write_valid,
                 tree_positions, anc_mask) = cache
                t = block_tables.shape[1] * k_pool.shape[2]
                cos, sin = precompute_rope(dh, t, cfg.rope_theta)
                q = apply_rope(q, cos, sin, positions=tree_positions)
                k = apply_rope(k, cos, sin, positions=tree_positions)
                k_pool = write_paged_kv(
                    k_pool, jnp.transpose(k, (0, 2, 1, 3)), block_tables,
                    offsets, write_valid)
                v_pool = write_paged_kv(
                    v_pool, jnp.transpose(v, (0, 2, 1, 3)), block_tables,
                    offsets, write_valid)
                from ..ops.attention import paged_tree_attention
                out = paged_tree_attention(q, k_pool, v_pool, block_tables,
                                           offsets, anc_mask,
                                           impl=cfg.paged_kernel)
                out = out.reshape(b, s, cfg.n_heads * dh)
                return (nn.Dense(cfg.dim, name="wo", **dense)(out),
                        (k_pool, v_pool))
            if len(cache) == 5:
                k_pool, v_pool, block_tables, offsets, write_valid = cache
                # Table rows cover ceil(max_len/bs) blocks; rope rows are
                # per-position, so the (possibly longer) gathered T only
                # adds masked tail rows — values at shared positions are
                # identical to the ring path's table.
                t = block_tables.shape[1] * k_pool.shape[2]
                cos, sin = precompute_rope(dh, t, cfg.rope_theta)
                pos = (offsets[:, None]
                       + jnp.arange(s, dtype=jnp.int32)[None, :])
                q = apply_rope(q, cos, sin, positions=pos)
                k = apply_rope(k, cos, sin, positions=pos)
                # Scatter ONLY the new tokens through the block table
                # BEFORE attending (so they attend to themselves); invalid
                # positions (pad/inactive) divert to null block 0.
                k_pool = write_paged_kv(
                    k_pool, jnp.transpose(k, (0, 2, 1, 3)), block_tables,
                    offsets, write_valid)
                v_pool = write_paged_kv(
                    v_pool, jnp.transpose(v, (0, 2, 1, 3)), block_tables,
                    offsets, write_valid)
                # paged_attention dispatches on (impl, S): under "pallas"
                # both the S=1 decode read and S>1 chunk reads (chunked /
                # packed prefill, chunk-mode spec-verify) stay in place —
                # this batch-general path is also what the packed
                # multi-request prefill program runs at B > 1.
                from ..ops.attention import paged_attention
                out = paged_attention(q, k_pool, v_pool, block_tables,
                                      offsets, impl=cfg.paged_kernel)
                out = out.reshape(b, s, cfg.n_heads * dh)
                return (nn.Dense(cfg.dim, name="wo", **dense)(out),
                        (k_pool, v_pool))
            k_cache, v_cache, offsets = cache
            t = k_cache.shape[2]
            cos, sin = precompute_rope(dh, t, cfg.rope_theta)
            pos = offsets[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            q = apply_rope(q, cos, sin, positions=pos)
            k = apply_rope(k, cos, sin, positions=pos)
            # Write the rotated keys/values head-major at each slot's next
            # position (mod T: the ring wraps per slot) BEFORE attending, so
            # the new tokens attend to themselves through the cache.
            k_cache = write_slot_kv(k_cache, jnp.transpose(k, (0, 2, 1, 3)),
                                    offsets % t)
            v_cache = write_slot_kv(v_cache, jnp.transpose(v, (0, 2, 1, 3)),
                                    offsets % t)
            out = cached_attention(q, k_cache, v_cache, offsets)
            out = out.reshape(b, s, cfg.n_heads * dh)
            return (nn.Dense(cfg.dim, name="wo", **dense)(out),
                    (k_cache, v_cache))

        if fused_rope_branch:
            # RoPE inside the kernels (ops/flash_attention.py
            # flash_attention_rope): raw head-major q/k/v plus the
            # interleave-duplicated (S, D) tables. No rotated q/k or rope
            # backward exists at the XLA level. Long-context shapes fall
            # through to XLA rope (see rope_fused_profitable).
            from ..ops.flash_attention import flash_attention_rope
            cos, sin = precompute_rope(dh, cfg.seq_len, cfg.rope_theta)
            cos2 = jnp.repeat(cos[:s], 2, axis=-1)
            sin2 = jnp.repeat(sin[:s], 2, axis=-1)
            if head_major is not None:  # qkv_einsum: already (B, H, S, D)
                qt_in, kt_in, vt_in = head_major
            else:
                qt_in = jnp.transpose(q, (0, 2, 1, 3))
                kt_in = jnp.transpose(k, (0, 2, 1, 3))
                vt_in = jnp.transpose(v, (0, 2, 1, 3))
            out_t = flash_attention_rope(qt_in, kt_in, vt_in,
                                         cos2, sin2, True)
            if cfg.fused_wo:
                # Contract the kernel's head-major output against the
                # (H, dh, D) view of wo directly — the explicit
                # (B,H,S,D) -> (B,S,H*dh) relayout disappears into the
                # matmul's own layout handling.
                wo = _Kernel((nq, cfg.dim), cfg.param_dtype, name="wo")()
                return jnp.einsum(
                    "bhsd,hde->bse", out_t,
                    wo.reshape(cfg.n_heads, dh, cfg.dim).astype(cfg.dtype))
            out = jnp.transpose(out_t, (0, 2, 1, 3))
        elif bhsd_branch:
            # Kernel-native layout path: transpose BEFORE rope so the rope
            # fusion computes in (and emits) exactly the (B, H, S, D)
            # layout the Pallas custom call consumes — the bshd path below
            # pays fp32 relayout copies at the boundary instead (the
            # 11.5 ms/step copy family in the BASELINE.md profile).
            from ..ops.flash_attention import flash_attention_bhsd
            cos, sin = precompute_rope(dh, cfg.seq_len, cfg.rope_theta)
            if head_major is not None:  # qkv_einsum: already (B, H, S, D)
                qh, kh, vh = head_major
            else:
                qh = jnp.transpose(q, (0, 2, 1, 3))
                kh = jnp.transpose(k, (0, 2, 1, 3))
                vh = jnp.transpose(v, (0, 2, 1, 3))
            qt = apply_rope_bhsd(qh, cos, sin)
            kt = apply_rope_bhsd(kh, cos, sin)
            vt = vh
            out = jnp.transpose(flash_attention_bhsd(qt, kt, vt, True),
                                (0, 2, 1, 3))
        else:
            # With sequence parallelism each shard holds a non-prefix
            # slice of the sequence; cos/sin come from a positions x freqs
            # outer product (sharded with the activations) rather than a
            # table gather, which the SPMD partitioner can only reshard by
            # full rematerialization.
            # head_major cannot reach here: the einsum projections are
            # gated on (fused_rope_branch or bhsd_branch) above, so this
            # path always has canonical Dense q/k/v.
            if positions is None:
                cos, sin = precompute_rope(dh, cfg.seq_len, cfg.rope_theta)
            else:
                cos, sin = rope_cos_sin(dh, cfg.rope_theta, positions)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            if ring:
                from ..ops.ring_attention import ring_attention
                out = ring_attention(q, k, v, axis_name="sequence",
                                     zigzag=(cfg.sp_layout == "zigzag"))
            else:
                if impl == "ring":  # ring requested but no sequence axis
                    impl = "auto"
                out = multihead_attention(q, k, v, impl=impl, causal=True)
        out = out.reshape(b, s, cfg.n_heads * dh)
        return nn.Dense(cfg.dim, name="wo", **dense)(out)


class FeedForward(nn.Module):
    """SwiGLU FFN (ref: model.py:218-254): w2(silu(w1 x) * w3 x)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hidden = cfg.ffn_hidden_dim
        dense = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_DENSE_INIT)
        if cfg.fused_w13:
            # Gate and up in ONE (D, 2*hidden) matmul (see _Kernel): x is
            # read once, and the backward's dx is one dot instead of two
            # accumulated ones.
            w1 = _Kernel((cfg.dim, hidden), cfg.param_dtype, name="w1")()
            w3 = _Kernel((cfg.dim, hidden), cfg.param_dtype, name="w3")()
            h13 = x @ jnp.concatenate([w1, w3], axis=1).astype(cfg.dtype)
            gate, up = h13[..., :hidden], h13[..., hidden:]
        else:
            gate = nn.Dense(hidden, name="w1", **dense)(x)
            up = nn.Dense(hidden, name="w3", **dense)(x)
        return nn.Dense(cfg.dim, name="w2", **dense)(jax.nn.silu(gate) * up)


class TransformerBlock(nn.Module):
    """Pre-norm residual block (ref: model.py:257-312)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions=None, cache=None, adapter=None):
        cfg = self.cfg
        normed = RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype,
                         name="attention_norm")(x)
        attn = Attention(cfg, name="attention")
        new_cache = None
        if cache is not None:
            attn_out, new_cache = attn(normed, positions, cache, adapter)
        else:
            attn_out = attn(normed, positions)
        h = x + attn_out
        h = constrain(h, "batch", "seq", "act_embed")
        if cfg.moe_experts:
            from .moe import MoEFeedForward
            ffn = MoEFeedForward(cfg, name="feed_forward")
        else:
            ffn = FeedForward(cfg, name="feed_forward")
        out = h + ffn(
            RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype, name="ffn_norm")(h))
        out = constrain(out, "batch", "seq", "act_embed")
        return out if cache is None else (out, new_cache)


class _ScanBlock(nn.Module):
    """Scan adapter: gives TransformerBlock the (carry, x) -> (carry, y)
    shape ``nn.scan`` requires; params nest one level deeper
    (``layers/block/...`` with a leading n_layers axis)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        block = TransformerBlock
        if self.cfg.remat:
            # prevent_cse=False: the scan's while loop already prevents
            # cross-iteration CSE, so the extra optimization barriers the
            # default inserts would only block in-body fusion
            block = nn.remat(TransformerBlock, prevent_cse=False,
                             static_argnums=())
        return block(self.cfg, name="block")(x, positions), None


class Transformer(nn.Module):
    """Trunk: embed -> n_layers blocks -> final norm -> untied head
    (ref: model.py:315-380).

    The reference's 32 distinct ``ModuleDict`` blocks (model.py:346-348)
    map to ``layer_impl="loop"``; ``"scan"`` is the TPU-idiomatic form —
    one block body compiled once by XLA and scanned over layer-stacked
    params, so compile time stops growing with depth.

    Setup-style (not compact) so the pipeline-parallel step can drive the
    pieces separately via ``apply(..., method="embed"/"head")`` while
    ``__call__`` stays the single-call path; attribute names keep the param
    tree byte-compatible with the compact form (``tok_embeddings``,
    ``layers_{i}`` / ``layers/block``, ``norm``, ``output``)."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        self.tok_embeddings = TokenEmbed(cfg)
        if cfg.layer_impl == "scan":
            self.layers = nn.scan(
                _ScanBlock,
                # 'losses': per-layer MoE router aux (models/moe.py sow)
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True},
                length=cfg.n_layers,
                in_axes=nn.broadcast,
                # NOTE: nn.scan(unroll=N) was measured and rejected: 62.6k
                # tokens/s at unroll 2 or 4 vs 80.6k at 1 on the headline
                # bench (v5e) — the unrolled bodies' param-stack slices
                # cost more than the recovered cross-layer fusion.
            )(cfg)
        else:
            block = TransformerBlock
            if cfg.remat:
                block = nn.remat(TransformerBlock, static_argnums=())
            # a module list attribute named ``layers`` yields param keys
            # layers_0..layers_{N-1}, matching the reference's ModuleDict
            self.layers = [block(cfg) for _ in range(cfg.n_layers)]
        self.norm = RMSNorm(cfg.dim, cfg.norm_eps, cfg.param_dtype)
        self.output = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype,
                               kernel_init=_DENSE_INIT)

    def embed(self, tokens):
        x = self.tok_embeddings(tokens)
        return constrain(x, "batch", "seq", "act_embed")

    def head(self, x):
        x = self.norm(x)
        logits = self.output(x)
        return constrain(logits, "batch", "seq", "vocab")

    def default_positions(self, seq_len: int):
        """(1, S) prefix positions — same cos/sin values as the
        precomputed-table path in Attention, broadcasting over batch."""
        return jnp.arange(seq_len, dtype=jnp.int32)[None, :]

    def hidden_states(self, tokens, positions=None):
        """embed -> trunk -> final norm, WITHOUT the output projection —
        the fused head+CE loss (ops/fused_ce.py) consumes these and blocks
        the head matmul into the loss so logits never materialize."""
        cfg = self.cfg
        x = self.embed(tokens)
        if cfg.layer_impl == "scan":
            if positions is None:
                # scan broadcasts positions to the body; materialize them
                positions = self.default_positions(tokens.shape[1])
            x, _ = self.layers(x, positions)
        else:
            for layer in self.layers:
                x = layer(x, positions)
        return self.norm(x)

    def __call__(self, tokens, positions=None):
        hidden = self.hidden_states(tokens, positions)
        # the training forward: its head matmul is read with the loss
        # (serving's head() keeps the bare ``output`` name)
        with scope("loss_head"):
            logits = self.output(hidden)
        return constrain(logits, "batch", "seq", "vocab")

    def forward_with_cache(self, tokens, cache_k, cache_v, offsets,
                           block_tables=None, write_valid=None,
                           adapter=None):
        """Prefill/decode forward through per-layer KV caches.

        ``tokens`` (B, S) occupy absolute positions ``offsets[b] + [0, S)``;
        each layer attends against (and appends to) its buffers from
        ``cache_k``/``cache_v`` (length-n_layers sequences). With
        ``block_tables`` None the buffers are per-slot (B, K, T, D) ring
        buffers; with ``block_tables`` (B, NB) they are paged (N, K, bs, D)
        block pools, writes route through the table, and ``write_valid``
        (B, S) masks which new positions are real (padding/inactive writes
        divert to null block 0; default: all valid). Loop trunk only — the
        inference engine converts scan-form checkpoints with
        :func:`unstack_layer_params`. ``adapter`` is an optional
        length-n_layers sequence of per-layer LoRA operand tuples
        ``(A_q, B_q, A_v, B_v, scale)`` — each factor with a leading batch
        dim, sliced by the engine from its paged adapter pool
        (inference/adapters.py); None means base-only everywhere. Returns
        ``(logits, (new_cache_k, new_cache_v))``.
        """
        if self.cfg.layer_impl != "loop":
            raise ValueError(
                "forward_with_cache requires layer_impl='loop'; convert "
                "scan-form checkpoints with unstack_layer_params")
        if block_tables is not None and write_valid is None:
            write_valid = jnp.ones(tokens.shape, jnp.bool_)
        x = self.embed(tokens)
        new_k, new_v = [], []
        for i, layer in enumerate(self.layers):
            c = ((cache_k[i], cache_v[i], offsets) if block_tables is None
                 else (cache_k[i], cache_v[i], block_tables, offsets,
                       write_valid))
            x, (k_i, v_i) = layer(x, None, c,
                                  None if adapter is None else adapter[i])
            new_k.append(k_i)
            new_v.append(v_i)
        return self.head(x), (tuple(new_k), tuple(new_v))

    def verify_with_cache(self, tokens, cache_k, cache_v, offsets,
                          block_tables, write_valid=None):
        """Speculative-decoding verify entry: score k+1 candidate positions
        per slot in one forward through the paged caches.

        ``tokens`` (B, k+1) is ``[last_committed, d_1 .. d_k]`` at absolute
        positions ``offsets[b] + [0, k]``; each row's logits are the
        target's next-token scores AFTER that prefix — the same masked
        attention the j-th sequential single-token decode computes
        (ops/attention.py ``paged_attention`` documents the masking
        argument), though only equal to it up to shape-dependent bf16 GEMM
        accumulation order: a one-ulp logit near-tie can flip an argmax
        between the chunked and single-step programs, which is why the
        engine's AOT verify program micro-steps S=1 forwards when bitwise
        greedy equivalence is required (inference/engine.py
        ``_verify_fn``). Paged layout only — the verify semantics
        depend on masked writes diverting to the null block so a rejected
        suffix can be abandoned without device-side rollback. This is a thin
        named delegation to :meth:`forward_with_cache`: the multi-token path
        there IS the verify math; the entry pins the contract (and gives the
        engine's AOT verify program a stable method name).
        """
        if block_tables is None:
            raise ValueError("verify_with_cache requires the paged layout "
                             "(block_tables)")
        return self.forward_with_cache(tokens, cache_k, cache_v, offsets,
                                       block_tables=block_tables,
                                       write_valid=write_valid)

    def tree_verify_with_cache(self, tokens, cache_k, cache_v, offsets,
                               block_tables, tree_positions, anc_mask,
                               write_valid=None):
        """Tree-speculative verify: score one flattened S-node token TREE
        per slot in a single forward through the paged caches.

        ``tokens`` (B, S) is ``[last_committed, node_1 .. node_{S-1}]`` in
        topological order; node i's KV is written at cache position
        ``offsets[b] + i`` while its rope position is ``tree_positions[b,
        i] = offsets[b] + depth(i)``, and attention inside the speculative
        window follows ``anc_mask`` (S, S) — ancestors ∪ self ∪ root —
        instead of the causal rule (ops/attention.py
        ``paged_tree_attention``). Row i's logits are therefore the
        target's next-token law after node i's root path, for EVERY branch
        of the tree in one dispatch. When the tree degenerates to a chain
        the mask equals the causal one and this reproduces
        :meth:`verify_with_cache` bit-for-bit on the gather impl (the
        chunk-mode caveat there about bf16 shape-dependent accumulation
        vs S=1 micro-steps applies unchanged — hence the engine's
        ``exact`` escape hatch scores only the primary chain).
        """
        if block_tables is None:
            raise ValueError("tree_verify_with_cache requires the paged "
                             "layout (block_tables)")
        if self.cfg.layer_impl != "loop":
            raise ValueError(
                "tree_verify_with_cache requires layer_impl='loop'; convert "
                "scan-form checkpoints with unstack_layer_params")
        if write_valid is None:
            write_valid = jnp.ones(tokens.shape, jnp.bool_)
        x = self.embed(tokens)
        new_k, new_v = [], []
        for i, layer in enumerate(self.layers):
            c = (cache_k[i], cache_v[i], block_tables, offsets, write_valid,
                 tree_positions, anc_mask)
            x, (k_i, v_i) = layer(x, None, c)
            new_k.append(k_i)
            new_v.append(v_i)
        return self.head(x), (tuple(new_k), tuple(new_v))


def stack_layer_params(params: dict, n_layers: int) -> dict:
    """Convert a loop-form param tree (``layers_{i}/...``) to the scan form
    (``layers/block/...`` leaves with a leading n_layers axis)."""
    layers = [params[f"layers_{i}"] for i in range(n_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    out = {k: v for k, v in params.items() if not k.startswith("layers_")}
    out["layers"] = {"block": stacked}
    return out


def unstack_layer_params(params: dict, n_layers: int) -> dict:
    """Inverse of :func:`stack_layer_params`."""
    stacked = params["layers"]["block"]
    out = {k: v for k, v in params.items() if k != "layers"}
    for i in range(n_layers):
        out[f"layers_{i}"] = jax.tree_util.tree_map(lambda a: a[i], stacked)
    return out
