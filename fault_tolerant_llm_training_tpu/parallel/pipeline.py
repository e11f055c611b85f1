"""Pipeline parallelism over the mesh's ``pipe`` axis: 1F1B and GPipe.

No reference counterpart (SURVEY.md §2.3: the reference has no parallelism
at all) — this is a beyond-parity scale-out path completing the mesh
portfolio (dp / pp / fsdp / sp / tp). TPU-native design shared by both
schedules:

- layer-stacked (scan-form) params are sharded over ``pipe`` on their
  leading layer axis by the path rules (parallel/sharding.py), so stage
  ``s`` *stores* only layers ``[s*L/P, (s+1)*L/P)`` — the memory win that
  motivates PP;
- the trunk runs under a partial-manual ``shard_map`` (``axis_names=
  {'pipe'}``): the pipe axis is hand-scheduled while data/fsdp/tensor
  shardings stay with the auto partitioner, so PP composes with DP/FSDP/TP
  without manual collectives for them;
- microbatches flow stage-to-stage via ``lax.ppermute``; the vocab axis
  shards over ``('pipe', 'tensor')`` (parallel/sharding.py) so every stage
  stores only its slice of the embed table / head weight and computes only
  its slice of any (.., S, V) logits — one head matmul total across the
  mesh.

Two schedules:

**1F1B** (:func:`pipeline_value_and_grad`, the training default): one
combined forward+backward tick loop of ``M + 2P - 1`` ticks. The head+CE
for microbatch ``m`` runs *inside* the loop the moment ``m``'s forward
leaves the last stage (a vocab-sharded online-softmax whose (m, l, picked)
stats merge with small (mb, S) psums over 'pipe' — the same algebra as
ops/fused_ce.py, which it reuses), so ``m``'s backward starts ``P`` ticks
later while later microbatches are still in forward flight. Consequences:

- trunk activation memory is O(P) in microbatches: each stage stashes at
  most ``2P-1`` microbatch *inputs* (a ring buffer) and recomputes its
  block internals during the backward tick (full-stage rematerialization
  — the same fwd+bwd work as GPipe-with-remat, ~4/3 the FLOPs of
  GPipe-without-remat), instead of the GPipe schedule's autodiff storing
  all ``M+P-1`` ticks of residuals. (The embed boundary and its
  cotangent remain O(B) full-batch buffers — they exist under any
  schedule, since embed and its backward run out-of-line.);
- logits exist only per-microbatch and per-vocab-shard: (mb, S, block)
  fp32 transients instead of the (B, S, V/P) fp32 tensor the out-of-line
  head materializes — at the reference's 131k vocab this is the larger win;
- gradients are assembled *explicitly* (the tick loop is never
  differentiated): stage-local layer grads accumulate in fp32 carries and
  leave sharded over 'pipe'; the boundary activations travel bf16 through
  the ppermutes (only psums are fp32 — bf16 psum trips an XLA partitioner
  CHECK, ROUND_NOTES.md);
- MoE router aux losses ride along naturally: each stage's forward tick
  accumulates its layers' sown aux (weighted by the microbatch's valid
  tokens — exactly the grad-accum semantics of training/step.py), and the
  backward tick's VJP carries the constant aux cotangent, so pp composes
  with MoE/ep.

**GPipe** (:func:`pipeline_hidden` / :func:`pipeline_apply`): the forward
tick scan of ``M + P - 1`` ticks with the head applied out-of-line; kept as
the eval/forward path and as the ``--pp-schedule gpipe`` fallback whose
autodiff yields the reverse pipeline (memory O(M)).

The jitted results compute exactly the same function as the plain trunk
(tests/test_pipeline.py pins loss/trajectory equivalence on the CPU mesh).

**Analytic bubble / efficiency model (SPMD lockstep).** Let F and B be one
stage's forward and backward tick cost (B ~ 2F). Every device executes the
same compiled tick body, so a tick costs F+B wall whether or not this
stage has work that tick (idle slots are zero-masked compute, not idle
time — the price of single-program pipelining on an SPMD compiler):

- 1F1B runs ``M + 2P - 1`` combined ticks -> wall = (M+2P-1)(F+B);
  bubble fraction = (2P-1)/(M+2P-1)  [M=8, P=2: 27%; M=16: 16%; M=32: 9%]
- GPipe runs an (M+P-1)-tick forward scan at F plus its autodiff reverse
  at B -> wall = (M+P-1)(F+B); bubble = (P-1)/(M+P-1)
  [M=8, P=2: 11%; M=16: 6%]

So in this SPMD formulation 1F1B pays P extra bubble ticks of wall
relative to GPipe — analytically (M+2P-1)/(M+P-1) = 1.22x at M=8/P=2,
1.12x at M=16/P=2. Measured on the 8-virtual-device CPU mesh
(scripts/pp_bench.py, dim-256 4-layer model): **1.26x and 1.15x** — the
analytic model tracks within 3-4%, the excess being the in-loop head+CE
and stash-ring bookkeeping. (The asynchronous-dispatch 1F1B of GPU
frameworks has no such penalty because stages genuinely idle rather than
execute masked ticks.) Its win is MEMORY: 0.145x GPipe's activation
allocation at M=8/P=2 (test_pipeline_1f1b_activation_memory), plus
per-microbatch per-vocab-shard logits — 1F1B is the default because
activation memory, not wall, is what kills long-context/deep-model PP
configs, and the wall gap closes as 1/M. Use ``--pp-schedule gpipe``
when M is small and memory is not binding.
"""

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import active_mesh


def pipeline_hidden(model, params, x, positions, mesh=None,
                    microbatches: int = 0) -> jax.Array:
    """Run the scan-form trunk through the GPipe schedule.

    ``x``: (B, S, D) embedded activations (global view); returns the final
    hidden states (B, S, D). Caller applies embed before and head after.
    """
    from ..models.llama import TransformerBlock

    mesh = mesh or active_mesh()
    pp = mesh.shape["pipe"]
    n_micro = microbatches or pp
    cfg = model.cfg
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp {pp}")
    if x.shape[0] % n_micro:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by microbatches {n_micro}")

    from flax import linen as nn

    block_cls = TransformerBlock
    if cfg.remat:
        block_cls = nn.remat(TransformerBlock, prevent_cse=False,
                             static_argnums=())
    block = block_cls(cfg)
    stacked = params["layers"]["block"]

    def local_layers(stack_local, h, pos):
        return _stage_layers(block, cfg, stack_local, h, pos,
                             collect_aux=False)[0]

    compute_dtype = x.dtype
    b, seq, d = x.shape
    mb = b // n_micro

    # Split into microbatches OUTSIDE the manual region, pad with the pp-1
    # drain ticks, and pin the sharding explicitly: the scan below then
    # consumes its xs natively (no dynamic_index over an axis the reshape
    # silently left batch-sharded — that indexing forced the partitioner
    # into an involuntary full rematerialization per tick). The constraint
    # puts the batch sharding on the per-microbatch batch dim when it
    # divides, and degrades to explicit (voluntary) replication when it
    # does not (tiny dryrun shapes).
    from ..parallel.sharding import constrain, suspend_constraints
    micro = x.astype(jnp.float32).reshape(n_micro, mb, seq, d)
    micro = jnp.concatenate(
        [micro, jnp.zeros((pp - 1, mb, seq, d), jnp.float32)], axis=0)
    micro = constrain(micro, None, "batch", None, None)

    def body(stack_local, micro, pos):
        s = jax.lax.axis_index("pipe")
        # boundary values travel in fp32: the cotangent of a replicated
        # (P()) shard_map input is accumulated with a psum over 'pipe', and
        # bf16 psums inside a partial-manual shard_map trip an XLA
        # partitioner CHECK (jax 0.9 / XLA CPU) — compute stays bf16
        micro = micro.astype(compute_dtype)
        ring = [(i, (i + 1) % pp) for i in range(pp)]

        # One lax.scan over the ticks (not an unrolled Python loop): the
        # layer scan inside is traced once, keeping compile time O(1) in
        # microbatches — the same reason the trunk itself is scanned.
        # Stage 0 injects microbatch t at tick t; stage P-1 emits finished
        # microbatch t-P+1, so the stacked ys hold them from tick P-1 on.
        def tick(recv, x_t):
            xin = jnp.where(s == 0, x_t, recv)
            out = local_layers(stack_local, xin, pos)
            recv = jax.lax.ppermute(out, "pipe", ring)
            return recv, out

        recv = jnp.zeros((mb, seq, d), compute_dtype)
        _, outs = jax.lax.scan(tick, recv, micro)
        outs = outs[pp - 1:]  # (n_micro, mb, seq, d), static slice
        outs = jnp.where(s == pp - 1, outs, jnp.zeros((), compute_dtype))
        # broadcast the last stage's result to every stage; fp32 for the
        # same partitioner reason as above, and it doubles as the fp32
        # boundary on the way out
        outs = jax.lax.psum(outs.astype(jnp.float32), "pipe")
        return outs.reshape(b, seq, d)

    stack_specs = jax.tree_util.tree_map(
        lambda leaf: P("pipe"), stacked)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(stack_specs, P(), P()),
                   out_specs=P(), axis_names={"pipe"}, check_vma=False)
    with suspend_constraints():
        # constraints inside the manual region would stamp all-auto-mesh
        # shardings that break the shard_map transpose (see sharding.py)
        hidden = fn(stacked, micro, positions)
    return hidden.astype(x.dtype)


def pipeline_apply(model, params, tokens, mesh=None,
                   microbatches: int = 0) -> jax.Array:
    """Full forward (embed -> pipelined trunk -> head) -> logits."""
    x = model.apply({"params": params}, tokens, method="embed")
    positions = model.default_positions(tokens.shape[1])
    hidden = pipeline_hidden(model, params, x, positions, mesh=mesh,
                             microbatches=microbatches)
    return model.apply({"params": params}, hidden, method="head")


def _stage_layers(block, cfg, stack_local, h, pos, collect_aux):
    """Apply one stage's slice of the layer stack to ``h``.

    Shared by the GPipe forward (pipeline_hidden) and the 1F1B tick loop
    (pipeline_value_and_grad) so their per-layer application can never
    diverge. Control flow follows ``cfg.pp_stage_unroll`` (default on):
    a static Python unroll over ``tree[i]`` slices — measured 22.5%
    faster than the lax.scan form on the chip and 20% through the full
    1F1B step on the CPU mesh (configs.py) — or the lax.scan form
    (O(1) compile in stage depth). ``collect_aux`` accumulates the MoE
    routers' sown aux. Returns (h_out, summed aux — 0.0 when not
    collecting)."""
    if cfg.pp_stage_unroll:
        aux = jnp.zeros((), jnp.float32)
        n_local = jax.tree_util.tree_leaves(stack_local)[0].shape[0]
        for i in range(n_local):
            layer_params = jax.tree_util.tree_map(lambda a: a[i],
                                                  stack_local)
            if collect_aux:
                h, mut = block.apply({"params": layer_params}, h, pos,
                                     mutable=["losses"])
                aux = aux + sum(jnp.sum(leaf) for leaf in
                                jax.tree_util.tree_leaves(mut))
            else:
                h = block.apply({"params": layer_params}, h, pos)
        return h, aux
    if collect_aux:
        def step(carry, layer_params):
            h, aux = carry
            out, mut = block.apply({"params": layer_params}, h, pos,
                                   mutable=["losses"])
            aux = aux + sum(jnp.sum(leaf) for leaf in
                            jax.tree_util.tree_leaves(mut))
            return (out, aux), None

        (h, aux), _ = jax.lax.scan(
            step, (h, jnp.zeros((), jnp.float32)), stack_local)
        return h, aux

    def step(c, layer_params):
        return block.apply({"params": layer_params}, c, pos), None

    out, _ = jax.lax.scan(step, h, stack_local)
    return out, jnp.zeros((), jnp.float32)


def _rmsnorm(scale, h, eps):
    """Functional twin of models/llama.py RMSNorm (fp32 internal, cast
    back, then scale) for the in-loop tail's explicit VJP."""
    hf = h.astype(jnp.float32)
    normed = hf * jax.lax.rsqrt(
        jnp.mean(hf * hf, axis=-1, keepdims=True) + eps)
    return normed.astype(h.dtype) * scale.astype(h.dtype)


def pipeline_value_and_grad(model, params, tokens, labels, mesh=None,
                            microbatches: int = 0):
    """1F1B train step core: ``((loss, num_valid), grads)``.

    Drop-in for ``jax.value_and_grad(loss_fn, has_aux=True)`` when the
    trunk is pipelined (training/step.py dispatches here). The tick loop
    is never differentiated; see the module docstring for the schedule.

    Lockstep timetable (stage ``s``, microbatch ``m``, ``P`` stages,
    ``M`` microbatches, one combined fwd+bwd slot per tick ``t``):

    - forward of ``m`` at stage ``s``:  ``t = s + m``  (GPipe issue rate)
    - head+CE (all stages, vocab-sharded) for ``m``: ``t = m + P - 1``
    - backward of ``m`` at stage ``s``: ``t = m + 2P - 1 - s``

    so ``T = M + 2P - 1`` ticks total and a stage holds at most ``2P-1``
    stashed microbatch inputs — O(P) trunk residuals, independent of M
    (the embed boundary/cotangent buffers stay O(B)). Loss semantics
    match grad accumulation (training/step.py): per-token 1/N cotangents
    with N the global valid count, and per-microbatch MoE aux weighted by
    the microbatch's valid tokens.
    """
    from flax import linen as nn

    from ..models.llama import TransformerBlock
    from ..ops.cross_entropy import DEFAULT_BLOCK
    from ..ops.fused_ce import _bwd_accum, _raw_stats
    from ..parallel.sharding import (
        constrain,
        suspend_constraints,
        vocab_shard_axes,
    )
    from ..training.step import IGNORE_INDEX

    mesh = mesh or active_mesh()
    pp = mesh.shape["pipe"]
    cfg = model.cfg
    n_micro = microbatches or pp
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp {pp}")
    if tokens.shape[0] % n_micro:
        raise ValueError(
            f"batch {tokens.shape[0]} not divisible by microbatches "
            f"{n_micro}")
    expected = {"tok_embeddings", "layers", "norm", "output"}
    if set(params) != expected:
        raise ValueError(
            f"pipelined grads cover params {sorted(expected)}; tree has "
            f"{sorted(params)}")

    b, seq = tokens.shape

    # ---- embed, out-of-line under the auto partitioner; its VJP turns the
    # pipeline's x-cotangent into the (vocab-sharded) table gradient
    emb_params = {"tok_embeddings": params["tok_embeddings"]}

    def embed_fn(ep):
        merged = dict(params, **ep)
        return model.apply({"params": merged}, tokens, method="embed")

    x, embed_vjp = jax.vjp(embed_fn, emb_params)
    positions = model.default_positions(seq)
    compute_dtype = x.dtype
    d = x.shape[-1]
    mb = b // n_micro

    valid = labels != IGNORE_INDEX
    num_valid = jnp.sum(valid)
    nf = jnp.maximum(num_valid.astype(jnp.float32), 1.0)
    safe_labels = jnp.where(valid, labels, 0)

    micro = x.reshape(n_micro, mb, seq, d)
    labels_m = safe_labels.reshape(n_micro, mb, seq)
    vmask_m = valid.reshape(n_micro, mb, seq)
    n_per_micro = jnp.sum(vmask_m, axis=(1, 2)).astype(jnp.float32)  # (M,)

    n_ticks = n_micro + 2 * pp - 1
    n_slots = 2 * pp - 1  # stash ring capacity = max in-flight microbatches

    # xs, padded to the tick count and pinned batch-sharded on the mb dim
    # (same reasoning as the GPipe path above): microbatch m enters stage 0
    # at tick m; labels/vmask align with the head tick m + P - 1, vmask's
    # False padding doubles as the "no head work this tick" gate.
    micro_xs = jnp.concatenate(
        [micro, jnp.zeros((n_ticks - n_micro, mb, seq, d), micro.dtype)], 0)
    micro_xs = constrain(micro_xs, None, "batch", None, None)
    labels_xs = jnp.concatenate(
        [jnp.zeros((pp - 1, mb, seq), labels_m.dtype), labels_m,
         jnp.zeros((pp, mb, seq), labels_m.dtype)], 0)
    vmask_xs = jnp.concatenate(
        [jnp.zeros((pp - 1, mb, seq), bool), vmask_m,
         jnp.zeros((pp, mb, seq), bool)], 0)
    ticks = jnp.arange(n_ticks, dtype=jnp.int32)

    # ---- head weight view: (D, V) -> (D, pipe_shards, Vl). 'pipe' is the
    # MAJOR vocab axis (parallel/sharding.py) so this reshape is
    # reshard-free and stage s's slice is the contiguous [s*Vl, (s+1)*Vl);
    # any 'tensor' sub-sharding stays auto inside the slice.
    # Cast the head weight to the COMPUTE dtype, mirroring nn.Dense
    # (dtype=cfg.dtype) and the fused-CE path (training/step.py casts
    # head_w the same way): under mixed precision (fp32 master params,
    # bf16 compute) the in-loop head must round w exactly where the
    # single-device path does, or the pipelined trajectory diverges from
    # the path it claims to reproduce (ADVICE r3). dw is assembled in
    # fp32 and cast to the param dtype on return, same as autodiff of
    # the cast would produce.
    w = params["output"]["kernel"].astype(cfg.dtype)
    v = w.shape[1]
    vaxes = vocab_shard_axes(w.shape, mesh)
    # When the vocab dim is indivisible by pp (degenerate configs only —
    # every real preset's vocab divides the pipe sizes in use), the weight
    # arrives pipe-replicated and every stage runs the full-vocab tail
    # redundantly (P× head FLOPs). Accepted: gating the tail per stage
    # would need divergent lax.conds around auto-axis collectives.
    pipe_shards = pp if "pipe" in vaxes else 1
    tensor_on_vocab = "tensor" in vaxes
    vl = v // pipe_shards
    w3 = w.reshape(d, pipe_shards, vl)
    w_spec = P(None, "pipe" if pipe_shards > 1 else None, None)
    # Blocked local head when the slice is big and unsharded; dense when
    # 'tensor' co-shards it (blocked dynamic slicing over a sharded vocab
    # would make the partitioner gather — same rule as cross_entropy_loss)
    # or when it is small anyway.
    blocked = (not tensor_on_vocab) and vl > DEFAULT_BLOCK
    scale = params["norm"]["scale"]
    stacked = params["layers"]["block"]
    stack_specs = jax.tree_util.tree_map(lambda leaf: P("pipe"), stacked)
    aux_weight = float(cfg.moe_aux_weight) if cfg.moe_experts else 0.0

    block_cls = TransformerBlock
    if cfg.remat:
        block_cls = nn.remat(TransformerBlock, prevent_cse=False,
                             static_argnums=())
    block = block_cls(cfg)

    def stage_fn(stack_local, h, pos):
        """This stage's layers; returns (h_out, summed router aux)."""
        return _stage_layers(block, cfg, stack_local, h, pos,
                             collect_aux=bool(cfg.moe_experts))

    def local_head_stats(h_norm, labels_loc, w_local):
        if blocked:
            return _raw_stats(h_norm, w_local, labels_loc, DEFAULT_BLOCK)
        lf = jnp.dot(h_norm, w_local, preferred_element_type=jnp.float32)
        m = jnp.max(lf, axis=-1)
        l = jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)
        hit = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
               == labels_loc[..., None])
        picked = jnp.sum(jnp.where(hit, lf, 0.0), axis=-1)
        return m, l, picked

    def local_head_bwd(h_norm, labels_loc, w_local, lse, gtok):
        if blocked:
            return _bwd_accum(h_norm, w_local, labels_loc, lse, gtok,
                              DEFAULT_BLOCK, dw_dtype=jnp.float32)
        lf = jnp.dot(h_norm, w_local, preferred_element_type=jnp.float32)
        p = jnp.exp(lf - lse[..., None])
        onehot = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
                  == labels_loc[..., None])
        ds = (gtok[..., None] * (p - onehot.astype(jnp.float32))
              ).astype(h_norm.dtype)
        dh = jnp.einsum("bsv,dv->bsd", ds, w_local,
                        preferred_element_type=jnp.float32)
        dw = jnp.einsum("bsd,bsv->dv", h_norm, ds,
                        preferred_element_type=jnp.float32)
        return dh, dw

    def body(stack_local, w3_local, scale_p, pos, micro_xs, labels_xs,
             vmask_xs, ticks, n_arr):
        s = jax.lax.axis_index("pipe")
        w_local = w3_local.reshape(d, vl)
        v0 = jnp.where(pipe_shards > 1, s * vl, 0)
        fwd_ring = [(i, (i + 1) % pp) for i in range(pp)]
        bwd_ring = [(i, (i - 1) % pp) for i in range(pp)]

        def tick(carry, xs_t):
            (fwd_recv, bwd_recv, hbar, stash, xbar, dstack, dw, dscale,
             nll_acc, aux_acc) = carry
            x_t, lab_t, vm_t, t = xs_t

            # ---- backward of microbatch m_b (reads the stash slot that
            # this tick's forward may immediately reuse — order matters)
            m_b = t - (2 * pp - 1) + s
            b_on = (m_b >= 0) & (m_b < n_micro)
            slot_b = jnp.where(b_on, m_b % n_slots, 0)
            x_saved = jax.lax.dynamic_index_in_dim(stash, slot_b, 0,
                                                   keepdims=False)
            g_in = jnp.where(s == pp - 1, hbar, bwd_recv)
            g_in = jnp.where(b_on, g_in, jnp.zeros_like(g_in))
            n_b = jax.lax.dynamic_index_in_dim(
                n_arr, jnp.clip(m_b, 0, n_micro - 1), 0, keepdims=False)
            # VJPs are linear in the cotangent: zeroed (g_in, aux_ct) on
            # off-schedule ticks yield exactly-zero grad contributions, so
            # no masking of the accumulators is needed.
            aux_ct = jnp.where(b_on, aux_weight * n_b / nf, 0.0)
            _, vjp_fn = jax.vjp(
                lambda sl, h: stage_fn(sl, h, pos), stack_local, x_saved)
            dstack_i, dx = vjp_fn((g_in, aux_ct))
            dstack = jax.tree_util.tree_map(
                lambda a, gi: a + gi.astype(jnp.float32), dstack, dstack_i)
            # stage 0's dx is the embed cotangent; park it in the (M+1)-row
            # buffer (row M is the spill row for every masked write, so the
            # update runs unconditionally — no full-buffer select per tick)
            wr = jnp.where((s == 0) & b_on,
                           jnp.clip(m_b, 0, n_micro - 1), n_micro)
            xbar = jax.lax.dynamic_update_index_in_dim(xbar, dx, wr, 0)

            # ---- forward of microbatch m_f
            m_f = t - s
            f_on = (m_f >= 0) & (m_f < n_micro)
            xin = jnp.where(s == 0, x_t, fwd_recv)
            out_f, aux_f = stage_fn(stack_local, xin, pos)
            n_f = jax.lax.dynamic_index_in_dim(
                n_arr, jnp.clip(m_f, 0, n_micro - 1), 0, keepdims=False)
            aux_acc = aux_acc + jnp.where(f_on, aux_f * n_f, 0.0)
            wrf = jnp.where(f_on, m_f % n_slots, n_slots)  # spill row
            stash = jax.lax.dynamic_update_index_in_dim(stash, xin, wrf, 0)

            # ---- head+CE for m_t = t - (P-1), whose forward just left the
            # last stage. All stages participate on their vocab slice; the
            # all-False vmask padding makes off-schedule ticks contribute
            # exact zeros (gtok = 0) with no NaN hazard (stats stay finite
            # on any input). psums are fp32 (bf16 psum trips XLA).
            h_m = jax.lax.psum(
                jnp.where(s == pp - 1, out_f, 0).astype(jnp.float32),
                "pipe").astype(compute_dtype)
            h_norm, norm_vjp = jax.vjp(
                lambda sc, h: _rmsnorm(sc, h, cfg.norm_eps), scale_p, h_m)
            labels_loc = lab_t - v0
            m_l, l_l, picked_l = local_head_stats(h_norm, labels_loc,
                                                  w_local)
            if pipe_shards > 1:
                m_g = jax.lax.pmax(m_l, "pipe")
                l_g = jax.lax.psum(l_l * jnp.exp(m_l - m_g), "pipe")
                picked_g = jax.lax.psum(picked_l, "pipe")
            else:
                m_g, l_g, picked_g = m_l, l_l, picked_l
            lse = m_g + jnp.log(l_g)
            nll_acc = nll_acc + jnp.sum(
                jnp.where(vm_t, lse - picked_g, 0.0))
            gtok = jnp.where(vm_t, 1.0, 0.0) / nf
            dh_norm, dw_i = local_head_bwd(h_norm, labels_loc, w_local,
                                           lse, gtok)
            dw = dw + dw_i
            if pipe_shards > 1:
                dh_norm = jax.lax.psum(dh_norm, "pipe")
            dscale_i, dh_m = norm_vjp(dh_norm.astype(h_norm.dtype))
            dscale = dscale + dscale_i.astype(jnp.float32)

            fwd_recv = jax.lax.ppermute(out_f, "pipe", fwd_ring)
            bwd_recv = jax.lax.ppermute(dx, "pipe", bwd_ring)
            return (fwd_recv, bwd_recv, dh_m, stash, xbar, dstack, dw,
                    dscale, nll_acc, aux_acc), None

        zeros_act = jnp.zeros((mb, seq, d), compute_dtype)
        init = (
            zeros_act, zeros_act, zeros_act,
            jnp.zeros((n_slots + 1, mb, seq, d), compute_dtype),
            jnp.zeros((n_micro + 1, mb, seq, d), compute_dtype),
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), stack_local),
            jnp.zeros((d, vl), jnp.float32),
            jnp.zeros((d,), jnp.float32),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        carry, _ = jax.lax.scan(
            tick, init, (micro_xs, labels_xs, vmask_xs, ticks))
        (_, _, _, _, xbar, dstack, dw, dscale, nll_acc, aux_acc) = carry
        # only stage 0 wrote real rows into xbar; fp32 psum broadcasts them
        # (the one place the boundary leaves bf16 — same rule as GPipe's
        # final broadcast above). nll/dscale are already stage-uniform.
        xbar_sum = jax.lax.psum(xbar[:n_micro].astype(jnp.float32), "pipe")
        aux_total = jax.lax.psum(aux_acc, "pipe")
        return (xbar_sum, dstack, dw[:, None, :], dscale, nll_acc,
                aux_total)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(stack_specs, w_spec, P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), stack_specs, w_spec, P(), P(), P()),
        axis_names={"pipe"}, check_vma=False)
    with suspend_constraints():
        xbar, dstack, dw3, dscale, sum_nll, aux_total = fn(
            stacked, w3, scale, positions, micro_xs, labels_xs, vmask_xs,
            ticks, n_per_micro)

    loss = (sum_nll + aux_weight * aux_total) / nf
    (demb,) = embed_vjp(xbar.astype(compute_dtype).reshape(b, seq, d))
    grads = {
        "tok_embeddings": demb["tok_embeddings"],
        "layers": {"block": jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), dstack, stacked)},
        "norm": {"scale": dscale.astype(scale.dtype)},
        # .astype targets the PARAM dtype (w above is the compute-dtype
        # cast view, which may differ under --master-weights fp32)
        "output": {"kernel": dw3.reshape(d, v).astype(
            params["output"]["kernel"].dtype)},
    }
    return (loss, num_valid), grads
