"""The jitted training step (ref hot loop: train.py:92-117).

Everything the reference does per step — forward, sum-reduced fp32
cross-entropy normalized by the valid-token count, backward, global-norm clip,
AdamW + schedule — is one pure function compiled once by XLA. The reference's
``torch.compile`` flag (train.py:61-63) has no equivalent switch: compilation
is the default mode on TPU, not an option.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
import optax

from ..obs.trace import scope
from ..ops.ring_attention import zigzag_layout_active, zigzag_perm
from ..parallel.mesh import mesh_axis_size
from ..training.state import TrainState
from ..utils.grad_clip import clip_grads_with_norm

IGNORE_INDEX = -100  # ref: dataset.py:50, train.py:94,101


@scope("loss_head")
def masked_mean_nll(nll, labels) -> Tuple[jax.Array, jax.Array]:
    """Sum per-token nll over non-ignored labels / their count (the
    reference's loss normalization, train.py:94,101-102) — the single
    assembly shared by every CE form. Returns (loss, num_valid)."""
    valid = labels != IGNORE_INDEX
    num_valid = jnp.sum(valid)
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(num_valid, 1)
    return loss, num_valid


@scope("loss_head")
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ce_block: int | None = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Sum-reduced fp32 CE over flattened (B*S, V) logits, divided by the
    number of non-ignored label tokens (ref: train.py:94,101-102).

    ``ce_block``: None = auto (vocab-blocked CE at vocab >= 64k, dense
    below); 0 = force dense; >0 = force that vocab block size. The blocked
    path (ops/cross_entropy.py) never materializes a (B, S, V) fp32 tensor
    — at the reference's 131k vocab the fp32 logits cast is the largest
    tensor in the step. When the vocab axis is actually SHARDED (tensor /
    pipe meshes), auto stays dense: the dense form below is gather-free
    and partitions cleanly, while the blocked slicing would make the
    partitioner all-gather the logits.

    Returns (loss, num_valid_tokens).
    """
    from ..ops.cross_entropy import (
        AUTO_THRESHOLD,
        DEFAULT_BLOCK,
        chunked_softmax_xent,
    )
    from ..parallel.sharding import shard_size
    valid = labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, labels, 0)
    if ce_block is None:
        v = logits.shape[-1]
        ce_block = (DEFAULT_BLOCK if v >= AUTO_THRESHOLD
                    and shard_size(v, "vocab") == 1 else 0)
    if ce_block:
        nll = chunked_softmax_xent(logits, safe_labels, ce_block)
    elif shard_size(logits.shape[-1], "vocab") > 1:
        # Vocab-sharded logits (tensor / pipe meshes): pick the label logit
        # with a masked iota reduction — every op partitions cleanly, where
        # a take_along_axis gather over the sharded vocab would force the
        # partitioner to all-gather the logits.
        lf = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(lf, axis=-1))
        lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
        hit = (jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
               == safe_labels[..., None])
        picked = jnp.sum(jnp.where(hit, lf, 0.0), axis=-1)
        nll = lse - picked
    else:
        # logsumexp-minus-picked-logit form: identical to
        # -log_softmax[label] but the V axis is reduced away immediately
        # (no (B, S, V) fp32 log-probability tensor; SURVEY.md §2.2).
        # Measured ~1% faster than the iota form on the single-chip
        # headline bench, so the replicated-vocab case keeps it.
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), safe_labels)
    return masked_mean_nll(nll, labels)


def make_optimizer(learning_rate: float, warmup_steps: int,
                   lr_schedule: str = "constant", decay_steps: int = 0
                   ) -> optax.GradientTransformation:
    """AdamW with torch defaults (ref: train.py:68 uses torch.optim.AdamW
    defaults: betas (0.9, 0.999), eps 1e-8, weight_decay 0.01) under the
    reference's linear-warmup-constant schedule (ref: utils.py:32-56), or
    warmup-cosine (``lr_schedule="cosine"``, decaying over ``decay_steps``
    — a beyond-parity option). Gradient clipping is applied *before* this
    transform with the torch coefficient semantics (utils/grad_clip.py)."""
    from ..utils.schedules import build_schedule
    schedule = build_schedule(learning_rate, warmup_steps, lr_schedule,
                              decay_steps)
    return optax.adamw(learning_rate=schedule, b1=0.9, b2=0.999, eps=1e-8,
                       weight_decay=0.01)


def model_loss(model, params, inputs, labels, microbatches: int = 0,
               train: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Forward + CE, shared by the train and eval steps (so the sequence-
    layout, pipeline, and MoE handling below can never diverge between
    them). With MoE and ``train=True`` the routers' load-balancing aux
    losses (sown into the 'losses' collection, models/moe.py) are added
    with weight ``cfg.moe_aux_weight``; eval reports pure CE.

    Returns (mean loss, num_valid_tokens)."""
    loss, (num_valid, _) = loss_and_stats(model, params, inputs, labels,
                                          microbatches, train)
    return loss, num_valid


def loss_and_stats(model, params, inputs, labels, microbatches: int = 0,
                   train: bool = True):
    """:func:`model_loss`'s forward + CE, returning also what the forward
    sowed into the 'stats' collection: (mean loss, (num_valid_tokens, a
    tuple of the sown arrays, empty where the model sows none)). The
    latent / expert class sows its expert layers' (pairs, touched)."""
    cfg = getattr(model, "cfg", None)
    sp = mesh_axis_size("sequence")
    if (cfg is not None and cfg.layer_impl == "scan"
            and mesh_axis_size("pipe") > 1):
        if cfg.moe_experts and train:
            # Only the GPipe-schedule TRAIN path lands here (1F1B trains
            # via pipeline_value_and_grad, which carries the aux; eval
            # reports pure CE and needs no aux). Guard at the point of the
            # drop, not only in the Trainer.
            raise NotImplementedError(
                "--pp-schedule gpipe with an MoE model would silently "
                "drop the router load-balancing loss; use the 1f1b "
                "schedule (the default)")
        from ..parallel.pipeline import pipeline_apply
        logits = pipeline_apply(model, params, inputs,
                                microbatches=microbatches)
        loss, num_valid = cross_entropy_loss(logits, labels)
        return loss, (num_valid, ())
    args = ()
    if cfg is not None and zigzag_layout_active(cfg, inputs.shape[1], sp):
        # Zigzag sequence layout (ops/ring_attention.py): permute the
        # token stream once so each sequence shard holds one early + one
        # mirrored late chunk; RoPE gets true positions, and the summed
        # CE below is permutation-invariant, so only attention's ring
        # schedule sees the layout.
        perm = jnp.asarray(zigzag_perm(inputs.shape[1], sp))
        inputs, labels = inputs[:, perm], labels[:, perm]
        args = (jnp.broadcast_to(perm[None, :], inputs.shape),)
    from ..ops.cross_entropy import AUTO_THRESHOLD
    from ..ops.fused_ce import (
        auto_min_bytes,
        fused_head_xent,
        sharded_fused_head_xent,
    )
    from ..parallel.sharding import shard_size
    # Per-DEVICE logits + cotangent footprint: batch, seq AND vocab shard
    # over their mesh axes, so the global product overestimates on
    # multi-chip meshes (OOM is a per-device phenomenon).
    vocab_shards = (shard_size(cfg.vocab_size, "vocab")
                    if cfg is not None else 1)
    logits_bytes = (
        inputs.shape[0] // shard_size(inputs.shape[0], "batch")
        * (inputs.shape[1] // shard_size(inputs.shape[1], "seq"))
        * (cfg.vocab_size // vocab_shards if cfg is not None else 0) * 6)
    fused = (cfg is not None and cfg.vocab_size >= AUTO_THRESHOLD
             and logits_bytes > auto_min_bytes())

    # One forward (with the MoE routers' sown aux when training), one loss
    # assembly — the fused path only changes WHICH function maps the
    # forward's output to per-token nll, so masking/normalization and the
    # aux handling cannot diverge between the paths.
    method = "hidden_states" if fused else None
    with_aux = bool(getattr(cfg, "moe_experts", 0)) and train
    out, mutated = model.apply(
        {"params": params}, inputs, *args, method=method,
        mutable=["stats", "losses"] if with_aux else ["stats"])
    aux = (sum(jnp.sum(leaf) for leaf in
               jax.tree_util.tree_leaves(mutated["losses"]))
           if with_aux else None)
    stats = tuple(jax.tree_util.tree_leaves(mutated.get("stats", {})))
    if fused:
        # Large vocab whose per-device logits + cotangent would not fit:
        # block the head matmul into the loss (ops/fused_ce.py) — logits
        # never materialize in any dtype. A sharded vocab axis (tensor /
        # pipe meshes) takes the shard_map form whose online stats fold
        # across the shards. See AUTO_MIN_BYTES for the measured tradeoff.
        head_w = params["output"]["kernel"].astype(cfg.dtype)
        safe = jnp.where(labels == IGNORE_INDEX, 0, labels)
        xent = (sharded_fused_head_xent if vocab_shards > 1
                else fused_head_xent)
        with scope("loss_head"):
            nll = xent(out, head_w, safe,
                       min(8192, head_w.shape[1] // vocab_shards))
        loss, num_valid = masked_mean_nll(nll, labels)
    else:
        loss, num_valid = cross_entropy_loss(out, labels)
    if aux is not None:
        loss = loss + cfg.moe_aux_weight * aux
    return loss, (num_valid, stats)


def make_eval_step(model, microbatches: int = 0, grad_accum: int = 1):
    """Forward-only loss for held-out evaluation (no reference counterpart —
    the reference never evaluates; SURVEY.md §5.5 notes loss is its only
    metric). Returns packed (sum_nll, num_valid) as one fp32 array so the
    host aggregates exactly across batches with one D2H transfer each:
    mean = sum(sum_nll) / sum(num_valid), weighting every token equally
    even when batches carry different pad counts.

    ``grad_accum > 1`` slices the eval batch through the same ``lax.scan``
    accumulation as the train step: a run that needs accumulation to fit
    activation memory must not get an eval pass with a grad_accum-fold
    larger activation footprint at the first --eval-frequency boundary."""

    def eval_one(params, inputs, labels):
        loss, num_valid = model_loss(model, params, inputs, labels,
                                     microbatches, train=False)
        return loss * num_valid, num_valid

    def eval_step(params, inputs, labels):
        if grad_accum <= 1:
            nll, n = eval_one(params, inputs, labels)
            return jnp.stack((nll, n.astype(jnp.float32)))
        b = inputs.shape[0] // grad_accum
        sl_inputs = inputs.reshape(grad_accum, b, *inputs.shape[1:])
        sl_labels = labels.reshape(grad_accum, b, *labels.shape[1:])

        def body(carry, sl):
            nll_acc, n_acc = carry
            nll, n = eval_one(params, sl[0], sl[1])
            return (nll_acc + nll, n_acc + n), None

        (nll, n), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (sl_inputs, sl_labels))
        return jnp.stack((nll, n.astype(jnp.float32)))

    return eval_step


def make_train_step(model, optimizer: optax.GradientTransformation,
                    grad_max_norm: float, microbatches: int = 0,
                    grad_accum: int = 1):
    """Build the pure ``(state, inputs, labels) -> (state, metrics)`` step.

    metrics: loss (fp32), grad_norm (fp32; host checks finiteness — the
    torch ``error_if_nonfinite`` raise cannot live inside jit, ref:
    utils.py:61), num_tokens, and packed = stack((loss, grad_norm)) — the
    single leaf the host loop fetches per step (one D2H transfer). What
    the forward sowed into 'stats' (the latent / expert class: its expert
    layers' pairs and touched experts) is appended to packed, so those
    counters ride the same transfer; under ``grad_accum`` nothing is.
    ``microbatches`` only matters under pipeline parallelism (0 = one
    microbatch per stage).

    ``grad_accum > 1`` splits the batch into that many slices and runs
    them through one ``lax.scan`` (peak activation memory drops by the
    factor), accumulating token-weighted gradients in fp32 — exactly the
    big-batch semantics of the reference's sum-CE / valid-token loss
    (train.py:101-102): slices with more valid tokens weigh more.
    """

    def loss_fn(params, inputs, labels):
        return loss_and_stats(model, params, inputs, labels, microbatches)

    cfg = getattr(model, "cfg", None)
    if (cfg is not None and cfg.layer_impl == "scan"
            and mesh_axis_size("pipe") > 1 and cfg.pp_schedule == "1f1b"):
        # 1F1B assembles gradients explicitly inside its tick loop
        # (parallel/pipeline.py) — autodiff never sees the schedule.
        from ..parallel.pipeline import pipeline_value_and_grad

        def value_and_grad(params, inputs, labels):
            (loss, n), grads = pipeline_value_and_grad(
                model, params, inputs, labels, microbatches=microbatches)
            return (loss, (n, ())), grads
    else:
        value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)

    def accum_value_and_grad(params, inputs, labels):
        if grad_accum <= 1:
            return value_and_grad(params, inputs, labels)
        b = inputs.shape[0] // grad_accum
        sl_inputs = inputs.reshape(grad_accum, b, *inputs.shape[1:])
        sl_labels = labels.reshape(grad_accum, b, *labels.shape[1:])

        def body(carry, sl):
            g_acc, nll_acc, n_acc = carry
            (loss, (n, _)), grads = value_and_grad(params, sl[0], sl[1])
            nf = n.astype(jnp.float32)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32) * nf, g_acc, grads)
            return (g_acc, nll_acc + loss * nf, n_acc + n), None

        init = (jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
        (g_acc, nll, n_tot), _ = jax.lax.scan(body, init,
                                              (sl_inputs, sl_labels))
        denom = jnp.maximum(n_tot.astype(jnp.float32), 1.0)
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / denom).astype(p.dtype), g_acc, params)
        return (nll / denom, (n_tot, ())), grads

    def train_step(state: TrainState, inputs: jax.Array, labels: jax.Array):
        (loss, (num_tokens, stats)), grads = accum_value_and_grad(
            state.params, inputs, labels)
        sown = tuple(v for leaf in stats
                     for v in leaf.astype(jnp.float32).reshape(-1))
        grads, grad_norm = clip_grads_with_norm(grads, grad_max_norm)
        with scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(step=state.step + 1, params=new_params,
                                  opt_state=new_opt_state)
        metrics = {"loss": loss, "grad_norm": grad_norm,
                   "num_tokens": num_tokens,
                   # (loss, grad_norm) as one array: the host loop fetches
                   # this single leaf per step — one device-to-host
                   # transfer instead of one per scalar (training/loop.py).
                   "packed": jnp.stack((loss, grad_norm) + sown)}
        return new_state, metrics

    return train_step
