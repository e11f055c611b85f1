"""Token sampling: greedy / temperature / top-k / top-p.

All pure functions of (logits, key, knobs) so the engine can fold them into
the jitted decode step; per-slot determinism comes from the key derivation
``fold_in(PRNGKey(request_seed), step)`` — restarting a request from its
prompt replays the identical key sequence, so sampled generations are
reproducible across engine restarts exactly like greedy ones
(tests/test_inference.py).

``temperature <= 0`` selects greedy argmax (the scheduler's default), so one
decode program serves mixed greedy/sampled slots without recompilation. How
much of the epilogue runs follows what the batch asks for
(:func:`epilogue_tier`): an all-greedy batch takes the argmax and nothing
else, and only a batch that holds a nucleus request sorts the vocabulary.

Speculative decoding (engine.py spec mode) adds two kernels on the same
filtered distributions:

- :func:`sample_token_with_probs` — the draft model's proposal step; it
  returns the token AND the exact post-filter distribution q it was drawn
  from (greedy: a one-hot), because the verify-side acceptance test needs
  q(d), not the raw logits.
- :func:`spec_accept` — the Leviathan/Chen accept/resample rule, vectorized
  over the k+1 verify positions. With one-hot greedy distributions the
  acceptance test ``u * q(d) < p(d)`` degenerates to exact argmax matching
  and the resample to the target argmax, so the single kernel serves both
  modes and greedy outputs stay BIT-identical to the non-speculative path.
- :func:`tree_accept` — the multi-branch generalization: one walk down a
  flattened draft TREE, greedy longest-accepted-path selection or
  SpecInfer-style recursive rejection per level, emitting the accepted
  path plus one resampled/bonus token.

:class:`AdaptiveK` is the one HOST-side piece here: the controller that
tunes the round width k from live acceptance, colocated with the accept
rule whose statistics drive it (the scheduler owns an instance when
serving opts in with ``--adaptive-spec-k``).
"""

from functools import partial
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import scope


def _top_k_filter(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Keep the k highest logits, -inf the rest (static k: part of the
    compiled program, an engine-level knob rather than a per-request one)."""
    kth = jax.lax.top_k(logits, top_k)[0][-1]
    return jnp.where(logits >= kth, logits, -jnp.inf)


def _top_p_filter(logits: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """Nucleus filter: keep the smallest prefix of the sorted distribution
    whose mass reaches ``top_p`` (always at least the argmax). ``top_p >= 1``
    keeps everything: the input comes back as it is, so a slot that asked
    for no nucleus draws the same token whether or not the sort ran."""
    sorted_logits = jnp.sort(logits)[::-1]
    probs = jax.nn.softmax(sorted_logits)
    cum = jnp.cumsum(probs)
    # token i is kept iff the mass BEFORE it is < top_p (the crossing token
    # is included); monotone cum makes this a prefix
    keep = jnp.sum((cum - probs < top_p).astype(jnp.int32))
    cutoff = sorted_logits[jnp.maximum(keep - 1, 0)]
    # at top_p == 1.0 the test above still drops tail tokens once the
    # float32 cumsum has rounded to 1.0
    return jnp.where((top_p >= 1.0) | (logits >= cutoff), logits, -jnp.inf)


TIERS = ("greedy", "sampled", "nucleus")


def epilogue_tier(temperature, top_p):
    """Index into :data:`TIERS` of the cheapest epilogue that serves every
    slot of a batch (or the one row of a prefill): ``greedy`` — no slot has
    ``temperature > 0``; ``sampled`` — some slot samples and no sampling
    slot has ``top_p < 1``; ``nucleus`` — some sampling slot has
    ``top_p < 1``. One rule for the device's branch (traced arrays) and the
    host's counter (the NumPy arrays ``decode_step`` dispatches), so the
    two cannot drift."""
    xp = jnp if isinstance(temperature, jax.Array) or isinstance(
        top_p, jax.Array) else np
    sampling = xp.asarray(temperature) > 0.0
    nucleus = sampling & (xp.asarray(top_p) < 1.0)
    return (xp.any(sampling).astype(xp.int32)
            + xp.any(nucleus).astype(xp.int32))


def _greedy_token(logits, key, temperature, top_p, top_k=0):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _sampled_token(logits, key, temperature, top_p, top_k=0, nucleus=False):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        scaled = _top_k_filter(scaled, top_k)
    if nucleus:
        scaled = _top_p_filter(scaled, top_p)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


# one row's token under each tier, in TIERS' order. A tier never computes
# another answer for a slot than a dearer tier would: greedy slots are the
# argmax in all three, and a sampling slot with top_p >= 1 hands
# ``categorical`` the same logits with and without the filter.
_TIER_TOKEN = (_greedy_token, _sampled_token,
               partial(_sampled_token, nucleus=True))


def sample_token(logits: jnp.ndarray, key: jax.Array,
                 temperature: jnp.ndarray, top_p: jnp.ndarray,
                 top_k: int = 0) -> jnp.ndarray:
    """One next-token id (int32) from unnormalized ``logits`` (V,) fp32.

    temperature/top_p are traced scalars; top_k is static. The row's own
    :func:`epilogue_tier` picks the branch, so a greedy prefill sorts
    nothing. (Batches go through :func:`sample_slot_tokens`: under a
    ``vmap`` this switch would run every branch.)
    """
    return jax.lax.switch(
        epilogue_tier(temperature, top_p),
        [partial(f, top_k=top_k) for f in _TIER_TOKEN],
        logits, key, temperature, top_p)


def slot_key(seed: jnp.ndarray, step: jnp.ndarray) -> jax.Array:
    """Per-slot, per-step PRNG key: request seed folded by decode step."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


@scope("sample")
def sample_slot_tokens(logits: jnp.ndarray, seeds: jnp.ndarray,
                       steps: jnp.ndarray, temperature: jnp.ndarray,
                       top_p: jnp.ndarray, top_k: int = 0) -> jnp.ndarray:
    """Whole-batch sampling epilogue: (slots, V) fp32 logits -> (slots,)
    int32 tokens, each slot under its own ``slot_key(seed, step)`` stream.

    This is THE sampling epilogue, fused and unfused alike: the decode
    programs (engine.py ``_paged_decode_fn``/``_decode_fn``, the burst
    loop's micro-steps) trace it in-program so the dispatch ends in token
    ids, and the unfused path (``decode_logits`` + host-side sampling)
    calls the very same function on the synced logits. One definition, one
    PRNG schedule — which is why a fused single step's streams bit-match
    the host-sampled ones.

    The batch's :func:`epilogue_tier` is taken ONCE, outside the ``vmap``,
    on the arrays the program is handed anyway, and each branch vmaps its
    per-row function: a ``cond`` under the ``vmap`` would lower to a
    ``select`` that runs every side, which is the whole-vocabulary sort
    for every greedy round.
    """
    def batched(row_token):
        def branch(logits, seeds, steps, temperature, top_p):
            keys = jax.vmap(slot_key)(seeds, steps)
            return jax.vmap(partial(row_token, top_k=top_k))(
                logits, keys, temperature, top_p)
        return branch

    return jax.lax.switch(
        epilogue_tier(temperature, top_p),
        [batched(f) for f in _TIER_TOKEN],
        logits, seeds, steps, temperature, top_p)


def draft_key(seed: jnp.ndarray, step: jnp.ndarray) -> jax.Array:
    """Draft-proposal PRNG stream, disjoint from :func:`slot_key`'s so the
    draft model's sampling never aliases the target's (``step`` here is the
    flat draft micro-step counter ``round * (k + 1) + i``)."""
    return jax.random.fold_in(slot_key(seed, step), 0x5D)


def verify_key(seed: jnp.ndarray, round_: jnp.ndarray) -> jax.Array:
    """Accept/resample PRNG stream for one verify round, disjoint from both
    :func:`slot_key` and :func:`draft_key`."""
    return jax.random.fold_in(slot_key(seed, round_), 0x7E)


def tree_key(seed: jnp.ndarray, round_: jnp.ndarray) -> jax.Array:
    """Accept/resample PRNG stream for one TREE-verify round
    (:func:`tree_accept`), disjoint from :func:`slot_key`,
    :func:`draft_key` and :func:`verify_key` (fold constant 0x3B)."""
    return jax.random.fold_in(slot_key(seed, round_), 0x3B)


def sample_token_with_probs(logits: jnp.ndarray, key: jax.Array,
                            temperature: jnp.ndarray, top_p: jnp.ndarray,
                            top_k: int = 0):
    """Like :func:`sample_token` but also returns the post-filter
    distribution the token was drawn from: softmax of the temperature-scaled,
    top-k/top-p-filtered logits for sampled slots, an exact one-hot at the
    argmax for greedy slots. The speculative accept test is stated on these
    distributions — using raw-softmax q with filtered sampling would bias
    the acceptance ratio."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        scaled = _top_k_filter(scaled, top_k)
    scaled = _top_p_filter(scaled, top_p)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    tok = jnp.where(temperature > 0.0, sampled, greedy)
    probs = jnp.where(temperature > 0.0, jax.nn.softmax(scaled),
                      jax.nn.one_hot(greedy, v, dtype=jnp.float32))
    return tok, probs


def _filtered_probs(logits: jnp.ndarray, temperature: jnp.ndarray,
                    top_p: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Row-wise post-filter target distributions for (S, V) logits; greedy
    rows are exact one-hots (see :func:`sample_token_with_probs`)."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        scaled = jax.vmap(_top_k_filter, in_axes=(0, None))(scaled, top_k)
    scaled = jax.vmap(_top_p_filter, in_axes=(0, None))(scaled, top_p)
    return jnp.where(temperature > 0.0, jax.nn.softmax(scaled, axis=-1),
                     jax.nn.one_hot(greedy, v, dtype=jnp.float32))


def spec_accept(draft_tokens: jnp.ndarray, draft_probs: jnp.ndarray,
                target_logits: jnp.ndarray, key: jax.Array,
                temperature: jnp.ndarray, top_p: jnp.ndarray,
                top_k: int = 0):
    """Speculative accept/resample for ONE slot (the engine vmaps it).

    draft_tokens: (k,) int32 proposals d_1..d_k.
    draft_probs:  (k, V) fp32 — q_i, the distribution d_i was drawn from.
    target_logits: (k+1, V) fp32 — verify-pass logits; row i scores the
                  position AFTER d_i's prefix (row 0 = after the committed
                  context), so row i's filtered distribution p_i is the
                  target's next-token law at d_i's position and row k's is
                  the bonus position past a fully-accepted draft.

    Rule (Leviathan et al. 2023; Chen et al. 2023): accept d_i while
    ``u_i < p_i(d_i) / q_i(d_i)`` holds for the leading run (stated below
    multiplicatively as ``u_i * q_i(d_i) < p_i(d_i)`` — no divide-by-zero);
    at the first rejection emit one token from the residual
    ``norm(max(p_a - q_a, 0))``; on full acceptance emit the bonus token
    from p_k. The emitted prefix is distributed EXACTLY as k+1 sequential
    target samples. Greedy rows make both q and p one-hots: the test
    becomes exact argmax matching (u < 1 always, uniform is [0, 1)) and the
    residual collapses to the target argmax — selected via a ``where`` so
    greedy never consumes gumbel noise and stays bit-exact.

    Returns ``(out_tokens, accepted)``: out_tokens (k+1,) int32 holds the
    a = accepted accepted drafts then the resampled/bonus token at index a
    (tail entries past a are zeros the caller ignores).
    """
    k, v = draft_probs.shape
    greedy_toks = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    p = _filtered_probs(target_logits, temperature, top_p, top_k)  # (k+1, V)
    q_d = jnp.take_along_axis(draft_probs, draft_tokens[:, None], 1)[:, 0]
    p_d = jnp.take_along_axis(p[:k], draft_tokens[:, None], 1)[:, 0]
    u = jax.random.uniform(jax.random.fold_in(key, 0), (k,))
    accept = u * q_d < p_d
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))  # leading-run length
    # residual at the first rejected position (q past row k is zero, so a
    # full accept resolves to the bonus distribution p_k itself)
    q_pad = jnp.concatenate(
        [draft_probs, jnp.zeros((1, v), draft_probs.dtype)], axis=0)
    p_a, q_a = jnp.take(p, a, axis=0), jnp.take(q_pad, a, axis=0)
    resid = jnp.maximum(p_a - q_a, 0.0)
    # resid sums to zero only through numerics (p==q exactly); fall back to
    # p_a so the categorical below stays well-defined
    resid = jnp.where(resid.sum() > 0.0, resid, p_a)
    resampled = jax.random.categorical(
        jax.random.fold_in(key, 1),
        jnp.log(jnp.maximum(resid, 1e-38))).astype(jnp.int32)
    bonus = jnp.where(temperature > 0.0, resampled,
                      jnp.take(greedy_toks, a))
    idx = jnp.arange(k + 1, dtype=jnp.int32)
    d_pad = jnp.concatenate(
        [draft_tokens, jnp.zeros((1,), jnp.int32)], axis=0)
    out = jnp.where(idx < a, d_pad, 0).at[a].set(bonus)
    return out, a


def tree_accept(tree_tokens: jnp.ndarray, draft_probs: jnp.ndarray,
                target_logits: jnp.ndarray, key: jax.Array,
                temperature: jnp.ndarray, top_p: jnp.ndarray,
                child_matrix: jnp.ndarray, depth: int, top_k: int = 0):
    """Tree-speculative accept/resample for ONE slot (the engine vmaps it).

    The round's token tree is flattened to S rows in topological order:
    row 0 is the committed last token (the root — never itself accepted),
    rows 1..S-1 are draft proposals. The STATIC structure arrives as
    ``child_matrix`` (S, C) int32 — row i lists node i's children in
    proposal order, padded with -1 — and ``depth`` (python int), the tree's
    maximum proposal depth, which bounds the walk's unrolled length.

    tree_tokens:   (S,) int32 — row 0 the committed token, rest proposals.
    draft_probs:   (S, V) fp32 — q_i, the distribution node i's token was
                   drawn from (row 0 unused).
    target_logits: (S, V) fp32 — tree-verify logits; row i is the target's
                   next-token law AFTER node i's token given node i's
                   ancestor path (so row 0 scores the first proposal level
                   and an accepted leaf's row is the bonus position).

    Walk from the root, one tree level per step. Greedy slots take the
    longest ACCEPTED path: a child is accepted iff its token equals the
    target argmax at the current node, so the walk is exact argmax matching
    level by level and stays bit-identical to non-speculative decode.
    Sampled slots run SpecInfer-style recursive rejection (Miao et al.
    2023): children are tried in order with ``u * q_c(t_c) < p(t_c)``
    against the current residual p (initialized to the filtered target
    distribution at the node); each rejection folds that child out,
    ``p <- norm(max(p - q_c, 0))``, and if every child is rejected one
    token is emitted from the final residual — so the emitted path is
    distributed EXACTLY as sequential target samples, branches only adding
    acceptance chances. On full acceptance to ``depth`` the extra token is
    the bonus sample from the leaf's target distribution. Both modes share
    one walk; greedy is selected with ``where`` and never consumes noise.

    Returns ``(out_tokens, path_nodes, accepted)``: out_tokens (depth+1,)
    int32 — the a = accepted proposal tokens then the resampled/bonus
    token at index a (tail zeros); path_nodes (depth,) int32 — the
    accepted nodes' ROW indices in walk order (tail zeros), which is what
    the KV commit remap consumes.
    """
    s, v = draft_probs.shape
    c_max = child_matrix.shape[1]
    greedy_toks = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)
    p_rows = _filtered_probs(target_logits, temperature, top_p, top_k)
    cur = jnp.int32(0)
    alive = jnp.bool_(True)
    resid = p_rows[0]          # sampled-mode residual at the current node
    stop_resid = p_rows[0]     # residual captured where the walk died
    a = jnp.int32(0)
    path = jnp.zeros((depth,), jnp.int32)
    out = jnp.zeros((depth + 1,), jnp.int32)
    for lvl in range(depth):
        kids = jnp.take(child_matrix, cur, axis=0)              # (C,)
        kid_ok = kids >= 0
        safe_kids = jnp.maximum(kids, 0)
        kid_tok = jnp.take(tree_tokens, safe_kids)              # (C,)
        # greedy: first child proposing the target argmax at cur
        g = jnp.take(greedy_toks, cur)
        g_match = kid_ok & (kid_tok == g)
        g_has = jnp.any(g_match)
        g_next = jnp.take(safe_kids, jnp.argmax(g_match))
        # sampled: recursive rejection across the children, in order
        p_lvl = resid
        s_has = jnp.bool_(False)
        s_next = jnp.int32(0)
        for c in range(c_max):
            ok = kid_ok[c] & ~s_has
            t_c = kid_tok[c]
            q_c = jnp.take(draft_probs, safe_kids[c], axis=0)   # (V,)
            u = jax.random.uniform(
                jax.random.fold_in(key, lvl * c_max + c), ())
            acc_c = ok & (u * q_c[t_c] < p_lvl[t_c])
            s_next = jnp.where(acc_c, safe_kids[c], s_next)
            s_has = s_has | acc_c
            new_p = jnp.maximum(p_lvl - q_c, 0.0)
            tot = new_p.sum()
            new_p = jnp.where(tot > 0.0, new_p / tot, p_lvl)
            p_lvl = jnp.where(ok & ~acc_c, new_p, p_lvl)
        samp = temperature > 0.0
        acc = alive & jnp.where(samp, s_has, g_has)
        nxt = jnp.where(samp, s_next, g_next)
        path = path.at[lvl].set(jnp.where(acc, nxt, path[lvl]))
        out = out.at[lvl].set(
            jnp.where(acc, jnp.take(tree_tokens, nxt), out[lvl]))
        a = a + acc.astype(jnp.int32)
        stop_resid = jnp.where(alive & ~acc, p_lvl, stop_resid)
        cur = jnp.where(acc, nxt, cur)
        resid = jnp.where(acc, jnp.take(p_rows, nxt, axis=0), resid)
        alive = acc
    # survivor's bonus comes from the leaf's full distribution; a dead
    # walk emits from the residual at the level it died
    final_resid = jnp.where(alive, resid, stop_resid)
    resampled = jax.random.categorical(
        jax.random.fold_in(key, depth * c_max + 1),
        jnp.log(jnp.maximum(final_resid, 1e-38))).astype(jnp.int32)
    extra = jnp.where(temperature > 0.0, resampled,
                      jnp.take(greedy_toks, cur))
    out = out.at[a].set(extra)
    return out, path, a


class AdaptiveK:
    """Per-request adaptive round width for speculative decoding.

    Each request keeps an EMA of its observed acceptance fraction
    (accepted / proposed per verify round). Its target width is the
    expected accepted-run length of a geometric chain at that rate —
    ``a / (1 - a)`` — clamped to ``[1, k_max]`` and snapped UP to the
    engine's compiled ladder (powers of two plus ``k_max``, matching
    ``InferenceEngine._spec_pair``). The batched round runs at the MIN
    target over active requests: speculation is all-slots-at-once, so the
    least-accepting stream sets the width everyone pays for.

    A request with no evidence yet is OPTIMISTIC (``k_max``); a stale
    draft — e.g. the target was hot-swapped and the draft lags a publish —
    drags acceptance down, the controller walks k toward 1, and serving
    degrades gracefully toward plain decode instead of burning k rejected
    proposals per round. :meth:`reset` clears every estimate when a fresh
    draft is installed (deploy/reload.py), restoring optimism.
    """

    def __init__(self, k_max: int, decay: float = 0.75):
        if k_max < 1:
            raise ValueError(f"k_max must be >= 1, got {k_max}")
        if not 0.0 <= decay < 1.0:
            raise ValueError(f"decay must be in [0, 1), got {decay}")
        self.k_max = int(k_max)
        self.decay = float(decay)
        rungs, r = [], 1
        while r < self.k_max:
            rungs.append(r)
            r *= 2
        rungs.append(self.k_max)
        self.rungs = tuple(rungs)
        self._rate: Dict[str, float] = {}

    def reset(self) -> None:
        """Forget every estimate (fresh draft installed)."""
        self._rate.clear()

    def forget(self, request_id: str) -> None:
        self._rate.pop(request_id, None)

    def observe(self, request_id: str, accepted: int, k: int) -> None:
        """Fold one verify round's ``accepted`` out of ``k`` proposals into
        the request's EMA."""
        if k <= 0:
            return
        x = min(max(float(accepted) / float(k), 0.0), 1.0)
        prev = self._rate.get(request_id)
        self._rate[request_id] = (x if prev is None
                                  else self.decay * prev
                                  + (1.0 - self.decay) * x)

    def acceptance(self, request_id: str) -> Optional[float]:
        return self._rate.get(request_id)

    def target_k(self, request_id: str) -> int:
        rate = self._rate.get(request_id)
        if rate is None:
            return self.k_max
        want = rate / max(1.0 - rate, 1e-6)
        want = min(max(want, 1.0), float(self.k_max))
        for r in self.rungs:
            if r >= want:
                return r
        return self.k_max

    def round_k(self, request_ids: Iterable[str]) -> int:
        """Width for one batched round: min target over active requests
        (``k_max`` when idle)."""
        targets = [self.target_k(i) for i in request_ids]
        return min(targets) if targets else self.k_max
