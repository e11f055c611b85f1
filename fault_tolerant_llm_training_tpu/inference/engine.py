"""Jitted prefill/decode engine over the trained modules.

The engine owns the device state of a serving process: the (possibly
tensor-parallel) params, the KV cache (paged block pools by default,
``kv_layout="ring"`` for the legacy per-slot ring buffers kept for
equivalence testing), and two families of compiled programs —

- **prefill**: one request's prompt through ``Transformer.forward_with_cache``
  into a single cache slot (B=1, S=bucket), sampling the first generated
  token from the last prompt position. Prompts are right-padded to a static
  **bucket** length; the whole bucket set is AOT-compiled at engine build
  (``jit(...).lower(...).compile()``), so serving never hits a compile stall
  mid-traffic — the same discipline as the trainer's AOT train step. Under
  the paged layout prefill is **chunked**: a prompt longer than the largest
  bucket streams through it in fixed-size chunks at increasing offsets
  (Sarathi-Serve's chunked prefill), so the bucket set caps COMPILE COUNT,
  not prompt length — any prompt up to ``max_len`` is served, and the host
  loop can be interrupted cleanly between chunks for the drain lifecycle.
  The chunk loop runs every chunk at an explicit absolute offset, which is
  also what makes PREFIX-CACHE hits cheap: ``prefill(start_pos=k)`` simply
  starts the loop at k, attending to the shared blocks' committed KV
  through the block row without recomputing them
  (inference/prefix_cache.py; ``enable_prefix_cache``). The one device op
  sharing needs — copy-on-write before resuming inside a shared block —
  is its own tiny AOT program (``cow_copy``), donated like the rest.
- **decode**: one token for ALL slots at once (B=slots, S=1, per-slot
  offsets = cache lengths). The cache is donated (``donate_argnums``), so
  XLA aliases the pools/ring buffers in place; the paged layout additionally
  takes the scheduler's (slots, blocks_per_slot) block tables as a plain
  host argument each call.

**Speculative decoding** (``spec_k > 0``, paged layout only) adds a second
model lifecycle inside the engine: a small DRAFT model (its own params,
its own paged block pool, its own AOT programs) proposes k tokens per
round, and the target scores all k+1 candidate positions in ONE verify
pass instead of k+1 decode dispatches —

- **draft-k**: ONE compiled program runs the k+1 chained draft micro-steps
  in a ``lax.fori_loop`` (feed ``[t_last, d_1 .. d_k]`` at offsets
  ``L .. L+k``; the final iteration only back-fills d_k's KV so a fully
  accepted round leaves the draft cache aligned), returning the proposals
  AND the post-filter distributions they were drawn from as device arrays
  — the host never syncs mid-round, so a round costs two dispatches total.
- **verify-k**: ONE compiled program scores the k+1 candidate positions —
  as chained S=1 micro-steps on the decode program's exact op shapes (see
  ``_verify_fn`` for why the single (slots, k+1) chunk through
  :meth:`Transformer.verify_with_cache` is numerically equivalent but not
  bitwise-pinned) — then the vectorized accept/resample kernel
  (sampler.py ``spec_accept``). Acceptance commits the prefix by setting
  the cache length to ``offset + accepted + 1``; the rejected suffix
  needs no device rollback — its stale KV sits past the committed length,
  masked by attention and overwritten next round. Greedy acceptance is
  exact argmax matching, so greedy speculative streams are BIT-identical
  to the non-speculative path (tests/test_spec_decode.py); sampled slots
  use distribution-preserving rejection sampling against the same
  per-slot temperature/top-p/top-k.

**Tree speculative decoding** (``spec_tree``, on top of spec mode) widens
each round from a k-chain to a branching token TREE at the same verify
cost: the draft proposes a top-k fan-out at every depth of its chain (the
siblings are free — they are top-k reads of distributions the chain
already computed), the flattened tree is scored in ONE ancestor-masked
verify forward (``models/llama.py tree_verify_with_cache`` over
``ops/attention.py paged_tree_attention``), and the accept walk
(sampler.py ``tree_accept``) takes the longest accepted PATH — so a round
whose primary proposal is rejected can still commit a sibling instead of
falling back to plain decode. The winning path's KV is committed by a
device-side remap inside the slot's own blocks (kv_cache.py
``remap_paged_path``); rejected branches rot as stale bytes past the
committed length, exactly the linear rejected-suffix story — no allocator
traffic per round. Tree shapes (``TreeShape``/``parse_spec_tree``,
serve.py ``--spec-tree``) compile into a (draft, verify) program ladder
keyed by fan-out tuple (:meth:`InferenceEngine._tree_pair`), so an
adaptive controller can shrink the tree with live acceptance. Under
``spec_verify_impl="exact"`` a tree round scores only its PRIMARY chain
through the k+1 chained S=1 micro-steps — the PR-4 escape hatch that
keeps greedy tree-spec streams bit-identical to non-speculative decode —
while ``"chunk"`` is the full multi-branch forward.

Checkpoints restore through the existing cross-topology
``checkpoint/manager.py`` path (:meth:`InferenceEngine.from_checkpoint`):
the abstract TrainState is rebuilt exactly as the trainer builds it, params
land sharded on the serving mesh, and scan-form trunks are converted to the
loop form (``models/llama.py unstack_layer_params``) — the cached forward
runs the loop trunk only.

Numerics: the cached path reuses the training projections, the same RoPE
table values at absolute positions, and an attention kernel mirroring
``xla_attention`` — cached decode logits bit-match the uncached forward
(tests/test_inference.py).
"""

import functools
import logging
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import build_model
from ..models.configs import LatentMoEConfig, TransformerConfig
from ..obs import events
from ..obs.trace import span
from ..models.llama import Transformer, unstack_layer_params
from ..obs.registry import default_registry
from ..ops.attention import describe_paged_kernel, resolve_paged_kernel
from ..parallel.mesh import use_mesh
from ..parallel.sharding import param_shardings
# Re-exported for backward compatibility: serve.py and tests imported
# these from here before the cache wiring moved to utils/ (so the trainer
# can use it without importing inference/).
from ..utils.compile_cache import enable_compilation_cache  # noqa: F401
from ..utils.device import describe_device
from .kv_cache import (
    KVCache,
    LatentKVCache,
    PagedKVCache,
    blocks_per_slot,
    cache_shardings,
    copy_kv_block,
    export_blocks,
    import_block_batch,
    import_blocks,
    init_cache,
    init_latent_cache,
    init_paged_cache,
    remap_paged_path,
)
from .sampler import (
    TIERS,
    draft_key,
    epilogue_tier,
    sample_slot_tokens,
    sample_token,
    sample_token_with_probs,
    slot_key,
    spec_accept,
    tree_accept,
    tree_key,
    verify_key,
)

logger = logging.getLogger()


def default_prefill_buckets(max_len: int, smallest: int = 16
                            ) -> Sequence[int]:
    """Power-of-two bucket ladder up to ``max_len`` (always included): a
    prompt pays at most 2x its own length in prefill compute, for
    log2(max_len/smallest) compiled programs."""
    buckets, b = [], smallest
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def program_cost(compiled) -> Optional[Tuple[float, float]]:
    """(flops, bytes accessed) of a compiled program as its compiler counts
    them (``cost_analysis()``), or None where it gives neither. The two are
    kept apart: the engine knows no peak of its device to weigh one against
    the other, so a cover is only called cheaper when it is cheaper by
    both (:func:`cover_plan`)."""
    try:
        counts = compiled.cost_analysis()
    except (NotImplementedError, RuntimeError):
        return None     # a backend without the analysis: no rule, no fault
    if isinstance(counts, (list, tuple)):
        counts = counts[0] if counts else None
    if not counts or "flops" not in counts or "bytes accessed" not in counts:
        return None
    return float(counts["flops"]), float(counts["bytes accessed"])


def cover_plan(rows: int, buckets: Sequence[int], cost=None) -> List[int]:
    """The bucket of each call that covers ``rows`` prompt rows, in order.

    The plain cover is the chunk loop's: whole chunks of the largest
    bucket, then the first bucket that holds the remainder. ``cost`` maps
    a bucket to its program's (flops, bytes) (:func:`program_cost`); where
    it is given, k calls of a smaller bucket replace the one call of the
    next chunk when their summed cost is lower by BOTH counts — so on any
    device, whatever its balance of the two — and of several such covers
    each has to beat the last one taken the same way, the fewer calls
    first. A small remainder over a ladder with a missing rung (80 rows
    over ``[64, 2048]``) then runs as two small calls; a large one, or any
    remainder over a ladder with the rung, runs as before."""
    buckets = sorted(buckets)
    plan: List[int] = []
    while rows > 0:
        m = min(rows, buckets[-1])
        pick = next(b for b in buckets if b >= m)
        if cost is not None:
            best = cost[pick]
            for b in reversed([b for b in buckets if b < pick]):
                calls = -(-m // b)
                total = tuple(calls * c for c in cost[b])
                if all(t < w for t, w in zip(total, best)):
                    pick, best = b, total
        plan.append(pick)
        rows -= pick
    return plan


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        tree)


class TreeShape:
    """STATIC structure of one speculative token tree.

    ``fanouts`` (f_1 .. f_depth, each >= 1) gives the branch width at each
    proposal depth: level l's f_l nodes are the draft's top-f_l candidates
    after the PRIMARY (first) node of level l-1, so the tree is the draft's
    one k-chain plus sibling fan-outs hanging off it — the chain costs the
    draft exactly what linear speculation costs, and the siblings are free
    top-k reads of distributions the chain already computed. ``(1,) * k``
    is therefore the linear k-chain itself.

    Flattened layout (what every consumer indexes by): row 0 is the root
    (the committed last token), rows ``level_start[l] ..
    level_start[l] + f_l`` are level l+1's nodes in proposal order, primary
    first. Node i's KV is written at cache position ``offset + i``; its
    rope position is ``offset + depths[i]``. Derived arrays are numpy and
    baked into the compiled programs as constants:

    - ``parents`` (S,): row index of each node's parent, -1 for the root.
    - ``depths`` (S,): proposal depth, root 0.
    - ``child_matrix`` (S, C): row i's children padded with -1 — the
      accept walk's transition table (sampler.py ``tree_accept``).
    - ``anc_mask`` (S, S) bool: ``anc_mask[r, j]`` iff j is on r's root
      path (ancestors, self, root) — the verify attention rule
      (ops/attention.py ``paged_tree_attention``).
    - ``primary_rows`` (depth,): the primary chain's row per level — what
      the ``exact`` verify mode scores.
    """

    def __init__(self, fanouts: Sequence[int]):
        fanouts = tuple(int(f) for f in fanouts)
        if not fanouts or any(f < 1 for f in fanouts):
            raise ValueError(f"tree fan-outs must be >= 1 per level, got "
                             f"{fanouts}")
        self.fanouts = fanouts
        self.depth = len(fanouts)
        self.size = 1 + sum(fanouts)                 # S rows incl. root
        self.c_max = max(fanouts)
        starts, s0 = [], 1
        for f in fanouts:
            starts.append(s0)
            s0 += f
        self.level_start = tuple(starts)
        self.primary_rows = tuple(starts)
        parents = np.full((self.size,), -1, np.int32)
        depths = np.zeros((self.size,), np.int32)
        child = np.full((self.size, self.c_max), -1, np.int32)
        prev_primary = 0
        for lvl, f in enumerate(fanouts):
            s0 = starts[lvl]
            for j in range(f):
                parents[s0 + j] = prev_primary
                depths[s0 + j] = lvl + 1
                child[prev_primary, j] = s0 + j
            prev_primary = s0
        self.parents, self.depths, self.child_matrix = parents, depths, child
        anc = np.zeros((self.size, self.size), bool)
        for r in range(self.size):
            anc[r, 0] = True
            a = r
            while a >= 0:
                anc[r, a] = True
                a = int(parents[a])
        self.anc_mask = anc

    def shrink_to(self, budget: int) -> "TreeShape":
        """The largest sub-shape spending at most ``budget`` draft tokens
        (``sum(fanouts)``): trailing fan-outs shed width first, then whole
        levels — so an adaptive controller walking its k ladder down maps
        each rung to a deterministic smaller tree, and budget 1 is always
        the linear single-proposal round."""
        budget = max(1, int(budget))
        f = list(self.fanouts)
        while sum(f) > budget:
            for i in range(len(f) - 1, -1, -1):
                if f[i] > 1:
                    f[i] -= 1
                    break
            else:
                f.pop()
        f = tuple(f)
        return self if f == self.fanouts else TreeShape(f)

    def __repr__(self):
        return f"TreeShape({','.join(str(f) for f in self.fanouts)})"


def parse_spec_tree(spec) -> TreeShape:
    """``--spec-tree`` value into a :class:`TreeShape`: a ``"2,2,1"``-style
    comma list of per-depth fan-outs, a sequence of ints, or an already
    built shape (passed through)."""
    if isinstance(spec, TreeShape):
        return spec
    if isinstance(spec, str):
        try:
            spec = [int(p) for p in spec.replace(" ", "").split(",") if p]
        except ValueError:
            raise ValueError(f"bad --spec-tree {spec!r}: want a comma list "
                             f"of per-depth fan-outs, e.g. '2,2,1'")
    return TreeShape(spec)


class InferenceEngine:
    """Slot-granular prefill/decode over a trained ``Transformer``.

    ``params`` is the bare 'params' collection of the checkpoint (scan or
    loop form — scan is converted). Host-side slot bookkeeping (which slot
    belongs to which request) lives in the scheduler; the engine only moves
    tensors.
    """

    def __init__(self, cfg: TransformerConfig, params, *, slots: int = 2,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 top_k: int = 0, cache_dtype=None, mesh=None,
                 kv_layout: str = "paged", kv_block_size: int = 16,
                 kv_num_blocks: Optional[int] = None,
                 draft_cfg: Optional[TransformerConfig] = None,
                 draft_params=None, spec_k: int = 0,
                 draft_num_blocks: Optional[int] = None,
                 spec_verify_impl: str = "exact",
                 spec_tree=None,
                 prefix_cache: bool = True,
                 paged_kernel: str = "auto",
                 prefill_batch: int = 1,
                 kv_dtype: str = "bf16",
                 adapter_rank: int = 0,
                 adapter_num_pages: int = 0,
                 adapter_page_elems: int = 0):
        if kv_layout not in ("paged", "ring"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}: 'bf16' "
                             f"(plain pools) or 'int8' (quantized pools "
                             f"with per-(block, kv-head) fp32 scales — "
                             f"kv_cache.QuantPool)")
        if kv_dtype == "int8":
            if kv_layout != "paged":
                raise ValueError("kv_dtype='int8' requires the paged KV "
                                 "layout: the scale pool is per-block, and "
                                 "the ring path has no block granularity "
                                 "to hang scales on")
            if cache_dtype is not None and (jnp.dtype(cache_dtype)
                                            != jnp.dtype(jnp.int8)):
                raise ValueError(
                    f"kv_dtype='int8' conflicts with cache_dtype="
                    f"{jnp.dtype(cache_dtype).name!r}: pass one or the "
                    f"other")
            cache_dtype = jnp.int8
        elif cache_dtype is not None and (jnp.dtype(cache_dtype)
                                          == jnp.dtype(jnp.int8)):
            kv_dtype = "int8"  # dtype request IS the mode switch
            if kv_layout != "paged":
                raise ValueError("int8 cache_dtype requires the paged KV "
                                 "layout")
        self.kv_dtype = kv_dtype
        if paged_kernel not in ("auto", "gather", "pallas"):
            raise ValueError(
                f"unknown paged_kernel {paged_kernel!r}: 'gather' "
                f"(assemble blocks then run the ring kernel — the "
                f"bit-exact reference), 'pallas' (read pool blocks in "
                f"place through the table, ops/paged_attention.py — equal "
                f"within fp32 accumulation tolerance) or 'auto' (one of "
                f"the two per program: in place for one-token queries on "
                f"one TPU device, ops/attention.py resolve_paged_kernel)")
        if paged_kernel == "pallas" and kv_layout != "paged":
            raise ValueError("paged_kernel selection requires the paged "
                             "KV layout")
        self.paged_kernel = paged_kernel
        # a model whose layers cache by kind (LatentMoEConfig: latent and
        # index-key pools behind the block tables, window rings a slot)
        # runs the same engine with programs of its own cache; what the
        # engine cannot do for it yet is refused here, by name
        self._latent = isinstance(cfg, LatentMoEConfig)
        if self._latent:
            self._refuse_for_latent(
                kv_layout=kv_layout, kv_dtype=kv_dtype, spec_k=spec_k,
                draft=draft_cfg is not None or draft_params is not None,
                spec_tree=spec_tree, adapter_rank=adapter_rank,
                prefill_batch=prefill_batch, paged_kernel=paged_kernel,
                mesh=mesh)
        device = describe_device()
        logger.info(f"Device | {device}")
        events.emit("backend_ready", device=device)
        # what the programs' paged reads resolve to, by query length (1,
        # or 2 for every longer one): the start-up line, the dispatch
        # counter and the scheduler's chunk counters all state this, not
        # the option
        with use_mesh(mesh):
            if self._latent:
                # its reads are XLA gathers of rows and blocks
                # (ops/latent_attention.py): no in-place kernel yet
                self._read_kernel = {1: "gather", 2: "gather"}
                logger.info("Paged kernel | latent caches: decode gather "
                            "of selected rows, prefill gather by key block")
            else:
                self._read_kernel = {
                    s_q: ("inplace" if resolve_paged_kernel(
                        paged_kernel, s_q, cfg.head_dim) == "pallas"
                          else "gather") for s_q in (1, 2)}
                logger.info(
                    f"Paged kernel | "
                    f"{describe_paged_kernel(paged_kernel, cfg.head_dim)}")
        reads = default_registry().counter(
            "paged_read_dispatches_total",
            "Dispatched programs that read the paged KV pool, by the "
            "kernel their reads resolved to (inplace = the Pallas kernels "
            "of ops/paged_attention.py, gather = gather-then-ring) and "
            "phase (decode = decode, burst and speculative rounds)")
        self._m_reads = {
            (phase, s_q): reads.labels(kernel=self._read_kernel[s_q],
                                       phase=phase)
            for phase, s_q in (("decode", 1), ("prefill", 1),
                               ("prefill", 2))}
        # a series is born with its first round: a window of greedy
        # traffic shows no sampled / nucleus series at all
        self._m_tiers = default_registry().counter(
            "sample_epilogue_rounds_total",
            "Decode rounds (decode_step, decode_burst) by the tier of the "
            "sampling epilogue their batch's temperature / top_p select "
            "(sampler.py epilogue_tier: greedy = argmax only, sampled = no "
            "sort, nucleus = the whole epilogue)")
        rows = default_registry().counter(
            "ftl_serve_prefill_rows_total",
            "Rows of the chunk programs the sequential prefill loops (not "
            "the packed lane) called, by kind: "
            "new = real rows at or past the position the call resumed at, "
            "recomputed = real rows before it (a window rebuild), padding = "
            "a call's bucket less its real rows")
        self._m_rows = {kind: rows.labels(kind=kind)
                        for kind in ("new", "recomputed", "padding")}
        if cfg.layer_impl == "scan":
            params = unstack_layer_params(params, cfg.n_layers)
            cfg = cfg.replace(layer_impl="loop")
        # remat only pays under grad; serving is forward-only
        self.cfg = cfg = cfg.replace(remat=False,
                                     paged_kernel=paged_kernel)
        self.mesh = mesh
        self.slots = slots
        self.max_len = max_len or cfg.seq_len
        self.top_k = top_k
        self.kv_layout = kv_layout
        self.restored_step: Optional[int] = None
        self.draft_restored_step: Optional[int] = None
        buckets = tuple(sorted(set(prefill_buckets
                                   or default_prefill_buckets(self.max_len))))
        if buckets[-1] > self.max_len:
            raise ValueError(f"prefill bucket {buckets[-1]} exceeds "
                             f"max_len {self.max_len}")
        self.prefill_buckets = buckets
        # Packed multi-request prefill (prefill_batch > 1): a second AOT
        # bucket ladder whose programs run P requests' next chunks in ONE
        # (P, bucket) dispatch — the scheduler's packed admission lane.
        self.prefill_batch = int(prefill_batch)
        if not 1 <= self.prefill_batch <= slots:
            raise ValueError(
                f"prefill_batch {prefill_batch} outside [1, slots={slots}]: "
                f"each packed row prefills into its own cache slot")
        if self.prefill_batch > 1 and kv_layout != "paged":
            raise ValueError("prefill_batch > 1 requires the paged KV "
                             "layout (each packed row writes through its "
                             "own block-table row)")
        if kv_layout == "paged":
            self.block_size = kv_block_size
            self.max_blocks_per_slot = blocks_per_slot(self.max_len,
                                                       kv_block_size)
            self.num_blocks = (kv_num_blocks
                               or slots * self.max_blocks_per_slot + 1)
        # Content-addressed prefix reuse (inference/prefix_cache.py): the
        # scheduler builds the radix tree only for engines that advertise
        # it. Paged-only — sharing is a property of the block indirection.
        self.enable_prefix_cache = bool(prefix_cache) and kv_layout == "paged"
        self.model = build_model(cfg)
        if self._latent:
            from ..models.latent_moe import STAT_COUNTERS
            self._stat_counters = {
                phase: [default_registry().counter(name, text).labels(
                    phase=phase) for name, text in STAT_COUNTERS.values()]
                for phase in ("prefill", "decode")}
            # the first position each slot's window rings hold of the
            # request last prefilled into it: the host's copy of
            # ``cache.win_from``, for a caller that resumes over held rings
            self._ring_from = np.zeros((slots,), np.int64)

        # --- speculative decoding: second model lifecycle ------------------
        self.spec_k = int(spec_k)
        self.draft_cfg = None
        self.draft_model = None
        if self.spec_k:
            if kv_layout != "paged":
                raise ValueError("speculative decoding requires the paged "
                                 "KV layout (masked null-block writes are "
                                 "what make rejected-suffix rollback free)")
            if draft_cfg is None or draft_params is None:
                raise ValueError("spec_k > 0 requires draft_cfg and "
                                 "draft_params")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: accept/resample compares the two "
                    f"models' distributions token-for-token")
            if not 1 <= self.spec_k < self.max_len:
                raise ValueError(f"spec_k {spec_k} outside [1, max_len)")
            if self.prefill_batch > 1:
                raise ValueError(
                    "prefill_batch > 1 and speculative decoding are "
                    "mutually exclusive: spec-mode prefill streams the "
                    "DRAFT pool sequentially after the target phase, and "
                    "packing that second lifecycle is a separate program "
                    "family")
            if spec_verify_impl not in ("exact", "chunk"):
                raise ValueError(
                    f"unknown spec_verify_impl {spec_verify_impl!r}: "
                    f"'exact' (k+1 chained S=1 micro-steps — greedy streams "
                    f"bit-identical to the non-speculative path by "
                    f"construction; the win is dispatch elimination, which "
                    f"pays on accelerators) or 'chunk' (one (slots, k+1) "
                    f"forward — additionally batches the verify FLOPs, but "
                    f"bf16 GEMM accumulation is shape-dependent and a "
                    f"one-ulp logit near-tie can flip an argmax vs the S=1 "
                    f"decode program)")
            self.spec_verify_impl = spec_verify_impl
            if draft_cfg.layer_impl == "scan":
                draft_params = unstack_layer_params(draft_params,
                                                    draft_cfg.n_layers)
                draft_cfg = draft_cfg.replace(layer_impl="loop")
            # the draft reads its pool through the same kernel: a spec
            # round's S=1 micro-steps are exactly the decode shapes the
            # in-place kernel serves
            self.draft_cfg = draft_cfg = draft_cfg.replace(
                remat=False, paged_kernel=self.paged_kernel)
            self.draft_num_blocks = (draft_num_blocks
                                     or slots * self.max_blocks_per_slot + 1)
            self.draft_model = Transformer(draft_cfg)
        elif draft_cfg is not None or draft_params is not None:
            raise ValueError("draft model given but spec_k == 0")

        # --- tree speculative decoding: branching rounds -------------------
        self.spec_tree: Optional[TreeShape] = None
        if spec_tree is not None:
            if not self.spec_k:
                raise ValueError("spec_tree requires speculative decoding "
                                 "(spec_k > 0 with a draft model): the tree "
                                 "is a widening of the spec round, not a "
                                 "third lifecycle")
            shape = parse_spec_tree(spec_tree)
            if shape.size >= self.max_len:
                raise ValueError(f"tree shape {shape} has {shape.size} rows "
                                 f">= max_len {self.max_len}: the verify "
                                 f"window must fit a slot")
            self.spec_tree = shape
            # refeed width: the max tokens one round can emit (depth
            # accepted + bonus). Fixed across the shrink ladder so every
            # rung's draft program shares one refeed layout, and doubles
            # as the draft-key stream stride (rungs never alias).
            self._tree_refeed = shape.depth + 1

        # --- multi-tenant LoRA adapter serving (inference/adapters.py) -----
        # A THIRD paged pool next to the target/draft KV pools: flat fp32
        # pages holding per-adapter low-rank factors, page 0 the reserved
        # null page. The fused programs take (pool, per-slot page rows,
        # per-slot scales) as trailing args ONLY when adapter_rank > 0, so
        # a no-adapter engine's programs are byte-identical to before; the
        # pool is passed per call (like params, never donated), which is
        # what makes page-in and hot-swap recompile-free.
        self.adapter_rank = int(adapter_rank)
        self.adapters = None
        self._adapter_layout = None
        self.adapter_pool = None
        if self.adapter_rank:
            if kv_layout != "paged":
                raise ValueError("adapter serving requires the paged KV "
                                 "layout (the adapter pool reuses the "
                                 "block-pool substrate)")
            if self.spec_k:
                raise ValueError(
                    "adapter serving and speculative decoding are mutually "
                    "exclusive: the draft model has no per-tenant factors, "
                    "so a draft proposal distribution would diverge from "
                    "every adapter's target and the verify pass would "
                    "reject its way back to plain decode")
            from .adapters import AdapterLayout, AdapterManager

            self._adapter_layout = AdapterLayout.from_cfg(
                cfg, self.adapter_rank,
                page_elems=adapter_page_elems or None)
            per = self._adapter_layout.pages_per_adapter
            # default pool: 4 resident adapters + the null page
            self.adapter_num_pages = int(adapter_num_pages) or 4 * per + 1
            self.adapter_pool = jnp.zeros(
                (self.adapter_num_pages, self._adapter_layout.page_elems),
                jnp.float32)
            self.adapters = AdapterManager(
                self._adapter_layout, self.adapter_num_pages,
                self._write_adapter_pages)
        elif adapter_num_pages or adapter_page_elems:
            raise ValueError("adapter pool sizing given but "
                             "adapter_rank == 0")

        with use_mesh(mesh):
            shardings = param_shardings(params, mesh)
            if shardings is not None:
                params = jax.device_put(params, shardings)
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
            cache = self._init_cache(cache_dtype)
            cs = cache_shardings(cache, mesh)
            self.cache = (jax.device_put(cache, cs) if cs is not None
                          else cache)
            if self.spec_k:
                dsh = param_shardings(draft_params, mesh)
                if dsh is not None:
                    draft_params = jax.device_put(draft_params, dsh)
                self.draft_params = jax.tree_util.tree_map(jnp.asarray,
                                                           draft_params)
                dcache = self._init_draft_cache(cache_dtype)
                dcs = cache_shardings(dcache, mesh)
                self.draft_cache = (jax.device_put(dcache, dcs)
                                    if dcs is not None else dcache)
            self._build_programs()

    @property
    def decode_read_kernel(self) -> str:
        """``"inplace"`` or ``"gather"``: what the one-token programs
        (decode, burst, a draft's micro-steps) read the pool through."""
        return self._read_kernel[1]

    @property
    def prefill_read_kernel(self) -> str:
        """The same for the S > 1 programs (chunked and packed prefill)."""
        return self._read_kernel[2]

    @staticmethod
    def _refuse_for_latent(*, kv_layout, kv_dtype, spec_k, draft, spec_tree,
                           adapter_rank, prefill_batch, paged_kernel, mesh):
        """What the engine cannot do yet for a model whose layers cache by
        kind: each refused by its name, nothing falls back."""
        what = "a LatentMoEConfig model (latent / index-key / window caches)"
        for bad, why in (
                (kv_layout != "paged",
                 "kv_layout='ring': its full layers live in block pools"),
                (kv_dtype != "bf16",
                 "kv_dtype='int8': no quantized latent or index-key pool"),
                (bool(spec_k) or draft or spec_tree is not None,
                 "speculative decoding (spec_k / draft / spec_tree): no "
                 "verify program over its caches"),
                (bool(adapter_rank),
                 "adapters (adapter_rank): no per-slot factors on its "
                 "projections"),
                (int(prefill_batch) > 1,
                 "prefill_batch > 1: a chunk is one slot's"),
                (paged_kernel == "pallas",
                 "paged_kernel='pallas': no in-place kernel reads its "
                 "pools"),
                (mesh is not None and mesh.devices.size > 1,
                 "a multi-device mesh: no sharding rules for its "
                 "parameters and pools, no expert exchange")):
            if bad:
                raise ValueError(f"{what} does not support {why}")

    def _init_cache(self, dtype=None):
        if self._latent:
            return init_latent_cache(self.cfg, self.slots, self.block_size,
                                     self.num_blocks, dtype=dtype)
        if self.kv_layout == "paged":
            return init_paged_cache(self.cfg, self.slots, self.max_len,
                                    self.block_size, self.num_blocks,
                                    dtype=dtype)
        return init_cache(self.cfg, self.slots, self.max_len, dtype=dtype)

    def _init_draft_cache(self, dtype=None):
        return init_paged_cache(self.draft_cfg, self.slots, self.max_len,
                                self.block_size, self.draft_num_blocks,
                                dtype=dtype)

    # --- adapter pool (multi-tenant LoRA) ----------------------------------

    def _write_adapter_pages(self, pages, values) -> None:
        """Land one adapter's flattened factors in pool rows ``pages`` —
        the AdapterManager's device write. A host-side scatter outside any
        compiled program: the pool is a per-call input (never donated), so
        the next dispatch simply reads the new bytes — no recompile."""
        idx = np.asarray(pages, np.int32)
        self.adapter_pool = self.adapter_pool.at[idx].set(
            jnp.asarray(values, jnp.float32))

    def _adapter_operand(self, apool, arows, ascales):
        """Traced: gather each row's adapter pages from the pool in ONE
        table lookup (the scalar-prefetched-table trick the paged KV
        kernels use — rows of the null adapter hit zero page 0) and slice
        the flat bytes into per-layer LoRA factor tuples for
        ``forward_with_cache``. None when the engine has no adapters —
        the programs trace exactly as before."""
        if apool is None:
            return None
        flat = apool[arows].reshape(arows.shape[0], -1)
        layers = self._adapter_layout.slice_layers(flat)
        return [(a_q, b_q, a_v, b_v, ascales)
                for (a_q, b_q, a_v, b_v) in layers]

    def _null_adapter_args(self, batch: int):
        """All-null (base-only) host-side adapter rows/scales for
        ``batch`` rows — what the host API substitutes when the caller
        passes none on an adapter-enabled engine."""
        p = self._adapter_layout.pages_per_adapter
        return (np.zeros((batch, p), np.int32),
                np.zeros((batch,), np.float32))

    # --- compiled programs -------------------------------------------------

    def _prefill_fn(self, params, cache, tokens, slot, prompt_len,
                    temperature, top_p, seed):
        """(1, bucket) prompt into cache slot ``slot``; returns the updated
        cache and the first sampled token. Pad positions beyond
        ``prompt_len`` do get written to the cache, but ``lengths`` masks
        them out, and decode overwrites each position before attending."""
        ksl = tuple(jax.lax.dynamic_slice_in_dim(l, slot, 1, 0)
                    for l in cache.k)
        vsl = tuple(jax.lax.dynamic_slice_in_dim(l, slot, 1, 0)
                    for l in cache.v)
        logits, (nk, nv) = self.model.apply(
            {"params": params}, tokens, ksl, vsl,
            jnp.zeros((1,), jnp.int32), method="forward_with_cache")
        k = tuple(jax.lax.dynamic_update_slice_in_dim(l, n, slot, 0)
                  for l, n in zip(cache.k, nk))
        v = tuple(jax.lax.dynamic_update_slice_in_dim(l, n, slot, 0)
                  for l, n in zip(cache.v, nv))
        lengths = jax.lax.dynamic_update_slice(cache.lengths,
                                               prompt_len[None], (slot,))
        last = jax.lax.dynamic_slice_in_dim(
            logits[0], prompt_len - 1, 1, 0)[0].astype(jnp.float32)
        tok = sample_token(last, slot_key(seed, jnp.int32(0)),
                           temperature, top_p, self.top_k)
        return KVCache(k=k, v=v, lengths=lengths), tok

    def _decode_fn(self, params, cache, tokens, active, temperature, top_p,
                   seeds, steps):
        """One token for every slot: feed each slot's last token at its
        cache length, sample the next. Inactive slots still run (static
        shapes) but their lengths do not advance, so their repeated write
        lands on the same masked position and is overwritten at the next
        prefill."""
        logits, (nk, nv) = self.model.apply(
            {"params": params}, tokens[:, None], cache.k, cache.v,
            cache.lengths, method="forward_with_cache")
        last = logits[:, 0].astype(jnp.float32)
        toks = sample_slot_tokens(last, seeds, steps, temperature, top_p,
                                  self.top_k)
        lengths = cache.lengths + active.astype(jnp.int32)
        return KVCache(k=nk, v=nv, lengths=lengths), toks

    def _paged_prefill_fn(self, model, params, cache, block_row, tokens,
                          slot, chunk_start, chunk_len, temperature, top_p,
                          seed, apool=None, arow=None, ascale=None):
        """One prefill CHUNK: (1, bucket) tokens at absolute positions
        ``chunk_start + [0, chunk_len)`` written through the slot's block
        ``block_row`` (blocks_per_slot,); pad positions past ``chunk_len``
        divert to null block 0 (unlike the ring path nothing may scribble
        past the slot's allocation). Returns the updated cache and a token
        sampled from the chunk's last real position — meaningful on the
        FINAL chunk (the host loop discards the rest: intermediate chunks'
        last logits predict tokens the prompt already contains).
        ``model`` is bound with functools.partial before jit — the same
        program body prefills the target and (spec mode) the draft."""
        valid = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                 < chunk_len)
        adapter = (None if apool is None else self._adapter_operand(
            apool, arow[None, :], ascale[None]))
        logits, (nk, nv) = model.apply(
            {"params": params}, tokens, cache.k, cache.v, chunk_start[None],
            block_tables=block_row[None, :], write_valid=valid,
            adapter=adapter, method="forward_with_cache")
        lengths = jax.lax.dynamic_update_slice(
            cache.lengths, (chunk_start + chunk_len)[None], (slot,))
        last = jax.lax.dynamic_slice_in_dim(
            logits[0], chunk_len - 1, 1, 0)[0].astype(jnp.float32)
        tok = sample_token(last, slot_key(seed, jnp.int32(0)),
                           temperature, top_p, self.top_k)
        return PagedKVCache(k=nk, v=nv, lengths=lengths), tok

    def _packed_prefill_fn(self, model, params, cache, block_rows, tokens,
                           slots, chunk_start, chunk_len, active,
                           temperature, top_p, seeds, apool=None,
                           arows=None, ascales=None):
        """P prefill CHUNKS in ONE dispatch: row i is request i's next
        (1, bucket) chunk at its OWN absolute offset ``chunk_start[i]``
        through its OWN block-table row — the batched sibling of
        ``_paged_prefill_fn``. Inactive pad rows (fewer than P requests
        share this round's bucket) run with all-False write_valid, so
        their writes divert to the null block and their lengths are left
        alone.

        Bit-exactness vs sequential B=1 prefill: the batch dim is a
        PARALLEL dim of every GEMM — each row's contraction shapes are
        exactly the (1, bucket) program's, unlike the S=1 -> S=k+1
        chunk-verify case where the contraction itself changes shape —
        and the per-row epilogue below is a static unroll whose ops
        (scalar length update, (V,) ``sample_token``) are the sequential
        program's exact shapes. Packed streams are therefore bit-identical
        to sequential prefill on the gather impl (asserted, not assumed:
        tests/test_paged_kv.py, the bench receipt)."""
        p_rows, bucket = tokens.shape
        valid = ((jnp.arange(bucket, dtype=jnp.int32)[None, :]
                  < chunk_len[:, None]) & active[:, None])
        logits, (nk, nv) = model.apply(
            {"params": params}, tokens, cache.k, cache.v, chunk_start,
            block_tables=block_rows, write_valid=valid,
            adapter=self._adapter_operand(apool, arows, ascales),
            method="forward_with_cache")
        lengths = cache.lengths
        toks = []
        for i in range(p_rows):
            lengths = jnp.where(
                active[i],
                jax.lax.dynamic_update_slice(
                    lengths, (chunk_start[i] + chunk_len[i])[None],
                    (slots[i],)),
                lengths)
            last = jax.lax.dynamic_slice_in_dim(
                logits[i], jnp.maximum(chunk_len[i] - 1, 0), 1,
                0)[0].astype(jnp.float32)
            toks.append(sample_token(last, slot_key(seeds[i], jnp.int32(0)),
                                     temperature[i], top_p[i], self.top_k))
        return PagedKVCache(k=nk, v=nv, lengths=lengths), jnp.stack(toks)

    def _paged_decode_fn(self, params, cache, block_tables, tokens, active,
                         temperature, top_p, seeds, steps, apool=None,
                         arows=None, ascales=None):
        """One token for every slot through the block tables; inactive
        slots still run (static shapes) but their write diverts to the
        null block and their lengths do not advance. The sampling
        epilogue (sampler.py ``sample_slot_tokens``) is traced INTO the
        program: logits -> temperature/top-k/top-p -> fold_in(seed, step)
        sample all run device-side, so one dispatch ends in token ids and
        the host syncs 4 bytes per slot instead of a (slots, V) logits
        plane (the unfused comparison point is :meth:`decode_logits`)."""
        logits, (nk, nv) = self.model.apply(
            {"params": params}, tokens[:, None], cache.k, cache.v,
            cache.lengths, block_tables=block_tables,
            write_valid=active[:, None],
            adapter=self._adapter_operand(apool, arows, ascales),
            method="forward_with_cache")
        last = logits[:, 0].astype(jnp.float32)
        toks = sample_slot_tokens(last, seeds, steps, temperature, top_p,
                                  self.top_k)
        lengths = cache.lengths + active.astype(jnp.int32)
        return PagedKVCache(k=nk, v=nv, lengths=lengths), toks

    def _latent_prefill_fn(self, params, cache, block_row, tokens, slot,
                           chunk_start, chunk_len, write_from, seq_from,
                           temperature, top_p, seed):
        """One prefill chunk of a ``LatentKVCache`` model: as
        ``_paged_prefill_fn``, with two positions more. ``seq_from`` is
        where this call began computing (the slot's window rings hold
        nothing of the request before it); ``write_from`` keeps the
        positions before it out of the full layers' pools: a call resumed
        from a prefix hit at P starts ``cfg.rebuild_span`` earlier, so that
        every sliding layer's window is exact from P on, and the rows in
        between are the shared blocks' own. Returns the counts of
        ``models/latent_moe.py`` ``STATS`` beside the token."""
        valid = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                 < chunk_len)
        logits, new, stats = self.model.apply(
            {"params": params}, tokens, cache, chunk_start[None],
            block_row[None, :], valid, slot[None], seq_from[None],
            write_from=write_from[None], logits_at=(chunk_len - 1)[None],
            method="forward_with_cache")
        lengths = jax.lax.dynamic_update_slice(
            cache.lengths, (chunk_start + chunk_len)[None], (slot,))
        tok = sample_token(logits[0, 0].astype(jnp.float32),
                           slot_key(seed, jnp.int32(0)),
                           temperature, top_p, self.top_k)
        return new.replace(lengths=lengths), tok, stats

    def _latent_decode_fn(self, params, cache, block_tables, tokens, active,
                          temperature, top_p, seeds, steps):
        """One token for every slot of a ``LatentKVCache`` model: as
        ``_paged_decode_fn`` (the same epilogue), returning the round's
        counts beside the tokens."""
        logits, new, stats = self.model.apply(
            {"params": params}, tokens[:, None], cache, cache.lengths,
            block_tables, active[:, None],
            jnp.arange(self.slots, dtype=jnp.int32), cache.win_from,
            method="forward_with_cache")
        last = logits[:, 0].astype(jnp.float32)
        toks = sample_slot_tokens(last, seeds, steps, temperature, top_p,
                                  self.top_k)
        lengths = cache.lengths + active.astype(jnp.int32)
        return new.replace(lengths=lengths), toks, stats

    def _paged_logits_fn(self, params, cache, block_tables, tokens, active,
                         apool=None, arows=None, ascales=None):
        """UNFUSED decode step: the identical forward, but the program
        ends at the last-position fp32 logits — sampling is left to the
        host (which then pays a full (slots, V) sync plus a second
        dispatch for the sampling math). Kept as the bench's baseline so
        the fused epilogue's win is measured, not asserted; streams
        bit-match the fused path because both feed the same
        ``sample_slot_tokens`` (sampler.py)."""
        logits, (nk, nv) = self.model.apply(
            {"params": params}, tokens[:, None], cache.k, cache.v,
            cache.lengths, block_tables=block_tables,
            write_valid=active[:, None],
            adapter=self._adapter_operand(apool, arows, ascales),
            method="forward_with_cache")
        last = logits[:, 0].astype(jnp.float32)
        lengths = cache.lengths + active.astype(jnp.int32)
        return PagedKVCache(k=nk, v=nv, lengths=lengths), last

    def _burst_decode_fn(self, n, params, cache, block_tables, tokens,
                         active, temperature, top_p, seeds, steps,
                         apool=None, arows=None, ascales=None):
        """A BURST of n chained decode micro-steps in ONE compiled program
        — the plain-decode sibling of the draft-k loop (``_draft_k_fn``):
        a ``lax.fori_loop`` whose body is one S=1 forward + the fused
        sampling epilogue, each iteration writing the fed token's KV
        through the block tables and feeding its sample to the next. The
        host pays ONE dispatch and ONE sync for n tokens instead of n of
        each.

        Bit-exactness: the body's op shapes are EXACTLY the single-step
        decode program's (S=1 forward, same epilogue), so greedy burst
        streams are bit-identical to n sequential ``decode_step`` calls
        by construction — the same structural argument as the 'exact'
        spec-verify mode (shape-dependent bf16 GEMM accumulation is why
        identical shapes matter). Sampled slots match too: micro-step i
        samples under ``slot_key(seed, steps + i)``, the key sequential
        decode would use at that step.

        EOS cannot stop the loop device-side (that would cost a sync per
        micro-step, the thing being amortized): a slot that hits EOS
        mid-burst keeps generating and the SCHEDULER truncates at banking
        (``_bank_burst``), exactly like a rejected spec suffix — the
        overshoot KV is stale pool content past the committed length,
        masked and later overwritten. ``n`` is partial-bound before jit
        (the ladder pattern of ``_compile_spec_pair``)."""
        b = self.slots
        offsets = cache.lengths
        toks0 = jnp.zeros((b, n), jnp.int32)
        valid = active[:, None]
        # rows/scales are loop-invariant: gather + slice once, reuse in
        # every micro-step (the same per-slot factors all burst long)
        adapter = self._adapter_operand(apool, arows, ascales)

        def body(i, carry):
            ck, cv, cur, toks = carry
            logits, (nk, nv) = self.model.apply(
                {"params": params}, cur[:, None], ck, cv, offsets + i,
                block_tables=block_tables, write_valid=valid,
                adapter=adapter, method="forward_with_cache")
            last = logits[:, 0].astype(jnp.float32)
            nxt = sample_slot_tokens(last, seeds, steps + i, temperature,
                                     top_p, self.top_k)
            toks = jax.lax.dynamic_update_slice_in_dim(
                toks, nxt[:, None], i, axis=1)
            return nk, nv, nxt, toks

        ck, cv, _cur, toks = jax.lax.fori_loop(
            0, n, body, (cache.k, cache.v, tokens, toks0))
        lengths = jnp.where(active, offsets + n, cache.lengths)
        return PagedKVCache(k=ck, v=cv, lengths=lengths), toks

    def _cow_fn(self, cache, src, dst):
        """Copy-on-write: duplicate pool block ``src`` into ``dst`` across
        every layer's K and V pools (kv_cache.py ``copy_kv_block``). Run
        once at admission when a full-prompt prefix-cache hit must resume
        prefill inside its final shared block — the copy is bitwise, so
        the resumed stream stays bit-identical to an uncached run. The
        cache is donated: XLA rewrites one block row per pool in place."""
        if isinstance(cache, LatentKVCache):
            return cache.copy_block(src, dst)
        return PagedKVCache(
            k=tuple(copy_kv_block(p, src, dst) for p in cache.k),
            v=tuple(copy_kv_block(p, src, dst) for p in cache.v),
            lengths=cache.lengths)

    def _draft_k_fn(self, k, params, cache, block_tables, tokens, offsets,
                    active, temperature, top_p, seeds, rounds):
        """All k chained draft micro-steps in ONE compiled program.

        Feeds ``[t_last, d_1 .. d_k]`` at offsets ``offsets + [0, k]``
        through a ``lax.fori_loop`` (the body — one draft forward — is
        traced once, so compile time is O(1) in k and the host pays one
        dispatch for the whole chain). Iteration i writes the fed token's
        KV through the draft block tables and samples proposal d_{i+1} with
        its post-filter distribution; a final trailing forward back-fills
        d_k's KV (sampling discarded) so a FULLY accepted round leaves the
        draft cache covering every emitted token — without it the next
        round's offsets would skip d_k's missing entry. (Folding that
        back-fill into a width-2 first micro-step was tried and measured
        SLOWER: S > 1 leaves the single-position decode attention path, and
        the generic chunk path's full-pool gather costs more than the one
        extra S=1 forward it saves.) Offsets come from the HOST's
        committed-token count, not cache.lengths: rejected suffixes from
        earlier rounds are rolled back simply by feeding the correct lower
        offset, their stale KV masked and overwritten.

        Returns (cache, draft_tokens (B, k) int32, draft_probs (B, k, V)
        fp32) — consumed by the verify program device-to-device.

        ``k`` is bound with functools.partial before jit (like the prefill
        programs bind ``model``): the adaptive-k ladder compiles the same
        body at several round widths. The PRNG stream stride stays
        ``spec_k + 1`` (the maximum width) whatever ``k`` is, so rounds
        run at different widths never reuse a draft key.
        """
        b = self.slots
        v = self.draft_cfg.vocab_size
        toks0 = jnp.zeros((b, k), jnp.int32)
        probs0 = jnp.zeros((b, k, v), jnp.float32)
        valid = active[:, None]

        def micro_step(i, cur, ck, cv):
            logits, (nk, nv) = self.draft_model.apply(
                {"params": params}, cur[:, None], ck, cv, offsets + i,
                block_tables=block_tables, write_valid=valid,
                method="forward_with_cache")
            return logits[:, 0].astype(jnp.float32), nk, nv

        def body(i, carry):
            ck, cv, cur, toks, probs = carry
            last, ck, cv = micro_step(i, cur, ck, cv)
            keys = jax.vmap(draft_key)(seeds, rounds * (self.spec_k + 1) + i)
            nxt, p = jax.vmap(sample_token_with_probs,
                              in_axes=(0, 0, 0, 0, None))(
                last, keys, temperature, top_p, self.top_k)
            toks = jax.lax.dynamic_update_slice_in_dim(
                toks, nxt[:, None], i, axis=1)
            probs = jax.lax.dynamic_update_slice_in_dim(
                probs, p[:, None, :], i, axis=1)
            return ck, cv, nxt, toks, probs

        ck, cv, cur, toks, probs = jax.lax.fori_loop(
            0, k, body, (cache.k, cache.v, tokens, toks0, probs0))
        _, ck, cv = micro_step(jnp.int32(k), cur, ck, cv)  # d_k KV back-fill
        lengths = jnp.where(active, offsets + k + 1, cache.lengths)
        return PagedKVCache(k=ck, v=cv, lengths=lengths), toks, probs

    def _verify_fn(self, k, params, cache, block_tables, tokens,
                   draft_tokens, draft_probs, offsets, active, temperature,
                   top_p, seeds, rounds):
        """Score all k+1 candidate positions in ONE compiled program and
        accept/resample (sampler.py ``spec_accept``).

        Two implementations of the scoring, selected by
        ``spec_verify_impl`` (same math, different numerics/perf point):

        - ``"exact"`` (default): k+1 chained S=1 micro-steps in a
          ``lax.fori_loop`` — the exact forward the decode program runs.
          Identical op shapes compile to identical GEMM accumulation
          orders, so the greedy bit-exactness invariant is STRUCTURAL.
          The host pays one dispatch for the whole verify; eliminating
          the k+1 decode dispatches is the speculative win on
          accelerators (the target FLOPs themselves are not reduced).
        - ``"chunk"``: one (B, k+1) forward through
          ``verify_with_cache`` — additionally batches the verify FLOPs
          into one GEMM pass, the extra win visible even where dispatch
          is free (the CPU bench). But bf16 GEMMs accumulate in a
          shape-dependent order, and a one-ulp logit near-tie is enough
          to flip an argmax between the S=1 and S=k+1 programs (observed
          once in ~10k greedy positions on the CPU bench: top-2 logits
          2.65625 vs 2.640625, the two programs picking opposite
          winners) — greedy equivalence is exact argmax matching on the
          CHUNK's logits, bitwise-equal to the non-speculative stream
          only up to such ties.

        Commits the accepted prefix by setting lengths to ``offsets +
        accepted + 1``; the rejected suffix's KV is stale pool content
        past that length — masked, then overwritten next round. Inactive
        slots write into the null block and keep their lengths.

        ``k`` is partial-bound like the draft program's (adaptive-k
        ladder); ``verify_key`` streams are per-ROUND, so width never
        enters the key schedule."""
        b = self.slots
        v = self.cfg.vocab_size
        seq = jnp.concatenate([tokens[:, None], draft_tokens], axis=1)
        valid = active[:, None]
        if self.spec_verify_impl == "chunk":
            chunk, (nk, nv) = self.model.apply(
                {"params": params}, seq, cache.k, cache.v, offsets,
                block_tables=block_tables, write_valid=valid,
                method="verify_with_cache")
            logits = chunk.astype(jnp.float32)
        else:
            logits0 = jnp.zeros((b, k + 1, v), jnp.float32)

            def body(i, carry):
                ck, cv, logits = carry
                cur = jax.lax.dynamic_slice_in_dim(seq, i, 1, axis=1)
                step, (sk, sv) = self.model.apply(
                    {"params": params}, cur, ck, cv, offsets + i,
                    block_tables=block_tables, write_valid=valid,
                    method="forward_with_cache")
                logits = jax.lax.dynamic_update_slice_in_dim(
                    logits, step.astype(jnp.float32), i, axis=1)
                return sk, sv, logits

            nk, nv, logits = jax.lax.fori_loop(
                0, k + 1, body, (cache.k, cache.v, logits0))
        keys = jax.vmap(verify_key)(seeds, rounds)
        out, acc = jax.vmap(spec_accept, in_axes=(0, 0, 0, 0, 0, 0, None))(
            draft_tokens, draft_probs, logits, keys,
            temperature, top_p, self.top_k)
        lengths = jnp.where(active, offsets + acc + 1, cache.lengths)
        return PagedKVCache(k=nk, v=nv, lengths=lengths), out, acc

    def _tree_draft_fn(self, shape, params, cache, block_tables, refeed,
                       refeed_len, offsets, active, temperature, top_p,
                       seeds, rounds):
        """Propose one token TREE per slot in ONE compiled program.

        The draft runs its ordinary linear chain — one refeed chunk plus
        depth-1 chained S=1 micro-steps — and the tree's branches fall out
        for free: at each level the PRIMARY child is the chain's own
        sample/argmax (drawn from the post-filter distribution q_l, which
        becomes its accept-test q row), and the f_l - 1 SIBLINGS are the
        top logits excluding it. A sibling is a deterministic pick, so its
        honest proposal law is the point mass at its token — its q row is
        the exact one-hot, under which ``tree_accept``'s test
        ``u * q(t) < p(t)`` reduces to accept-with-probability-p(t) and
        the residual fold to removing t from p: a valid rejection step
        that only ADDS acceptance chances on top of the primary chain.

        The REFEED chunk replaces linear spec's first micro-step + d_k
        back-fill: ``refeed`` (B, R) holds the tokens the PREVIOUS round
        emitted (count ``refeed_len``, bonus token last), written at
        positions ``offsets - refeed_len + 1 .. offsets``. A tree round
        can commit tokens the draft chain never fed (an accepted sibling),
        so the draft cache's last window is re-derived from the committed
        truth every round — which also covers the fresh bonus token, hence
        no separate back-fill. Invariant: before the chunk the draft KV is
        correct up to ``offsets - refeed_len``; after it, up to
        ``offsets``; the micro-steps then write the primary chain at
        ``offsets + 1 ..`` (stale beyond the commit, overwritten by the
        next refeed). R and the draft-key stride are the BASE shape's
        ``depth + 1`` whatever rung is running, so ladder rungs share one
        refeed layout and never alias a key.

        Returns (cache, tree_tokens (B, S) — row 0 the root token — and
        draft_probs (B, S, V) — row 0 zeros, primary rows q_l, sibling
        rows one-hots)."""
        b = self.slots
        v = self.draft_cfg.vocab_size
        s = shape.size
        r_w = refeed.shape[1]
        base = offsets - refeed_len + 1
        valid = ((jnp.arange(r_w, dtype=jnp.int32)[None, :]
                  < refeed_len[:, None]) & active[:, None])
        logits, (ck, cv) = self.draft_model.apply(
            {"params": params}, refeed, cache.k, cache.v, base,
            block_tables=block_tables, write_valid=valid,
            method="forward_with_cache")
        last = jnp.take_along_axis(
            logits, (refeed_len - 1)[:, None, None], axis=1
        )[:, 0].astype(jnp.float32)
        t_last = jnp.take_along_axis(refeed, (refeed_len - 1)[:, None],
                                     axis=1)[:, 0]
        tree_toks = jnp.zeros((b, s), jnp.int32).at[:, 0].set(t_last)
        probs = jnp.zeros((b, s, v), jnp.float32)
        for lvl, f in enumerate(shape.fanouts):      # static unroll
            keys = jax.vmap(draft_key)(
                seeds, rounds * self._tree_refeed + lvl)
            nxt, p = jax.vmap(sample_token_with_probs,
                              in_axes=(0, 0, 0, 0, None))(
                last, keys, temperature, top_p, self.top_k)
            s0 = shape.level_start[lvl]
            tree_toks = tree_toks.at[:, s0].set(nxt)
            probs = probs.at[:, s0, :].set(p)
            if f > 1:
                masked = last.at[jnp.arange(b), nxt].set(-jnp.inf)
                _, sib = jax.lax.top_k(masked, f - 1)
                sib = sib.astype(jnp.int32)
                tree_toks = tree_toks.at[:, s0 + 1:s0 + f].set(sib)
                probs = probs.at[:, s0 + 1:s0 + f, :].set(
                    jax.nn.one_hot(sib, v, dtype=jnp.float32))
            if lvl < shape.depth - 1:
                step, (ck, cv) = self.draft_model.apply(
                    {"params": params}, nxt[:, None], ck, cv,
                    offsets + lvl + 1, block_tables=block_tables,
                    write_valid=active[:, None],
                    method="forward_with_cache")
                last = step[:, 0].astype(jnp.float32)
        lengths = jnp.where(active, offsets + shape.depth, cache.lengths)
        return (PagedKVCache(k=ck, v=cv, lengths=lengths), tree_toks,
                probs)

    def _tree_verify_fn(self, shape, params, cache, block_tables,
                        tree_tokens, draft_probs, offsets, active,
                        temperature, top_p, seeds, rounds):
        """Score one flattened token tree per slot and commit the winning
        path, in ONE compiled program.

        ``"chunk"`` mode is the real tree: a single (B, S) ancestor-masked
        forward (``tree_verify_with_cache`` — node KV at ``offsets + row``,
        rope at ``offsets + depth(row)``) scores every branch at once, the
        vmapped accept walk (sampler.py ``tree_accept``) picks the longest
        accepted path under ``tree_key``, and the epilogue REMAPS the
        winners' KV rows from tree-window to committed positions inside the
        slot's own blocks (kv_cache.py ``remap_paged_path``) — losers rot
        as stale bytes past the committed length, so a round still costs
        zero allocator traffic.

        ``"exact"`` mode scores only the PRIMARY chain through the linear
        k+1 chained S=1 micro-steps (:meth:`_verify_fn`, which also does
        the accept under ``verify_key``): the chain's rows land at their
        committed positions directly, so no remap — and the op shapes
        being the decode program's keeps greedy tree-spec streams
        bit-identical to non-speculative decode, the escape hatch the
        multi-branch chunk forward (shape-dependent bf16 accumulation)
        cannot offer. Siblings are proposed but never scored there.

        Returns (cache, out (B, depth+1), accepted (B,), path (B, depth))
        — ``path`` is the accepted nodes' tree rows, what the scheduler's
        branch-utilization gauge reads."""
        b = self.slots
        depth = shape.depth
        if self.spec_verify_impl == "chunk":
            tpos = (offsets[:, None]
                    + jnp.asarray(shape.depths, jnp.int32)[None, :])
            anc = jnp.asarray(shape.anc_mask)
            cm = jnp.asarray(shape.child_matrix, jnp.int32)
            valid = jnp.broadcast_to(active[:, None], tree_tokens.shape)
            logits, (nk, nv) = self.model.apply(
                {"params": params}, tree_tokens, cache.k, cache.v, offsets,
                block_tables=block_tables, tree_positions=tpos,
                anc_mask=anc, write_valid=valid,
                method="tree_verify_with_cache")
            logits = logits.astype(jnp.float32)
            keys = jax.vmap(tree_key)(seeds, rounds)
            out, path, acc = jax.vmap(
                lambda tt, dp, tl, ky, te, tp_: tree_accept(
                    tt, dp, tl, ky, te, tp_, cm, depth, self.top_k))(
                tree_tokens, draft_probs, logits, keys, temperature, top_p)
            nk = tuple(remap_paged_path(p, block_tables, offsets, path, acc)
                       for p in nk)
            nv = tuple(remap_paged_path(p, block_tables, offsets, path, acc)
                       for p in nv)
            lengths = jnp.where(active, offsets + acc + 1, cache.lengths)
            return PagedKVCache(k=nk, v=nv, lengths=lengths), out, acc, path
        prim = list(shape.primary_rows)
        new_cache, out, acc = self._verify_fn(
            depth, params, cache, block_tables, tree_tokens[:, 0],
            tree_tokens[:, prim], draft_probs[:, prim], offsets, active,
            temperature, top_p, seeds, rounds)
        path = jnp.broadcast_to(
            jnp.asarray(prim, jnp.int32)[None, :], (b, depth))
        return new_cache, out, acc, path

    def _adapter_abstract(self, batch=None):
        """Abstract trailing adapter args for the paged programs — a
        ``(pool, rows (batch, P), scales (batch,))`` triple (batch
        defaults to slots) and the B=1 prefill variant ``(pool, row (P,),
        scalar scale)``. Both EMPTY tuples when the engine has no
        adapters, so no-adapter lowerings are unchanged."""
        if not self.adapter_rank:
            return (), ()
        b = self.slots if batch is None else batch
        pool_abs = jax.ShapeDtypeStruct(
            (self.adapter_num_pages, self._adapter_layout.page_elems),
            jnp.float32)
        per = self._adapter_layout.pages_per_adapter
        return ((pool_abs, jax.ShapeDtypeStruct((b, per), jnp.int32),
                 jax.ShapeDtypeStruct((b,), jnp.float32)),
                (pool_abs, jax.ShapeDtypeStruct((per,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.float32)))

    def _build_programs(self):
        p_abs, c_abs = _abstract(self.params), _abstract(self.cache)
        scalar_i = jax.ShapeDtypeStruct((), jnp.int32)
        scalar_f = jax.ShapeDtypeStruct((), jnp.float32)
        slots_i = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        slots_f = jax.ShapeDtypeStruct((self.slots,), jnp.float32)
        slots_b = jax.ShapeDtypeStruct((self.slots,), jnp.bool_)
        self._prefill = {}
        if self.kv_layout == "paged":
            tables_abs = jax.ShapeDtypeStruct(
                (self.slots, self.max_blocks_per_slot), jnp.int32)
            row_abs = jax.ShapeDtypeStruct((self.max_blocks_per_slot,),
                                           jnp.int32)
            # adapter-enabled engines append (pool, page rows, scales) to
            # the paged programs; without adapters the arg tuples are
            # empty and the lowered programs are byte-identical to before
            ad_slots, ad_one = self._adapter_abstract()
            if self._latent:
                self._decode = jax.jit(
                    self._latent_decode_fn, donate_argnums=(1,)).lower(
                    p_abs, c_abs, tables_abs, slots_i, slots_b, slots_f,
                    slots_f, slots_i, slots_i).compile()
                self._cow = jax.jit(
                    self._cow_fn, donate_argnums=(0,)).lower(
                    c_abs, scalar_i, scalar_i).compile()
                for b in self.prefill_buckets:
                    tok_abs = jax.ShapeDtypeStruct((1, b), jnp.int32)
                    self._prefill[b] = jax.jit(
                        self._latent_prefill_fn, donate_argnums=(1,)).lower(
                        p_abs, c_abs, row_abs, tok_abs, scalar_i, scalar_i,
                        scalar_i, scalar_i, scalar_i, scalar_f, scalar_f,
                        scalar_i).compile()
                # what the chunk loop's cover rule goes by (cover_plan)
                cost = {b: program_cost(p) for b, p in self._prefill.items()}
                self._bucket_cost = (cost if all(cost.values()) else None)
                return
            self._decode = jax.jit(
                self._paged_decode_fn, donate_argnums=(1,)).lower(
                p_abs, c_abs, tables_abs, slots_i, slots_b, slots_f,
                slots_f, slots_i, slots_i, *ad_slots).compile()
            self._decode_logits = jax.jit(
                self._paged_logits_fn, donate_argnums=(1,)).lower(
                p_abs, c_abs, tables_abs, slots_i, slots_b,
                *ad_slots).compile()
            # burst programs compile on first use (decode_burst(n) —
            # serving picks ONE n, so the ladder is usually one rung)
            self._burst_programs = {}
            self._cow = jax.jit(
                self._cow_fn, donate_argnums=(0,)).lower(
                c_abs, scalar_i, scalar_i).compile()
            for b in self.prefill_buckets:
                tok_abs = jax.ShapeDtypeStruct((1, b), jnp.int32)
                self._prefill[b] = jax.jit(
                    functools.partial(self._paged_prefill_fn, self.model),
                    donate_argnums=(1,)).lower(
                    p_abs, c_abs, row_abs, tok_abs, scalar_i, scalar_i,
                    scalar_i, scalar_f, scalar_f, scalar_i,
                    *ad_one).compile()
            self._packed_prefill = {}
            if self.prefill_batch > 1:
                p = self.prefill_batch
                rows_abs = jax.ShapeDtypeStruct(
                    (p, self.max_blocks_per_slot), jnp.int32)
                p_i = jax.ShapeDtypeStruct((p,), jnp.int32)
                p_f = jax.ShapeDtypeStruct((p,), jnp.float32)
                p_b = jax.ShapeDtypeStruct((p,), jnp.bool_)
                ad_pack = self._adapter_abstract(batch=p)[0]
                for b in self.prefill_buckets:
                    tok_abs = jax.ShapeDtypeStruct((p, b), jnp.int32)
                    self._packed_prefill[b] = jax.jit(
                        functools.partial(self._packed_prefill_fn,
                                          self.model),
                        donate_argnums=(1,)).lower(
                        p_abs, c_abs, rows_abs, tok_abs, p_i, p_i, p_i,
                        p_b, p_f, p_f, p_i, *ad_pack).compile()
            if self.spec_k:
                dp_abs = _abstract(self.draft_params)
                dc_abs = _abstract(self.draft_cache)
                self._spec_programs = {}
                self._draft_k, self._verify = self._spec_pair(self.spec_k)
                if self.spec_tree is not None:
                    self._tree_programs = {}
                    self._tree_draft, self._tree_verify = self._tree_pair(
                        self.spec_tree)
                self._draft_prefill = {}
                for b in self.prefill_buckets:
                    tok_abs = jax.ShapeDtypeStruct((1, b), jnp.int32)
                    self._draft_prefill[b] = jax.jit(
                        functools.partial(self._paged_prefill_fn,
                                          self.draft_model),
                        donate_argnums=(1,)).lower(
                        dp_abs, dc_abs, row_abs, tok_abs, scalar_i,
                        scalar_i, scalar_i, scalar_f, scalar_f,
                        scalar_i).compile()
            return
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,)).lower(
            p_abs, c_abs, slots_i, slots_b, slots_f, slots_f, slots_i,
            slots_i).compile()
        for b in self.prefill_buckets:
            tok_abs = jax.ShapeDtypeStruct((1, b), jnp.int32)
            self._prefill[b] = jax.jit(
                self._prefill_fn, donate_argnums=(1,)).lower(
                p_abs, c_abs, tok_abs, scalar_i, scalar_i, scalar_f,
                scalar_f, scalar_i).compile()

    def _compile_spec_pair(self, k: int):
        """AOT-compile one (draft-k, verify) program pair at round width
        ``k``. The k-value is bound with functools.partial (the draft/
        verify bodies are width-generic); everything else — shardings,
        donation, op shapes per micro-step — matches the default pair, so
        a ladder rung's greedy stream is bit-identical to running the
        default pair with the extra proposals rejected."""
        p_abs, c_abs = _abstract(self.params), _abstract(self.cache)
        dp_abs = _abstract(self.draft_params)
        dc_abs = _abstract(self.draft_cache)
        slots_i = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        slots_f = jax.ShapeDtypeStruct((self.slots,), jnp.float32)
        slots_b = jax.ShapeDtypeStruct((self.slots,), jnp.bool_)
        tables_abs = jax.ShapeDtypeStruct(
            (self.slots, self.max_blocks_per_slot), jnp.int32)
        dtoks_abs = jax.ShapeDtypeStruct((self.slots, k), jnp.int32)
        dprobs_abs = jax.ShapeDtypeStruct(
            (self.slots, k, self.cfg.vocab_size), jnp.float32)
        draft = jax.jit(
            functools.partial(self._draft_k_fn, k),
            donate_argnums=(1,)).lower(
            dp_abs, dc_abs, tables_abs, slots_i, slots_i, slots_b,
            slots_f, slots_f, slots_i, slots_i).compile()
        verify = jax.jit(
            functools.partial(self._verify_fn, k),
            donate_argnums=(1,)).lower(
            p_abs, c_abs, tables_abs, slots_i, dtoks_abs, dprobs_abs,
            slots_i, slots_b, slots_f, slots_f, slots_i, slots_i).compile()
        return draft, verify

    def _compile_tree_pair(self, shape: TreeShape):
        """AOT-compile one (tree-draft, tree-verify) program pair for
        ``shape``. The shape is bound with functools.partial — its derived
        arrays (depths, ancestor mask, child matrix) bake into the
        programs as constants; the refeed width stays the BASE shape's so
        every rung shares one host-side refeed layout."""
        p_abs, c_abs = _abstract(self.params), _abstract(self.cache)
        dp_abs = _abstract(self.draft_params)
        dc_abs = _abstract(self.draft_cache)
        slots_i = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        slots_f = jax.ShapeDtypeStruct((self.slots,), jnp.float32)
        slots_b = jax.ShapeDtypeStruct((self.slots,), jnp.bool_)
        tables_abs = jax.ShapeDtypeStruct(
            (self.slots, self.max_blocks_per_slot), jnp.int32)
        refeed_abs = jax.ShapeDtypeStruct(
            (self.slots, self._tree_refeed), jnp.int32)
        ttoks_abs = jax.ShapeDtypeStruct((self.slots, shape.size), jnp.int32)
        tprobs_abs = jax.ShapeDtypeStruct(
            (self.slots, shape.size, self.cfg.vocab_size), jnp.float32)
        draft = jax.jit(
            functools.partial(self._tree_draft_fn, shape),
            donate_argnums=(1,)).lower(
            dp_abs, dc_abs, tables_abs, refeed_abs, slots_i, slots_i,
            slots_b, slots_f, slots_f, slots_i, slots_i).compile()
        verify = jax.jit(
            functools.partial(self._tree_verify_fn, shape),
            donate_argnums=(1,)).lower(
            p_abs, c_abs, tables_abs, ttoks_abs, tprobs_abs, slots_i,
            slots_b, slots_f, slots_f, slots_i, slots_i).compile()
        return draft, verify

    def _tree_pair(self, shape: TreeShape):
        """The compiled (tree-draft, tree-verify) pair for ``shape``,
        compiling on first use — the tree sibling of :meth:`_spec_pair`.
        Only shrinkages of the configured base shape are legal (the
        adaptive ladder walks ``TreeShape.shrink_to``), so the ladder is
        finitely bounded and every rung fits the base refeed layout."""
        if self.spec_tree is None:
            raise ValueError("engine built without a tree shape "
                             "(spec_tree unset)")
        shape = parse_spec_tree(shape)
        if (shape.depth > self.spec_tree.depth
                or shape.size > self.spec_tree.size):
            raise ValueError(f"tree rung {shape} exceeds the configured "
                             f"base shape {self.spec_tree}")
        pair = self._tree_programs.get(shape.fanouts)
        if pair is None:
            with use_mesh(self.mesh):  # traced as the build-time programs
                pair = self._compile_tree_pair(shape)
            self._tree_programs[shape.fanouts] = pair
        return pair

    def _compile_burst(self, n: int):
        """AOT-compile the n-token burst decode program (``n`` bound with
        functools.partial like the spec ladder's width)."""
        p_abs, c_abs = _abstract(self.params), _abstract(self.cache)
        slots_i = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        slots_f = jax.ShapeDtypeStruct((self.slots,), jnp.float32)
        slots_b = jax.ShapeDtypeStruct((self.slots,), jnp.bool_)
        tables_abs = jax.ShapeDtypeStruct(
            (self.slots, self.max_blocks_per_slot), jnp.int32)
        return jax.jit(
            functools.partial(self._burst_decode_fn, n),
            donate_argnums=(1,)).lower(
            p_abs, c_abs, tables_abs, slots_i, slots_b, slots_f, slots_f,
            slots_i, slots_i, *self._adapter_abstract()[0]).compile()

    def _burst_program(self, n: int):
        """The compiled n-token burst program, compiling on first use.
        A serving process runs one configured burst width, so this is at
        most a couple of one-time compiles (the scheduler's final partial
        burst clamps n to the smallest remaining budget)."""
        if self.kv_layout != "paged":
            raise ValueError("burst decode requires the paged KV layout "
                             "(the loop writes KV through block tables)")
        if self._latent:
            raise ValueError("burst decode (n > 1) is not written for a "
                             "LatentMoEConfig model")
        n = int(n)
        if not 1 <= n <= self.max_len:
            raise ValueError(f"burst width {n} outside [1, {self.max_len}]")
        prog = self._burst_programs.get(n)
        if prog is None:
            with use_mesh(self.mesh):  # traced as the build-time programs
                prog = self._compile_burst(n)
            self._burst_programs[n] = prog
        return prog

    def _spec_pair(self, k: int):
        """The compiled (draft-k, verify) pair for round width ``k``,
        compiling on first use. The default width ``spec_k`` is compiled
        at engine build (never a stall); other rungs compile once when an
        adaptive-k controller first requests them — the controller's
        ladder is O(log spec_k) wide, so a serving process pays at most a
        handful of one-time compiles over its whole lifetime, each inside
        an admission pause."""
        k = int(k)
        if not 1 <= k <= self.spec_k:
            raise ValueError(f"spec round width {k} outside "
                             f"[1, {self.spec_k}]")
        pair = self._spec_programs.get(k)
        if pair is None:
            with use_mesh(self.mesh):  # traced as the build-time programs
                pair = self._compile_spec_pair(k)
            self._spec_programs[k] = pair
        return pair

    # --- host API ----------------------------------------------------------

    def _prepare_params(self, params, current, what: str):
        """Validate a replacement param tree against the serving one
        (same structure, shapes, dtypes — the AOT programs were lowered
        against ``current``'s abstract tree and would otherwise fail
        opaquely at dispatch), then shard it exactly as ``__init__``
        does."""
        cur_leaves, cur_def = jax.tree_util.tree_flatten(current)
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if cur_def != new_def:
            raise ValueError(f"{what} reload: param tree structure does "
                             f"not match the serving model")
        for c, n in zip(cur_leaves, new_leaves):
            if c.shape != n.shape or c.dtype != n.dtype:
                raise ValueError(
                    f"{what} reload: param leaf {n.shape}/{n.dtype} does "
                    f"not match serving {c.shape}/{c.dtype}")
        with use_mesh(self.mesh):
            shardings = param_shardings(params, self.mesh)
            if shardings is not None:
                params = jax.device_put(params, shardings)
            return jax.tree_util.tree_map(jnp.asarray, params)

    def reload_params(self, params) -> None:
        """Hot-swap the TARGET params under the existing AOT programs.

        No re-compile: every program takes params per call and only the
        cache is donated, so installing a new (structurally identical)
        tree is one device_put. The caller (deploy/reload.py) owns the
        surrounding lifecycle — pausing admission, letting the in-flight
        decode round finish, flushing the prefix cache whose KV was
        computed under the old weights — and hands the tree over in LOOP
        form (the engine converted at build; scan-form checkpoints go
        through ``unstack_layer_params`` first, as the constructor did)."""
        self.params = self._prepare_params(params, self.params, "target")

    def reload_draft_params(self, params) -> None:
        """Hot-swap the DRAFT params (speculative decoding) in the same
        admission pause as :meth:`reload_params`. The draft cache's
        content becomes stale draft-KV of the OLD draft — harmless: each
        round re-addresses only the committed prefix, and in-flight
        slots' acceptance just dips until the new draft's KV dominates
        (the adaptive-k controller resets alongside)."""
        if not self.spec_k:
            raise ValueError("engine built without a draft model "
                             "(spec_k == 0)")
        self.draft_params = self._prepare_params(params, self.draft_params,
                                                 "draft")

    def cow_copy(self, src_block: int, dst_block: int) -> None:
        """Copy-on-write one pool block: ``src_block``'s K/V (all layers)
        into ``dst_block``. The scheduler calls this before remapping a
        slot's table away from a shared block it must write into (prefix
        cache, full-prompt hit); the shared original is never written."""
        if self.kv_layout != "paged":
            raise ValueError("copy-on-write requires the paged KV layout")
        self.cache = self._cow(self.cache, np.int32(src_block),
                               np.int32(dst_block))

    def export_slot_blocks(self, blocks, out_dir: str, *, slot: int,
                           meta=None) -> dict:
        """Serialize pool rows ``blocks`` (the slot's committed KV, in
        block-table order) into a checksummed artifact directory — the
        device side of spill and handoff. ``length`` is captured from the
        live cache so the restore resumes the decode position exactly.
        Returns the artifact manifest."""
        self._need_kv_blocks("block export")
        length = int(np.asarray(self.cache.lengths)[slot])
        return export_blocks(self.cache, blocks, out_dir,
                             length=length, meta=meta)

    def import_slot_blocks(self, art_dir: str, dest_blocks,
                           slot: int) -> dict:
        """Verify artifact ``art_dir`` (CRC of every payload BEFORE any
        device write) and scatter it into pool rows ``dest_blocks``, then
        restore ``slot``'s fill count from the manifest's recorded length.
        Raises ``KVBlockIntegrityError`` with the cache untouched on any
        mismatch. Returns the manifest."""
        self._need_kv_blocks("block import")
        cache, manifest = import_blocks(self.cache, art_dir, dest_blocks)
        self.cache = cache.replace(
            lengths=cache.lengths.at[slot].set(
                np.int32(manifest["length"])))
        return manifest

    def import_pool_blocks(self, art_dir: str, dest_blocks) -> dict:
        """Verify artifact ``art_dir`` and scatter it into pool rows
        ``dest_blocks`` WITHOUT touching any slot's fill count — the
        disaggregated decode import sets the length once, after every
        shipment is resident, via :meth:`set_slot_length`. Raises
        ``KVBlockIntegrityError`` with the cache untouched on any
        mismatch. Returns the manifest."""
        self._need_kv_blocks("block import")
        cache, manifest = import_blocks(self.cache, art_dir, dest_blocks)
        self.cache = cache
        return manifest

    def import_pool_block_batch(self, parts,
                                allow_partial: bool = False) -> list:
        """Verify every artifact in ``parts`` ((art_dir, dest_blocks)
        pairs) and land them all in ONE scatter per pool array, WITHOUT
        touching any slot's fill count — the disaggregated decode
        admission imports a request's whole shipment train as a single
        device write, then sets the length once via
        :meth:`set_slot_length`. Raises ``KVBlockIntegrityError`` with
        the cache untouched on any mismatch (verification of every
        payload precedes the first device write). Returns the manifests
        in ``parts`` order."""
        self._need_kv_blocks("block import")
        cache, manifests = import_block_batch(
            self.cache, parts, allow_partial=allow_partial)
        self.cache = cache
        return manifests

    def _need_kv_blocks(self, what: str) -> None:
        """Spill, handoff, shipments and the fleet store move K/V blocks
        of a ``PagedKVCache``; no other cache has an artifact format."""
        if self.kv_layout != "paged":
            raise ValueError(f"{what} requires the paged KV layout")
        if self._latent:
            raise ValueError(
                f"{what} is not written for a LatentMoEConfig model: its "
                f"blocks hold latent and index-key rows and its sliding "
                f"layers hold rings, and the artifact format is K/V blocks")

    def set_slot_length(self, slot: int, length: int) -> None:
        """Set ``slot``'s fill count directly (paged only) — the decode
        side of a disaggregated admission, after every shipment's blocks
        are resident, so the first decode round attends to the full
        committed prefix."""
        if self.kv_layout != "paged":
            raise ValueError("slot length set requires the paged KV layout")
        self.cache = self.cache.replace(
            lengths=self.cache.lengths.at[slot].set(np.int32(int(length))))

    def _adapter_call_args(self, rows, scales, batch=None):
        """Host-side trailing adapter args for the batched paged programs
        (empty tuple when the engine has no adapters). ``rows``/``scales``
        default to all-null (base-only) so adapter-enabled engines serve
        plain traffic without the caller carrying adapter state."""
        if not self.adapter_rank:
            if rows is not None or scales is not None:
                raise ValueError("adapter rows given but engine built "
                                 "without adapters (adapter_rank == 0)")
            return ()
        if rows is None or scales is None:
            rows, scales = self._null_adapter_args(
                self.slots if batch is None else batch)
        return (self.adapter_pool, np.asarray(rows, np.int32),
                np.asarray(scales, np.float32))

    def _prefill_adapter_args(self, row, scale):
        """Trailing adapter args for the B=1 prefill programs: one page
        row + one scalar scale (None -> the null adapter)."""
        if not self.adapter_rank:
            if row is not None:
                raise ValueError("adapter row given but engine built "
                                 "without adapters (adapter_rank == 0)")
            return ()
        per = self._adapter_layout.pages_per_adapter
        if row is None:
            row, scale = np.zeros((per,), np.int32), 0.0
        return (self.adapter_pool,
                np.asarray(row, np.int32).reshape(per),
                np.float32(scale))

    def _stream_chunks(self, draft: bool, row, ids, slot, temperature,
                       top_p, seed, stop_check, on_chunk, start_pos=0,
                       adapter_row=None, adapter_scale=0.0):
        """Stream ``ids`` through the paged prefill bucket programs of the
        target (or, spec mode, the draft) model, beginning at absolute
        position ``start_pos`` (0 = full prompt; a prefix-cache hit resumes
        at its first uncached position — the chunk loop already runs every
        chunk at an explicit offset, so resumption is just a nonzero start);
        returns the final chunk's sampled token, or None if ``stop_check``
        fired between chunks."""
        n = ids.size
        chunk = self.prefill_buckets[-1]
        start, tok = int(start_pos), None
        while start < n:
            m = min(chunk, n - start)
            bucket = next(b for b in self.prefill_buckets if b >= m)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :m] = ids[start:start + m]
            args = (row, padded, np.int32(slot), np.int32(start),
                    np.int32(m), np.float32(temperature), np.float32(top_p),
                    np.int32(seed))
            self._m_reads["prefill", min(bucket, 2)].inc()
            if draft:
                self.draft_cache, tok = self._draft_prefill[bucket](
                    self.draft_params, self.draft_cache, *args)
            else:
                self._m_rows["new"].inc(m)
                self._m_rows["padding"].inc(bucket - m)
                self.cache, tok = self._prefill[bucket](
                    self.params, self.cache, *args,
                    *self._prefill_adapter_args(adapter_row, adapter_scale))
            start += m
            if on_chunk is not None:
                on_chunk()
            if start < n and stop_check is not None and stop_check():
                return None  # interrupted between chunks; request unserved
        return tok

    def prefill(self, slot: int, token_ids, block_row=None,
                draft_block_row=None, temperature: float = 0.0,
                top_p: float = 1.0, seed: int = 0,
                stop_check: Optional[Callable[[], bool]] = None,
                on_chunk: Optional[Callable[[], None]] = None,
                start_pos: int = 0,
                draft_start_pos: int = 0,
                adapter_row=None,
                adapter_scale: float = 0.0,
                rings_held: Optional[Tuple[int, int]] = None
                ) -> Optional[int]:
        """Prompt into ``slot``; returns the first generated token id.

        Ring layout: the prompt must fit the largest bucket (one shot).
        Paged layout: ``block_row`` (blocks_per_slot,) is the slot's block
        table row from the scheduler's allocator, and prompts LONGER than
        the largest bucket stream through it in chunks of that bucket size
        (the last chunk picks its best-fit bucket). ``on_chunk`` fires after
        every finished chunk; between chunks ``stop_check`` is consulted —
        if it returns True the prefill stops cleanly AFTER the current chunk
        and returns None (caller frees the blocks and reports the request
        unserved: the drain-lifecycle contract for mid-prompt signals).

        ``start_pos`` (paged only) resumes the prompt at an absolute
        position: positions [0, start_pos) are NOT computed — the block
        row's leading entries must already hold their committed KV
        (prefix-cache hit blocks). The resumed chunks attend to those
        positions through the same block tables, and the chunk programs
        are the identical AOT bucket set a zero-offset prefill uses, so a
        cache-hit stream is bitwise the uncached stream.

        Spec mode additionally prefills the DRAFT cache through
        ``draft_block_row`` (its own pool's allocation) after the target
        phase — same chunking, same ``stop_check`` at every chunk boundary
        including the phase boundary, so a mid-prompt drain still frees
        BOTH pools and reports the request unserved. The draft phase's
        sampled token is discarded (the target's first token is the one
        emitted; the draft proposes only from round 1 on). The draft phase
        resumes at ``draft_start_pos`` under the same contract as the
        target's ``start_pos``: the scheduler keeps a DRAFT-pool mirror of
        the prefix cache fed the same insertions, so a shared system
        prompt skips the draft prefill compute too, and because the shared
        draft blocks hold the bytes a zero-offset draft prefill would have
        written, a cache-hit spec stream's proposals — and therefore the
        stream itself — are unchanged cache-on vs cache-off
        (tests/test_spec_decode.py asserts it).

        ``rings_held`` (a ``LatentKVCache`` model only) is ``(length,
        win_from)`` of the request that last ran in ``slot``, for a caller
        that knows this prompt continues it: the slot's window rings still
        hold that request's last ``cfg.window_ring`` positions before
        ``length``, so a call resumed at ``start_pos`` recomputes nothing
        to rebuild them (:meth:`rings_cover` says when, and the call
        refuses otherwise). Without it a resumed call rebuilds the windows
        (:meth:`_stream_latent_chunks`).
        """
        ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = ids.size
        begin = self._first_computed(start_pos, rings_held)

        def first_bucket():  # of the (usually only) chunk
            if self._latent:
                return cover_plan(max(n - begin, 1), self.prefill_buckets,
                                  self._bucket_cost)[0]
            m = min(self.prefill_buckets[-1], max(n - int(start_pos), 1))
            return next(b for b in self.prefill_buckets if b >= m)

        with span("ftl:engine.prefill", new_tokens=n - int(start_pos),
                  start_pos=int(start_pos), bucket=first_bucket,
                  held=int(rings_held is not None),
                  rebuilt_rows=int(start_pos) - begin):
            return self._prefill_spanned(
                ids, slot, block_row, draft_block_row, temperature, top_p,
                seed, stop_check, on_chunk, start_pos, draft_start_pos,
                adapter_row, adapter_scale, rings_held)

    def _first_computed(self, start_pos: int, rings_held) -> int:
        """The first position a prefill resumed at ``start_pos`` computes:
        ``start_pos`` itself, or — a ``LatentKVCache`` model with no held
        rings to resume over — ``cfg.rebuild_span`` positions before it,
        to rebuild the sliding layers' windows."""
        if self._latent and rings_held is None:
            return max(0, int(start_pos) - self.cfg.rebuild_span)
        return int(start_pos)

    def rings_cover(self, length: int, start_pos: int) -> bool:
        """Whether a slot whose window rings were last written up to
        position ``length`` (exclusive) still holds every row a prefill
        resumed at ``start_pos`` reads: the ``sliding_window - 1``
        positions before ``start_pos``. The ring keeps ``[length -
        window_ring, length)``, and the rows of ``[start_pos, length)``
        lie under every window the call reads and are overwritten as it
        writes them."""
        return (self._latent and 0 <= length - start_pos
                <= self.cfg.window_ring - self.cfg.sliding_window)

    def window_from(self, slot: int) -> int:
        """The first position ``slot``'s window rings hold of the request
        last prefilled into it (the host's copy of ``cache.win_from``)."""
        return int(self._ring_from[slot])

    def _prefill_spanned(self, ids, slot, block_row, draft_block_row,
                         temperature, top_p, seed, stop_check, on_chunk,
                         start_pos, draft_start_pos, adapter_row,
                         adapter_scale, rings_held=None) -> Optional[int]:
        """:meth:`prefill` inside its ``ftl:engine.prefill`` span."""
        n = ids.size
        if start_pos and self.kv_layout != "paged":
            raise ValueError("start_pos requires the paged KV layout")
        if self.kv_layout != "paged":
            if not 0 < n <= self.prefill_buckets[-1]:
                raise ValueError(f"prompt length {n} outside "
                                 f"(0, {self.prefill_buckets[-1]}]")
            bucket = next(b for b in self.prefill_buckets if b >= n)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = ids
            with span("ftl:engine.prefill.dispatch"):
                self.cache, tok = self._prefill[bucket](
                    self.params, self.cache, padded, np.int32(slot),
                    np.int32(n), np.float32(temperature),
                    np.float32(top_p), np.int32(seed))
            with span("ftl:engine.prefill.sync"):
                return int(tok)
        if not 0 < n <= self.max_len:
            raise ValueError(f"prompt length {n} outside (0, {self.max_len}]")
        if block_row is None:
            raise ValueError("paged prefill requires the slot's block_row")
        row = np.asarray(block_row, np.int32).reshape(-1)
        if row.shape[0] != self.max_blocks_per_slot:
            raise ValueError(f"block_row has {row.shape[0]} entries, "
                             f"expected {self.max_blocks_per_slot}")
        if self.spec_k and draft_block_row is None:
            raise ValueError("spec-mode prefill requires draft_block_row")
        if not 0 <= start_pos < n:
            raise ValueError(f"start_pos {start_pos} outside [0, {n})")
        if rings_held is not None:
            if not self._latent:
                raise ValueError("rings_held: this model keeps no window "
                                 "rings in its slots")
            length, win_from = (int(v) for v in rings_held)
            if not (self.rings_cover(length, start_pos)
                    and 0 <= win_from <= start_pos):
                raise ValueError(
                    f"rings_held: rings written up to {length} from "
                    f"{win_from} do not cover a resume at {start_pos} "
                    f"(window {self.cfg.sliding_window}, ring "
                    f"{self.cfg.window_ring})")
            rings_held = (length, win_from)
        stats = None
        with span("ftl:engine.prefill.dispatch"):
            if self._latent:
                tok, stats = self._stream_latent_chunks(
                    row, ids, slot, temperature, top_p, seed, stop_check,
                    on_chunk, start_pos, rings_held)
            else:
                tok = self._stream_chunks(
                    False, row, ids, slot, temperature, top_p, seed,
                    stop_check, on_chunk, start_pos=start_pos,
                    adapter_row=adapter_row, adapter_scale=adapter_scale)
        if tok is None:
            return None
        if self.spec_k:
            if stop_check is not None and stop_check():
                return None  # drain at the target/draft phase boundary
            drow = np.asarray(draft_block_row, np.int32).reshape(-1)
            if drow.shape[0] != self.max_blocks_per_slot:
                raise ValueError(
                    f"draft_block_row has {drow.shape[0]} entries, "
                    f"expected {self.max_blocks_per_slot}")
            if not 0 <= draft_start_pos <= n:
                raise ValueError(f"draft_start_pos {draft_start_pos} "
                                 f"outside [0, {n}]")
            if draft_start_pos == n:
                # Full-prompt draft hit. Unlike the target (which must
                # re-derive the LAST position's logits to sample the first
                # token, hence its COW resume at n-1), the draft phase
                # samples nothing — its only job is committed KV for
                # positions [0, n), and the shared blocks already hold it.
                # Nothing to compute: just commit the fill count.
                lengths = np.asarray(self.draft_cache.lengths).copy()
                lengths[slot] = n
                self.draft_cache = self.draft_cache.replace(
                    lengths=jnp.asarray(lengths))
            else:
                with span("ftl:engine.prefill.dispatch"):
                    draft_tok = self._stream_chunks(
                        True, drow, ids, slot, temperature, top_p, seed,
                        stop_check, on_chunk, start_pos=draft_start_pos)
                if draft_tok is None:
                    return None
        with span("ftl:engine.prefill.sync"):
            tok = int(tok)
        if stats:
            counts = self._count_stats("prefill", stats)
            with span("ftl:engine.prefill.stats", **counts):
                pass
        return tok

    def _stream_latent_chunks(self, row, ids, slot, temperature, top_p,
                              seed, stop_check, on_chunk, start_pos,
                              rings_held=None):
        """:meth:`_stream_chunks` for a ``LatentKVCache`` model. A call
        that resumes at ``start_pos`` (a prefix-cache hit) begins
        ``cfg.rebuild_span`` positions earlier: the full layers read those
        positions from the cached blocks and write nothing before
        ``start_pos``, the sliding layers recompute them, and from
        ``start_pos`` on every layer's output is what an uncached prefill
        gives — a sliding layer's window is exact once its input has been
        for ``sliding_window - 1`` positions, and the full layers below
        the first sliding layer are exact at once. Over ``rings_held`` =
        (length, win_from), checked by the caller, it begins AT
        ``start_pos``: the rings already hold the windows, of a request
        whose rows begin at ``win_from``. The calls that cover the rows are
        :func:`cover_plan`'s, by the compiled programs' own costs. Returns
        (the final chunk's token or None, the chunks' counts, still on the
        device)."""
        n = ids.size
        resume = int(start_pos)
        start = self._first_computed(resume, rings_held)
        seq_from = start if rings_held is None else rings_held[1]
        self._ring_from[slot] = seq_from
        tok, stats = None, []
        for bucket in cover_plan(n - start, self.prefill_buckets,
                                 self._bucket_cost):
            m = min(bucket, n - start)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :m] = ids[start:start + m]
            self._m_reads["prefill", min(bucket, 2)].inc()
            again = min(max(resume - start, 0), m)
            self._m_rows["recomputed"].inc(again)
            self._m_rows["new"].inc(m - again)
            self._m_rows["padding"].inc(bucket - m)
            self.cache, tok, st = self._prefill[bucket](
                self.params, self.cache, row, padded, np.int32(slot),
                np.int32(start), np.int32(m), np.int32(resume),
                np.int32(seq_from), np.float32(temperature),
                np.float32(top_p), np.int32(seed))
            stats.append(st)
            start += m
            if on_chunk is not None:
                on_chunk()
            if start < n and stop_check is not None and stop_check():
                return None, stats
        return tok, stats

    def _count_stats(self, phase: str, stats) -> dict:
        """The counts a ``LatentKVCache`` model's programs returned beside
        their tokens (already read back with them), summed over ``stats``:
        into the counters of ``phase`` and, by the names of
        ``models/latent_moe.py`` ``STATS``, for the round's stats span."""
        from ..models.latent_moe import STATS

        total = np.sum([np.asarray(st, np.int64) for st in stats], axis=0)
        for counter, value in zip(self._stat_counters[phase], total):
            counter.inc(float(value))
        return {name: int(v) for name, v in zip(STATS, total)}

    def prefill_packed(self, rows, bucket: int, adapter_rows=None,
                       adapter_scales=None):
        """ONE packed prefill round: each entry of ``rows`` is a
        ``(slot, chunk_ids, start, block_row, temperature, top_p, seed)``
        tuple — request ``slot``'s NEXT prompt chunk (``chunk_ids``, at
        most ``bucket`` tokens) at absolute position ``start`` through its
        ``block_row`` — and all of them run in one (P, bucket) dispatch
        (P = ``prefill_batch``; missing rows are inactive padding).

        The caller (the scheduler's packed admission lane) owns the chunk
        loop the sequential :meth:`prefill` runs internally: it computes
        each row's next chunk with the SAME best-fit bucket discipline
        ``_stream_chunks`` uses and groups rows by bucket, which is what
        keeps per-row chunk shapes — and therefore the streams, on the
        gather impl — bit-identical to sequential prefill. Returns one
        sampled token id per row; only a row whose chunk was its prompt's
        FINAL chunk has a meaningful token (the first generated token),
        exactly like the sequential chunk loop's intermediate discards.

        Prefix-cache divergent starts need nothing special here: a resumed
        row simply arrives with ``start`` > 0 and a block row whose leading
        entries are the shared blocks, as in sequential resumption."""
        if self.kv_layout != "paged":
            raise ValueError("packed prefill requires the paged KV layout")
        if self.prefill_batch < 2:
            raise ValueError("engine built without the packed prefill lane "
                             "(prefill_batch < 2)")
        bucket = int(bucket)
        with span("ftl:engine.prefill", rows=len(rows), bucket=bucket,
                  new_tokens=lambda: sum(np.size(r[1]) for r in rows)):
            return self._prefill_packed_spanned(rows, bucket, adapter_rows,
                                                adapter_scales)

    def _prefill_packed_spanned(self, rows, bucket: int, adapter_rows,
                                adapter_scales):
        """:meth:`prefill_packed` inside its ``ftl:engine.prefill`` span."""
        if bucket not in self.prefill_buckets:
            raise ValueError(f"bucket {bucket} not in compiled set "
                             f"{self.prefill_buckets}")
        p = self.prefill_batch
        if not 1 <= len(rows) <= p:
            raise ValueError(f"{len(rows)} packed rows outside [1, {p}]")
        toks = np.zeros((p, bucket), np.int32)
        block_rows = np.zeros((p, self.max_blocks_per_slot), np.int32)
        slots = np.zeros((p,), np.int32)
        starts = np.zeros((p,), np.int32)
        lens = np.zeros((p,), np.int32)
        active = np.zeros((p,), bool)
        temp = np.zeros((p,), np.float32)
        tp = np.ones((p,), np.float32)
        seeds = np.zeros((p,), np.int32)
        for i, (slot, ids, start, row, temperature, top_p, seed) in \
                enumerate(rows):
            ids = np.asarray(ids, np.int32).reshape(-1)
            if not 0 < ids.size <= bucket:
                raise ValueError(f"packed row {i}: chunk length {ids.size} "
                                 f"outside (0, {bucket}]")
            row = np.asarray(row, np.int32).reshape(-1)
            if row.shape[0] != self.max_blocks_per_slot:
                raise ValueError(f"packed row {i}: block_row has "
                                 f"{row.shape[0]} entries, expected "
                                 f"{self.max_blocks_per_slot}")
            toks[i, :ids.size] = ids
            block_rows[i] = row
            slots[i] = slot
            starts[i] = start
            lens[i] = ids.size
            active[i] = True
            temp[i] = temperature
            tp[i] = top_p
            seeds[i] = seed
        ad = ()
        if self.adapter_rank:
            per = self._adapter_layout.pages_per_adapter
            a_rows = np.zeros((p, per), np.int32)
            a_scales = np.zeros((p,), np.float32)
            if adapter_rows is not None:
                for i, (r, s) in enumerate(zip(adapter_rows,
                                               adapter_scales)):
                    a_rows[i] = np.asarray(r, np.int32).reshape(per)
                    a_scales[i] = s
            ad = (self.adapter_pool, a_rows, a_scales)
        elif adapter_rows is not None:
            raise ValueError("adapter rows given but engine built "
                             "without adapters (adapter_rank == 0)")
        self._m_reads["prefill", min(bucket, 2)].inc()
        with span("ftl:engine.prefill.dispatch"):
            self.cache, out = self._packed_prefill[bucket](
                self.params, self.cache, block_rows, toks, slots, starts,
                lens, active, temp, tp, seeds, *ad)
        with span("ftl:engine.prefill.sync"):
            return [int(t) for t in np.asarray(out)[:len(rows)]]

    def decode_step(self, tokens, active, temperature, top_p, seeds, steps,
                    block_tables=None, adapter_rows=None,
                    adapter_scales=None) -> np.ndarray:
        """One decode iteration over all slots; host arrays in/out. The
        paged layout additionally takes the scheduler's (slots,
        blocks_per_slot) block tables, and adapter-enabled engines take
        each slot's adapter page row + scale (``adapter_rows`` (slots, P)
        / ``adapter_scales`` (slots,); None = all base-only)."""
        if self.kv_layout == "paged" and block_tables is None:
            raise ValueError("paged decode requires block_tables")
        temperature, top_p = self._count_epilogue(temperature, top_p)
        with self._decode_span(active):
            stats = None
            with span("ftl:engine.decode.dispatch"):
                if self._latent:
                    self.cache, toks, stats = self._decode(
                        self.params, self.cache,
                        np.asarray(block_tables, np.int32),
                        np.asarray(tokens, np.int32),
                        np.asarray(active, bool), temperature, top_p,
                        np.asarray(seeds, np.int32),
                        np.asarray(steps, np.int32))
                elif self.kv_layout == "paged":
                    self.cache, toks = self._decode(
                        self.params, self.cache,
                        np.asarray(block_tables, np.int32),
                        np.asarray(tokens, np.int32),
                        np.asarray(active, bool), temperature, top_p,
                        np.asarray(seeds, np.int32),
                        np.asarray(steps, np.int32),
                        *self._adapter_call_args(adapter_rows,
                                                 adapter_scales))
                else:
                    self.cache, toks = self._decode(
                        self.params, self.cache,
                        np.asarray(tokens, np.int32),
                        np.asarray(active, bool), temperature, top_p,
                        np.asarray(seeds, np.int32),
                        np.asarray(steps, np.int32))
            with span("ftl:engine.decode.sync"):
                toks = np.asarray(toks)
            if stats is not None:
                counts = self._count_stats("decode", [stats])
                with span("ftl:engine.decode.stats", **counts):
                    pass
            return toks

    def _count_epilogue(self, temperature, top_p):
        """The float32 arrays a decode round dispatches, counted under the
        tier its epilogue takes on the device: the program's branch and
        this count evaluate the same rule on the same values."""
        temperature = np.asarray(temperature, np.float32)
        top_p = np.asarray(top_p, np.float32)
        self._m_tiers.labels(
            tier=TIERS[epilogue_tier(temperature, top_p)]).inc()
        return temperature, top_p

    def _decode_span(self, active, lengths=None, n: int = 1,
                     window: int = 1):
        """The ``ftl:engine.decode`` span of one decode round.
        ``live_tokens`` is the KV the round's target-model passes attend
        to: over the active slots and the ``n`` sequential iterations of
        a burst, the slot's committed length plus the ``window`` positions
        the pass itself writes (1 for a decode step; a speculative
        verify's k + 1 or tree size; its draft passes read another
        model's cache and are not counted). With no ``lengths`` from the
        caller the slot lengths are read back from the device — one
        (slots,) transfer of an array the previous round already
        finished, and only while a profiler is running."""
        def live_tokens():
            act = np.asarray(active, bool)
            lens = np.asarray(self.cache.lengths if lengths is None
                              else lengths)[act]
            # iteration i of a burst attends to i more positions
            return int(n * (int(lens.sum()) + act.sum() * window)
                       + act.sum() * (n * (n - 1) // 2))

        if self.kv_layout == "paged":
            self._m_reads["decode", 1].inc()
        return span("ftl:engine.decode", n=n, live_tokens=live_tokens,
                    slots_active=lambda: int(np.count_nonzero(active)))

    def decode_logits(self, tokens, active, block_tables=None,
                      adapter_rows=None, adapter_scales=None) -> np.ndarray:
        """UNFUSED decode iteration: run the forward, sync the (slots, V)
        fp32 logits to the host, sample nothing. The caller samples with
        sampler.py ``sample_slot_tokens`` — same function the fused
        programs trace — which is what pins the fused/unfused stream
        bit-match the bench asserts. Paged layout only (it exists as the
        fused epilogue's measured baseline)."""
        if self.kv_layout != "paged" or self._latent:
            raise ValueError("decode_logits requires the paged KV layout "
                             "of a K/V model")
        if block_tables is None:
            raise ValueError("paged decode requires block_tables")
        self.cache, logits = self._decode_logits(
            self.params, self.cache, np.asarray(block_tables, np.int32),
            np.asarray(tokens, np.int32), np.asarray(active, bool),
            *self._adapter_call_args(adapter_rows, adapter_scales))
        return np.asarray(logits)

    def decode_burst(self, tokens, active, temperature, top_p, seeds, steps,
                     n, block_tables=None, adapter_rows=None,
                     adapter_scales=None) -> np.ndarray:
        """A burst of ``n`` decode iterations in ONE dispatch + ONE host
        sync; returns (slots, n) token ids. Greedy streams are bit-equal
        to ``n`` sequential :meth:`decode_step` calls and sampled slots
        share their PRNG schedule (``_burst_decode_fn`` documents why);
        EOS/budget truncation of the overshoot is the scheduler's job
        (``Scheduler._bank_burst``). ``n == 1`` runs the ordinary decode
        program — same math, no extra compile."""
        if self.kv_layout != "paged":
            raise ValueError("burst decode requires the paged KV layout")
        if block_tables is None:
            raise ValueError("paged decode requires block_tables")
        n = int(n)
        if n == 1:
            return self.decode_step(tokens, active, temperature, top_p,
                                    seeds, steps,
                                    block_tables=block_tables,
                                    adapter_rows=adapter_rows,
                                    adapter_scales=adapter_scales)[:, None]
        prog = self._burst_program(n)
        temperature, top_p = self._count_epilogue(temperature, top_p)
        with self._decode_span(active, n=n):
            with span("ftl:engine.decode.dispatch"):
                self.cache, toks = prog(
                    self.params, self.cache,
                    np.asarray(block_tables, np.int32),
                    np.asarray(tokens, np.int32), np.asarray(active, bool),
                    temperature, top_p,
                    np.asarray(seeds, np.int32), np.asarray(steps, np.int32),
                    *self._adapter_call_args(adapter_rows, adapter_scales))
            with span("ftl:engine.decode.sync"):
                return np.asarray(toks)

    def spec_round(self, tokens, lengths, active, temperature, top_p, seeds,
                   rounds, block_tables=None, draft_block_tables=None,
                   k=None):
        """One speculative round over all slots: k draft proposals then one
        verify pass — two dispatches for up to k+1 emitted tokens.

        ``lengths`` (slots,) is each slot's COMMITTED KV count, i.e.
        ``prompt_len + emitted - 1`` (the last emitted token's KV is not yet
        written; the round writes it at ``lengths[s]`` first) — the host
        derives it from its own token bookkeeping, which is what makes
        rejected-suffix rollback free: stale device KV past the committed
        prefix is simply re-addressed. ``tokens`` is each slot's last
        emitted token, ``rounds`` its per-request round counter (PRNG
        stream index). Returns ``(out_tokens (slots, k+1), accepted
        (slots,))`` host arrays: slot s emitted ``accepted[s] + 1`` tokens,
        ``out_tokens[s, :accepted[s] + 1]`` (accepted draft prefix plus the
        verify pass's bonus/resampled token).

        ``k`` (default ``spec_k``) selects the round width from the
        compiled ladder (:meth:`_spec_pair`) — an adaptive-k controller
        shrinks it when live acceptance drops (e.g. a freshly hot-swapped
        target running against a stale draft) so a bad draft degrades
        toward plain decode instead of burning k rejected proposals per
        round. ``out_tokens`` is then (slots, k+1).
        """
        if not self.spec_k:
            raise ValueError("engine built without a draft model "
                             "(spec_k == 0)")
        if block_tables is None or draft_block_tables is None:
            raise ValueError("spec_round requires both pools' block tables")
        draft_prog, verify_prog = (
            (self._draft_k, self._verify) if k is None
            else self._spec_pair(k))
        toks = np.asarray(tokens, np.int32)
        lens = np.asarray(lengths, np.int32)
        act = np.asarray(active, bool)
        temp = np.asarray(temperature, np.float32)
        tp = np.asarray(top_p, np.float32)
        sd = np.asarray(seeds, np.int32)
        rd = np.asarray(rounds, np.int32)
        with self._decode_span(act, lens,
                               window=(self.spec_k if k is None else k) + 1):
            with span("ftl:engine.decode.dispatch"):
                self.draft_cache, d_toks, d_probs = draft_prog(
                    self.draft_params, self.draft_cache,
                    np.asarray(draft_block_tables, np.int32), toks, lens,
                    act, temp, tp, sd, rd)
                self.cache, out, acc = verify_prog(
                    self.params, self.cache,
                    np.asarray(block_tables, np.int32),
                    toks, d_toks, d_probs, lens, act, temp, tp, sd, rd)
            with span("ftl:engine.decode.sync"):
                return np.asarray(out), np.asarray(acc)

    def spec_tree_round(self, refeed, refeed_len, lengths, active,
                        temperature, top_p, seeds, rounds,
                        block_tables=None, draft_block_tables=None,
                        shape=None):
        """One TREE-speculative round over all slots: a branching draft
        then one ancestor-masked verify — still two dispatches, but up to
        ``depth + 1`` emitted tokens with extra acceptance chances at
        every level (an accepted sibling where linear spec would have
        rejected the whole suffix).

        ``lengths`` is the committed-KV convention of :meth:`spec_round`;
        ``refeed`` (slots, depth+1) / ``refeed_len`` carry the tokens the
        PREVIOUS round emitted per slot (first round: just the prefill
        token, len 1) — the draft rewrites their KV window before
        proposing, because a committed sibling is a token its chain never
        fed (``_tree_draft_fn`` documents the invariant). ``shape``
        (default the configured ``spec_tree``) selects the rung from the
        compiled ladder; an adaptive controller passes
        ``engine.spec_tree.shrink_to(k)``.

        Returns ``(out_tokens (slots, depth+1), accepted (slots,), path
        (slots, depth))`` host arrays: slot s emitted ``accepted[s] + 1``
        tokens; ``path[s, :accepted[s]]`` is the accepted nodes' tree rows
        (primary chain under ``exact`` verify), which is how the scheduler
        attributes acceptance to branches."""
        if self.spec_tree is None:
            raise ValueError("engine built without a tree shape "
                             "(spec_tree unset)")
        if block_tables is None or draft_block_tables is None:
            raise ValueError("spec_tree_round requires both pools' block "
                             "tables")
        shape = self.spec_tree if shape is None else parse_spec_tree(shape)
        draft_prog, verify_prog = self._tree_pair(shape)
        rf = np.zeros((self.slots, self._tree_refeed), np.int32)
        src = np.asarray(refeed, np.int32)
        rf[:, :src.shape[1]] = src[:, :self._tree_refeed]
        rl = np.clip(np.asarray(refeed_len, np.int32), 1, self._tree_refeed)
        lens = np.asarray(lengths, np.int32)
        act = np.asarray(active, bool)
        temp = np.asarray(temperature, np.float32)
        tp = np.asarray(top_p, np.float32)
        sd = np.asarray(seeds, np.int32)
        rd = np.asarray(rounds, np.int32)
        with self._decode_span(act, lens, window=shape.size):
            with span("ftl:engine.decode.dispatch"):
                self.draft_cache, t_toks, t_probs = draft_prog(
                    self.draft_params, self.draft_cache,
                    np.asarray(draft_block_tables, np.int32), rf, rl, lens,
                    act, temp, tp, sd, rd)
                self.cache, out, acc, path = verify_prog(
                    self.params, self.cache,
                    np.asarray(block_tables, np.int32),
                    t_toks, t_probs, lens, act, temp, tp, sd, rd)
            with span("ftl:engine.decode.sync"):
                return (np.asarray(out), np.asarray(acc),
                        np.asarray(path))

    def fork_slot(self, src_slot: int, dst_slot: int, length: int,
                  src_row, allocator):
        """COW-fork slot ``src_slot``'s first ``length`` committed tokens
        into ``dst_slot`` — the beam-search primitive over the paged
        substrate. Full shared blocks are NOT copied: ``dst``'s table row
        aliases them and the allocator refcount rises (``incref``), the
        same sharing contract the prefix cache uses; only the partial
        boundary block (``length % block_size != 0``) is duplicated
        device-side (:meth:`cow_copy`) into a freshly allocated block, so
        both beams can keep writing inside it without seeing each other.
        Returns ``dst``'s block row (np.int32, padded with 0), or None if
        the pool cannot supply the boundary block (caller's admission
        problem — nothing was acquired). The caller owns both slots'
        host bookkeeping and later frees each row through the uniform
        allocator path (shared blocks drop a ref, the private boundary
        block frees outright — tests/test_spec_decode.py pins the
        contract, double-free raise included)."""
        if self.kv_layout != "paged" or self._latent:
            raise ValueError("fork_slot requires the paged KV layout of a "
                             "K/V model (a fork would have to copy the "
                             "source slot's window rings)")
        if not (0 <= src_slot < self.slots and 0 <= dst_slot < self.slots
                and src_slot != dst_slot):
            raise ValueError("fork_slot: bad slot pair "
                             f"({src_slot}, {dst_slot})")
        if not 0 < length <= self.max_len:
            raise ValueError(f"fork length {length} outside (0, "
                             f"{self.max_len}]")
        row = np.asarray(src_row, np.int32).reshape(-1)
        if row.shape[0] != self.max_blocks_per_slot:
            raise ValueError(f"src_row has {row.shape[0]} entries, "
                             f"expected {self.max_blocks_per_slot}")
        n_full, rem = divmod(length, self.block_size)
        dst_row = np.zeros_like(row)
        fresh = None
        if rem:
            fresh = allocator.alloc(1)
            if fresh is None:
                return None
        for i in range(n_full):
            allocator.incref([int(row[i])])
            dst_row[i] = row[i]
        if rem:
            dst_row[n_full] = fresh[0]
            self.cow_copy(int(row[n_full]), int(fresh[0]))
        lengths = np.asarray(self.cache.lengths).copy()
        lengths[dst_slot] = length
        self.cache = self.cache.replace(lengths=jnp.asarray(lengths))
        return dst_row

    def reset(self) -> None:
        """Zero all slot lengths (the buffers' stale contents are masked).
        Any prefix cache built over the old pool contents dies with them —
        a scheduler is per-stream, so resetting the engine and building a
        fresh ``Scheduler`` (fresh radix tree) is the supported pattern."""
        with use_mesh(self.mesh):
            cache = self._init_cache(
                dtype=None if self._latent else self.cache.k[0].dtype)
            cs = cache_shardings(cache, self.mesh)
            self.cache = (jax.device_put(cache, cs) if cs is not None
                          else cache)
            if self._latent:
                self._ring_from[:] = 0
            if self.spec_k:
                dcache = self._init_draft_cache(
                    dtype=self.draft_cache.k[0].dtype)
                dcs = cache_shardings(dcache, self.mesh)
                self.draft_cache = (jax.device_put(dcache, dcs)
                                    if dcs is not None else dcache)

    # --- construction from a training checkpoint ---------------------------

    @classmethod
    def from_checkpoint(cls, checkpoint_path: str, job_id: str,
                        cfg: TransformerConfig, *, step: Optional[int] = None,
                        mesh=None, **engine_kwargs) -> "InferenceEngine":
        """Restore a training checkpoint and build an engine on it.

        ``cfg`` must be the architecture the checkpoint was trained with
        (scan/loop form included — the abstract TrainState has to match the
        saved tree); the restore itself is the trainer's own cross-topology
        path, so a checkpoint written on any mesh loads onto this one
        (:func:`restore_params`). ``engine_kwargs`` passes through to the
        constructor — including ``draft_cfg``/``draft_params``/``spec_k``
        for speculative decoding (serve.py restores the draft checkpoint
        through the same :func:`restore_params` path first).
        """
        params, restored_step = restore_params(checkpoint_path, job_id, cfg,
                                               step=step, mesh=mesh)
        logger.info("Model loaded from checkpoint")  # ref: train.py:58
        engine = cls(cfg, params, mesh=mesh, **engine_kwargs)
        engine.restored_step = restored_step
        return engine


def restore_params(checkpoint_path: str, job_id: str, cfg: TransformerConfig,
                   *, step: Optional[int] = None, mesh=None):
    """Restore ONLY the params collection of a training checkpoint.

    The abstract TrainState is rebuilt exactly as the trainer builds it
    (the saved tree must match, optimizer state included — restored
    alongside and dropped), so a checkpoint written on any training
    topology loads onto the serving mesh. Factored out of
    :meth:`InferenceEngine.from_checkpoint` so the speculative-decoding
    path can load a DRAFT model's checkpoint — any preset, its own
    training run — through the identical cross-topology machinery.
    Returns ``(params, restored_step)``.
    """
    from ..checkpoint.manager import CheckpointManager
    from ..parallel.mesh import make_mesh
    from ..parallel.sharding import param_pspecs
    from ..training.state import TrainState
    from ..training.step import make_optimizer
    from jax.sharding import NamedSharding

    model = build_model(cfg)
    # only the opt_state TREE matters (restored then dropped); any
    # schedule yields the same optax.adamw structure
    optimizer = make_optimizer(1e-4, 1)
    dummy = jnp.zeros((1, cfg.seq_len), jnp.int32)

    def init_fn(key):
        params = model.init(key, dummy)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    # Orbax needs target shardings; without a serving mesh, restore onto
    # a trivial single-device mesh (replicated specs, device 0).
    restore_mesh = mesh or make_mesh(dp=1, devices=jax.devices()[:1])
    with use_mesh(restore_mesh):
        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        specs = param_pspecs(abstract)
        abstract = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(restore_mesh, s)),
            abstract, specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        mngr = CheckpointManager(checkpoint_path, job_id,
                                 enable_async=False)
        state, _data, restored_step = mngr.restore(abstract, step=step)
        mngr.close()
    return state.params, restored_step
