"""Slot-based continuous batching (Orca-style, static-shape XLA flavor).

One decode program serves all slots every iteration; requests are admitted
into free slots *between* decode iterations (no stop-the-world batch
boundary, the Orca/vLLM scheduling insight) and evicted the moment they hit
EOS or their token budget — a freed slot is re-filled on the very next
iteration. All shapes stay static: "admission" is a prefill into one slot of
the fixed (slots, ...) cache, "eviction" is host bookkeeping plus the mask
bit in the decode step.

Under the engine's paged KV layout (the default) the scheduler also owns
the :class:`BlockAllocator`: admission is gated by FREE BLOCK COUNT —
``ceil((prompt + max_new_tokens) / block_size)`` blocks per request — not
just by a free slot, so a long-context cache no longer reserves ``max_len``
per slot and far more requests fit the same HBM; eviction frees the blocks
for the next admission. A request whose blocks aren't available yet simply
waits at the head of the queue (FIFO, no starvation) — exhaustion queues,
it never crashes.

With the engine's prefix cache enabled (``enable_prefix_cache``, the paged
default) admission first walks the content-addressed radix tree
(inference/prefix_cache.py): blocks covering a cached prompt prefix are
attached to the slot's table at ZERO allocation cost (a refcount each) and
prefill resumes at the first divergent block through the existing chunked
path — a fully-shared prompt skips all but its last position. A full-prompt
hit still needs that last position's logits, so the final shared block is
COPY-ON-WRITE duplicated (engine.cow_copy) into a private block before
prefill resumes inside it; shared blocks are never written. Under pool
pressure, admission evicts LRU cached prefixes no live slot references
before making the head of the queue wait. The DRAFT pool (speculative
mode) runs a MIRROR of the same scheme: a second radix tree over the
draft allocator, fed the same insertions at the same block boundaries, so
a shared system prompt skips the draft prefill compute too — with tree
speculation refeeding the draft every round, draft prefill is no longer a
negligible fraction of admission cost. The mirror is strictly cheaper
than the target's cache in one way: a FULL-prompt draft hit needs no
copy-on-write resume at all (the draft phase samples nothing — covering
every prompt position means there is nothing left to compute), so the
draft phase is skipped outright. Admission still gates on the COMBINED
footprint, and a shortage on either side rolls back BOTH pools' acquired
references; decode/spec rounds only ever write at positions >=
prompt_len, which live in the slot's private blocks, so sharing never
constrains them. Cache-hit spec streams are bit-identical to cache-off
(shared draft blocks hold the bytes a zero-offset draft prefill would
have written — tests/test_spec_decode.py asserts it).

With ``prefill_batch > 1`` (engine built to match) admission switches to
the PACKED prefill lane: allocation keeps the exact sequential front-half
(prefix acquire-first, COW, rollback), but prompts then stream through
per-step packed ROUNDS — up to ``prefill_batch`` pending rows' next
chunks, grouped on the head row's best-fit bucket, in ONE (P, bucket)
dispatch — interleaved with the decode rounds instead of draining the
queue one prompt at a time. Prefill work between two decode rounds is
bounded by P * bucket tokens (Sarathi-style stall-free batching), and
per-row chunk shapes match the sequential loop exactly, so packed streams
stay bit-identical to sequential prefill on the gather impl.

When the engine was built with a draft model (``spec_k > 0``) the
scheduler runs SPECULATIVE rounds instead of single-token decode
iterations: each round emits 1..k+1 tokens per slot (engine.py
``spec_round``). The draft model has its own block pool, so the scheduler
owns a SECOND :class:`BlockAllocator` and block table; admission is gated
by the COMBINED draft+target footprint (both pools must cover the
request, or it waits at the head of the queue), and eviction/drain frees
both pools together. Acceptance statistics are exported per round
(``ftl_spec_*`` metrics) and per request (Completion spec fields).

With a TREE shape on top (``engine.spec_tree``) every speculative round
is a tree round (engine.py ``spec_tree_round``): the scheduler feeds the
round the tokens the PREVIOUS round banked for the slot (the refeed
window — a committed sibling is a token the draft chain never fed), picks
the round's shape from the adaptive controller's budget via
``TreeShape.shrink_to`` when one is installed, and attributes acceptance
per node row — ``spec_tree_nodes_total``, the ``spec_accepted_path_len``
histogram and the branch-utilization gauge (accepted tokens taken OFF the
primary chain) come from the returned path. Banking keeps the linear
rounds' truncation contract, so EOS/budget eviction and the drain
lifecycle are unchanged; a mid-stream drain frees branch scratch with the
slot's ordinary allocation (tree rows live inside it), leaving the leak
guard clean.

The scheduler is also the drain point for the fault-tolerant serving
lifecycle: ``stop_admission()`` (serve.py calls it when a SIGUSR1/SIGTERM
flag fires) freezes the queue while active slots run to completion, so
in-flight requests finish and queued ones are reported unserved — the
serving analogue of the trainer's save-on-signal exit policy. Chunked
prefills consult ``stop_check`` between chunks, so a signal that lands
mid-prompt finishes the current chunk only, frees the request's blocks and
reports it unserved — the drain stays exact even for long prompts.

Disaggregated roles (DistServe/Splitwise): ``role="prefill"`` keeps both
prefill lanes but exports every committed chunk's full blocks as an
incremental checksummed shipment (kv_cache.export_blocks) and finishes the
request with reason ``"prefill"`` — its decode belongs to a decode-role
peer. ``role="decode"`` admits such requests by importing the shipments
(CRC + journal agreement verified BEFORE any device write, prefix-cache
deduped) and resumes decode bit-exactly at the committed offset; any
verification failure degrades to the committed-prefix replay, which the
decode engine can always run because its prefill path is intact — that IS
the fallback ladder. ``role="both"`` (default) is the colocated engine.
"""

import dataclasses
import logging
import os
import shutil
import tempfile
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..obs import events, reqtrace
from ..obs.trace import span
from ..obs.registry import (
    SPEC_TOKEN_BUCKETS,
    MetricRegistry,
    default_registry,
)
from ..utils.logging import (
    AUDIT_ADAPTER_FMT,
    AUDIT_DISAGG_SHIP_FMT,
    AUDIT_HANDOFF_FMT,
    AUDIT_KV_LEAK_FMT,
    AUDIT_KV_STORE_FMT,
    AUDIT_KV_TIER_FMT,
    AUDIT_KV_XPORT_FMT,
)
from .kv_cache import (
    BLOCK_MANIFEST_NAME,
    KVBlockIntegrityError,
    LatentKVCache,
    artifact_bytes,
    block_bytes,
    export_blocks,
    verify_block_artifact,
)
from .prefix_cache import PrefixCache, chain_hashes
from .transport import FsTransport

logger = logging.getLogger()


class BlockAllocator:
    """Host-side REFCOUNTED free list over the paged cache's block pool.

    Block 0 is the reserved null/scratch block (inference/kv_cache.py):
    free block-table entries point at it and masked writes divert into it,
    so it is never handed out. Blocks are born at refcount 1 (``alloc``);
    prefix sharing takes extra references (``incref``: the cache's own hold
    on an inserted block, and each additional slot admitted onto a cached
    prefix — inference/prefix_cache.py documents the full ownership
    protocol). ``free()`` DECREMENTS; a block returns to the free list only
    when its last holder drops it. Releasing a block that has no live
    reference still raises — an allocator bug corrupting two requests'
    caches should fail loudly, not silently cross-wire their KV.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # LIFO: reuse warm
        self._ref: Dict[int, int] = {}  # block -> live reference count

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1  # block 0 reserved

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._ref)

    @property
    def shared_count(self) -> int:
        """Blocks with more than one live reference (prefix sharing)."""
        return sum(1 for c in self._ref.values() if c > 1)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks at refcount 1, or None if fewer than n are free
        (caller queues or evicts cached prefixes)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def incref(self, blocks: Sequence[int]) -> None:
        """One extra reference per block (must be live)."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"incref of unallocated block {b}")
            self._ref[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; the last drop frees the block."""
        for b in blocks:
            if b not in self._ref:
                raise ValueError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)


@dataclasses.dataclass
class Request:
    id: str
    prompt: Sequence[int]          # token ids, BOS included by the caller
    max_new_tokens: int = 32
    temperature: float = 0.0       # <= 0 -> greedy
    top_p: float = 1.0
    seed: int = 0
    # Migrated-replay prefix (fleet journal): tokens a previous owner
    # already committed for this request. When non-empty, admission
    # prefills prompt + committed[:-1] (re-deriving the KV the dead host
    # held — a prefix-cache hit makes this cheap), banks the committed
    # list as already-generated output, and resumes decode at step
    # len(committed) so the fold_in(seed, step) PRNG continues the SAME
    # stream the original host was producing. committed counts toward
    # max_new_tokens; an empty tuple is a normal fresh request.
    committed: Sequence[int] = ()
    # Span-trail key (obs/reqtrace.py), minted at intake and carried
    # through the journal so a migrated request's trace joins across
    # hosts. Empty string = tracing off for this request.
    trace_id: str = ""
    # Tenant LoRA adapter this request decodes under (adapters.py).
    # "" = the null adapter: base-model-only, bit-identical to an
    # engine without adapter serving. A registered-but-unresident name
    # queues the request behind a verified page-in at admission.
    adapter: str = ""


@dataclasses.dataclass
class Completion:
    request_id: str
    prompt_len: int
    tokens: List[int]              # generated ids (EOS included if hit)
    reason: str                    # "eos" | "length"
    submitted_at: float
    first_token_at: float
    finished_at: float
    # Speculative-decoding accounting (zero in non-spec mode): draft tokens
    # proposed for this request, proposals the verify pass accepted, and
    # tokens EMITTED-NOT-PROPOSED — the verify pass's bonus/corrected
    # tokens, i.e. output the draft never suggested (the drain audit logs
    # these per request so an operator can see how much of a stream the
    # draft actually produced).
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_emitted_not_proposed: int = 0
    trace_id: str = ""

    @property
    def ttft_seconds(self) -> float:
        """Time to first token (queue wait + prefill)."""
        return self.first_token_at - self.submitted_at

    @property
    def latency_seconds(self) -> float:
        return self.finished_at - self.submitted_at

    @property
    def tpot_seconds(self) -> float:
        """Time per output token AFTER the first (the first token is
        prefill's and is priced by TTFT — the DistServe/Splitwise
        split). 0.0 for single-token requests."""
        decoded = len(self.tokens) - 1
        dt = self.finished_at - self.first_token_at
        return dt / decoded if decoded > 0 and dt > 0 else 0.0

    @property
    def decode_tokens_per_sec(self) -> float:
        decoded = len(self.tokens) - 1  # first token came from prefill
        dt = self.finished_at - self.first_token_at
        return decoded / dt if decoded > 0 and dt > 0 else 0.0


@dataclasses.dataclass
class _PendingPrefill:
    """One admitted-but-not-yet-prefilled request in the PACKED prefill
    lane (``prefill_batch > 1``): its slot and blocks are already owned
    (allocation, prefix-cache references and the full-hit COW all happened
    at admission, exactly as in the sequential lane), but the prompt
    streams chunk-by-chunk through ``Scheduler._prefill_round`` — packed
    with other rows into one dispatch per round — instead of draining
    in one blocking ``engine.prefill`` call."""
    request: Request
    submitted_at: float
    slot: int
    row: np.ndarray         # full padded block-table row
    blocks: List[int]       # every block to free exactly once on abort
    start_pos: int          # prefix-resume offset (0 = no cache hit)
    pos: int                # next absolute position to prefill
    eff: Sequence[int]      # effective prefill prompt (replay appends the
                            # committed prefix; == request.prompt otherwise)


@dataclasses.dataclass(frozen=True)
class _HeldRings:
    """What a FREE slot's window rings still hold, recorded when the
    request in it finished normally: the stream whose whole blocks end at
    chain hash ``key``, written up to position ``length`` (exclusive), its
    rows in the rings beginning at ``win_from``. A next turn of the same
    stream, admitted into the same slot, resumes at the key's block
    boundary and rebuilds no window (``InferenceEngine.prefill``
    ``rings_held``)."""
    key: bytes
    length: int
    win_from: int


class _Slot:
    def __init__(self, request: Request, first_token: int,
                 submitted_at: float, now: float):
        self.request = request
        committed = list(getattr(request, "committed", ()) or ())
        if committed:
            # Migrated replay: the committed prefix is already-generated
            # output (banked here, not re-emitted), and the replay prefill's
            # sampled token was discarded by the caller — the next decode
            # feeds committed[-1] and folds (seed, len(committed)), the
            # exact step the previous owner would have run next.
            self.tokens = committed
            self.steps = len(committed)
        else:
            self.tokens = [first_token]
            self.steps = 1  # decode-step counter; prefill consumed step 0
        self.submitted_at = submitted_at
        self.first_token_at = now
        # tree-spec refeed window: the tokens banked by the LAST round
        # (prefill counts as round 0 with just the first token) — the
        # next tree round rewrites their draft KV before proposing
        self.emitted = [self.tokens[-1]]
        # spec-mode per-request accounting (see Completion)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_corrected = 0


@dataclasses.dataclass
class _SpilledRequest:
    """A preempted request parked in the host spill tier: its PRIVATE
    blocks live as a checksummed artifact on disk, its shared prefix-cache
    blocks were released (the cache's own reference keeps them warm), and
    everything needed to resume the stream bit-exactly — tokens, step
    index, refeed window, timestamps — is preserved host-side. fold_in
    (seed, step) is stateless in the step index, so the restored slot's
    next decode folds exactly the key the preempted slot would have."""

    request: Request
    submitted_at: float
    first_token_at: float
    tokens: List[int]
    steps: int
    emitted: List[int]
    shared_tokens: List[int]     # token ids covered by released shared blocks
    private_positions: List[int]  # block-table positions of exported blocks
    blocks_total: int            # full row size to re-allocate on restore
    artifact_dir: str
    bytes: int


class _StepSeconds(deque):
    """The newest decode-iteration wall times (for percentiles) and, in
    ``total``, the sum of ALL appended since the last ``clear()`` — a
    server appends one per step for its whole life, so the list is bounded
    and the sum is kept running instead of re-added every step."""

    KEEP = 65536

    def __init__(self):
        super().__init__(maxlen=self.KEEP)
        self.total = 0.0

    def append(self, seconds: float) -> None:
        self.total += seconds
        super().append(seconds)

    def clear(self) -> None:
        self.total = 0.0
        super().clear()


class Scheduler:
    """Continuous-batching loop over an :class:`~.engine.InferenceEngine`."""

    def __init__(self, engine, eos_token_id: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricRegistry] = None,
                 stop_check: Optional[Callable[[], bool]] = None,
                 adaptive_k=None, decode_burst: int = 1,
                 prefill_batch: int = 1, adaptive_burst: bool = False,
                 enable_spill: bool = False,
                 spill_dir: Optional[str] = None,
                 on_spill: Optional[Callable[[str, int], None]] = None,
                 role: str = "both",
                 ship_dir: Optional[str] = None,
                 on_ship: Optional[Callable] = None,
                 on_prefill_chunk: Optional[Callable[[int], None]] = None,
                 kv_store=None,
                 on_store_put: Optional[Callable[[str, int], None]] = None,
                 transport=None,
                 pacing: Optional[Callable[[], Optional[int]]] = None,
                 kv_store_max_bytes: int = 0):
        self.engine = engine
        if getattr(engine, "_latent", False):
            # the tiers that move K/V blocks have no format for latent and
            # index-key blocks or window rings (engine._need_kv_blocks);
            # refused here, by name, before a request is taken
            for bad, what in (
                    (enable_spill or spill_dir, "the spill tier "
                     "(enable_spill / spill_dir)"),
                    (role != "both", f"role={role!r} (block shipments "
                     f"between a prefill and a decode engine)"),
                    (kv_store is not None, "the fleet KV store (kv_store)"),
                    (int(decode_burst) > 1 or adaptive_burst,
                     "decode_burst > 1"),
                    (int(prefill_batch) > 1, "prefill_batch > 1")):
                if bad:
                    raise ValueError(
                        f"a LatentMoEConfig model does not support {what}")
        self.eos_token_id = eos_token_id
        self.clock = clock
        self.queue: deque = deque()        # (Request, submitted_at)
        self.active: Dict[int, _Slot] = {}  # slot index -> state
        self.completed: List[Completion] = []
        self.admission_open = True
        self.iterations = 0
        self.max_concurrent = 0
        # decode-iteration wall times: the newest ones, plus their total
        self.step_seconds = _StepSeconds()
        # Drain probe consulted BETWEEN prefill chunks (serve.py passes the
        # signal flag) so a mid-prompt SIGUSR1/SIGTERM aborts cleanly at a
        # chunk boundary; run(stop=...) installs its callable here too.
        self.stop_check = stop_check
        self.kv_layout = getattr(engine, "kv_layout", "ring")
        self.prefill_chunks = 0
        self.max_block_utilization = 0.0
        if self.kv_layout == "paged":
            self.allocator = BlockAllocator(engine.num_blocks)
            self.block_tables = np.zeros(
                (engine.slots, engine.max_blocks_per_slot), np.int32)
            self._slot_blocks: Dict[int, List[int]] = {}
        # Spill tier (module docstring): on pool exhaustion, preempt the
        # coldest active request into a host-side checksummed artifact
        # instead of making the head of the queue wait. A plain directory
        # is the tier in both configs — ``spill_dir`` names a persistent
        # location, ``enable_spill`` alone uses a process-private tmpdir
        # (the "host RAM" tier: same code path, kernel page cache holds
        # the bytes).
        self.enable_spill = bool(enable_spill or spill_dir)
        self._spill_dir_arg = spill_dir
        self._spill_root: Optional[str] = None
        self._spilled: Dict[str, _SpilledRequest] = {}
        self._spill_order: List[str] = []      # FIFO restore order
        self._on_spill = on_spill
        self.spill_exports = 0                 # artifact ordinal (chaos key)
        self.spill_restores = 0
        self.spill_rejects = 0
        # Handoff import-admission (fleet.py): request id -> verified
        # artifact dir; _admit imports the shipped blocks instead of
        # replay-prefilling, falling back to replay on any failure.
        self._handoff_artifacts: Dict[str, str] = {}
        self.handoff_imports = 0
        self.handoff_rejects = 0
        # Disaggregated prefill/decode (DistServe/Splitwise split over the
        # checksummed artifact path). role="prefill": admissions run the
        # ordinary prefill lanes but every committed chunk is EXPORTED as
        # an incremental block shipment (``on_ship`` fires per artifact —
        # fleet.py journals it) and the request finishes with reason
        # "prefill" instead of entering decode. role="decode": submit()
        # accepts the journaled shipment list and admission IMPORTS the
        # shipped blocks — prefix-cache-deduped — instead of replay-
        # prefilling; any verification failure degrades to the bit-exact
        # committed-prefix replay (the full prefill path stays available,
        # which IS the fallback ladder). role="both" is the colocated
        # engine, unchanged.
        self.role = str(role)
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r} "
                             f"(want both|prefill|decode)")
        if self.role != "both" and self.kv_layout != "paged":
            raise ValueError("prefill/decode roles require the paged KV "
                             "layout (shipments are block artifacts)")
        if self.role != "both" and int(getattr(engine, "spec_k", 0) or 0):
            raise ValueError("prefill/decode roles do not support "
                             "speculative decoding (the draft pool's "
                             "blocks are not shipped)")
        self._ship_dir_arg = ship_dir
        self._ship_root_path: Optional[str] = None
        self._on_ship = on_ship
        self._on_prefill_chunk = on_prefill_chunk
        # request id -> {"shipped": blocks exported, "seq": next artifact}
        self._ship_state: Dict[str, dict] = {}
        self._ship_req_gen: Dict[str, int] = {}  # assignment generation
        # request id -> (journaled shipment records, generation) — the
        # decode-side admission input (fleet.py feeds it from the journal's
        # "decode" record)
        self._shipments: Dict[str, tuple] = {}
        self.ship_exports = 0                  # artifact ordinal (chaos key)
        self.ship_imports = 0
        self.ship_rejects = 0
        # Fleet-global KV store (inference/kvstore.py BlockStore): after a
        # prefill commits, the prompt's full prefix blocks PUBLISH as a
        # content-addressed train; at admission, a store train deeper than
        # the local prefix-cache hit is FETCHED through the batched
        # verify-before-first-device-write import. Any CRC reject or miss
        # degrades to local chunked prefill — corruption costs recompute,
        # never correctness. ``on_store_put`` is the chaos hook
        # (store_corrupt), threaded into BlockStore.publish.
        self.kv_store = kv_store
        self._on_store_put = on_store_put
        self.store_publishes = 0
        self.store_fetches = 0
        self.store_fetch_blocks = 0
        self.store_rejects = 0
        # Pluggable KV transport (inference/transport.py): every block
        # train this scheduler exports (shipments, store publishes) or
        # imports (shipment admission, store fetches) moves through ONE
        # transport object. FsTransport (default) is the existing
        # filesystem artifact path verbatim; MemTransport adds the
        # same-pod device-push lane with metadata-only verification and
        # the mem -> fs -> replay fallback ladder.
        self.transport = transport if transport is not None else FsTransport()
        # Prefill-admission pacing (ROADMAP item 2's control plane): a
        # prefill-role engine consults ``pacing()`` — the decode fleet's
        # free-block count, derived from the heartbeat leases — before
        # admitting a new prompt, and defers admission (queue intact)
        # while the decode pool cannot land the blocks the prompt's
        # shipments will carry. None (or a pacing() of None — no decode
        # peers visible yet) never stalls: the ladder degrades to the
        # unpaced behavior rather than deadlocking a booting fleet.
        self.pacing = pacing
        self.prefill_paced = 0
        self._paced_logged: set = set()
        # Publish backpressure (the sweeper daemon's other half): skip
        # store publishes while the folded resident bytes exceed the
        # byte budget, so publishers stop racing the LRU sweep. 0 = no
        # budget (publish always).
        self.kv_store_max_bytes = int(kv_store_max_bytes or 0)
        self.store_publish_skipped = 0
        self.store_partial_hits = 0
        self.lane_fallbacks = 0
        self.mem_lane_imports = 0
        if self.kv_store is not None and self.kv_layout != "paged":
            raise ValueError("the fleet KV store requires the paged KV "
                             "layout (trains are block artifacts)")
        if self.enable_spill and self.kv_layout != "paged":
            raise ValueError("the spill tier requires the paged KV layout")
        if self.enable_spill and int(getattr(engine, "spec_k", 0) or 0):
            raise ValueError("the spill tier does not support speculative "
                             "decoding (the draft pool's blocks are "
                             "derivable scratch, not committed state)")
        # Speculative mode: the draft model's pool gets its own allocator
        # and block table; admission requires BOTH footprints (below).
        self.spec_k = int(getattr(engine, "spec_k", 0) or 0)
        # Multi-token fused decode (engine.decode_burst): each step() runs
        # ONE n-token burst program — 1 dispatch + 1 host sync for n
        # tokens. Admission, EOS eviction, and the serve loop's
        # stop/drain probes all happen BETWEEN bursts (a burst is inside
        # one step() call, and the drain contract only ever promised
        # iteration-boundary checks), so the signal-drain audit sequence
        # is unchanged — a drain just lands at the next burst boundary,
        # at most n-1 tokens later than per-token decode would.
        self.decode_burst = int(decode_burst)
        if self.decode_burst < 1:
            raise ValueError(f"decode_burst {decode_burst} must be >= 1")
        if self.decode_burst > 1:
            if self.kv_layout != "paged":
                raise ValueError("decode_burst > 1 requires the paged KV "
                                 "layout")
            if self.spec_k:
                raise ValueError(
                    "decode_burst > 1 and speculative decoding are "
                    "mutually exclusive: a spec round already amortizes "
                    "dispatches over k+1 tokens")
            if not hasattr(engine, "decode_burst"):
                raise ValueError("engine does not implement decode_burst")
        # Burst-aware adaptive n: under queue / pending-prefill pressure
        # each step() scales the burst width DOWN (halving per waiting
        # unit, floor 1) before the existing per-slot budget clamp, so a
        # long burst never starves admission while the queue piles up —
        # idle-queue steps still run the full configured width. The
        # engine's compile-on-first-use burst ladder absorbs the handful
        # of distinct widths this produces.
        self.adaptive_burst = bool(adaptive_burst)
        if self.adaptive_burst and self.decode_burst < 2:
            raise ValueError("adaptive_burst requires decode_burst > 1 "
                             "(there is no width to scale down)")
        # Packed multi-request prefill (engine.prefill_packed): admission
        # allocates slots/blocks as usual but ENQUEUES the prompt instead
        # of streaming it to completion; each step() then dispatches ONE
        # packed round — up to prefill_batch pending rows' next chunks in
        # one (P, bucket) program — before the decode round, so prefill
        # work between decode rounds is bounded by P * bucket tokens
        # (Sarathi-style stall-free mixed batching) instead of a whole
        # prompt per admission.
        self.prefill_batch = int(prefill_batch)
        self._pending_prefill: deque = deque()  # _PendingPrefill rows
        if self.prefill_batch < 1:
            raise ValueError(f"prefill_batch {prefill_batch} must be >= 1")
        if self.prefill_batch > 1:
            if self.kv_layout != "paged":
                raise ValueError("prefill_batch > 1 requires the paged KV "
                                 "layout")
            if self.spec_k:
                raise ValueError(
                    "prefill_batch > 1 and speculative decoding are "
                    "mutually exclusive (the draft prefill lifecycle is "
                    "sequential; engine.py enforces the same)")
            if not hasattr(engine, "prefill_packed"):
                raise ValueError("engine does not implement prefill_packed")
            if getattr(engine, "prefill_batch", 1) != self.prefill_batch:
                raise ValueError(
                    f"scheduler prefill_batch {self.prefill_batch} != "
                    f"engine prefill_batch "
                    f"{getattr(engine, 'prefill_batch', 1)}: the packed "
                    f"programs were compiled at the engine's width")
        self.prefill_packed_rounds = 0
        self.prefill_packed_rows = 0
        self.prefill_inplace_chunks = 0
        self.prefill_gather_chunks = 0
        # Multi-tenant LoRA adapter serving (adapters.py): engines built
        # with adapter_rank > 0 carry an AdapterManager; the scheduler
        # keeps one adapter page row + scale per slot (the decode
        # dispatch's gather operands) and accounts the COMBINED
        # KV+adapter footprint at admission — a request naming an
        # unresident adapter waits at the head of the queue until a
        # verified page-in lands it (never crashes the loop).
        self.adapters = getattr(engine, "adapters", None)
        if self.adapters is not None:
            per = self.adapters.layout.pages_per_adapter
            self._adapter_rows = np.zeros((engine.slots, per), np.int32)
            self._adapter_scales = np.zeros((engine.slots,), np.float32)
            self._slot_adapter: Dict[int, str] = {}
            self.adapter_waits = 0
            self.adapter_rejects = 0
            self._adapter_pageins_seen = 0
            self._adapter_evictions_seen = 0
        # Dispatch/sync accounting (the fused-decode win in receipts):
        # how many device programs were launched and how many host syncs
        # were paid for the decode tokens generated.
        self.decode_dispatches = 0
        self.decode_host_syncs = 0
        self.decode_tokens = 0
        # Optional sampler.AdaptiveK controller: when present, every spec
        # round runs at its chosen width (min per-request target) instead
        # of the engine's fixed spec_k — serve.py --adaptive-spec-k.
        self.adaptive_k = adaptive_k if self.spec_k else None
        if self.spec_k:
            self.draft_allocator = BlockAllocator(engine.draft_num_blocks)
            self.draft_block_tables = np.zeros(
                (engine.slots, engine.max_blocks_per_slot), np.int32)
            self._slot_draft_blocks: Dict[int, List[int]] = {}
            self.spec_rounds = 0
            self.spec_draft_tokens = 0
            self.spec_accepted_tokens = 0
        # Tree speculation (engine.spec_tree): every spec round becomes a
        # tree round; acceptance is attributed per node row (module
        # docstring) so branch utilization is observable.
        self.spec_tree = (getattr(engine, "spec_tree", None)
                          if self.spec_k else None)
        if self.spec_tree is not None:
            self.spec_tree_rounds = 0
            self.spec_tree_nodes = 0
            self.spec_tree_accepted = 0
            self.spec_tree_off_primary = 0
        # /metrics surface (obs/registry.py): serve.py --metrics-port scrapes
        # these live while the batching loop runs.
        r = registry or default_registry()
        self._m_ttft = r.histogram(
            "ftl_serve_ttft_seconds",
            "Time to first token (queue wait + prefill) per request")
        self._m_tpot = r.histogram(
            "ftl_serve_tpot_seconds",
            "Time per output token after the first (decode-loop latency "
            "per token, per request)",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        self._m_decode = r.histogram(
            "ftl_serve_decode_step_seconds",
            "Wall time of one batched decode iteration")
        self._m_tokens = r.counter("ftl_serve_tokens_generated_total",
                                   "Tokens generated across all requests")
        self._m_done = r.counter(
            "ftl_serve_requests_completed_total",
            "Requests completed, by finish reason (eos|length)")
        self._m_occupancy = r.gauge(
            "ftl_serve_slot_occupancy",
            "Active decode slots / total slots (0-1)")
        self._m_queue = r.gauge("ftl_serve_queue_depth",
                                "Requests waiting for a free slot")
        self._m_tps = r.gauge("ftl_serve_tokens_per_sec",
                              "Aggregate decode throughput (running)")
        self._m_blocks_free = r.gauge(
            "ftl_serve_kv_blocks_free",
            "Free KV cache blocks in the paged pool (block 0 excluded)")
        self._m_blocks_total = r.gauge(
            "ftl_serve_kv_blocks_total",
            "Usable KV cache blocks in the paged pool (capacity; the "
            "federation aggregator rolls free/total up per engine role)")
        self._m_block_util = r.gauge(
            "ftl_serve_kv_block_utilization",
            "Allocated / usable KV cache blocks (0-1)")
        self._m_chunks = r.counter(
            "ftl_serve_prefill_chunks_total",
            "Prefill chunks executed (chunked long-prompt prefill)")
        self._m_prefill_batch = r.histogram(
            "prefill_batch_size",
            "Requests packed per packed-prefill dispatch (packed lane "
            "only; capacity is --prefill-batch)",
            buckets=SPEC_TOKEN_BUCKETS)
        self._m_prefill_inplace = r.counter(
            "prefill_inplace_total",
            "Prefill chunks whose paged reads resolved to the in-place "
            "Pallas kernel (--paged-kernel pallas, S>1 chunk grid)")
        self._m_prefill_gather = r.counter(
            "prefill_gather_total",
            "Prefill chunks whose paged reads resolved to the gather-"
            "then-ring reference kernel (--paged-kernel gather, and "
            "auto's S>1)")
        self._m_spec_draft = r.counter(
            "ftl_spec_draft_tokens_total",
            "Draft-model tokens proposed (speculative decoding)")
        self._m_spec_accepted = r.counter(
            "ftl_spec_accepted_tokens_total",
            "Draft proposals accepted by the target verify pass")
        self._m_spec_rate = r.gauge(
            "ftl_spec_acceptance_rate",
            "Running accepted/proposed draft-token ratio (0-1)")
        self._m_spec_round_k = r.gauge(
            "ftl_spec_round_k",
            "Draft proposals per speculative round (adaptive-k controller "
            "output; fixed spec_k without one)")
        self._m_spec_round_tokens = r.histogram(
            "ftl_spec_tokens_per_round",
            "Tokens banked per verify round (accepted prefix + bonus, "
            "after EOS/budget truncation)",
            buckets=SPEC_TOKEN_BUCKETS)
        self._m_tree_nodes = r.counter(
            "spec_tree_nodes_total",
            "Tree nodes scored by tree-verify dispatches (root included; "
            "active slots x shape size per round)")
        self._m_tree_path_len = r.histogram(
            "spec_accepted_path_len",
            "Accepted path length per slot per tree-verify round "
            "(0..depth, before EOS/budget truncation)",
            buckets=SPEC_TOKEN_BUCKETS)
        self._m_tree_branch_util = r.gauge(
            "spec_tree_branch_utilization",
            "Accepted tokens taken OFF the primary draft chain / accepted "
            "tokens (0-1, running; 0 under the exact verify mode)")
        self._m_dispatches = r.counter(
            "decode_dispatches_total",
            "Device programs launched for decode (burst counts 1 per "
            "burst; a spec round counts its draft + verify pair)")
        self._m_host_syncs = r.counter(
            "decode_host_syncs_total",
            "Host round-trips paid for decode results (one per "
            "device->host token/logit transfer)")
        self._m_burst_tokens = r.histogram(
            "decode_burst_tokens",
            "Tokens banked per active slot per decode dispatch (after "
            "EOS/budget truncation; 1 for per-token decode)",
            buckets=SPEC_TOKEN_BUCKETS)
        self._m_prefix_hit_rate = r.gauge(
            "kv_prefix_hit_rate",
            "Prompt tokens served from the prefix cache / prompt tokens "
            "admitted (0-1, running)")
        self._m_blocks_shared = r.gauge(
            "kv_blocks_shared",
            "KV pool blocks with more than one live reference "
            "(prefix sharing)")
        self._m_prefix_evictions = r.counter(
            "prefix_evictions_total",
            "Cached prefix blocks evicted under pool pressure (LRU, "
            "refcount-0 only)")
        self._m_blocks_spilled = r.gauge(
            "kv_blocks_spilled",
            "KV blocks currently parked in the host spill tier "
            "(checksummed artifacts; restored on demand)")
        self._m_spill_bytes = r.gauge(
            "kv_spill_bytes",
            "Payload bytes currently held by the host spill tier")
        self._m_spill_restores = r.counter(
            "kv_spill_restore_total",
            "Spilled requests restored to device blocks (CRC-verified "
            "import + prefix-cache re-acquire)")
        self._m_handoff_shipped = r.counter(
            "handoff_blocks_shipped_total",
            "KV blocks moved through checksummed handoff artifacts "
            "(exported at drain or imported on a survivor)")
        self._m_handoff_rejected = r.counter(
            "handoff_crc_rejected_total",
            "Handoff artifacts rejected by CRC/size/geometry verification "
            "(the request falls back to committed-prefix replay)")
        self._m_ship_exports = r.counter(
            "disagg_shipments_exported_total",
            "Incremental KV block shipments exported by a prefill-role "
            "engine (one checksummed artifact per committed chunk group)")
        self._m_ship_imports = r.counter(
            "disagg_shipments_imported_total",
            "Block shipments CRC-verified and imported by a decode-role "
            "engine (prefix-cache-deduped shipments count as imported)")
        self._m_ship_rejected = r.counter(
            "disagg_shipments_rejected_total",
            "Shipment admissions rejected by CRC/metadata/coverage "
            "verification (the request falls back to committed-prefix "
            "replay on the decode engine)")
        self._m_store_hits = r.counter(
            "kv_store_hits_total",
            "Admissions that landed a fleet-store prefix train instead of "
            "prefilling it (verified cross-host fetches)")
        self._m_store_fetch_blocks = r.counter(
            "kv_store_fetch_blocks_total",
            "KV blocks imported from fleet-store trains (CRC-verified "
            "before the first device write)")
        self._m_store_rejected = r.counter(
            "kv_store_crc_rejected_total",
            "Fleet-store fetches rejected by CRC/metadata verification "
            "(the request falls back to local chunked prefill)")
        self._m_store_bytes = r.gauge(
            "kv_store_bytes",
            "Resident payload bytes in the fleet-global KV store "
            "(journal-folded, as of this host's last publish/fetch)")
        self._m_store_hit_depth = r.histogram(
            "kv_store_hit_depth",
            "Blocks imported per fleet-store hit (train depth at the "
            "admitting host)",
            buckets=SPEC_TOKEN_BUCKETS)
        self._m_store_publishes = r.counter(
            "kv_store_publish_total",
            "Committed prefix trains published to the fleet store "
            "(deduped re-publishes of an identical chain hash excluded)")
        self._m_xport_bytes = r.counter(
            "kv_transport_bytes_total",
            "KV block-train payload bytes moved through the pluggable "
            "transport, by lane: fs counts artifact writes and "
            "CRC-verified imports, mem counts device-to-device pushes "
            "and metadata-verified landings")
        self._m_store_partial = r.counter(
            "kv_store_partial_hits_total",
            "Fleet-store fetches that landed a PREFIX of a longer "
            "published train (sub-train addressability): only the "
            "covered blocks import, the rest chunk-prefills locally")
        self._m_store_skipped = r.counter(
            "kv_store_publish_skipped_total",
            "Store publishes skipped under byte-budget backpressure "
            "(folded resident bytes over --kv-store-max-bytes; the "
            "sweeper daemon owns getting back under)")
        self._m_paced = r.counter(
            "prefill_paced_total",
            "Prefill admissions deferred because the decode fleet's "
            "free-block gauges (heartbeat leases) could not land the "
            "prompt's shipments (ROADMAP item 2 pacing loop)")
        self._m_lane_fallbacks = r.counter(
            "kv_transport_lane_fallbacks_total",
            "Block-train imports that degraded from the mem lane to the "
            "fs artifact (fabric miss or metadata digest mismatch)")
        self._m_adapter_slots = r.gauge(
            "adapter_slots_active",
            "Decode slots currently pinned to each LoRA adapter "
            "(labelled by adapter; the null adapter is unlabelled base "
            "traffic and is not counted)")
        self._m_adapter_resident_bytes = r.gauge(
            "adapter_pages_resident_bytes",
            "LoRA factor bytes resident in the paged adapter pool "
            "(stale hot-swapped versions included until their last "
            "in-flight slot drains)")
        self._m_adapter_pageins = r.counter(
            "adapter_pagein_total",
            "Adapter artifacts CRC-verified and paged into the adapter "
            "pool (hot-swap loads included)")
        self._m_adapter_evictions = r.counter(
            "adapter_evictions_total",
            "Cold adapters evicted from the adapter pool under page "
            "pressure (refcount-0 residents only, LRU order)")
        # Content-addressed prefix reuse: only engines that OPT IN get the
        # cache (InferenceEngine sets enable_prefix_cache in paged mode;
        # test doubles without the attribute keep plain allocation).
        self.prefix_cache: Optional[PrefixCache] = None
        self.prefix_cow_copies = 0
        self.prefill_seconds = 0.0
        self._leak_audited = False
        if (self.kv_layout == "paged"
                and getattr(engine, "enable_prefix_cache", False)):
            self.prefix_cache = PrefixCache(
                self.allocator, engine.block_size,
                evictions_counter=self._m_prefix_evictions)
        # A model whose sliding layers keep a ring of rows a slot
        # (LatentKVCache): a free slot's record of what its rings hold.
        # Set at a normal finish, dropped by whatever else touches the slot
        # (any admission into it, a drain roll-back, spill, restore, a
        # weight swap).
        self._keeps_rings = isinstance(getattr(engine, "cache", None),
                                       LatentKVCache)
        self.held_rings: Dict[int, _HeldRings] = {}
        resumes = r.counter(
            "ftl_serve_window_resumes_total",
            "Prefix-hit admissions of a model that keeps window rings in "
            "its slots, by how the windows were had: held = the slot's own "
            "rings, left by the stream's last request; rebuilt = recomputed "
            "before the hit")
        self._m_resumes = {how: resumes.labels(how=how)
                           for how in ("held", "rebuilt")}
        # DRAFT-pool mirror (module docstring): same radix scheme over the
        # draft allocator, fed the same insertions, so shared prompts skip
        # draft prefill too. Full-prompt draft hits skip the phase outright
        # (no COW — the draft samples nothing at prefill).
        self.draft_prefix_cache: Optional[PrefixCache] = None
        if self.spec_k and self.prefix_cache is not None:
            self.draft_prefix_cache = PrefixCache(
                self.draft_allocator, engine.block_size,
                evictions_counter=self._m_prefix_evictions)
        if self.kv_layout == "paged":
            self._m_blocks_free.set(self.allocator.free_count)
            self._m_blocks_total.set(self.allocator.capacity)

    # --- queue management --------------------------------------------------

    def _blocks_needed(self, request: Request) -> int:
        # replay-invariant: committed tokens live inside the same
        # prompt + max_new_tokens budget the original admission sized
        bs = self.engine.block_size
        return -(-(len(request.prompt) + request.max_new_tokens) // bs)

    @staticmethod
    def _effective_prompt(request: Request) -> Sequence[int]:
        """What prefill actually processes: a migrated replay re-derives
        the dead host's KV by prefilling the prompt PLUS all but the last
        committed token (the last one is the next decode's input, exactly
        where the original stream stood)."""
        committed = list(getattr(request, "committed", ()) or ())
        if committed:
            return list(request.prompt) + committed[:-1]
        return request.prompt

    def _check_replay(self, request: Request, first) -> None:
        """Replay-integrity check. The replay prefill re-samples a token
        from the last committed position's logits; that sample is
        discarded (the committed list is the truth), but where sampling
        is PRNG-free (greedy) or the fold index coincides (a 1-token
        replay re-folds (seed, 0) exactly as the original prefill did) it
        must BIT-MATCH the journaled token — a mismatch means the journal
        and the model disagree and the migration must not proceed."""
        committed = list(request.committed)
        if first is None or not committed:
            return
        if request.temperature <= 0 or len(committed) == 1:
            if int(first) != int(committed[-1]):
                raise RuntimeError(
                    f"request {request.id}: replay re-derived token "
                    f"{int(first)} but the journal committed "
                    f"{int(committed[-1])} — journal/model divergence")

    def submit(self, request: Request,
               handoff_artifact: Optional[str] = None,
               handoff_gen: int = 0,
               shipments: Optional[Sequence[dict]] = None,
               ship_gen: int = 0) -> None:
        committed = list(getattr(request, "committed", ()) or ())
        if shipments and self.role == "prefill":
            raise ValueError(
                f"request {request.id}: a prefill-role engine cannot "
                f"accept block shipments (it only exports them)")
        if self.role == "prefill":
            # generation the shipments will be journaled under (audit)
            self._ship_req_gen[request.id] = int(ship_gen)
        if handoff_artifact and committed:
            # Block-shipment admission: _admit imports the artifact's
            # committed blocks instead of replay-prefilling; any
            # verification failure falls back to the replay path below.
            self._handoff_artifacts[request.id] = (handoff_artifact,
                                                   int(handoff_gen))
        if shipments and committed:
            # Disaggregated admission: _admit imports the prefill engine's
            # incremental shipments instead of replay-prefilling; any
            # verification failure falls back to the replay path below.
            self._shipments[request.id] = (
                [dict(s) for s in shipments], int(ship_gen))
        if committed and len(committed) >= request.max_new_tokens:
            raise ValueError(
                f"request {request.id}: {len(committed)} committed tokens "
                f"already meet max_new_tokens {request.max_new_tokens} — "
                f"nothing to decode; the caller should record it done")
        aname = str(getattr(request, "adapter", "") or "")
        if aname:
            # adapter serving is opt-in at engine build; an unregistered
            # name is a caller error HERE (not a crash in the decode
            # loop) — registered-but-unresident queues behind a verified
            # page-in at admission
            if self.adapters is None:
                raise ValueError(
                    f"request {request.id} names adapter {aname!r} but "
                    f"the engine was built without adapter serving "
                    f"(adapter_rank=0)")
            if not self.adapters.known(aname):
                raise ValueError(
                    f"request {request.id} names unregistered adapter "
                    f"{aname!r}")
        if len(request.prompt) + request.max_new_tokens > self.engine.max_len:
            raise ValueError(
                f"request {request.id}: prompt {len(request.prompt)} + "
                f"max_new_tokens {request.max_new_tokens} exceeds the "
                f"cache max_len {self.engine.max_len}")
        if (self.kv_layout == "paged"
                and self._blocks_needed(request) > self.allocator.capacity):
            raise ValueError(
                f"request {request.id}: needs {self._blocks_needed(request)} "
                f"KV blocks but the pool only has "
                f"{self.allocator.capacity} usable blocks")
        if (self.spec_k and self._blocks_needed(request)
                > self.draft_allocator.capacity):
            raise ValueError(
                f"request {request.id}: needs {self._blocks_needed(request)} "
                f"DRAFT KV blocks but the draft pool only has "
                f"{self.draft_allocator.capacity} usable blocks")
        self.queue.append((request, self.clock()))

    def stop_admission(self) -> None:
        """Drain mode: active slots finish, the queue stays unserved."""
        self.admission_open = False

    def resume_admission(self) -> None:
        """Reopen admission after a hot weight swap's pause
        (deploy/reload.py). NOT part of the signal-drain lifecycle — a
        drain's stop is final for the process; the reloader only restores
        the admission state it found open."""
        self.admission_open = True

    def pending(self) -> bool:
        return bool(self.active or self._pending_prefill
                    or ((self.queue or self._spilled)
                        and self.admission_open))

    def unserved(self) -> List[Request]:
        """Queued requests a drain leaves behind. Spilled requests count:
        each is reported as a replay request carrying its generated tokens
        as the committed prefix, so a journal requeue resumes the stream
        bit-exactly on whoever picks it up (the artifact itself dies with
        this process's tier)."""
        out = [r for r, _ in self.queue]
        for rid in self._spill_order:
            sp = self._spilled[rid]
            out.append(dataclasses.replace(sp.request,
                                           committed=tuple(sp.tokens)))
        return out

    # --- one decode iteration ----------------------------------------------

    def _acquire_adapter(self, req: Request, slot: int) -> None:
        """Pin ``req``'s adapter version to ``slot`` (+1 allocator ref
        per page) and bank its gather operands. The null adapter pins
        nothing — rows divert to null page 0 with scale 0, the base-only
        gate. Callers guarantee residency (the admission gate's verified
        page-in ran first)."""
        if self.adapters is None:
            return
        aname = str(getattr(req, "adapter", "") or "")
        arow, ascale = self.adapters.acquire(aname, slot)
        self._adapter_rows[slot] = arow
        self._adapter_scales[slot] = ascale
        if aname:
            self._slot_adapter[slot] = aname

    def _release_adapter(self, slot: int) -> None:
        """Drop a slot's adapter pin (slot freed, drain rollback, or
        finish) and zero its gather operands — the next occupant starts
        from the null divert."""
        if self.adapters is None:
            return
        self.adapters.release(slot)
        self._adapter_rows[slot] = 0
        self._adapter_scales[slot] = 0.0
        self._slot_adapter.pop(slot, None)

    def _finish(self, slot: int, reason: str, done: List[Completion]) -> None:
        st = self.active.pop(slot)
        self._ship_state.pop(st.request.id, None)
        self._release_adapter(slot)
        if self.adaptive_k is not None:
            self.adaptive_k.forget(st.request.id)
        if self.kv_layout == "paged":
            blocks = self._slot_blocks.pop(slot, None)
            if blocks:
                if reason in ("length", "eos"):
                    self._keep_written(slot, st, blocks)
                self.allocator.free(blocks)
                self.block_tables[slot] = 0
        if self.spec_k:
            dblocks = self._slot_draft_blocks.pop(slot, None)
            if dblocks:
                self.draft_allocator.free(dblocks)
                self.draft_block_tables[slot] = 0
        c = Completion(request_id=st.request.id,
                       prompt_len=len(st.request.prompt),
                       tokens=list(st.tokens), reason=reason,
                       submitted_at=st.submitted_at,
                       first_token_at=st.first_token_at,
                       finished_at=self.clock(),
                       spec_proposed=st.spec_proposed,
                       spec_accepted=st.spec_accepted,
                       spec_emitted_not_proposed=st.spec_corrected,
                       trace_id=str(getattr(st.request, "trace_id", "")
                                    or ""))
        self.completed.append(c)
        done.append(c)
        self._m_ttft.observe(c.ttft_seconds)
        if len(c.tokens) > 1:
            self._m_tpot.observe(c.tpot_seconds)
        self._m_done.labels(reason=reason).inc()
        self._trace(st.request, "done", reason=reason,
                    tokens=len(c.tokens), ttft=c.ttft_seconds,
                    tpot=c.tpot_seconds)

    def _keep_written(self, slot: int, st: _Slot,
                      blocks: Sequence[int]) -> None:
        """A request ended normally: the whole blocks of what its slot
        WROTE — the prompt and every generated token but the last, which
        was sampled and never fed — go into the prefix cache before the
        slot's one ``allocator.free`` (the cache takes its own reference a
        new node; blocks cached at admission are skipped), so the stream's
        next turn hits up to its last whole block and not only up to this
        request's prompt. A model that keeps window rings also records
        what the slot's rings hold now."""
        if self.prefix_cache is None:
            return
        written = np.concatenate([
            np.asarray(st.request.prompt, np.int32).reshape(-1),
            np.asarray(st.tokens[:-1], np.int32)])
        keys = chain_hashes(written, self.engine.block_size)
        self.prefix_cache.insert(written, blocks, keys=keys)
        if self._keeps_rings and keys:
            self.held_rings[slot] = _HeldRings(
                keys[-1], int(written.size), self.engine.window_from(slot))

    def _take_slot(self, free: List[int], hit) -> tuple:
        """The slot an admission goes into, taken off ``free``, and — for
        a model that keeps window rings — what ``engine.prefill`` may
        resume over: ``(slot, rings_held or None)``. A prefix hit that
        ends exactly at the last whole block a FREE slot's finished request
        wrote goes back into that slot, whose rings still hold the windows
        (counted ``held``); every other hit rebuilds them (``rebuilt``),
        in the first free slot whose rings no stream is waiting for, if
        there is one. Whatever record the taken slot had is dropped: its
        rings are about to be written."""
        slot = next((s for s in free if s not in self.held_rings), free[0])
        held = None
        if self._keeps_rings and hit is not None:
            if not hit.full:
                for s in free:
                    rec = self.held_rings.get(s)
                    if (rec is not None and rec.key == hit.keys[-1]
                            and self.engine.rings_cover(rec.length,
                                                        hit.tokens)):
                        slot, held = s, (rec.length, rec.win_from)
                        break
            self._m_resumes["held" if held else "rebuilt"].inc()
        free.remove(slot)
        self.held_rings.pop(slot, None)
        return slot, held

    def _trace(self, request: Request, span: str,
               dur: Optional[float] = None, **payload) -> None:
        """Emit one reqtrace span for a traced request (no-op when the
        request carries no trace_id — direct Scheduler users like the
        bench driver opt out by default)."""
        tid = str(getattr(request, "trace_id", "") or "")
        if tid:
            reqtrace.emit(tid, request.id, span, dur=dur, **payload)

    def _count_chunk(self) -> None:
        self.prefill_chunks += 1
        self._m_chunks.inc()
        # which kernel the chunk's paged reads resolved to — the
        # serving-visible proof there is no silent gather under pallas
        # (under "auto" the engine says what its S>1 programs took)
        if getattr(self.engine, "prefill_read_kernel",
                   "gather") == "inplace":
            self.prefill_inplace_chunks += 1
            self._m_prefill_inplace.inc()
        else:
            self.prefill_gather_chunks += 1
            self._m_prefill_gather.inc()
        if self._on_prefill_chunk is not None:
            # chaos hook (prefill_kill): fires BEFORE the chunk's shipment
            # exports, so a kill at ordinal N lands with chunk N computed
            # but unshipped — the mid-chunk death the disagg scenario needs
            self._on_prefill_chunk(self.prefill_chunks - 1)

    def _drain_requested(self) -> bool:
        return self.stop_check is not None and bool(self.stop_check())

    def _admit(self, done: List[Completion]) -> None:
        if self._spilled:
            # Parked requests come home FIRST: a restore needs only a free
            # slot plus its private blocks (shared prefix re-acquired from
            # the cache), and runs before any new admission can take them.
            self._try_restores(done)
        taken = set(self.active)
        taken.update(p.slot for p in self._pending_prefill)
        free = [s for s in range(self.engine.slots) if s not in taken]
        while free and self.queue:
            if self._spilled:
                # A spilled request is still waiting for blocks: freed
                # capacity flows to its restore before any NEW admission
                # (strict anti-starvation — a preempted stream can never
                # be overtaken indefinitely by fresh arrivals).
                break
            req, submitted_at = self.queue[0]
            if self.role == "prefill" and self.pacing is not None:
                # Shipment pacing (ROADMAP item 2's control plane): every
                # block this prompt prefills becomes a shipment the decode
                # fleet must land, so admit only when the decode pool's
                # free-block gauges (heartbeat leases, via pacing()) cover
                # the need. Deferral keeps the queue intact — FIFO order
                # and the submit contract are untouched, the head simply
                # waits like it does for local pool shortage. pacing()
                # returning None (no decode peers visible) never stalls.
                decode_free = self.pacing()
                if (decode_free is not None
                        and decode_free < self._blocks_needed(req)):
                    self.prefill_paced += 1
                    self._m_paced.inc()
                    if req.id not in self._paced_logged:
                        # one audit line per request, not per retry round
                        self._paced_logged.add(req.id)
                        self._audit_xport(
                            "pace", self.transport.name, req.id,
                            self._blocks_needed(req),
                            f"decode fleet has {decode_free} free "
                            f"block(s), admission deferred")
                    break
            aname = str(getattr(req, "adapter", "") or "")
            if aname and self.adapters is not None \
                    and not self.adapters.resident(aname):
                # Combined KV+adapter admission: the adapter half of the
                # footprint must land (CRC-verified page-in, cold-adapter
                # eviction under pressure) BEFORE any KV blocks are
                # grabbed. A full pool leaves the head queued (FIFO, the
                # same wait as KV shortage); a corrupt artifact rejects
                # THIS request with the pool untouched — never a crash.
                from .adapters import AdapterIntegrityError
                try:
                    paged_in = self.adapters.page_in(aname)
                except (AdapterIntegrityError, KeyError) as e:
                    self.queue.popleft()
                    self.adapter_rejects += 1
                    events.emit_audit(logger, AUDIT_ADAPTER_FMT.format(
                        action="reject", name=aname,
                        pages=self.adapters.layout.pages_per_adapter,
                        detail=f"request {req.id}: {e}"), "adapter")
                    now = self.clock()
                    c = Completion(
                        request_id=req.id, prompt_len=len(req.prompt),
                        tokens=[], reason="adapter_rejected",
                        submitted_at=submitted_at, first_token_at=now,
                        finished_at=now,
                        trace_id=str(getattr(req, "trace_id", "") or ""))
                    self.completed.append(c)
                    done.append(c)
                    self._m_done.labels(reason="adapter_rejected").inc()
                    self._trace(req, "done", reason="adapter_rejected")
                    continue
                if not paged_in:
                    self.adapter_waits += 1
                    break
                events.emit_audit(logger, AUDIT_ADAPTER_FMT.format(
                    action="page-in", name=aname,
                    pages=self.adapters.layout.pages_per_adapter,
                    detail=f"request {req.id} admitted behind verified "
                           f"load"), "adapter")
            art_entry = self._handoff_artifacts.get(req.id)
            if (art_entry is not None and self.kv_layout == "paged"
                    and not self.spec_k):
                # Block-shipment admission: import the handed-off blocks
                # instead of replay-prefilling the committed prefix.
                outcome = self._admit_from_handoff(req, submitted_at, free,
                                                   art_entry, done)
                if outcome == "wait":
                    break
                if outcome == "imported":
                    continue
                # "fallback": artifact rejected — the replay path below
                # re-derives the stream bit-exactly from prompt+committed
            ship_entry = self._shipments.get(req.id)
            if (ship_entry is not None and self.kv_layout == "paged"
                    and not self.spec_k):
                # Disaggregated admission: import the prefill engine's
                # incremental shipments (prefix-cache-deduped) instead of
                # replay-prefilling the committed prefix.
                outcome = self._admit_from_shipments(req, submitted_at,
                                                     free, ship_entry, done)
                if outcome == "wait":
                    break
                if outcome == "imported":
                    continue
                # "fallback": shipment rejected — the replay path below
                # re-derives the stream bit-exactly from prompt+committed
            if (self.kv_store is not None and self.prefix_cache is not None
                    and not self.spec_k):
                # Fleet-store fetch: land the deepest published train
                # matching this prompt in the LOCAL prefix cache first, so
                # every admission lane below (sequential, packed, full-hit
                # COW, drain rollback) sees it as an ordinary deep prefix
                # hit. A miss or CRC reject changes nothing — the local
                # chunked prefill below IS the fallback.
                self._maybe_store_fetch(req)
            # replay admissions prefill prompt + committed[:-1]; every
            # prefix-cache and prefill path below works on this view
            eff = self._effective_prompt(req)
            blocks, dblocks = None, None
            hit, dhit = None, None
            if self.kv_layout == "paged":
                # admission is by free-BLOCK count, not free-slot count:
                # the head of the queue waits (FIFO, no starvation) until
                # eviction frees enough blocks for its actual need. A
                # prefix-cache hit covers its blocks at zero cost (one
                # refcount each); only the remainder is allocated fresh —
                # plus one COW block when the hit covers the whole prompt
                # (prefill must resume inside the final shared block). On
                # shortage, LRU cached prefixes no live slot references
                # are evicted before the head of the queue waits. Spec
                # mode admits by the COMBINED footprint — both pools must
                # cover the request, and a partial grab is rolled back so
                # a draft-pool shortage can't strand target blocks.
                total = self._blocks_needed(req)
                if self.prefix_cache is not None:
                    hit = self.prefix_cache.match(eff)
                    if not hit.blocks:
                        hit = None
                fresh = total - (len(hit.blocks) if hit else 0) \
                    + (1 if hit and hit.full else 0)
                if hit is not None:
                    # reference the hit FIRST: the eviction below can then
                    # never free the prefix this slot is about to reuse
                    self.prefix_cache.acquire(hit)
                blocks = self.allocator.alloc(fresh)
                if blocks is None and self.prefix_cache is not None:
                    if self.prefix_cache.evict(
                            fresh - self.allocator.free_count):
                        blocks = self.allocator.alloc(fresh)
                if blocks is None and self.enable_spill:
                    # Spill tier: preempt the coldest active request into
                    # a host-side checksummed artifact instead of making
                    # the head of the queue wait for a natural eviction.
                    blocks = self._spill_for(fresh, free)
                if blocks is None:
                    if hit is not None:
                        self.allocator.free(hit.blocks)
                    break
                if self.spec_k:
                    # DRAFT-pool mirror of the same protocol. A full draft
                    # hit takes NO extra COW block: the draft phase is
                    # skipped outright (module docstring). A shortage here
                    # rolls back every reference both pools acquired.
                    if self.draft_prefix_cache is not None:
                        dhit = self.draft_prefix_cache.match(eff)
                        if not dhit.blocks:
                            dhit = None
                    dfresh = total - (len(dhit.blocks) if dhit else 0)
                    if dhit is not None:
                        self.draft_prefix_cache.acquire(dhit)
                    dblocks = self.draft_allocator.alloc(dfresh)
                    if (dblocks is None
                            and self.draft_prefix_cache is not None):
                        if self.draft_prefix_cache.evict(
                                dfresh - self.draft_allocator.free_count):
                            dblocks = self.draft_allocator.alloc(dfresh)
                    if dblocks is None:
                        if dhit is not None:
                            self.draft_allocator.free(dhit.blocks)
                        self.allocator.free(blocks)
                        if hit is not None:
                            self.allocator.free(hit.blocks)
                        break
            self.queue.popleft()
            slot, rings_held = self._take_slot(free, hit)
            self._acquire_adapter(req, slot)
            self._trace(req, "queue", dur=self.clock() - submitted_at,
                        slot=slot)
            if self.kv_layout == "paged":
                start_pos = 0
                slot_blocks = blocks
                if hit is not None:
                    slot_blocks = list(hit.blocks)
                    start_pos = hit.tokens
                    fresh_tail = blocks
                    if hit.full:
                        # Full-prompt hit: sampling the first token needs
                        # the LAST prompt position's logits, so prefill
                        # resumes at prompt_len - 1 — a write into the
                        # final shared block. Copy-on-write: duplicate it
                        # into the first fresh block, remap, and drop this
                        # slot's reference on the shared original.
                        cow_dst = blocks[0]
                        self.engine.cow_copy(slot_blocks[-1], cow_dst)
                        self.allocator.free([slot_blocks[-1]])
                        slot_blocks[-1] = cow_dst
                        start_pos = hit.tokens - 1
                        fresh_tail = blocks[1:]
                        self.prefix_cache.cow_copies += 1
                        self.prefix_cow_copies += 1
                    slot_blocks = slot_blocks + fresh_tail
                row = np.zeros((self.engine.max_blocks_per_slot,), np.int32)
                row[:len(slot_blocks)] = slot_blocks
                self.block_tables[slot] = row
                if self.role == "prefill":
                    # incremental-shipment ledger; a prefix-cache hit's
                    # leading blocks are committed KV by definition, so
                    # they ship IMMEDIATELY as artifact 0 — the decode
                    # engine can be importing them while prefill still
                    # streams the divergent remainder
                    self._ship_state[req.id] = {
                        "shipped": 0, "seq": 0,
                        "gen": self._ship_req_gen.pop(req.id, 0)}
                    if start_pos:
                        self._ship_commit(req, slot_blocks, eff, start_pos)
                if self.prefill_batch > 1:
                    # PACKED lane: ownership established (blocks, prefix
                    # references, full-hit COW all done above) — enqueue
                    # the prompt for the chunk-interleaved rounds instead
                    # of streaming it to completion here. Prefix insert /
                    # hit accounting moves to the row's completion, where
                    # the sequential lane does it too.
                    self._pending_prefill.append(_PendingPrefill(
                        request=req, submitted_at=submitted_at, slot=slot,
                        row=row, blocks=slot_blocks, start_pos=start_pos,
                        pos=start_pos, eff=eff))
                    continue
                spec_kw = {}
                slot_dblocks = dblocks
                if self.spec_k:
                    draft_start = 0
                    if dhit is not None:
                        # mirror of the target's hit splice, minus the
                        # full-hit COW: the shared blocks lead the row, the
                        # fresh tail covers the divergent prompt remainder
                        # and the generation budget; a full hit resumes at
                        # == prompt_len, i.e. skips the draft phase.
                        slot_dblocks = list(dhit.blocks) + dblocks
                        draft_start = dhit.tokens
                    drow = np.zeros((self.engine.max_blocks_per_slot,),
                                    np.int32)
                    drow[:len(slot_dblocks)] = slot_dblocks
                    self.draft_block_tables[slot] = drow
                    # only spec-mode engines need (or accept) the draft
                    # row — non-spec engine doubles keep the old signature
                    spec_kw["draft_block_row"] = drow
                    if self.draft_prefix_cache is not None:
                        spec_kw["draft_start_pos"] = draft_start
                if self.prefix_cache is not None:
                    # only cache-aware engines accept the offset kwarg —
                    # test doubles without enable_prefix_cache never see it
                    spec_kw["start_pos"] = start_pos
                if self.adapters is not None:
                    # only adapter engines accept the adapter kwargs
                    spec_kw["adapter_row"] = self._adapter_rows[slot]
                    spec_kw["adapter_scale"] = float(
                        self._adapter_scales[slot])
                if rings_held is not None:
                    spec_kw["rings_held"] = rings_held
                on_chunk = self._count_chunk
                if self.role == "prefill":
                    # chunk-granular shipping: each finished chunk commits
                    # its KV, so its full blocks export right here — the
                    # incremental half of the disaggregated pipeline (the
                    # packed lane does the same in _prefill_round)
                    chunk_max = self.engine.prefill_buckets[-1]
                    ship_pos = {"pos": start_pos}
                    _req, _blocks, _eff = req, slot_blocks, eff

                    def on_chunk():
                        self._count_chunk()
                        ship_pos["pos"] += min(chunk_max,
                                               len(_eff) - ship_pos["pos"])
                        self._ship_commit(_req, _blocks, _eff,
                                          ship_pos["pos"])
                t0 = self.clock()
                first = self.engine.prefill(
                    slot, eff, block_row=row,
                    temperature=req.temperature, top_p=req.top_p,
                    seed=req.seed, stop_check=self._drain_requested,
                    on_chunk=on_chunk, **spec_kw)
                pf_dur = self.clock() - t0
                self.prefill_seconds += pf_dur
                if first is None:
                    # Drain fired mid-prompt: the engine finished the
                    # current chunk and stopped. Free the slot's blocks
                    # exactly once each (fresh, COW and acquired shared
                    # references alike — shared blocks survive under the
                    # cache's own reference), put the request back at the
                    # head so it is REPORTED unserved, and close
                    # admission — the drain stays exact.
                    self.allocator.free(slot_blocks)
                    self.block_tables[slot] = 0
                    self._ship_state.pop(req.id, None)
                    if self.spec_k:
                        self.draft_allocator.free(slot_dblocks)
                        self.draft_block_tables[slot] = 0
                    self._release_adapter(slot)
                    self.queue.appendleft((req, submitted_at))
                    self.stop_admission()
                    return
                self._slot_blocks[slot] = slot_blocks
                if self.spec_k:
                    self._slot_draft_blocks[slot] = slot_dblocks
                    if self.draft_prefix_cache is not None:
                        self.draft_prefix_cache.insert(eff, slot_dblocks)
                        self.draft_prefix_cache.note_admission(
                            draft_start, len(eff))
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(eff, slot_blocks)
                    self.prefix_cache.note_admission(start_pos, len(eff))
                    self._m_prefix_hit_rate.set(self.prefix_cache.hit_rate)
                    self._maybe_store_publish(req, eff, slot_blocks)
            else:
                t0 = self.clock()
                first = self.engine.prefill(slot, eff,
                                            temperature=req.temperature,
                                            top_p=req.top_p, seed=req.seed)
                pf_dur = self.clock() - t0
                self.prefill_seconds += pf_dur
            self._check_replay(req, first)
            st = self.active[slot] = _Slot(req, first, submitted_at,
                                           self.clock())
            self._trace(req, "prefill", dur=pf_dur,
                        prompt_tokens=len(eff), packed=False,
                        replayed=len(list(req.committed or ())))
            self._trace(req, "first_token",
                        ttft=st.first_token_at - st.submitted_at)
            self.max_concurrent = max(self.max_concurrent, len(self.active))
            self._m_tokens.inc()  # the prefill's first token
            if self.role == "prefill":
                # prefill engine's contract: decode belongs to a decode
                # engine. The final shipment exported with the last chunk;
                # finish with the first token as the committed handoff
                # point (fleet.py journals prefill_done, the router places
                # the decode). EOS/budget on that token are the DECODE
                # admission's finish checks — uniform either way.
                self._finish(slot, "prefill", done)
                continue
            # a request can finish straight out of prefill (a replay can
            # arrive with EOS as its last committed token, or within one
            # token of its budget — the same checks, on the banked tail)
            if (self.eos_token_id is not None
                    and st.tokens[-1] == self.eos_token_id):
                self._finish(slot, "eos", done)
            elif len(st.tokens) >= req.max_new_tokens:
                self._finish(slot, "length", done)

    # --- spill tier + handoff (tiered KV-block lifecycle) -------------------

    def _spill_tier_root(self) -> str:
        if self._spill_root is None:
            if self._spill_dir_arg:
                os.makedirs(self._spill_dir_arg, exist_ok=True)
                self._spill_root = self._spill_dir_arg
            else:
                self._spill_root = tempfile.mkdtemp(prefix="kv_spill_")
        return self._spill_root

    def _audit_tier(self, action: str, rid: str, blocks: int,
                    nbytes: int) -> None:
        tier = self._spill_dir_arg or "host-ram"
        events.emit_audit(logger, AUDIT_KV_TIER_FMT.format(
            action=action, id=rid, blocks=blocks, bytes=nbytes, tier=tier),
            "kv_tier")

    def _audit_handoff(self, action: str, rid: str, gen: int, blocks: int,
                       detail: str) -> None:
        events.emit_audit(logger, AUDIT_HANDOFF_FMT.format(
            action=action, id=rid, gen=gen, blocks=blocks, detail=detail),
            "handoff")

    def _set_spill_gauges(self) -> None:
        self._m_blocks_spilled.set(
            sum(len(sp.private_positions) for sp in self._spilled.values()))
        self._m_spill_bytes.set(
            float(sum(sp.bytes for sp in self._spilled.values())))

    def _pick_spill_victim(self) -> Optional[int]:
        """The COLDEST active request: the one farthest from completion
        (largest remaining token budget — it would hold its blocks the
        longest), ties broken toward the most recently submitted, then
        the highest slot. Deterministic for a fixed workload."""
        best, best_key = None, None
        for slot, st in self.active.items():
            remaining = st.request.max_new_tokens - len(st.tokens)
            if remaining <= 0:
                continue
            key = (remaining, st.submitted_at, slot)
            if best_key is None or key > best_key:
                best, best_key = slot, key
        return best

    def _spill_for(self, fresh: int, free: List[int]) -> Optional[List[int]]:
        """Preempt victims until ``fresh`` blocks allocate (or no victim
        remains). Freed victim slots rejoin the admission ``free`` list."""
        blocks = None
        while blocks is None:
            victim = self._pick_spill_victim()
            if victim is None or not self._spill_slot(victim):
                return None
            free.append(victim)
            free.sort()
            blocks = self.allocator.alloc(fresh)
            if blocks is None and self.prefix_cache is not None:
                if self.prefix_cache.evict(
                        fresh - self.allocator.free_count):
                    blocks = self.allocator.alloc(fresh)
        return blocks

    def _spill_slot(self, slot: int) -> bool:
        """Export ``slot``'s PRIVATE blocks to the spill tier and release
        the device row. Shared prefix-cache blocks are NOT spilled — their
        bytes stay warm on the device under the cache's own reference and
        the restore re-acquires them by content; only this slot's
        references are dropped. Returns False if the slot holds nothing
        spillable (row fully shared, or sharing isn't the leading prefix
        the restore splice depends on)."""
        st = self.active[slot]
        rid = st.request.id
        if rid in self._spilled:
            raise RuntimeError(f"request {rid} is already spilled — "
                               f"double spill")
        row_blocks = list(self._slot_blocks[slot])
        shared = 0
        while (shared < len(row_blocks)
               and self.allocator.refcount(row_blocks[shared]) > 1):
            shared += 1
        if any(self.allocator.refcount(b) > 1 for b in row_blocks[shared:]):
            return False
        private = row_blocks[shared:]
        if not private:
            return False
        bs = self.engine.block_size
        # positions 0..lengths[slot) hold the KV of prompt+tokens in
        # order; the shared leading blocks therefore cover exactly the
        # first shared*bs of that stream — the content-addressed key the
        # restore re-matches against the prefix cache
        full_stream = list(st.request.prompt) + [int(t) for t in st.tokens]
        shared_tokens = full_stream[:shared * bs]
        art_dir = os.path.join(self._spill_tier_root(),
                               f"spill_{self.spill_exports:04d}_{rid}")
        manifest = self.engine.export_slot_blocks(
            private, art_dir, slot=slot,
            meta={"kind": "spill", "request_id": rid,
                  "tokens": [int(t) for t in st.tokens],
                  "positions": list(range(shared, len(row_blocks)))})
        nbytes = artifact_bytes(manifest)
        ordinal = self.spill_exports
        self.spill_exports += 1
        if self._on_spill is not None:
            # chaos hook (spill_corrupt): keyed by export ordinal
            self._on_spill(art_dir, ordinal)
        self._spilled[rid] = _SpilledRequest(
            request=st.request, submitted_at=st.submitted_at,
            first_token_at=st.first_token_at,
            tokens=[int(t) for t in st.tokens], steps=st.steps,
            emitted=list(st.emitted), shared_tokens=shared_tokens,
            private_positions=list(range(shared, len(row_blocks))),
            blocks_total=len(row_blocks), artifact_dir=art_dir,
            bytes=nbytes)
        self._spill_order.append(rid)
        self.active.pop(slot)
        del self._slot_blocks[slot]
        self.allocator.free(row_blocks)
        self.block_tables[slot] = 0
        # the parked request drops its adapter pin too — a cold adapter
        # may evict while it waits; the restore pages it back in verified
        self._release_adapter(slot)
        self._set_spill_gauges()
        self._audit_tier("export", rid, len(private), nbytes)
        self._trace(st.request, "spill", blocks=len(private), bytes=nbytes)
        return True

    def spill(self, slot: int) -> None:
        """Explicit preemption (tests; the future SLO scheduler's
        preempt-by-class hook): spill ``slot``'s active request to the
        host tier now."""
        if not self.enable_spill:
            raise RuntimeError("spill tier disabled (enable_spill/"
                               "spill_dir not set)")
        if slot not in self.active:
            raise KeyError(f"slot {slot} has no active request")
        if not self._spill_slot(slot):
            raise RuntimeError(f"slot {slot} holds no spillable private "
                               f"blocks")

    def _try_restores(self, done: List[Completion]) -> None:
        taken = set(self.active)
        taken.update(p.slot for p in self._pending_prefill)
        free = [s for s in range(self.engine.slots) if s not in taken]
        for rid in list(self._spill_order):
            if not free:
                return
            outcome = self._restore_one(rid, free[0], done)
            if outcome == "wait":
                # FIFO across the tier: the oldest parked request gets the
                # next blocks; younger ones don't overtake it
                return
            if outcome == "restored":
                self.held_rings.pop(free.pop(0), None)

    def _restore_one(self, rid: str, slot: int,
                     done: List[Completion]) -> str:
        """Bring one spilled request back onto the device: re-acquire its
        shared prefix from the cache by content, allocate private blocks,
        CRC-verify + import the artifact, and resurrect the slot state so
        the next decode folds exactly the step the preempted stream would
        have. Any failure — evicted prefix, rejected artifact — falls back
        to a bit-exact replay from prompt+committed. Returns
        'restored' | 'wait' | 'replay'."""
        sp = self._spilled.get(rid)
        if sp is None:
            raise RuntimeError(f"request {rid} is not spilled — "
                               f"double restore")
        aname = str(getattr(sp.request, "adapter", "") or "")
        if aname and self.adapters is not None \
                and not self.adapters.resident(aname):
            # the adapter may have evicted while the request was parked:
            # page it back in (verified) before touching any KV blocks,
            # so a shortage or reject leaves both pools untouched
            from .adapters import AdapterIntegrityError
            try:
                if not self.adapters.page_in(aname):
                    return "wait"
            except (AdapterIntegrityError, KeyError) as e:
                self._spill_fallback(rid, f"adapter page-in rejected: {e}")
                return "replay"
        bs = self.engine.block_size
        n_shared = len(sp.shared_tokens) // bs
        hit = None
        if n_shared:
            if self.prefix_cache is not None:
                h = self.prefix_cache.match(sp.shared_tokens)
                if h.blocks and h.tokens >= len(sp.shared_tokens):
                    hit = h
            if hit is None:
                # the cache evicted the shared prefix while we were
                # parked: those device bytes are gone — replay fallback
                self._spill_fallback(rid, "shared prefix evicted")
                return "replay"
            self.prefix_cache.acquire(hit)
        n_private = len(sp.private_positions)
        blocks = self.allocator.alloc(n_private)
        if blocks is None and self.prefix_cache is not None:
            if self.prefix_cache.evict(
                    n_private - self.allocator.free_count):
                blocks = self.allocator.alloc(n_private)
        if blocks is None:
            if hit is not None:
                self.allocator.free(hit.blocks)
            return "wait"
        try:
            self.engine.import_slot_blocks(sp.artifact_dir, blocks, slot)
        except KVBlockIntegrityError as e:
            self.allocator.free(blocks)
            if hit is not None:
                self.allocator.free(hit.blocks)
            self._spill_fallback(rid, f"restore rejected: {e}")
            return "replay"
        slot_blocks = (list(hit.blocks)[:n_shared] if hit is not None
                       else []) + blocks
        row = np.zeros((self.engine.max_blocks_per_slot,), np.int32)
        row[:len(slot_blocks)] = slot_blocks
        self.block_tables[slot] = row
        self._slot_blocks[slot] = slot_blocks
        st = _Slot(sp.request, sp.tokens[-1], sp.submitted_at,
                   sp.first_token_at)
        st.tokens = list(sp.tokens)
        st.steps = sp.steps
        st.emitted = list(sp.emitted)
        self._acquire_adapter(sp.request, slot)
        self.active[slot] = st
        self.max_concurrent = max(self.max_concurrent, len(self.active))
        self._drop_spilled(rid)
        self.spill_restores += 1
        self._m_spill_restores.inc()
        self._audit_tier("restore", rid, n_private, sp.bytes)
        self._trace(sp.request, "restore", blocks=n_private,
                    shared=n_shared)
        return "restored"

    def _spill_fallback(self, rid: str, detail: str) -> None:
        """Restore impossible: requeue a replay request at the head —
        prompt + committed re-derives the stream bit-exactly (the PR 11
        migration invariant), so a lost/corrupt artifact costs prefill
        compute, never correctness."""
        sp = self._spilled[rid]
        self.spill_rejects += 1
        self._audit_tier("reject", rid, len(sp.private_positions), sp.bytes)
        logger.warning("Spill restore of request %s fell back to "
                       "committed-prefix replay: %s", rid, detail)
        replay = dataclasses.replace(sp.request, committed=tuple(sp.tokens))
        self.queue.appendleft((replay, sp.submitted_at))
        self._drop_spilled(rid)
        self._trace(sp.request, "spill_replay", blocks=0, detail=detail)

    def _drop_spilled(self, rid: str) -> None:
        sp = self._spilled.pop(rid)
        self._spill_order.remove(rid)
        shutil.rmtree(sp.artifact_dir, ignore_errors=True)
        self._set_spill_gauges()

    def discard_spilled(self) -> int:
        """Drain epilogue: drop every parked artifact. The requests were
        reported unserved with their committed prefixes (see
        :meth:`unserved`) — the journal requeue is their durable form; the
        tier dies with this process. Returns how many were discarded."""
        n = len(self._spilled)
        for rid in list(self._spill_order):
            self._drop_spilled(rid)
        if self._spill_root is not None and not self._spill_dir_arg:
            shutil.rmtree(self._spill_root, ignore_errors=True)
            self._spill_root = None
        return n

    def export_handoff(self, slot: int, out_dir: str, gen: int = 0) -> dict:
        """Drain-with-handoff (fleet.py): serialize ``slot``'s committed
        blocks — shared prefix included, the survivor's cache is a
        different pool — into a checksummed artifact, release the device
        row, and requeue the request with its committed prefix so it is
        REPORTED unserved exactly like a plain drain. The journal's
        ``handoff`` record then lets the router ship blocks instead of
        replaying; a missing/torn/corrupt artifact degrades to the
        existing replay migration. Returns the shipment summary."""
        st = self.active[slot]
        rid = st.request.id
        bs = self.engine.block_size
        length = int(np.asarray(self.engine.cache.lengths)[slot])
        n = -(-length // bs)
        row_blocks = list(self._slot_blocks[slot])
        manifest = self.engine.export_slot_blocks(
            row_blocks[:n], out_dir, slot=slot,
            meta={"kind": "handoff", "request_id": rid,
                  "prompt": [int(t) for t in st.request.prompt],
                  "tokens": [int(t) for t in st.tokens],
                  "positions": list(range(n))})
        nbytes = artifact_bytes(manifest)
        self.active.pop(slot)
        del self._slot_blocks[slot]
        self.allocator.free(row_blocks)
        self.block_tables[slot] = 0
        replay = dataclasses.replace(st.request, committed=tuple(st.tokens))
        self.queue.appendleft((replay, st.submitted_at))
        self._m_handoff_shipped.inc(n)
        self._audit_handoff("export", rid, gen, n,
                            os.path.basename(out_dir))
        self._trace(st.request, "handoff_export", blocks=n, bytes=nbytes)
        return {"dir": out_dir, "blocks": n, "bytes": nbytes,
                "tokens": [int(t) for t in st.tokens], "request": replay}

    def _admit_from_handoff(self, req: Request, submitted_at: float,
                            free: List[int], art_entry,
                            done: List[Completion]) -> str:
        """Admission by block import: verify the handed-off artifact
        (CRC + journal agreement) BEFORE touching the device, allocate the
        request's full footprint, scatter the shipped blocks in, and
        resurrect the slot at the exact decode step the departed host
        would have run next — no replay prefill. Returns 'imported',
        'wait' (pool shortage: head-of-line semantics unchanged), or
        'fallback' (artifact rejected; the caller's replay path serves the
        request bit-exactly)."""
        art_dir, gen = art_entry
        from .kv_cache import verify_block_artifact
        committed = [int(t) for t in (req.committed or ())]
        try:
            manifest = verify_block_artifact(art_dir)
        except KVBlockIntegrityError as e:
            self._handoff_reject(req, gen, str(e))
            return "fallback"
        meta = manifest.get("meta", {})
        n = len(manifest.get("blocks", []))
        total = self._blocks_needed(req)
        if (meta.get("kind") != "handoff"
                or [int(t) for t in meta.get("tokens", [])] != committed
                or ([int(t) for t in meta.get("prompt", [])]
                    != [int(t) for t in req.prompt])
                or n > total):
            self._handoff_reject(req, gen,
                                 "artifact disagrees with the journal")
            return "fallback"
        blocks = self.allocator.alloc(total)
        if blocks is None and self.prefix_cache is not None:
            if self.prefix_cache.evict(total - self.allocator.free_count):
                blocks = self.allocator.alloc(total)
        if blocks is None and self.enable_spill:
            blocks = self._spill_for(total, free)
        if blocks is None:
            return "wait"
        slot = free[0]
        try:
            self.engine.import_slot_blocks(art_dir, blocks[:n], slot)
        except KVBlockIntegrityError as e:
            self.allocator.free(blocks)
            self._handoff_reject(req, gen, str(e))
            return "fallback"
        self.queue.popleft()
        self.held_rings.pop(free.pop(0), None)
        self._handoff_artifacts.pop(req.id, None)
        row = np.zeros((self.engine.max_blocks_per_slot,), np.int32)
        row[:len(blocks)] = blocks
        self.block_tables[slot] = row
        self._slot_blocks[slot] = blocks
        eff = self._effective_prompt(req)
        if self.prefix_cache is not None:
            # the imported row covers the full committed prompt — cache it
            # so sibling prompts share it, exactly as a prefill would have
            self.prefix_cache.insert(eff, blocks)
            self.prefix_cache.note_admission(len(eff), len(eff))
            self._m_prefix_hit_rate.set(self.prefix_cache.hit_rate)
        self._trace(req, "queue", dur=self.clock() - submitted_at,
                    slot=slot)
        self._acquire_adapter(req, slot)
        st = self.active[slot] = _Slot(req, committed[-1], submitted_at,
                                       self.clock())
        self.handoff_imports += 1
        self._m_handoff_shipped.inc(n)
        self._audit_handoff("import", req.id, gen, n,
                            os.path.basename(art_dir))
        self._trace(req, "handoff_import", blocks=n,
                    committed=len(committed))
        self._trace(req, "first_token",
                    ttft=st.first_token_at - st.submitted_at)
        self.max_concurrent = max(self.max_concurrent, len(self.active))
        if (self.eos_token_id is not None
                and st.tokens[-1] == self.eos_token_id):
            self._finish(slot, "eos", done)
        elif len(st.tokens) >= req.max_new_tokens:
            self._finish(slot, "length", done)
        return "imported"

    def _handoff_reject(self, req: Request, gen: int, detail: str) -> None:
        self._handoff_artifacts.pop(req.id, None)
        self.handoff_rejects += 1
        self._m_handoff_rejected.inc()
        self._audit_handoff("reject", req.id, gen, 0, detail)
        logger.warning("Handoff import of request %s rejected (%s); "
                       "falling back to committed-prefix replay", req.id,
                       detail)
        self._trace(req, "handoff_reject", detail=detail)

    # --- disaggregated prefill/decode shipping ------------------------------

    def _ship_root(self) -> str:
        if self._ship_root_path is None:
            if self._ship_dir_arg:
                os.makedirs(self._ship_dir_arg, exist_ok=True)
                self._ship_root_path = self._ship_dir_arg
            else:
                self._ship_root_path = tempfile.mkdtemp(prefix="kv_ship_")
        return self._ship_root_path

    def _audit_ship(self, action: str, rid: str, seq: int, gen: int,
                    start: int, end: int, detail: str) -> None:
        events.emit_audit(logger, AUDIT_DISAGG_SHIP_FMT.format(
            action=action, id=rid, seq=seq, gen=gen, start=start, end=end,
            detail=detail), "disagg_ship")

    def _audit_xport(self, action: str, lane: str, rid: str, blocks: int,
                     detail: str) -> None:
        events.emit_audit(logger, AUDIT_KV_XPORT_FMT.format(
            action=action, lane=lane, id=rid, blocks=blocks,
            detail=detail), "kv_xport", action=action, lane=lane, id=rid,
            blocks=blocks)

    def _ship_commit(self, req: Request, slot_blocks: List[int],
                     eff: Sequence[int], pos: int) -> None:
        """Export the blocks the prefill just COMMITTED — full blocks up
        to absolute position ``pos``, everything once ``pos`` reaches the
        prompt end — as one incremental checksummed shipment. Chunk
        boundaries rarely align with block boundaries, so a chunk whose
        tokens all land inside a still-open block ships nothing; the next
        boundary crossing carries it. The partially-filled final block
        ships only with the LAST commit (its bytes keep changing until
        then), which is what makes "decode never reads an uncommitted
        block" structural: a shipment's blocks are immutable on export."""
        st = self._ship_state.get(req.id)
        if st is None:
            return
        bs = self.engine.block_size
        end = -(-len(eff) // bs) if pos >= len(eff) else pos // bs
        if end <= st["shipped"]:
            return
        start = st["shipped"]
        seq = st["seq"]
        length = int(min(pos, len(eff)))
        art_dir = os.path.join(
            self._ship_root(),
            f"ship_{self.ship_exports:05d}_{req.id}_{seq:02d}")
        t0 = self.clock()
        manifest = self.transport.export(
            self.engine.cache, list(slot_blocks[start:end]), art_dir,
            length=length,
            meta={"kind": "ship", "request_id": req.id,
                  "prompt": [int(t) for t in eff],
                  "seq": seq, "start_block": start, "end_block": end})
        dur = self.clock() - t0
        nbytes = artifact_bytes(manifest)
        ordinal = self.ship_exports
        self.ship_exports += 1
        st["shipped"] = end
        st["seq"] = seq + 1
        self._m_ship_exports.inc()
        self._m_handoff_shipped.inc(end - start)
        self._m_xport_bytes.labels(lane="fs").inc(nbytes)
        self._audit_ship("export", req.id, seq, st.get("gen", 0), start,
                         end, os.path.basename(art_dir))
        if self.transport.name == "mem":
            # the mem lane rides the same export: the device arrays are
            # already in the fabric, addressed by the artifact path
            self._m_xport_bytes.labels(lane="mem").inc(nbytes)
            self._audit_xport("push", "mem", req.id, end - start,
                              f"seq {seq}, {nbytes} byte(s)")
        self._trace(req, "block_ship", dur=dur, seq=seq,
                    blocks=end - start, bytes=nbytes, length=length)
        if self._on_ship is not None:
            # fleet.py: chaos (ship_corrupt, keyed by export ordinal)
            # then the journal's ship record
            self._on_ship(req, art_dir, ordinal, seq, start, end, length)

    def _admit_from_shipments(self, req: Request, submitted_at: float,
                              free: List[int], ship_entry,
                              done: List[Completion]) -> str:
        """Decode-side admission by incremental block import: CRC-verify
        EVERY shipment and check contiguous coverage of the committed
        prompt BEFORE touching the device (decode never reads an
        uncommitted block), dedupe the leading shipments against the
        prefix cache (already-resident shared-prompt blocks are acquired
        by content, not re-imported), scatter the rest in, and resurrect
        the slot at the exact decode step the prefill engine committed —
        fold_in(seed, len(committed)) continues the SAME stream. Returns
        'imported', 'wait' (pool shortage: head-of-line semantics
        unchanged) or 'fallback' (rejected: the caller's replay path
        re-derives the stream bit-exactly, the PR 13 degradation
        contract)."""
        ships, gen = ship_entry
        committed = [int(t) for t in (req.committed or ())]
        eff = [int(t) for t in self._effective_prompt(req)]
        bs = self.engine.block_size
        n_ship_blocks = -(-len(eff) // bs)
        ships = sorted((dict(s) for s in ships),
                       key=lambda s: int(s.get("seq", 0)))
        if not committed or not ships:
            self._ship_reject(req, gen, "no shipments for the committed "
                                        "prefix")
            return "fallback"
        pos = 0
        for s in ships:
            if int(s.get("start_block", -1)) != pos:
                pos = -1
                break
            pos = int(s.get("end_block", -1))
        if (pos != n_ship_blocks
                or int(ships[-1].get("length", -1)) != len(eff)):
            self._ship_reject(req, gen, "shipments do not cover the "
                                        "committed prompt contiguously")
            return "fallback"
        # Lane ladder: try the transport's lanes in preference order (mem
        # first when available, then the durable fs artifact). Each lane
        # verifies EVERY shipment under its own contract — mem checks the
        # push-time metadata digest, fs re-runs the CRC walk — before any
        # device write; a non-final lane failing degrades the whole train,
        # never a mixed import.
        lane, fail_detail = None, ""
        for cand in self.transport.lanes:
            ok = True
            for s in ships:
                art = str(s.get("artifact", ""))
                try:
                    manifest = self.transport.verify(art, lane=cand)
                except (KVBlockIntegrityError, OSError) as e:
                    ok = False
                    fail_detail = f"{os.path.basename(art)}: {e}"
                    break
                meta = manifest.get("meta", {})
                s_start = int(s.get("start_block", -1))
                s_end = int(s.get("end_block", -1))
                if (meta.get("kind") != "ship"
                        or str(meta.get("request_id")) != req.id
                        or [int(t) for t in meta.get("prompt", [])] != eff
                        or int(meta.get("seq", -1)) != int(s.get("seq", 0))
                        or int(meta.get("start_block", -1)) != s_start
                        or int(meta.get("end_block", -1)) != s_end
                        or int(manifest.get("length", -1))
                        != int(s.get("length", -1))
                        or len(manifest.get("blocks", []))
                        != s_end - s_start):
                    ok = False
                    fail_detail = (f"{os.path.basename(art)} disagrees "
                                   f"with the journal")
                    break
            if ok:
                lane = cand
                break
            if cand != self.transport.lanes[-1]:
                self.lane_fallbacks += 1
                self._m_lane_fallbacks.inc()
                self._audit_xport("fallback", cand, req.id, len(ships),
                                  fail_detail)
        if lane is None:
            self._ship_reject(req, gen, fail_detail)
            return "fallback"
        # prefix-cache dedupe: shipments whose blocks are already resident
        # (a shared prompt another decode admitted) are skipped, not
        # re-imported — clamped DOWN to a shipment boundary because an
        # artifact imports whole, and to FULL blocks only (the cache never
        # holds the partial final block, which decode will write into)
        n_full = len(eff) // bs
        n_use, hit = 0, None
        if self.prefix_cache is not None and n_full:
            h = self.prefix_cache.match(eff)
            covered = min(h.tokens // bs, n_full) if h.blocks else 0
            if covered:
                n_use = max([int(s["start_block"]) for s in ships
                             if int(s["start_block"]) <= covered] + [0])
            if n_use:
                hit = self.prefix_cache.match(eff[:n_use * bs])
                if hit.blocks and hit.tokens >= n_use * bs:
                    self.prefix_cache.acquire(hit)
                else:
                    hit, n_use = None, 0
        total = self._blocks_needed(req)
        blocks = self.allocator.alloc(total - n_use)
        if blocks is None and self.prefix_cache is not None:
            if self.prefix_cache.evict(
                    (total - n_use) - self.allocator.free_count):
                blocks = self.allocator.alloc(total - n_use)
        if blocks is None and self.enable_spill:
            blocks = self._spill_for(total - n_use, free)
        if blocks is None:
            if hit is not None:
                self.allocator.free(hit.blocks)
            return "wait"
        slot = free[0]
        t0 = self.clock()
        imported = 0
        parts = []
        for s in ships:
            s_start, s_end = int(s["start_block"]), int(s["end_block"])
            if s_end <= n_use:
                continue  # deduped: resident via the prefix cache
            parts.append((str(s["artifact"]),
                          blocks[s_start - n_use:s_end - n_use]))
            imported += s_end - s_start
        try:
            if parts:
                # the whole shipment train lands as ONE scatter per pool
                # array — admission stall stays off the decode-round tail
                try:
                    self.transport.import_batch(self.engine, parts,
                                                lane=lane)
                except KVBlockIntegrityError as e:
                    if lane == "fs":
                        raise
                    # the mem landing failed between verify and scatter:
                    # degrade this train to the durable fs artifacts
                    self.lane_fallbacks += 1
                    self._m_lane_fallbacks.inc()
                    self._audit_xport("fallback", lane, req.id, imported,
                                      str(e))
                    lane = "fs"
                    self.transport.import_batch(self.engine, parts,
                                                lane="fs")
        except KVBlockIntegrityError as e:
            self.allocator.free(blocks)
            if hit is not None:
                self.allocator.free(hit.blocks)
            self._ship_reject(req, gen, str(e))
            return "fallback"
        # all shipments resident: the slot's committed length lands ONCE
        self.engine.set_slot_length(slot, len(eff))
        imp_dur = self.clock() - t0
        self.queue.popleft()
        self.held_rings.pop(free.pop(0), None)
        self._shipments.pop(req.id, None)
        slot_blocks = (list(hit.blocks)[:n_use] if hit is not None
                       else []) + blocks
        row = np.zeros((self.engine.max_blocks_per_slot,), np.int32)
        row[:len(slot_blocks)] = slot_blocks
        self.block_tables[slot] = row
        self._slot_blocks[slot] = slot_blocks
        if self.prefix_cache is not None:
            # the imported row covers the committed prompt — cache it so
            # sibling prompts dedupe against it, exactly as prefill would
            self.prefix_cache.insert(eff, slot_blocks)
            self.prefix_cache.note_admission(n_use * bs, len(eff))
            self._m_prefix_hit_rate.set(self.prefix_cache.hit_rate)
        self._trace(req, "queue", dur=self.clock() - submitted_at,
                    slot=slot)
        self._acquire_adapter(req, slot)
        st = self.active[slot] = _Slot(req, committed[-1], submitted_at,
                                       self.clock())
        self.ship_imports += 1
        self._m_ship_imports.inc(len(ships))
        self._m_handoff_shipped.inc(imported)
        if lane == "mem":
            self.mem_lane_imports += 1
        self._audit_xport("land", lane, req.id, imported,
                          f"{len(ships)} shipment(s), "
                          f"{imp_dur * 1e3:.1f} ms")
        self._audit_ship("import", req.id, int(ships[-1].get("seq", 0)),
                         gen, n_use, n_ship_blocks,
                         f"{imported} imported, {n_use} deduped")
        self._trace(req, "shipment_import", dur=imp_dur,
                    shipments=len(ships), blocks=imported, deduped=n_use)
        self._trace(req, "first_token",
                    ttft=st.first_token_at - st.submitted_at)
        self.max_concurrent = max(self.max_concurrent, len(self.active))
        if (self.eos_token_id is not None
                and st.tokens[-1] == self.eos_token_id):
            self._finish(slot, "eos", done)
        elif len(st.tokens) >= req.max_new_tokens:
            self._finish(slot, "length", done)
        return "imported"

    def _ship_reject(self, req: Request, gen: int, detail: str) -> None:
        self._shipments.pop(req.id, None)
        self.ship_rejects += 1
        self._m_ship_rejected.inc()
        self._audit_ship("reject", req.id, -1, gen, 0, 0, detail)
        logger.warning("Shipment import of request %s rejected (%s); "
                       "falling back to committed-prefix replay", req.id,
                       detail)
        self._trace(req, "ship_reject", detail=detail)

    # --- fleet-global KV store (inference/kvstore.py) -----------------------

    def _audit_store(self, action: str, key: str, rid: str, blocks: int,
                     detail: str) -> None:
        events.emit_audit(logger, AUDIT_KV_STORE_FMT.format(
            action=action, key=key[:12], id=rid, blocks=blocks,
            detail=detail), "kv_store")

    def _maybe_store_fetch(self, req: Request) -> None:
        """Fetch the deepest fleet-store train matching ``req``'s prompt
        into the local prefix cache, when it beats the local hit depth.
        The train lands through the batched verify-before-first-device-
        write import into fresh blocks, is inserted under its content
        address (the cache's own reference keeps the blocks), and the
        normal admission then matches it like any resident prefix. The
        in-flight fetch holds a journaled store refcount so the sweeper
        can never evict the train mid-import; any CRC/metadata reject or
        pool shortage leaves the pool untouched and the request on the
        local-prefill path."""
        bs = self.engine.block_size
        eff = self._effective_prompt(req)
        keys = chain_hashes(eff, bs)
        if not keys:
            return
        store_hit = self.kv_store.match(keys)
        if store_hit is None:
            return
        local = self.prefix_cache.match(eff)
        n = store_hit.depth
        if n <= local.depth:
            return  # the local cache already covers at least as much
        owner = f"fetch-{req.id}"
        self.kv_store.acquire(store_hit.key, owner)
        blocks = self.allocator.alloc(n)
        if blocks is None:
            if self.prefix_cache.evict(n - self.allocator.free_count):
                blocks = self.allocator.alloc(n)
        if blocks is None:
            # pool pressure: not a reject — plain local admission decides
            self.kv_store.release(store_hit.key, owner)
            return
        t0 = self.clock()
        try:
            # lane ladder: mem fabric first when the transport has it,
            # the CRC-verified artifact as the terminal rung
            manifest, lane = None, "fs"
            for cand in self.transport.lanes:
                try:
                    manifest = self.transport.import_batch(
                        self.engine, [(store_hit.art_dir, blocks)],
                        lane=cand,
                        allow_partial=store_hit.partial)[0]
                    lane = cand
                    break
                except (KVBlockIntegrityError, OSError, ValueError) as e:
                    if cand == self.transport.lanes[-1]:
                        raise
                    self.lane_fallbacks += 1
                    self._m_lane_fallbacks.inc()
                    self._audit_xport("fallback", cand, req.id, n, str(e))
            meta = manifest.get("meta", {})
            mkeys = [str(k) for k in meta.get("keys", [])]
            # a partial (sub-train) hit imports a PREFIX of a longer
            # train: the manifest must hold at least n blocks and its
            # per-block chain must agree with the prompt's at depth n
            if (meta.get("kind") != "store"
                    or str(meta.get("key", "")) != store_hit.key
                    or len(manifest.get("blocks", [])) < n
                    or (store_hit.partial
                        and (len(mkeys) < n
                             or mkeys[n - 1] != keys[n - 1].hex()))):
                raise KVBlockIntegrityError(
                    "store train manifest disagrees with its content "
                    "address")
        except (KVBlockIntegrityError, OSError, ValueError) as e:
            self.allocator.free(blocks)
            self.kv_store.release(store_hit.key, owner)
            self.store_rejects += 1
            self._m_store_rejected.inc()
            self._audit_store("reject", store_hit.key, req.id, 0, str(e))
            logger.warning("Fleet-store fetch for request %s rejected "
                           "(%s); falling back to local chunked prefill",
                           req.id, e)
            self._trace(req, "store_reject", key=store_hit.key,
                        detail=str(e))
            return
        dur = self.clock() - t0
        # content-address the imported blocks: insert takes the cache's
        # reference, then this fetch's own allocation reference drops —
        # exactly one holder, the ownership protocol every other resident
        # prefix lives under. Keys the cache already holds keep their
        # canonical block; the duplicate import rows free back to the pool.
        self.prefix_cache.insert(eff[:n * bs], blocks)
        self.allocator.free(blocks)
        self.kv_store.touch(store_hit.key)
        self.kv_store.release(store_hit.key, owner)
        self.store_fetches += 1
        self.store_fetch_blocks += n
        self._m_store_hits.inc()
        self._m_store_fetch_blocks.inc(n)
        self._m_store_hit_depth.observe(n)
        self._m_store_bytes.set(self.kv_store.resident_bytes())
        if lane == "mem":
            self.mem_lane_imports += 1
        if store_hit.partial:
            self.store_partial_hits += 1
            self._m_store_partial.inc()
        self._audit_store(
            "fetch", store_hit.key, req.id, n,
            f"depth {n}"
            + (f" of {store_hit.blocks} (partial)" if store_hit.partial
               else "")
            + f", lane {lane}, {dur * 1e3:.1f} ms")
        self._trace(req, "store_fetch", dur=dur, key=store_hit.key,
                    depth=n, lane=lane, partial=store_hit.partial,
                    prompt_tokens=len(eff))

    def _maybe_store_publish(self, req: Request, eff: Sequence[int],
                             slot_blocks: Sequence[int]) -> None:
        """Publish the just-committed prompt's full prefix blocks as one
        content-addressed train. Dedup is free: identical prefixes hash
        identically, so a key any host already published skips the export
        outright — which also makes a fetched-then-reinserted prefix a
        no-op here."""
        if self.kv_store is None:
            return
        bs = self.engine.block_size
        keys = chain_hashes(eff, bs)
        if not keys or self.kv_store.has(keys[-1].hex()):
            return
        n = len(keys)
        if (self.kv_store_max_bytes
                and self.kv_store.resident_bytes()
                > self.kv_store_max_bytes):
            # byte-budget backpressure: the sweeper daemon owns getting
            # resident bytes back under budget; publishers just stop
            # adding to the pile (and say so) until it does
            self.store_publish_skipped += 1
            self._m_store_skipped.inc()
            self._audit_store("skip", keys[-1].hex(), req.id, n,
                              "resident bytes over budget")
            return
        t0 = self.clock()
        manifest = self.kv_store.publish(
            self.engine.cache, keys, list(slot_blocks[:n]),
            length=n * bs, meta={"request_id": req.id},
            on_put=self._on_store_put, transport=self.transport)
        if manifest is None:
            return
        dur = self.clock() - t0
        nbytes = artifact_bytes(manifest)
        key = keys[-1].hex()
        self.store_publishes += 1
        self._m_store_publishes.inc()
        self._m_store_bytes.set(self.kv_store.resident_bytes())
        self._audit_store("publish", key, req.id, n, f"{nbytes} byte(s)")
        self._trace(req, "store_publish", dur=dur, key=key, blocks=n,
                    bytes=nbytes)

    def _abort_pending_prefill(self) -> None:
        """Drain landed while packed rows were mid-prompt: free every
        pending row's blocks exactly once (fresh, COW and acquired shared
        references alike — shared blocks survive under the cache's own
        reference), requeue the requests at the head in admission order so
        they are REPORTED unserved, and close admission — the sequential
        lane's mid-chunk drain contract, at round granularity."""
        for p in reversed(self._pending_prefill):
            self.allocator.free(p.blocks)
            self.block_tables[p.slot] = 0
            self._ship_state.pop(p.request.id, None)
            self._release_adapter(p.slot)
            self.queue.appendleft((p.request, p.submitted_at))
        self._pending_prefill.clear()
        self.stop_admission()

    def _finish_prefill(self, p: _PendingPrefill, first: int,
                        done: List[Completion]) -> None:
        """A packed row's FINAL chunk landed: the round's sampled token is
        its first generated token — promote the row to an active decode
        slot (everything the sequential lane does after engine.prefill
        returns, including the straight-out-of-prefill finish checks)."""
        self._slot_blocks[p.slot] = p.blocks
        if self.prefix_cache is not None:
            self.prefix_cache.insert(p.eff, p.blocks)
            self.prefix_cache.note_admission(p.start_pos, len(p.eff))
            self._m_prefix_hit_rate.set(self.prefix_cache.hit_rate)
            self._maybe_store_publish(p.request, p.eff, p.blocks)
        self._check_replay(p.request, first)
        st = self.active[p.slot] = _Slot(p.request, first, p.submitted_at,
                                         self.clock())
        self._trace(p.request, "prefill", prompt_tokens=len(p.eff),
                    packed=True,
                    replayed=len(list(p.request.committed or ())))
        self._trace(p.request, "first_token",
                    ttft=st.first_token_at - st.submitted_at)
        self.max_concurrent = max(self.max_concurrent, len(self.active))
        self._m_tokens.inc()  # the prefill's first token
        if self.role == "prefill":
            # same prefill-engine contract as the sequential lane
            self._finish(p.slot, "prefill", done)
            return
        if (self.eos_token_id is not None
                and st.tokens[-1] == self.eos_token_id):
            self._finish(p.slot, "eos", done)
        elif len(st.tokens) >= p.request.max_new_tokens:
            self._finish(p.slot, "length", done)

    def _prefill_round(self, done: List[Completion]) -> None:
        """ONE packed prefill round: walk the pending rows in admission
        order, take up to ``prefill_batch`` whose next chunk best-fits the
        HEAD row's bucket (each row computes its chunk with exactly the
        sequential ``_stream_chunks`` discipline — largest bucket while
        the remainder exceeds it, best-fit on the final chunk — which is
        what keeps per-row chunk shapes, and so the streams on the gather
        impl, bit-identical to sequential prefill), and dispatch them in
        one (P, bucket) program. Rows needing a different bucket stay
        pending for a later round. The drain probe runs at round
        boundaries, the packed analogue of the sequential lane's
        between-chunk ``stop_check``."""
        if self._drain_requested():
            self._abort_pending_prefill()
            return
        chunk = self.engine.prefill_buckets[-1]
        head_bucket = None
        batch: List = []  # (row, chunk_len) pairs this round
        for p in self._pending_prefill:
            m = min(chunk, len(p.eff) - p.pos)
            bucket = next(b for b in self.engine.prefill_buckets if b >= m)
            if head_bucket is None:
                head_bucket = bucket
            if bucket != head_bucket:
                continue
            batch.append((p, m))
            if len(batch) == self.prefill_batch:
                break
        rows = [(p.slot,
                 np.asarray(p.eff[p.pos:p.pos + m], np.int32),
                 p.pos, p.row, p.request.temperature, p.request.top_p,
                 p.request.seed) for p, m in batch]
        packed_kw = {}
        if self.adapters is not None:
            # each packed row gathers ITS slot's adapter pages — one
            # dispatch across rows with different adapters
            packed_kw = dict(
                adapter_rows=[self._adapter_rows[p.slot] for p, _ in batch],
                adapter_scales=[float(self._adapter_scales[p.slot])
                                for p, _ in batch])
        t0 = self.clock()
        toks = self.engine.prefill_packed(rows, head_bucket, **packed_kw)
        self.prefill_seconds += self.clock() - t0
        self.prefill_packed_rounds += 1
        self.prefill_packed_rows += len(rows)
        self._m_prefill_batch.observe(len(rows))
        for (p, m), tok in zip(batch, toks):
            self._count_chunk()
            p.pos += m
            if self.role == "prefill":
                # packed analogue of the sequential lane's per-chunk ship
                self._ship_commit(p.request, p.blocks, p.eff, p.pos)
            if p.pos >= len(p.eff):
                self._pending_prefill.remove(p)
                self._finish_prefill(p, tok, done)

    def _sync_adapter_metrics(self) -> None:
        """Mirror the AdapterManager's counters onto the /metrics surface
        (the manager counts monotonically; the registry counters advance
        by the delta since the last sync)."""
        mgr = self.adapters
        self._m_adapter_pageins.inc(mgr.pageins - self._adapter_pageins_seen)
        self._adapter_pageins_seen = mgr.pageins
        self._m_adapter_evictions.inc(
            mgr.evictions - self._adapter_evictions_seen)
        self._adapter_evictions_seen = mgr.evictions
        self._m_adapter_resident_bytes.set(mgr.resident_bytes())
        counts = mgr.active_slots()
        for name in mgr.served:
            self._m_adapter_slots.labels(adapter=name).set(
                counts.get(name, 0))

    def step(self) -> List[Completion]:
        """Admit into free slots, run one decode iteration, evict finished
        requests. Returns the completions produced by this iteration.
        In the packed-prefill lane, one packed chunk round runs before the
        decode round, so admitted prompts and active decodes interleave
        instead of prefill draining the queue first."""
        with span("ftl:sched.step", active=len(self.active),
                  queued=len(self.queue)):
            return self._step()

    def _step(self) -> List[Completion]:
        done: List[Completion] = []
        if self.admission_open:
            with span("ftl:sched.admit"):
                self._admit(done)
        if self._pending_prefill:
            with span("ftl:sched.prefill_round"):
                self._prefill_round(done)
        self._m_queue.set(len(self.queue))
        self._m_occupancy.set(len(self.active) / max(self.engine.slots, 1))
        if self.kv_layout == "paged":
            self._m_blocks_free.set(self.allocator.free_count)
            self._m_blocks_total.set(self.allocator.capacity)
            util = self.allocator.used_count / max(self.allocator.capacity, 1)
            self._m_block_util.set(util)
            self.max_block_utilization = max(self.max_block_utilization, util)
            self._m_blocks_shared.set(self.allocator.shared_count)
        if self.adapters is not None:
            self._sync_adapter_metrics()
        if not self.active:
            return done
        slots = self.engine.slots
        with span("ftl:sched.pack"):
            tokens = np.zeros((slots,), np.int32)
            active = np.zeros((slots,), bool)
            temperature = np.zeros((slots,), np.float32)
            top_p = np.ones((slots,), np.float32)
            seeds = np.zeros((slots,), np.int32)
            steps = np.zeros((slots,), np.int32)
            for s, st in self.active.items():
                tokens[s] = st.tokens[-1]
                active[s] = True
                temperature[s] = st.request.temperature
                top_p[s] = st.request.top_p
                seeds[s] = st.request.seed
                steps[s] = st.steps
        t0 = self.clock()
        burst_out = None
        if self.spec_k:
            # Speculative round: lengths[s] is the slot's committed KV
            # count (prompt + emitted − 1 positions hold keys; the latest
            # emitted token is the round's input and is written by the
            # draft/verify programs themselves). steps doubles as the
            # round counter that derives the per-round PRNG streams.
            with span("ftl:sched.pack"):
                lengths = np.zeros((slots,), np.int32)
                for s, st in self.active.items():
                    lengths[s] = len(st.request.prompt) + len(st.tokens) - 1
            round_k = self.spec_k
            if self.adaptive_k is not None:
                round_k = self.adaptive_k.round_k(
                    st.request.id for st in self.active.values())
            self._m_spec_round_k.set(round_k)
            if self.spec_tree is not None:
                # TREE round: the adaptive budget maps to a deterministic
                # sub-shape of the configured tree; the refeed window
                # carries each slot's previously banked tokens (bonus
                # last) so the draft rewrites their KV before proposing.
                tree_shape = (self.spec_tree if self.adaptive_k is None
                              else self.spec_tree.shrink_to(round_k))
                r_w = self.engine._tree_refeed
                with span("ftl:sched.pack"):
                    refeed = np.zeros((slots, r_w), np.int32)
                    refeed_len = np.ones((slots,), np.int32)
                    for s, st in self.active.items():
                        em = st.emitted[-r_w:]
                        refeed[s, :len(em)] = em
                        refeed_len[s] = len(em)
                out, acc, path = self.engine.spec_tree_round(
                    refeed, refeed_len, lengths, active, temperature,
                    top_p, seeds, steps, block_tables=self.block_tables,
                    draft_block_tables=self.draft_block_tables,
                    shape=tree_shape)
            else:
                spec_kw = {}
                if self.adaptive_k is not None:
                    # only ladder-aware engines take the width kwarg —
                    # test doubles built before adaptive-k keep the old
                    # signature
                    spec_kw["k"] = round_k
                out, acc = self.engine.spec_round(
                    tokens, lengths, active, temperature, top_p, seeds,
                    steps, block_tables=self.block_tables,
                    draft_block_tables=self.draft_block_tables, **spec_kw)
            self.decode_dispatches += 2  # draft + verify programs
            self.decode_host_syncs += 1  # one result sync per round
            self._m_dispatches.inc(2)
            self._m_host_syncs.inc()
        elif self.kv_layout == "paged" and self.decode_burst > 1:
            # One n-token burst program: clamp n to the tightest remaining
            # budget so KV writes never walk past a slot's allocated
            # blocks (admission sized them for prompt + max_new_tokens);
            # EOS overshoot inside the burst is truncated at banking.
            n = self.decode_burst
            if self.adaptive_burst:
                # halve per unit of admission pressure so a burst never
                # walls off the queue; the budget clamp below is unchanged
                pressure = len(self.queue) + len(self._pending_prefill)
                if pressure:
                    n = max(1, n // (1 + pressure))
            for st in self.active.values():
                n = min(n, st.request.max_new_tokens - len(st.tokens))
            n = max(int(n), 1)
            ad_kw = ({} if self.adapters is None else dict(
                adapter_rows=self._adapter_rows,
                adapter_scales=self._adapter_scales))
            burst_out = self.engine.decode_burst(
                tokens, active, temperature, top_p, seeds, steps, n,
                block_tables=self.block_tables, **ad_kw)
            self.decode_dispatches += 1
            self.decode_host_syncs += 1
            self._m_dispatches.inc()
            self._m_host_syncs.inc()
        elif self.kv_layout == "paged":
            ad_kw = ({} if self.adapters is None else dict(
                adapter_rows=self._adapter_rows,
                adapter_scales=self._adapter_scales))
            next_tokens = self.engine.decode_step(
                tokens, active, temperature, top_p, seeds, steps,
                block_tables=self.block_tables, **ad_kw)
            self.decode_dispatches += 1
            self.decode_host_syncs += 1
            self._m_dispatches.inc()
            self._m_host_syncs.inc()
        else:
            next_tokens = self.engine.decode_step(tokens, active, temperature,
                                                  top_p, seeds, steps)
            self.decode_dispatches += 1
            self.decode_host_syncs += 1
            self._m_dispatches.inc()
            self._m_host_syncs.inc()
        step_wall = self.clock() - t0
        self.step_seconds.append(step_wall)
        self._m_decode.observe(step_wall)
        wall = self.step_seconds.total
        if wall > 0:
            self._m_tps.set(self._m_tokens.value / wall)
        self.iterations += 1
        with span("ftl:sched.bank"):
            if self.spec_k:
                if self.spec_tree is not None:
                    self._bank_tree(out, acc, path, tree_shape, done)
                else:
                    self._bank_spec(out, acc, done, k=round_k)
            elif burst_out is not None:
                self._bank_burst(burst_out, done)
            else:
                self._bank_tokens(next_tokens, done)
        return done

    def _bank_tokens(self, next_tokens: np.ndarray,
                     done: List[Completion]) -> None:
        """Bank one plain decode step's (slots,) tokens."""
        for s in list(self.active):
            st = self.active[s]
            tok = int(next_tokens[s])
            st.tokens.append(tok)
            st.steps += 1
            self.decode_tokens += 1
            self._m_tokens.inc()
            self._m_burst_tokens.observe(1)
            self._trace(st.request, "decode_round", tokens=1, mode="token")
            if self.eos_token_id is not None and tok == self.eos_token_id:
                self._finish(s, "eos", done)
            elif len(st.tokens) >= st.request.max_new_tokens:
                self._finish(s, "length", done)

    def _bank_burst(self, out: np.ndarray, done: List[Completion]) -> None:
        """Bank one fused burst's (slots, n) tokens, truncating each slot
        at EOS and at its max_new_tokens budget — discarded overshoot is
        tokens the sequential path would never have produced, so the
        emitted stream stays identical to per-token decode (the same
        truncation contract as ``_bank_spec``; the device's overshoot KV
        is stale pool content past the committed length, masked and
        overwritten by the slot's next occupant)."""
        n = out.shape[1]
        for s in list(self.active):
            st = self.active[s]
            banked = 0
            finished = None
            for i in range(n):
                tok = int(out[s, i])
                st.tokens.append(tok)
                st.steps += 1
                banked += 1
                self._m_tokens.inc()
                if (self.eos_token_id is not None
                        and tok == self.eos_token_id):
                    finished = "eos"
                    break
                if len(st.tokens) >= st.request.max_new_tokens:
                    finished = "length"
                    break
            self.decode_tokens += banked
            self._m_burst_tokens.observe(banked)
            self._trace(st.request, "decode_round", tokens=banked,
                        mode="burst")
            if finished:
                self._finish(s, finished, done)

    def _bank_spec(self, out: np.ndarray, acc: np.ndarray,
                   done: List[Completion], k: Optional[int] = None) -> None:
        """Bank one verify round's output: the accepted draft prefix plus
        the bonus/corrected token at position acc, truncated by EOS and by
        the request's max_new_tokens budget (truncation discards tokens the
        non-spec path would never have produced, keeping the emitted stream
        identical to sequential decoding). ``k`` is the round's actual
        width (adaptive-k may run below spec_k; accounting follows it)."""
        k = self.spec_k if k is None else int(k)
        self.spec_rounds += 1
        n_active = len(self.active)
        self.spec_draft_tokens += k * n_active
        self._m_spec_draft.inc(k * n_active)
        round_accepted = 0
        for s in list(self.active):
            st = self.active[s]
            a = int(acc[s])
            st.steps += 1
            st.spec_proposed += k
            st.spec_accepted += a
            round_accepted += a
            if self.adaptive_k is not None:
                self.adaptive_k.observe(st.request.id, a, k)
            banked = 0
            finished = None
            for i in range(a + 1):
                tok = int(out[s, i])
                st.tokens.append(tok)
                banked += 1
                self._m_tokens.inc()
                if i == a:
                    # position acc is the verifier's own token (bonus on
                    # full accept, correction otherwise) — emitted without
                    # ever having been proposed by the draft.
                    st.spec_corrected += 1
                if self.eos_token_id is not None and tok == self.eos_token_id:
                    finished = "eos"
                    break
                if len(st.tokens) >= st.request.max_new_tokens:
                    finished = "length"
                    break
            self.decode_tokens += banked
            self._m_spec_round_tokens.observe(banked)
            self._m_burst_tokens.observe(banked)
            self._trace(st.request, "decode_round", tokens=banked,
                        mode="spec", accepted=a)
            if finished:
                self._finish(s, finished, done)
        self.spec_accepted_tokens += round_accepted
        self._m_spec_accepted.inc(round_accepted)
        if self.spec_draft_tokens:
            self._m_spec_rate.set(
                self.spec_accepted_tokens / self.spec_draft_tokens)

    def _bank_tree(self, out: np.ndarray, acc: np.ndarray, path: np.ndarray,
                   shape, done: List[Completion]) -> None:
        """Bank one TREE round under ``_bank_spec``'s truncation contract.
        The round proposed ``sum(fanouts)`` draft tokens (the tree minus
        its root) and scored ``shape.size`` nodes in one verify dispatch;
        ``path[s, :acc[s]]`` names the accepted nodes' tree rows, which is
        what attributes acceptance to branches — a row off
        ``shape.primary_rows`` is a token linear speculation would have
        thrown away with the rejected suffix. The banked tokens become the
        slot's refeed window for the next round."""
        budget = shape.size - 1
        self.spec_rounds += 1
        self.spec_tree_rounds += 1
        n_active = len(self.active)
        self.spec_draft_tokens += budget * n_active
        self._m_spec_draft.inc(budget * n_active)
        self.spec_tree_nodes += shape.size * n_active
        self._m_tree_nodes.inc(shape.size * n_active)
        primary = shape.primary_rows
        round_accepted = 0
        for s in list(self.active):
            st = self.active[s]
            a = int(acc[s])
            st.steps += 1
            st.spec_proposed += budget
            st.spec_accepted += a
            round_accepted += a
            self._m_tree_path_len.observe(a)
            self.spec_tree_accepted += a
            self.spec_tree_off_primary += sum(
                1 for j in range(a) if int(path[s, j]) != primary[j])
            if self.adaptive_k is not None:
                self.adaptive_k.observe(st.request.id, a, shape.depth)
            banked = 0
            finished = None
            emitted: List[int] = []
            for i in range(a + 1):
                tok = int(out[s, i])
                st.tokens.append(tok)
                emitted.append(tok)
                banked += 1
                self._m_tokens.inc()
                if i == a:
                    # the verifier's own token (bonus or correction) —
                    # emitted without ever having been proposed
                    st.spec_corrected += 1
                if self.eos_token_id is not None and tok == self.eos_token_id:
                    finished = "eos"
                    break
                if len(st.tokens) >= st.request.max_new_tokens:
                    finished = "length"
                    break
            st.emitted = emitted
            self.decode_tokens += banked
            self._m_spec_round_tokens.observe(banked)
            self._m_burst_tokens.observe(banked)
            self._trace(st.request, "decode_round", tokens=banked,
                        mode="tree", accepted=a)
            if finished:
                self._finish(s, finished, done)
        self.spec_accepted_tokens += round_accepted
        self._m_spec_accepted.inc(round_accepted)
        if self.spec_draft_tokens:
            self._m_spec_rate.set(
                self.spec_accepted_tokens / self.spec_draft_tokens)
        if self.spec_tree_accepted:
            self._m_tree_branch_util.set(
                self.spec_tree_off_primary / self.spec_tree_accepted)

    def run(self, stop: Optional[Callable[[], bool]] = None
            ) -> List[Completion]:
        """Drive until idle; ``stop()`` returning True switches to drain
        mode (finish active, leave the queue). Returns all completions."""
        if stop is not None and self.stop_check is None:
            self.stop_check = stop  # also probed between prefill chunks
        while self.pending():
            if stop is not None and self.admission_open and stop():
                self.stop_admission()
            self.step()
        # drain/idle contract: every block is free or cache-held — a leak
        # here is a refcount bug, turned into a hard failure (tests drive
        # run(); serve.py audits non-strict to keep its exit-0 contract)
        self.audit_block_leaks(strict=True)
        return self.completed

    def audit_block_leaks(self, strict: bool = True) -> List[str]:
        """Allocator leak guard for the drained/idle state (no active
        slots): every block in EITHER pool must be either free or held
        solely by its pool's prefix cache (exactly one reference — the
        draft pool runs the mirror cache, module docstring). Violations
        are audited ONCE (``[KV LEAK]``) through the flight recorder and,
        in strict mode, raised. Returns the violation descriptions."""
        if self.kv_layout != "paged" or self.active or self._pending_prefill:
            return []
        leaks: List[str] = []
        cached = (self.prefix_cache.cached_blocks
                  if self.prefix_cache is not None else 0)
        extra = self.allocator.used_count - cached
        if extra != 0 or self.allocator.shared_count or self._slot_blocks:
            leaks.append(AUDIT_KV_LEAK_FMT.format(
                pool="target", leaked=extra,
                used=self.allocator.used_count, cached=cached))
        if self.spec_k:
            dcached = (self.draft_prefix_cache.cached_blocks
                       if self.draft_prefix_cache is not None else 0)
            dextra = self.draft_allocator.used_count - dcached
            if (dextra != 0 or self.draft_allocator.shared_count
                    or self._slot_draft_blocks):
                leaks.append(AUDIT_KV_LEAK_FMT.format(
                    pool="draft", leaked=dextra,
                    used=self.draft_allocator.used_count, cached=dcached))
        if self.adapters is not None:
            # adapter-pool half of the guard: with no active slots every
            # allocated adapter page belongs to a resident (or stale
            # in-swap) record holding exactly its base reference — any
            # surplus is a slot pin that never released
            aused = self.adapters.allocator.used_count
            aresident = self.adapters.resident_pages()
            if (aused != aresident or self.adapters.allocator.shared_count
                    or self._slot_adapter):
                leaks.append(AUDIT_KV_LEAK_FMT.format(
                    pool="adapter", leaked=aused - aresident,
                    used=aused, cached=aresident))
        if self.enable_spill and self._spill_root is not None:
            # cross-tier half of the guard: every parked request must have
            # an intact artifact (manifest present), and every artifact
            # directory in the tier must belong to a parked request —
            # device pool + spill tier + cache-held = accounted
            tracked = {sp.artifact_dir for sp in self._spilled.values()}
            missing = [d for d in sorted(tracked) if not os.path.isfile(
                os.path.join(d, BLOCK_MANIFEST_NAME))]
            try:
                on_disk = {os.path.join(self._spill_root, name)
                           for name in os.listdir(self._spill_root)
                           if os.path.isdir(
                               os.path.join(self._spill_root, name))}
            except OSError:
                on_disk = set()
            orphans = sorted(on_disk - tracked)
            if missing or orphans:
                leaks.append(AUDIT_KV_LEAK_FMT.format(
                    pool="spill", leaked=len(missing) + len(orphans),
                    used=len(self._spilled), cached=0))
        if leaks and not self._leak_audited:
            self._leak_audited = True
            for text in leaks:
                events.emit_audit(logger, text, "kv_leak")
        if leaks and strict:
            raise RuntimeError("KV block leak after drain: "
                               + "; ".join(leaks))
        return leaks

    # --- aggregate metrics -------------------------------------------------

    def metrics(self) -> dict:
        lat = np.asarray(self.step_seconds or [0.0])
        generated = sum(len(c.tokens) for c in self.completed) + sum(
            len(st.tokens) for st in self.active.values())
        wall = self.step_seconds.total
        tps = generated / wall if wall > 0 else 0.0
        self._m_tps.set(tps)
        out = {
            "iterations": self.iterations,
            "requests_completed": len(self.completed),
            "tokens_generated": int(generated),
            "max_concurrent": self.max_concurrent,
            "decode_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "decode_p95_ms": float(np.percentile(lat, 95) * 1e3),
            "tokens_per_sec": tps,
            "tokens_per_sec_per_slot": tps / max(self.engine.slots, 1),
            "prefill_chunks": self.prefill_chunks,
            "prefill_seconds": self.prefill_seconds,
            "prefill_batch": self.prefill_batch,
            "prefill_packed_rounds": self.prefill_packed_rounds,
            "prefill_packed_rows": self.prefill_packed_rows,
            "prefill_packed_occupancy": (
                self.prefill_packed_rows
                / (self.prefill_packed_rounds * self.prefill_batch)
                if self.prefill_packed_rounds else 0.0),
            "prefill_inplace_chunks": self.prefill_inplace_chunks,
            "prefill_gather_chunks": self.prefill_gather_chunks,
            "decode_burst": self.decode_burst,
            "adaptive_burst": self.adaptive_burst,
            "decode_dispatches": self.decode_dispatches,
            "decode_host_syncs": self.decode_host_syncs,
            "decode_tokens": self.decode_tokens,
            "dispatches_per_token": (
                self.decode_dispatches / self.decode_tokens
                if self.decode_tokens else 0.0),
            "host_syncs_per_token": (
                self.decode_host_syncs / self.decode_tokens
                if self.decode_tokens else 0.0),
        }
        ttfts = [c.ttft_seconds for c in self.completed]
        tpots = [c.tpot_seconds for c in self.completed
                 if len(c.tokens) > 1]
        for name, vals in (("ttft", ttfts), ("tpot", tpots)):
            arr = np.asarray(vals or [0.0])
            for q in (50, 95, 99):
                out[f"{name}_p{q}_ms"] = float(
                    np.percentile(arr, q) * 1e3)
        out["engine_role"] = self.role
        if self.role != "both" or self.ship_exports or self.ship_imports \
                or self.ship_rejects:
            out["ship_exports"] = self.ship_exports
            out["ship_imports"] = self.ship_imports
            out["ship_rejects"] = self.ship_rejects
        if self.kv_store is not None or self.store_publishes \
                or self.store_fetches or self.store_rejects:
            out["kv_store_publishes"] = self.store_publishes
            out["kv_store_fetches"] = self.store_fetches
            out["kv_store_fetch_blocks"] = self.store_fetch_blocks
            out["kv_store_rejects"] = self.store_rejects
            out["kv_store_partial_hits"] = self.store_partial_hits
            out["kv_store_publish_skipped"] = self.store_publish_skipped
        out["kv_transport_lane"] = self.transport.name
        out["kv_transport_bytes"] = dict(self.transport.lane_bytes)
        out["kv_transport_land_seconds"] = dict(
            self.transport.land_seconds)
        if self.transport.name == "mem" or self.lane_fallbacks:
            out["kv_transport_mem_imports"] = self.mem_lane_imports
            out["kv_transport_lane_fallbacks"] = self.lane_fallbacks
        if self.pacing is not None or self.prefill_paced:
            out["prefill_paced"] = self.prefill_paced
        if self.adapters is not None:
            ast = self.adapters.stats()
            out["adapters_served"] = ast["served"]
            out["adapters_resident"] = list(ast["resident"])
            out["adapter_pages_resident"] = ast["resident_pages"]
            out["adapter_pages_resident_bytes"] = ast["resident_bytes"]
            out["adapter_pageins"] = ast["pageins"]
            out["adapter_evictions"] = ast["evictions"]
            out["adapter_pool_pages_free"] = ast["free_pages"]
            out["adapter_stale_versions"] = ast["stale_versions"]
            out["adapter_waits"] = self.adapter_waits
            out["adapter_rejects"] = self.adapter_rejects
        if self.kv_layout == "paged":
            out["kv_blocks_total"] = self.allocator.capacity
            out["kv_blocks_free"] = self.allocator.free_count
            out["kv_block_utilization_peak"] = self.max_block_utilization
            # storage-dtype surface (--kv-dtype): what a block costs in
            # the selected layout — the bench's blocks-per-byte-budget
            # numbers read straight off these
            out["kv_dtype"] = getattr(self.engine, "kv_dtype", "bf16")
            cache = getattr(self.engine, "cache", None)
            out["kv_bytes_per_block"] = (
                block_bytes(cache) if cache is not None else 0)
            if self.prefix_cache is not None:
                pc = self.prefix_cache
                out["prefix_lookups"] = pc.lookups
                out["prefix_hits"] = pc.hits
                out["prefix_hit_tokens"] = pc.hit_tokens
                out["prefix_hit_rate"] = pc.hit_rate
                out["prefix_cached_blocks"] = pc.cached_blocks
                out["prefix_evictions"] = pc.evictions
                out["prefix_cow_copies"] = pc.cow_copies
                out["kv_blocks_shared"] = self.allocator.shared_count
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["spec_rounds"] = self.spec_rounds
            out["spec_draft_tokens"] = self.spec_draft_tokens
            out["spec_accepted_tokens"] = self.spec_accepted_tokens
            out["spec_acceptance_rate"] = (
                self.spec_accepted_tokens / self.spec_draft_tokens
                if self.spec_draft_tokens else 0.0)
            out["draft_kv_blocks_total"] = self.draft_allocator.capacity
            out["draft_kv_blocks_free"] = self.draft_allocator.free_count
            if self.draft_prefix_cache is not None:
                dpc = self.draft_prefix_cache
                out["draft_prefix_hits"] = dpc.hits
                out["draft_prefix_hit_tokens"] = dpc.hit_tokens
                out["draft_prefix_hit_rate"] = dpc.hit_rate
                out["draft_prefix_cached_blocks"] = dpc.cached_blocks
            if self.spec_tree is not None:
                out["spec_tree"] = ",".join(
                    str(f) for f in self.spec_tree.fanouts)
                out["spec_tree_rounds"] = self.spec_tree_rounds
                out["spec_tree_nodes"] = self.spec_tree_nodes
                out["spec_tree_accepted_off_primary"] = (
                    self.spec_tree_off_primary)
                out["spec_tree_branch_utilization"] = (
                    self.spec_tree_off_primary / self.spec_tree_accepted
                    if self.spec_tree_accepted else 0.0)
                out["spec_accepted_per_round"] = (
                    self.spec_accepted_tokens / self.spec_rounds
                    if self.spec_rounds else 0.0)
        return out
