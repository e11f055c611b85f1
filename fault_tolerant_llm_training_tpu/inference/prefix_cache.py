"""Content-addressed prefix cache over the paged KV block pool.

Production traffic is dominated by shared prompt prefixes (system prompts,
few-shot templates, multi-turn history). The paged layout already stores
KV in global pool blocks addressed through per-slot tables
(inference/kv_cache.py) — exactly the substrate vLLM's PagedAttention
assumed and SGLang's RadixAttention built on: if two token streams share
their first k*block_size tokens, their first k blocks hold the KV of the
same tokens at the same positions, so the second request can point its
table at the FIRST request's blocks and skip the prefill compute for them
entirely.

**What is cached, and when.** The whole blocks of a prompt, at admission,
once its prefill has written them; and the whole blocks of what a request's
slot WROTE — its prompt and every generated token but the last (sampled,
never fed) — when the request ends normally (``Scheduler._finish``, reason
``length`` / ``eos``), so a session's next turn hits up to the last whole
block of its history and not only up to the last prompt. Blocks cached at
admission hold rows a prefill program wrote: two prompts that share them
get bitwise the rows an uncached prefill of either writes (same programs,
same shapes, same inputs). Blocks cached at a finish also hold rows the
DECODE program wrote, one token a round: the same model on the same
tokens, equal to a prefill's rows to the rounding of the compute dtype and
not bitwise. A stream served over them is the stream a re-prefill would
serve up to that rounding (a near-tie of two logits may fall the other
way); nothing else about a hit changes.

**Keying.** Each fully-committed (block-aligned) block is keyed by a
chain hash ``h_i = sha256(h_{i-1} || tokens of block i)`` — the key of
block i commits the entire token prefix up to and including it, so a flat
``dict`` keyed by chain hash IS a radix tree over token-block paths
(parent = the i-1 prefix, children = every cached one-block extension).
Partial trailing blocks are never cached: a block's bytes are only
reusable once every position in it is committed content.

**Ownership protocol** (the part that must survive drain/eviction/chaos):
the allocator's per-block refcount is the single source of truth.

- The cache holds exactly ONE reference per cached node (taken at
  ``insert`` — at admission or at a normal finish, BEFORE the slot's own
  ``free`` — and dropped at ``evict``/``flush``).
- Every slot whose table row contains the block holds one reference:
  fresh blocks are born at refcount 1 by ``alloc``; cache-hit blocks are
  increfed by ``acquire`` at admission. A slot's blocks are released by
  the scheduler's ONE uniform ``allocator.free(slot_blocks)`` at finish /
  drain-rollback — hit or miss, COW or not, every block is freed exactly
  once per holder, and the pool's double-free guard stays load-bearing.
- Eviction (LRU, childless nodes first) only considers nodes whose block
  has refcount 1 — i.e. held by the cache alone. Evicting a node whose
  prefix a live slot still reads would free nothing anyway (the slot's
  reference keeps the block allocated); restricting candidates keeps
  eviction an actual release valve under pool pressure.

The cache itself never touches the device: hits are served by table
indices, and the one device operation sharing requires — copy-on-write
when prefill must resume INSIDE a shared block — lives in the engine
(``InferenceEngine.cow_copy`` over ``kv_cache.copy_kv_block``).
"""

import dataclasses
import hashlib
from typing import Dict, List, Optional, Sequence

import numpy as np


def chain_hashes(prompt: Sequence[int], block_size: int) -> List[bytes]:
    """Chain hash per fully-committed prompt block: ``h_i = sha256(h_{i-1}
    || block_i token bytes)`` (int32 little-endian), ``h_{-1} = b""``. The
    trailing partial block (if any) contributes nothing — only bit-reusable
    block contents get keys."""
    ids = np.asarray(prompt, np.int32).reshape(-1)
    out: List[bytes] = []
    h = b""
    for i in range(ids.size // block_size):
        h = hashlib.sha256(
            h + ids[i * block_size:(i + 1) * block_size].tobytes()).digest()
        out.append(h)
    return out


@dataclasses.dataclass
class _Node:
    block: int                 # pool block holding this prefix block's KV
    parent: Optional[bytes]    # chain hash of the one-shorter prefix
    children: int = 0          # cached one-block extensions
    tick: int = 0              # LRU clock (match/insert touch)


@dataclasses.dataclass
class PrefixHit:
    """One admission's lookup result: the longest cached chain-hash walk.

    ``tokens`` is the prompt length the hit covers (``len(blocks) *
    block_size``); ``full`` means the hit covers the ENTIRE prompt — the
    admission still needs the LAST prompt position's logits to sample the
    first token, so prefill resumes at ``prompt_len - 1``, which writes
    inside the final shared block and therefore triggers copy-on-write."""

    keys: List[bytes]
    blocks: List[int]
    tokens: int
    full: bool

    @property
    def depth(self) -> int:
        """Hit depth in whole blocks — the unit the fleet store and the
        router's cache-affinity key compare prefixes in."""
        return len(self.blocks)


class PrefixCache:
    """Host-side radix tree of committed token blocks, refcounted through
    the scheduler's :class:`~.scheduler.BlockAllocator` (see module
    docstring for the ownership protocol)."""

    def __init__(self, allocator, block_size: int, evictions_counter=None):
        self.allocator = allocator
        self.block_size = block_size
        self._nodes: Dict[bytes, _Node] = {}
        self._tick = 0
        # admission accounting (kv_prefix_hit_rate is hit_tokens over
        # prompt_tokens: the fraction of admitted prompt positions whose
        # prefill compute the cache absorbed)
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        self.prompt_tokens = 0
        self.evictions = 0
        self.cow_copies = 0
        self._m_evictions = evictions_counter

    @property
    def cached_blocks(self) -> int:
        return len(self._nodes)

    @property
    def hit_rate(self) -> float:
        return (self.hit_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0)

    # --- admission-side API (scheduler._admit) -----------------------------

    def match(self, prompt: Sequence[int]) -> PrefixHit:
        """Longest cached prefix of ``prompt``, in whole blocks. Touches the
        LRU tick of every node on the walk but takes NO references —
        ``acquire`` the hit before anything (eviction included) can run."""
        self._tick += 1
        keys: List[bytes] = []
        blocks: List[int] = []
        for key in chain_hashes(prompt, self.block_size):
            node = self._nodes.get(key)
            if node is None:
                break
            node.tick = self._tick
            keys.append(key)
            blocks.append(node.block)
        tokens = len(blocks) * self.block_size
        return PrefixHit(keys=keys, blocks=blocks, tokens=tokens,
                         full=tokens == len(prompt) and tokens > 0)

    def acquire(self, hit: PrefixHit) -> None:
        """Take the admitted slot's reference on every hit block — BEFORE
        any fresh allocation or eviction, so pool-pressure eviction can
        never free the prefix the slot is about to reuse."""
        self.allocator.incref(hit.blocks)

    def insert(self, prompt: Sequence[int], slot_blocks: Sequence[int],
               keys: Optional[List[bytes]] = None) -> int:
        """Cache the fully-committed blocks of ``prompt`` — a
        just-prefilled prompt, or everything a finished request's slot
        wrote: ``slot_blocks[i]`` holds block i's KV. Already-cached keys are
        skipped (their canonical block stays; a COW'd private copy is never
        re-inserted over it). Each NEW node takes the cache's own allocator
        reference. ``keys`` are the prompt's :func:`chain_hashes`, for a
        caller that already has them. Returns the number of nodes added."""
        added = 0
        parent: Optional[bytes] = None
        self._tick += 1
        if keys is None:
            keys = chain_hashes(prompt, self.block_size)
        for i, key in enumerate(keys):
            node = self._nodes.get(key)
            if node is None:
                block = int(slot_blocks[i])
                self.allocator.incref([block])
                self._nodes[key] = _Node(block=block, parent=parent,
                                         tick=self._tick)
                if parent is not None:
                    self._nodes[parent].children += 1
                added += 1
            else:
                node.tick = self._tick
            parent = key
        return added

    def note_admission(self, skipped_tokens: int, prompt_tokens: int) -> None:
        self.lookups += 1
        self.hits += 1 if skipped_tokens else 0
        self.hit_tokens += skipped_tokens
        self.prompt_tokens += prompt_tokens

    # --- release valve -----------------------------------------------------

    def evict(self, need: int) -> int:
        """Free up to ``need`` blocks by dropping LRU cached prefixes no
        live slot references (allocator refcount 1 == the cache's own).
        Childless nodes only — dropping a leaf may expose its parent as the
        next candidate, so long-dead chains unwind leaf-first. Returns the
        number of blocks actually freed (0 = everything cached is in use)."""
        freed = 0
        while freed < need:
            cands = [(node.tick, key) for key, node in self._nodes.items()
                     if node.children == 0
                     and self.allocator.refcount(node.block) == 1]
            if not cands:
                break
            _, key = min(cands)
            self._drop(key)
            freed += 1
            self.evictions += 1
            if self._m_evictions is not None:
                self._m_evictions.inc()
        return freed

    def flush(self) -> int:
        """Drop every cached prefix (cache references released; blocks a
        live slot still reads stay allocated until that slot finishes).
        Returns the number of nodes dropped. Not counted as eviction —
        this is the explicit reset used by tests and engine resets."""
        n = len(self._nodes)
        for node in self._nodes.values():
            self.allocator.free([node.block])
        self._nodes.clear()
        return n

    def _drop(self, key: bytes) -> None:
        node = self._nodes.pop(key)
        if node.parent is not None and node.parent in self._nodes:
            self._nodes[node.parent].children -= 1
        self.allocator.free([node.block])
