"""Fleet serving host: one member of a multi-host serving fleet.

``python -m fault_tolerant_llm_training_tpu.inference.fleet`` runs ONE
engine+scheduler process that (a) registers a heartbeat lease with
capacity metadata in the shared KV store (ft/lease.py) and renews it
every loop iteration, (b) tails the router's journal file
(inference/journal.py) for ``assign``/``migrate`` records addressed to
it and submits them to the continuous-batching scheduler, and (c)
journals its own ``progress`` records (the FULL committed token list) at
every decode-round boundary plus a ``done`` record per completion — the
replayable trail the router migrates from when this host dies.

Migrated requests arrive with a non-empty ``committed`` baseline: the
scheduler replays ``prompt + committed[:-1]`` as the prefill (cheap under
the prefix cache), seeds the slot with the committed stream, and the
``fold_in(seed, step)`` PRNG makes the continuation bit-identical to the
stream the dead host would have produced (scheduler.py `_Slot`).

Death and fencing (the split-brain contract, ft/lease.py docstring):

- A SIGKILL (chaos ``host_kill``) leaves no handler, no drain — the
  lease simply stops renewing, the router's sweep renders the dead
  verdict and tombstones BEFORE migrating.
- The host self-fences when it cannot prove its own lease live
  (tombstoned, or ttl elapsed since its last successful renewal): it
  exits WITHOUT another journal write, so a zombie that stalled past its
  ttl (chaos ``heartbeat_delay`` > ttl) can never double-commit against
  the migrated replica.
- A signal drain (SIGUSR1/SIGTERM) finishes in-flight requests, then
  persists anything still queued as ``requeue`` records and runs the
  KV-block leak guard — the campaign pins "Fleet drain leak guard:
  clean" on every survivor.

With ``--handoff`` a signal drain SHIPS its in-flight requests instead of
finishing them: each active slot's committed KV blocks are exported as a
checksummed artifact next to the journal (scheduler ``export_handoff``), a
``handoff`` journal record points at it, and the request is requeued with
its committed baseline. The router then migrates by block import on the
survivor when the artifact CRC-verifies, and by the ordinary
committed-prefix replay when it is missing, torn, or rejected — a SIGKILL
leaves no artifact and naturally takes the replay path, so the handoff
fast path adds no new way to lose a request.

``--role prefill|decode`` splits the host into one side of the
disaggregated pipeline (DistServe/Splitwise over the artifact path): a
prefill host admits ``assign``/``migrate`` records, exports each committed
chunk's blocks as an incremental shipment (``ship`` journal records, chaos
``ship_corrupt`` keyed by export ordinal) and journals ``prefill_done``; a
decode host admits the router's ``decode`` records, imports the verified
shipments into its own pool (prefix-cache-deduped) and decodes bit-exactly
from the committed offset. The role and the pool's kv-dtype ride in the
heartbeat lease, so the router places by role and rejects mixed-dtype
prefill->decode pairs at placement time. Death of either side is the
ordinary fence/migrate machinery; a rejected or stale shipment degrades to
the committed-prefix replay on whatever host holds the request.
"""

import argparse
import json
import os
import sys
import threading
import time

from ..chaos import FLEET_FAULTS, ChaosInjector, parse_schedule
from ..data.tokenizer import load_tokenizer
from ..ft.lease import FileKVStore, LeaseRegistry
from ..ft.retry import RetryDeadlineExceeded, retry_with_backoff
from ..ft.signals import SignalFlag
from ..models.configs import get_config
from ..obs import events, reqtrace
from ..obs.prometheus import MetricsServer
from ..obs.registry import REGISTRY
from ..utils.logging import (
    AUDIT_ADAPTER_SUMMARY_FMT,
    AUDIT_FLEET_JOIN_FMT,
    AUDIT_FLEET_LEAVE_FMT,
    AUDIT_KV_QUANT_FMT,
    AUDIT_KV_XPORT_FMT,
    AUDIT_KV_STORE_FMT,
    AUDIT_LATENCY_FMT,
    AUDIT_REQUEST_DONE_FMT,
    AUDIT_SERVE_DRAINING_FMT,
    AUDIT_SERVE_READY_FMT,
    init_logger,
    logger,
)
from .engine import (
    InferenceEngine,
    enable_compilation_cache,
)
from .journal import RequestJournal, persist_unserved
from .kv_cache import bf16_block_bytes, block_bytes
from .kvstore import BlockStore, run_sweeper
from .scheduler import Request, Scheduler
from .transport import make_transport, resolve_lane

ROUTER_JOURNAL = "router.jsonl"

_M_ENGINE_ROLE = REGISTRY.gauge(
    "engine_role",
    "Disaggregated serving role as an info label "
    "(engine_role{engine_role=...} 1)")
_M_KV_TRANSPORT = REGISTRY.gauge(
    "kv_transport_lane",
    "Resolved KV transport lane as an info label "
    "(kv_transport_lane{lane=...} 1): the lane this process exports "
    "block trains on after same-pod auto-detect")


class _AssignmentFollower:
    """Tail ``router.jsonl`` for assign/migrate records addressed to this
    host. Byte-offset tracking, complete (newline-terminated) lines only —
    the same torn-read discipline as serve.py's request follower."""

    def __init__(self, journal_dir: str, host_id: str,
                 read_deadline: float = 0.5):
        self.path = os.path.join(journal_dir, ROUTER_JOURNAL)
        self.host_id = host_id
        self.offset = 0
        self.read_deadline = read_deadline

    def _read_tail(self) -> bytes:
        with open(self.path, "rb") as fh:
            fh.seek(self.offset)
            return fh.read()

    def poll(self):
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return []  # router not started yet — normal, don't retry
        if size <= self.offset:
            return []
        try:
            # the file exists and has new bytes: a read failure here is
            # transient (ft/retry.py backoff), not a missing journal
            data = retry_with_backoff(self._read_tail,
                                      deadline_seconds=self.read_deadline,
                                      retry_on=(OSError,),
                                      what="router journal read")
        except RetryDeadlineExceeded:
            return []  # next poll re-reads from the same offset
        end = data.rfind(b"\n")
        if end < 0:
            return []
        chunk = data[:end + 1]
        self.offset += len(chunk)
        out = []
        for line in chunk.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if (rec.get("kind") in ("assign", "migrate", "decode")
                    and rec.get("host") == self.host_id):
                out.append(rec)
        return out


def get_fleet_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="fault_tolerant_llm_training_tpu.inference.fleet",
        description="One serving-fleet host: heartbeat lease + journal-"
                    "driven request intake with migration replay.")
    p.add_argument("--host-id", required=True,
                   help="this host's fleet identity (lease + journal key)")
    p.add_argument("--store", required=True,
                   help="shared KV-store directory (leases + tombstones)")
    p.add_argument("--journal-dir", required=True,
                   help="shared request-journal directory")
    p.add_argument("--lease-ttl", type=float, default=2.0,
                   help="heartbeat lease ttl in seconds: miss renewals for "
                        "longer and the router declares this host dead")
    p.add_argument("--kv-deadline", type=float, default=1.0,
                   help="bounded retry deadline per KV-store operation")
    p.add_argument("--checkpoint-path", required=True)
    p.add_argument("--checkpoint-job-id", required=True)
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--model", default="tiny")
    p.add_argument("--vocab-size", type=int, default=0)
    p.add_argument("--tokenizer-name-or-path", default="byte")
    p.add_argument("--layer-impl", default="loop", choices=("loop", "scan"))
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--max-len", type=int, default=0)
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated AOT prefill lengths (default: "
                        "power-of-two ladder); the largest bucket is the "
                        "prefill CHUNK size, so a prefill-role host ships "
                        "one incremental block artifact per largest-"
                        "bucket's worth of committed prompt")
    p.add_argument("--kv-block-size", type=int, default=16)
    p.add_argument("--kv-num-blocks", type=int, default=0)
    p.add_argument("--kv-dtype", default="bf16",
                   choices=("bf16", "int8"),
                   help="paged KV pool storage dtype (serve.py "
                        "--kv-dtype): int8 stores blocks quantized with "
                        "per-(block, kv-head) scales, ~2x blocks at the "
                        "same HBM. Handoff/spill artifacts carry the "
                        "scales inside the CRC'd payload, so migration "
                        "stays bit-exact within the dtype — but every "
                        "fleet host must run the SAME kv-dtype: an "
                        "artifact exported under one dtype is geometry-"
                        "rejected by the other and the migration falls "
                        "back to the committed-prefix replay")
    p.add_argument("--paged-kernel", default="auto",
                   choices=("auto", "gather", "pallas"))
    p.add_argument("--adapter-rank", type=int, default=0,
                   help="multi-tenant LoRA serving rank (serve.py "
                        "--adapter-rank); 0 = off. Every fleet host must "
                        "run the same rank or migrated adapter streams "
                        "land on a host that cannot serve them")
    p.add_argument("--adapter-pages", type=int, default=0,
                   help="adapter page pool size incl. the null page "
                        "(serve.py --adapter-pages); 0 = room for 4")
    p.add_argument("--adapter", action="append", default=[],
                   metavar="NAME=DIR", dest="adapters",
                   help="register a published adapter artifact at startup "
                        "(repeatable, serve.py --adapter)")
    p.add_argument("--compile-cache-dir", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-eos", action="store_true")
    p.add_argument("--log-frequency", type=int, default=8)
    p.add_argument("--poll-seconds", type=float, default=0.05,
                   help="idle sleep between loop iterations with no work")
    p.add_argument("--max-run-seconds", type=float, default=0.0,
                   help="safety timeout: drain and exit after this long "
                        "(0 = run until signaled)")
    p.add_argument("--metrics-port", type=int, default=0)
    p.add_argument("--event-log", default="")
    p.add_argument("--trace-log", default="",
                   help="request-span trail (obs/reqtrace.py); defaults "
                        "to trace_<name>.jsonl next to --event-log")
    p.add_argument("--chaos", default="",
                   help="fault schedule: host_kill / sigusr1 / sigterm "
                        "keyed by decode iteration (serve.py convention); "
                        "heartbeat_delay keyed by fleet loop iteration; "
                        "handoff_corrupt / spill_corrupt / ship_corrupt / "
                        "store_corrupt keyed by export ordinal; "
                        "prefill_kill keyed by completed-prefill-chunk "
                        "ordinal")
    p.add_argument("--handoff", action="store_true",
                   help="on a signal drain, ship in-flight requests' "
                        "committed KV blocks as checksummed artifacts "
                        "(journal 'handoff' records) instead of finishing "
                        "them; survivors import the blocks, or replay the "
                        "committed prefix if the artifact fails CRC")
    p.add_argument("--spill-dir", default="",
                   help="enable the scheduler's spill tier: on pool "
                        "exhaustion, preempt the coldest request's blocks "
                        "into checksummed artifacts under this directory "
                        "and restore on demand")
    p.add_argument("--kv-store-dir", default="",
                   help="fleet-global KV block store root "
                        "(inference/kvstore.py): publish every finished "
                        "prefill's full-block KV train as a checksummed, "
                        "content-addressed artifact and fetch the deepest "
                        "published prefix before each local prefill; a "
                        "CRC reject or miss degrades to the ordinary "
                        "local chunked prefill")
    p.add_argument("--kv-store-max-bytes", type=int, default=0,
                   help="fleet-store byte budget: > 0 starts the in-"
                        "process sweeper daemon (lease-elected leader "
                        "LRU-evicts down to the budget) AND applies "
                        "publish backpressure — publishers skip store "
                        "publishes (kv_store_publish_skipped_total) "
                        "while resident bytes exceed the budget; 0 = "
                        "unbounded, no sweeper")
    p.add_argument("--kv-store-sweep-interval", type=float, default=2.0,
                   help="seconds between sweeper daemon rounds "
                        "(--kv-store-max-bytes > 0)")
    p.add_argument("--kv-transport", default="fs", choices=("fs", "mem"),
                   help="requested KV block-train transport lane "
                        "(inference/transport.py). Fleet peers are "
                        "separate OS processes with no shared fabric, so "
                        "'mem' auto-detects down to 'fs' here (with a "
                        "log line); the in-process transport drill "
                        "(chaos_campaign 'transport') is where the mem "
                        "lane actually engages")
    p.add_argument("--role", default="both",
                   choices=("both", "prefill", "decode"),
                   help="disaggregated pipeline role: 'prefill' admits "
                        "assign/migrate records, ships each committed "
                        "chunk's KV blocks as CRC'd artifacts and journals "
                        "prefill_done; 'decode' admits the router's "
                        "'decode' records and imports the verified "
                        "shipments before decoding bit-exactly from the "
                        "committed offset; 'both' (default) is the "
                        "colocated host")
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = get_fleet_args(argv)
    init_logger()
    flag = SignalFlag()
    flag.register()
    chaos = None
    if args.chaos:
        chaos = ChaosInjector(
            parse_schedule(args.chaos, allowed=FLEET_FAULTS),
            seed=args.seed)
        logger.info(f"Chaos schedule | {chaos.describe()}")
    if args.event_log:
        events.configure(args.event_log, job=f"fleet_{args.host_id}",
                         host=os.getpid())
    trace_log = args.trace_log or (
        reqtrace.derive_trace_path(args.event_log) if args.event_log
        else "")
    if trace_log:
        reqtrace.configure(trace_log, job=f"fleet_{args.host_id}",
                           host=args.host_id)
    metrics_server = None
    bound_metrics_port = 0
    if args.metrics_port:
        metrics_server = MetricsServer(port=args.metrics_port)
        # the BOUND port (not the requested one: port 0 = ephemeral)
        # rides in the lease value so the federation aggregator can
        # discover scrape targets from the lease sweep alone
        bound_metrics_port = metrics_server.start()
        logger.info(f"Metrics | serving /metrics on port "
                    f"{bound_metrics_port}")

    with flag.deferred():  # block delivery across compile + Orbax restore
        cache_dir = enable_compilation_cache(args.compile_cache_dir)
        if cache_dir:
            logger.info(f"Compilation cache | {cache_dir}")
        tokenizer = load_tokenizer(args.tokenizer_name_or_path)
        vocab = args.vocab_size or tokenizer.vocab_size
        cfg = get_config(args.model, vocab_size=vocab,
                         layer_impl=args.layer_impl)
        buckets = (tuple(int(b) for b in args.prefill_buckets.split(","))
                   if args.prefill_buckets else None)
        engine = InferenceEngine.from_checkpoint(
            args.checkpoint_path, args.checkpoint_job_id, cfg,
            step=args.step, slots=args.slots,
            max_len=args.max_len or None, prefill_buckets=buckets,
            kv_layout="paged",
            kv_block_size=args.kv_block_size,
            kv_num_blocks=args.kv_num_blocks or None,
            paged_kernel=args.paged_kernel,
            kv_dtype=args.kv_dtype,
            adapter_rank=args.adapter_rank,
            adapter_num_pages=args.adapter_pages)
        if args.adapters:
            if not args.adapter_rank:
                raise SystemExit("--adapter requires --adapter-rank")
            for spec in args.adapters:
                name, sep, art_dir = spec.partition("=")
                if not (sep and name and art_dir):
                    raise SystemExit(f"--adapter expects NAME=DIR, "
                                     f"got {spec!r}")
                engine.adapters.register(name, art_dir)
                logger.info("Adapter registered | %s -> %s", name, art_dir)
        events.emit_audit(
            logger, AUDIT_SERVE_READY_FMT.format(
                model=args.model, step=engine.restored_step,
                slots=args.slots),
            "ready", step=engine.restored_step, slots=args.slots,
            model=args.model)
        # Same-pod auto-detect: every consumer of a fleet host's exports
        # (the router, survivors, its decode peer) is ANOTHER OS process,
        # and the mem fabric is process-local — a requested mem lane
        # degrades to fs here, by construction rather than by failure.
        lane = resolve_lane(args.kv_transport, colocated=False)
        if lane != args.kv_transport:
            # auditable, not just a log line: the degradation rides the
            # same [KV XPORT] contract + fallback counter the scheduler's
            # per-shipment mem->fs misses use, so a fleet that silently
            # lost its fast lane shows up in both the audit grep and the
            # /metrics rollup
            events.emit_audit(
                logger, AUDIT_KV_XPORT_FMT.format(
                    action="degrade", lane=lane, id="-", blocks=0,
                    detail=f"requested {args.kv_transport} lane — fleet "
                           f"peers are separate processes with no shared "
                           f"fabric"),
                "kv_xport", action="degrade", lane=lane,
                requested=args.kv_transport)
            REGISTRY.counter(
                "kv_transport_lane_fallbacks_total",
                "Block-train imports that degraded from the mem lane to "
                "the fs artifact (fabric miss or metadata digest "
                "mismatch)").inc()
        transport = make_transport(lane)
        _M_KV_TRANSPORT.labels(lane=lane).set(1)

        def on_ship(req, art_dir, ordinal, seq, start, end, length):
            # Late-bound over `journal`/`gens` (created right below, before
            # the scheduler can run a prefill). Chaos first (ship_corrupt,
            # keyed by export ordinal) so the journal record always names
            # the artifact in its final — possibly poisoned — state.
            if chaos is not None:
                chaos.on_ship(art_dir, ordinal)
            journal.ship(req.id, args.host_id, art_dir, seq, start, end,
                         length, gens.get(req.id, 0),
                         trace_id=req.trace_id, lane=transport.name)

        def pacing():
            # Decode-fleet landing capacity read off the heartbeat
            # leases: free blocks summed over live decode-capable peers.
            # None (= never stall) when no decode peer is visible — a
            # lone prefill host joining first must not deadlock its own
            # admission on a fleet that has not assembled yet.
            peers = [l for h, l in lease.leases().items()
                     if h != args.host_id and l.live
                     and l.role in ("decode", "both")]
            if not peers:
                return None
            return sum(int(l.blocks_free) for l in peers)

        # writer IS the lease host id: the store journal's residency
        # evidence must key by the same names the router's capacity
        # estimates use, or cache-affinity placement never matches
        kv_store = (BlockStore(args.kv_store_dir, writer=args.host_id)
                    if args.kv_store_dir else None)
        sched = Scheduler(engine,
                          eos_token_id=(None if args.no_eos
                                        else tokenizer.eos_token_id),
                          stop_check=lambda: flag.signum is not None,
                          spill_dir=args.spill_dir or None,
                          on_spill=(chaos.on_spill if chaos is not None
                                    else None),
                          role=args.role,
                          ship_dir=(os.path.join(args.journal_dir,
                                                 f"ships_{args.host_id}")
                                    if args.role == "prefill" else None),
                          on_ship=(on_ship if args.role == "prefill"
                                   else None),
                          on_prefill_chunk=(chaos.on_prefill_chunk
                                            if chaos is not None
                                            else None),
                          kv_store=kv_store,
                          on_store_put=(chaos.on_store_put
                                        if chaos is not None else None),
                          transport=transport,
                          pacing=(pacing if args.role == "prefill"
                                  else None),
                          kv_store_max_bytes=args.kv_store_max_bytes)
    _M_ENGINE_ROLE.labels(engine_role=args.role).set(1)

    store = FileKVStore(args.store)
    lease = LeaseRegistry(store, host_id=args.host_id,
                          ttl_seconds=args.lease_ttl,
                          deadline_seconds=args.kv_deadline)
    journal = RequestJournal(args.journal_dir,
                             writer=f"host_{args.host_id}")
    follower = _AssignmentFollower(args.journal_dir, args.host_id)

    def capacity():
        slots_free = max(0, engine.slots - len(sched.active)
                         - len(sched._pending_prefill) - len(sched.queue))
        blocks_free = (sched.allocator.free_count
                       if sched.kv_layout == "paged" else 0)
        return slots_free, blocks_free, getattr(engine, "block_size", 1)

    slots_free, blocks_free, block_size = capacity()
    lease.register(slots_free, blocks_free, block_size,
                   role=args.role, kv_dtype=engine.kv_dtype,
                   metrics_port=bound_metrics_port)
    events.emit_audit(
        logger, AUDIT_FLEET_JOIN_FMT.format(
            host=args.host_id, slots=slots_free, blocks=blocks_free,
            ttl=lease.ttl),
        "fleet_join", host=args.host_id, slots=slots_free,
        blocks=blocks_free, ttl=lease.ttl)
    events.flush()

    # Fleet-store sweeper daemon: a lease-holding background loop — the
    # lexically-lowest LIVE host (kvstore.sweep_leader over the same
    # heartbeat leases the router reads) LRU-evicts unreferenced trains
    # down to the byte budget; every other host's loop stands down, and
    # leadership follows lease liveness when hosts die or fence. The
    # publish side of the same budget is the scheduler's backpressure
    # skip (kv_store_publish_skipped_total).
    sweeper = None
    sweep_stop = threading.Event()
    if kv_store is not None and args.kv_store_max_bytes > 0:
        def _on_evict(evicted):
            for key in evicted:
                events.emit_audit(
                    logger, AUDIT_KV_STORE_FMT.format(
                        action="sweep", key=key[:12], id="-", blocks=0,
                        detail="fleet LRU eviction (over byte budget)"),
                    "kv_store", action="sweep", key=key,
                    host=args.host_id)

        sweeper = threading.Thread(
            target=run_sweeper,
            args=(kv_store, args.kv_store_max_bytes),
            kwargs=dict(interval=args.kv_store_sweep_interval,
                        stop=sweep_stop.is_set,
                        leases=lease.leases, host_id=args.host_id,
                        on_evict=_on_evict),
            daemon=True, name=f"kvstore-sweeper-{args.host_id}")
        sweeper.start()
        logger.info("Fleet store sweeper | budget %d byte(s), interval "
                    "%.1fs, leader by lease election",
                    args.kv_store_max_bytes,
                    args.kv_store_sweep_interval)

    gens = {}     # rid -> generation of my current/last assignment
    done_ids = set()
    n_done = 0    # consumed prefix of sched.completed
    it = 0
    t0 = time.monotonic()
    exit_reason = None  # None = keep serving; else drain with this reason

    def emit_completions():
        nonlocal n_done
        for c in sched.completed[n_done:]:
            gen = gens.get(c.request_id, 0)
            if c.reason == "prefill":
                # dedicated-prefill completion: the committed stream is
                # ONE token (the first), the KV already shipped — journal
                # prefill_done so the router can place the decode half.
                # No decoded-output print: the request is not finished,
                # the decode host owns the final stream.
                journal.prefill_done(c.request_id, args.host_id, c.tokens,
                                     gen, kv_dtype=engine.kv_dtype,
                                     trace_id=c.trace_id)
                done_ids.add(c.request_id)
                events.emit_audit(
                    logger, AUDIT_REQUEST_DONE_FMT.format(
                        id=c.request_id, reason=c.reason,
                        prompt_tokens=c.prompt_len,
                        new_tokens=len(c.tokens),
                        ttft_ms=c.ttft_seconds * 1e3,
                        tps=c.decode_tokens_per_sec),
                    "request_done", id=c.request_id, reason=c.reason,
                    tokens=len(c.tokens), gen=gen, host=args.host_id)
                continue
            journal.done(c.request_id, args.host_id, c.tokens, c.reason,
                         gen=gen, trace_id=c.trace_id)
            done_ids.add(c.request_id)
            decoded = (c.tokens[:-1]
                       if (not args.no_eos and c.reason == "eos")
                       else c.tokens)
            events.emit_audit(
                logger, AUDIT_REQUEST_DONE_FMT.format(
                    id=c.request_id, reason=c.reason,
                    prompt_tokens=c.prompt_len, new_tokens=len(c.tokens),
                    ttft_ms=c.ttft_seconds * 1e3,
                    tps=c.decode_tokens_per_sec),
                "request_done", id=c.request_id, reason=c.reason,
                tokens=len(c.tokens), gen=gen, host=args.host_id)
            logger.info("Request %s output: %r", c.request_id,
                        tokenizer.decode(decoded))
        n_done = len(sched.completed)

    while exit_reason is None:
        it += 1
        if chaos is not None:
            chaos.on_heartbeat(it)  # heartbeat_delay: a slow-but-alive host
        slots_free, blocks_free, block_size = capacity()
        renewed = lease.renew(slots_free, blocks_free, block_size,
                              role=args.role, kv_dtype=engine.kv_dtype,
                              metrics_port=bound_metrics_port)
        if not renewed or lease.fenced():
            # self-fence: this host can no longer prove its lease live —
            # a migrated replica may already be running, so NO further
            # journal writes (split-brain contract, ft/lease.py)
            events.emit_audit(
                logger, AUDIT_FLEET_LEAVE_FMT.format(
                    host=args.host_id, reason="fenced"),
                "fleet_leave", host=args.host_id, reason="fenced")
            events.flush()
            reqtrace.flush()
            if metrics_server is not None:
                metrics_server.stop()
            sys.exit(0)

        for rec in follower.poll():
            rid = str(rec["id"])
            gen = int(rec.get("gen", 0))
            if rid in done_ids or gens.get(rid, -1) >= gen:
                continue  # stale or duplicate assignment
            gens[rid] = gen
            committed = [int(t) for t in rec.get("committed") or []]
            trace_id = str(rec.get("trace_id", "") or "")
            try:
                sched.submit(Request(
                    id=rid,
                    prompt=[int(t) for t in rec.get("prompt", [])],
                    max_new_tokens=int(rec.get("max_new_tokens", 32)),
                    temperature=float(rec.get("temperature", 0.0)),
                    top_p=float(rec.get("top_p", 1.0)),
                    seed=int(rec.get("seed", 0)),
                    committed=tuple(committed),
                    trace_id=trace_id),
                    # router-verified block-shipment artifact (if any):
                    # admission imports the blocks; any failure falls back
                    # to the committed-prefix replay
                    handoff_artifact=str(rec.get("handoff", "") or ""),
                    handoff_gen=gen,
                    # disaggregated intake: a 'decode' record carries the
                    # prefill host's verified shipment list; admission
                    # imports them (prefix-cache-deduped), or replays the
                    # committed prefix when the list is empty/rejected
                    shipments=rec.get("shipments") or None,
                    ship_gen=gen)
            except ValueError as e:
                logger.warning(f"[FLEET] rejecting assignment {rid}: {e}")
                continue
            if trace_id:
                reqtrace.emit(trace_id, rid, "assign", gen=gen,
                              committed=len(committed),
                              kind=str(rec.get("kind", "assign")))

        if flag.signum is not None:
            exit_reason = "drain"
            break
        if args.max_run_seconds and (time.monotonic() - t0
                                     > args.max_run_seconds):
            logger.warning("[FLEET] max-run-seconds reached; draining")
            exit_reason = "timeout"
            break

        if sched.pending():
            if chaos is not None:
                # host_kill lands here, keyed by decode iteration like
                # serve.py's on_serve_step: SIGKILL mid-decode, no
                # handler, no drain — the router's lease sweep takes it
                # from there. Progress through this round is already
                # journaled, so the migration replays a committed prefix.
                chaos.on_fleet_step(sched.iterations)
            sched.step()
            emit_completions()
            # decode-round boundary: journal the FULL committed stream of
            # every active slot — the baseline a migration replays from
            for st in sched.active.values():
                journal.progress(st.request.id, args.host_id, st.tokens,
                                 gen=gens.get(st.request.id, 0),
                                 trace_id=st.request.trace_id)
            if sched.iterations % args.log_frequency == 0:
                logger.info(
                    "Fleet host %s | iter %d | active %d | queued %d | "
                    "done %d", args.host_id, sched.iterations,
                    len(sched.active), len(sched.queue),
                    len(sched.completed))
        else:
            time.sleep(args.poll_seconds)

    # ---- signal / timeout drain: finish in-flight, requeue the rest ----
    events.emit_audit(
        logger, AUDIT_SERVE_DRAINING_FMT.format(
            signum=flag.signum or 0, active=len(sched.active)),
        "drain", phase="begin", signum=flag.signum,
        active=len(sched.active))
    sched.stop_admission()
    if args.handoff and (sched.active or sched._pending_prefill):
        # Block-shipment drain: instead of finishing in-flight requests,
        # export each active slot's committed blocks as a checksummed
        # artifact next to the journal and record a `handoff` pointer.
        # Mid-prefill rows have no committed KV worth shipping — requeue
        # them first, the ordinary way. The artifact is written and
        # fsynced BEFORE its journal record, so a record always names a
        # complete artifact.
        if sched._pending_prefill:
            sched._abort_pending_prefill()
        n_handoff = 0
        for slot in sorted(sched.active):
            st = sched.active[slot]
            rid = st.request.id
            gen = gens.get(rid, 0)
            art = os.path.join(args.journal_dir,
                               f"handoff_{rid}_g{gen}")
            info = sched.export_handoff(slot, art, gen=gen)
            if chaos is not None:
                # handoff_corrupt: seeded byte flip in a payload (the
                # manifest is spared), keyed by export ordinal — the
                # survivor's CRC verify must reject it and replay
                chaos.on_handoff(art, n_handoff)
            journal.handoff(rid, args.host_id, art, info["tokens"],
                            gen=gen, trace_id=st.request.trace_id)
            n_handoff += 1
    else:
        while sched.active or sched._pending_prefill:
            sched.step()
            emit_completions()
            for st in sched.active.values():
                journal.progress(st.request.id, args.host_id, st.tokens,
                                 gen=gens.get(st.request.id, 0),
                                 trace_id=st.request.trace_id)
    emit_completions()
    persist_unserved(journal, sched.unserved(), reason=exit_reason,
                     gens=gens)
    if sched.enable_spill:
        sched.discard_spilled()
    leaks = sched.audit_block_leaks(strict=False)
    if not leaks:
        logger.info("Fleet drain leak guard: clean")
    else:
        logger.warning("Fleet drain leak guard: %d violation(s)",
                       len(leaks))
    # the --kv-dtype receipt, same line serve.py's drain summary emits
    bpb = block_bytes(engine.cache)
    ratio = bf16_block_bytes(engine.cache) / bpb
    events.emit_audit(
        logger, AUDIT_KV_QUANT_FMT.format(
            dtype=engine.kv_dtype, bytes_per_block=bpb, ratio=ratio,
            blocks_total=engine.num_blocks),
        "kv_quant", dtype=engine.kv_dtype, bytes_per_block=bpb,
        ratio=ratio, blocks_total=engine.num_blocks)
    if sched.adapters is not None:
        # multi-tenant adapter receipt, same line serve.py's drain emits
        am = sched.metrics()
        events.emit_audit(
            logger, AUDIT_ADAPTER_SUMMARY_FMT.format(
                served=am["adapters_served"],
                pageins=am["adapter_pageins"],
                evictions=am["adapter_evictions"],
                resident_bytes=am["adapter_pages_resident_bytes"],
                rejects=am["adapter_rejects"]),
            "adapter_summary", served=am["adapters_served"],
            pageins=am["adapter_pageins"],
            evictions=am["adapter_evictions"],
            resident_bytes=am["adapter_pages_resident_bytes"],
            rejects=am["adapter_rejects"])
    # Per-request latency audit: the drain summary every SLO check greps.
    for c in sched.completed:
        events.emit_audit(
            logger, AUDIT_LATENCY_FMT.format(
                id=c.request_id, trace=c.trace_id or "-",
                ttft_ms=c.ttft_seconds * 1e3,
                tpot_ms=c.tpot_seconds * 1e3,
                tokens=len(c.tokens), reason=c.reason),
            "latency", id=c.request_id, trace=c.trace_id,
            ttft=c.ttft_seconds, tpot=c.tpot_seconds,
            tokens=len(c.tokens), reason=c.reason)
    if sweeper is not None:
        # stop the sweep loop BEFORE the lease leaves: a leaving leader
        # must not race its own liveness test mid-round
        sweep_stop.set()
        sweeper.join(timeout=5.0)
    events.emit_audit(
        logger, AUDIT_FLEET_LEAVE_FMT.format(
            host=args.host_id, reason=exit_reason),
        "fleet_leave", host=args.host_id, reason=exit_reason)
    lease.leave()
    events.flush()
    reqtrace.flush()
    if metrics_server is not None:
        metrics_server.stop()
    # exit 0 always — the exit POLICY is in the logs, same contract as
    # serve.py and training
    sys.exit(0)


if __name__ == "__main__":
    main()
