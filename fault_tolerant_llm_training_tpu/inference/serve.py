"""Fault-tolerant serving lifecycle driver.

``python -m fault_tolerant_llm_training_tpu.inference.serve`` restores a
training checkpoint into the inference engine and drives the
continuous-batching scheduler, under the SAME signal discipline as training
(ft/signals.py): the POSIX handler only records SIGUSR1/SIGTERM; the serve
loop checks the flag between decode iterations and switches to drain mode —
admission stops, in-flight requests run to completion, queued requests are
reported unserved — then exits 0 with the ``[EXIT HANDLER]`` audit strings
(utils/logging.py), so the Slurm pre-warning -> drain -> resubmit pattern the
trainer uses for checkpoints applies unchanged to serving. Engine build
(compilation, Orbax restore) runs with signal delivery blocked
(``flag.deferred()``) for the same native-code EINTR reasons as train.py.

``--follow`` turns the one-shot batch driver into the serving half of the
CONTINUOUS DEPLOYMENT LOOP (deploy/): the process stays up after the
initial prompt set, tails ``--request-file`` for new requests (JSONL, one
request per appended line) and polls the trainer's ``published.json``
between decode iterations. Each new publish is verified BEFORE load and
hot-swapped into the running engine without dropping in-flight requests
(deploy/reload.py has the swap state machine); a corrupt publish is
rejected + audited and serving continues on current weights. The drain
lifecycle is unchanged — SIGUSR1/SIGTERM finishes active requests and
exits 0.
"""

import argparse
import json
import os
import sys
import time

from ..chaos import SERVE_FAULTS, ChaosInjector, parse_schedule
from ..checkpoint.manager import update_checkpoint_age_gauge
from ..data.tokenizer import load_tokenizer
from ..deploy.reload import HotReloader, PointerWatcher
from ..ft.signals import SignalFlag
from ..models.configs import get_config
from ..obs import events, reqtrace
from ..obs.prometheus import MetricsServer
from ..obs.registry import REGISTRY
from ..utils.config import JOBID
from ..utils.logging import (
    AUDIT_ADAPTER_SUMMARY_FMT,
    AUDIT_KV_QUANT_FMT,
    AUDIT_LATENCY_FMT,
    AUDIT_REQUEST_DONE_FMT,
    AUDIT_SERVE_COMPLETED,
    AUDIT_SERVE_DRAINED_FMT,
    AUDIT_SERVE_DRAINING_FMT,
    AUDIT_SERVE_PREFILL_FMT,
    AUDIT_SERVE_PREFIX_FMT,
    AUDIT_SERVE_READY_FMT,
    AUDIT_SERVE_START,
    AUDIT_SERVE_STEP_FMT,
    AUDIT_SERVE_TREE_SPEC_FMT,
    init_logger,
    logger,
)
from .engine import (
    InferenceEngine,
    enable_compilation_cache,
    restore_params,
)
from .kv_cache import bf16_block_bytes, block_bytes
from .kvstore import BlockStore
from .sampler import AdaptiveK
from .scheduler import Request, Scheduler
from .transport import make_transport, resolve_lane

_IMPORTS_DONE_T = time.time()  # flight recorder: imports_done

_DEMO_PROMPT = "alpha bravo charlie delta echo"

_M_KV_BYTES_PER_BLOCK = REGISTRY.gauge(
    "kv_bytes_per_block",
    "Bytes one paged KV pool block costs in the selected storage dtype "
    "(every layer's K+V slices; int8 mode includes the scale rows)")
_M_KV_DTYPE = REGISTRY.gauge(
    "kv_dtype",
    "Paged KV pool storage dtype as an info label (kv_dtype{dtype=...} 1)")
_M_ENGINE_ROLE = REGISTRY.gauge(
    "engine_role",
    "Disaggregated serving role as an info label "
    "(engine_role{engine_role=...} 1); serve.py is always the colocated "
    "'both' — dedicated prefill/decode roles are fleet.py --role")
_M_KV_TRANSPORT = REGISTRY.gauge(
    "kv_transport_lane",
    "Resolved KV transport lane as an info label "
    "(kv_transport_lane{lane=...} 1): the lane this process exports "
    "block trains on after same-pod auto-detect")


class _RequestFollower:
    """Tail a JSONL request file (``--follow --request-file``).

    Each line appended by the driver is one request:
    ``{"id": "...", "prompt": "text", "max_new_tokens": 8, ...}`` —
    missing knobs fall back to the serve flags. Only COMPLETE lines
    (newline-terminated) are consumed, tracked by byte offset, so a
    driver caught mid-append never yields a torn request."""

    def __init__(self, path: str, tokenizer, args):
        self.path = path
        self.tokenizer = tokenizer
        self.args = args
        self.offset = 0
        self.count = 0

    def ingest(self, sched: Scheduler) -> int:
        try:
            size = os.path.getsize(self.path)
        except OSError:
            return 0
        if size <= self.offset:
            return 0
        with open(self.path, "rb") as fh:
            fh.seek(self.offset)
            data = fh.read()
        end = data.rfind(b"\n")
        if end < 0:
            return 0
        chunk = data[:end + 1]
        self.offset += len(chunk)
        n = 0
        for line in chunk.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
                prompt = self.tokenizer.encode(str(d["prompt"]))
            except (ValueError, KeyError, TypeError):
                logger.warning(f"[SERVE] skipping malformed request line "
                               f"{line!r}")
                continue
            rid = str(d.get("id", f"file{self.count}"))
            self.count += 1
            # the driver may carry its own trace_id (a router intake that
            # this serve process replays); otherwise mint one here — the
            # span trail starts at whichever process saw the request first
            max_new = int(d.get("max_new_tokens", self.args.max_new_tokens))
            trace_id = (str(d.get("trace_id", "") or "")
                        or reqtrace.mint_trace_id(rid))
            reqtrace.emit(trace_id, rid, "intake",
                          prompt_tokens=len(prompt), max_new_tokens=max_new)
            sched.submit(Request(
                id=rid, prompt=prompt,
                max_new_tokens=max_new,
                temperature=float(d.get("temperature",
                                        self.args.temperature)),
                top_p=float(d.get("top_p", self.args.top_p)),
                seed=int(d.get("seed", self.args.seed + self.count)),
                trace_id=trace_id,
                adapter=str(d.get("adapter", "") or "")))
            n += 1
        return n


def get_serve_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="fault_tolerant_llm_training_tpu.inference.serve",
        description="Serve a training checkpoint with continuous batching "
                    "and signal-drained shutdown.")
    p.add_argument("--checkpoint-path", required=True,
                   help="directory passed to training's --checkpoint-path")
    p.add_argument("--checkpoint-job-id", required=True,
                   help="job id the checkpoint was written under "
                        "(checkpoint_{id}/ subdirectory)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: latest)")
    p.add_argument("--model", default="tiny",
                   help="model preset the checkpoint was trained with "
                        "(models/configs.py PRESETS). The model class is "
                        "found from the preset's type: the Llama-family "
                        "presets build models/llama.py Transformer; "
                        "tiny-latent-moe (latent attention of two widths, "
                        "a top-k indexer, a sliding window, sigmoid-routed "
                        "experts of which the engine is told the held "
                        "range) builds models/latent_moe.py. For that class "
                        "the engine refuses by name --kv-dtype int8, "
                        "--kv-layout ring, --spec-k / --spec-tree, "
                        "--adapter-rank, --prefill-batch > 1, "
                        "--paged-kernel pallas, --decode-burst > 1, the "
                        "spill tier and a multi-device mesh")
    p.add_argument("--vocab-size", type=int, default=0,
                   help="0 = take the tokenizer's vocab (training default)")
    p.add_argument("--tokenizer-name-or-path", default="byte")
    p.add_argument("--layer-impl", default="loop",
                   choices=("loop", "scan"),
                   help="trunk form the checkpoint was trained with "
                        "(scan checkpoints are converted for decoding)")
    p.add_argument("--slots", type=int, default=2,
                   help="concurrent decode slots (continuous batching)")
    p.add_argument("--max-len", type=int, default=0,
                   help="KV cache length per slot; 0 = model seq_len")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated AOT prefill lengths "
                        "(default: power-of-two ladder); with the paged "
                        "layout, longer prompts stream through the largest "
                        "bucket in chunks instead of being rejected")
    p.add_argument("--kv-layout", default="paged",
                   choices=("paged", "ring"),
                   help="KV cache layout: block-paged pool admitted by "
                        "free-block count (default), or the legacy "
                        "max_len-per-slot ring buffers")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="positions per KV block (paged layout)")
    p.add_argument("--kv-dtype", default="bf16",
                   choices=("bf16", "int8"),
                   help="paged KV pool storage dtype: 'bf16' (plain "
                        "pools), or 'int8' — blocks stored quantized "
                        "with per-(block, kv-head) fp32 scales in a "
                        "parallel scale pool, dequantized inside the "
                        "attention kernels (fused into the block DMA "
                        "under --paged-kernel pallas). Roughly halves "
                        "bytes/block, so the same HBM budget holds ~2x "
                        "the blocks (the [KV QUANT] drain line says); "
                        "greedy argmax ties may flip vs bf16 — the "
                        "within-dtype bit-exactness contracts (exact "
                        "spec-verify, burst, spill/handoff) all still "
                        "hold")
    p.add_argument("--kv-num-blocks", type=int, default=0,
                   help="total KV pool blocks incl. the null block; 0 = "
                        "full reservation parity (slots * max_len worth). "
                        "Set LOWER to serve more slots at the same HBM, "
                        "admission queues on block exhaustion")
    p.add_argument("--spill-dir", default="",
                   help="spill tier for the paged KV pool: on block "
                        "exhaustion the scheduler preempts the coldest "
                        "request and parks its private blocks as a "
                        "checksummed host artifact under this directory "
                        "(inference/kv_cache.py), restoring them on demand "
                        "bit-exactly; '' = spill disabled (admission waits "
                        "on exhaustion instead)")
    p.add_argument("--kv-store-dir", default="",
                   help="fleet-global KV block store root "
                        "(inference/kvstore.py): publish finished "
                        "prefills' full-block KV trains as checksummed "
                        "content-addressed artifacts and fetch the "
                        "deepest published prefix before each local "
                        "prefill; '' = store disabled")
    p.add_argument("--kv-store-max-bytes", type=int, default=0,
                   help="store publish byte budget: when the folded "
                        "resident bytes exceed this, publishes are "
                        "skipped (kv_store_publish_skipped_total) until "
                        "a sweep gets back under; 0 = unbounded")
    p.add_argument("--kv-transport", default="fs", choices=("fs", "mem"),
                   help="KV block-train transport lane "
                        "(inference/transport.py): 'fs' moves CRC-"
                        "verified filesystem artifacts (the durable "
                        "form); 'mem' additionally pushes trains device-"
                        "to-device in-process and verifies manifest "
                        "METADATA only, degrading to fs (then committed-"
                        "prefix replay) on any mismatch. serve.py is one "
                        "process, so 'mem' always applies here")
    p.add_argument("--paged-kernel", default="auto",
                   choices=("auto", "gather", "pallas"),
                   help="paged attention kernel (paged layout): 'auto' "
                        "(default) takes one of the two per program — "
                        "in place for one-token queries (decode) on one "
                        "TPU device, the gather for prefill chunks, on "
                        "several devices and off the chip; the start-up "
                        "line 'Paged kernel | ...' prints what it "
                        "resolved to; 'gather' "
                        "assembles each slot's blocks into a contiguous "
                        "view and runs the ring kernel on it — the "
                        "bit-exact reference; 'pallas' reads pool blocks "
                        "in place through the block table "
                        "(ops/paged_attention.py) — no gathered copy, "
                        "equal within fp32 accumulation tolerance")
    p.add_argument("--decode-burst", type=int, default=1,
                   help="tokens per decode dispatch (paged layout): n > 1 "
                        "runs an n-token fused burst program — one "
                        "dispatch + one host sync per n tokens, greedy "
                        "streams bit-identical to per-token decode. "
                        "Admission/EOS eviction and the drain/stop probes "
                        "land at burst boundaries (at most n-1 tokens "
                        "later); mutually exclusive with --spec-k")
    p.add_argument("--adaptive-burst", action="store_true",
                   help="scale the burst width DOWN under queue / pending-"
                        "prefill pressure (halving per waiting unit, floor "
                        "1) so long bursts never starve admission; the "
                        "existing per-slot budget clamp is unchanged. "
                        "Requires --decode-burst > 1")
    p.add_argument("--prefill-batch", type=int, default=1,
                   help="packed multi-request prefill (paged layout): P > 1 "
                        "packs up to P admitted requests' next prompt "
                        "chunks — each at its own absolute offset and "
                        "block-table row, prefix-cache resume offsets "
                        "included — into ONE (P, bucket) AOT dispatch per "
                        "scheduler step, interleaved with decode rounds "
                        "instead of draining admission one prompt at a "
                        "time. Streams stay bit-identical to sequential "
                        "prefill on the gather impl; mutually exclusive "
                        "with --spec-k")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the content-addressed prefix cache "
                        "(paged layout): admissions sharing a committed "
                        "prompt prefix then re-run the full prefill "
                        "instead of pointing their block tables at the "
                        "shared blocks (copy-on-write on divergence)")
    p.add_argument("--compile-cache-dir",
                   default=None,
                   help="JAX persistent compilation cache directory "
                        "(default: .jax_compile_cache in the checkout; '' "
                        "disables; the JAX_COMPILATION_CACHE_DIR env var "
                        "wins over this flag). Warm engine builds skip "
                        "the AOT prefill/decode compiles")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: draft proposes k tokens per "
                        "round, one verify pass scores all k+1 positions "
                        "(0 = off). Requires --draft-checkpoint-path and "
                        "the paged KV layout; greedy output is bit-exact "
                        "vs --spec-k 0")
    p.add_argument("--draft-checkpoint-path", default="",
                   help="training checkpoint directory of the DRAFT model")
    p.add_argument("--draft-checkpoint-job-id", default="",
                   help="job id the draft checkpoint was written under")
    p.add_argument("--draft-step", type=int, default=None,
                   help="draft checkpoint step (default: latest)")
    p.add_argument("--draft-preset", default="tiny",
                   help="model preset the draft checkpoint was trained "
                        "with (any models/configs.py preset; must share "
                        "the target's vocab)")
    p.add_argument("--draft-layer-impl", default="loop",
                   choices=("loop", "scan"))
    p.add_argument("--draft-kv-num-blocks", type=int, default=0,
                   help="draft KV pool blocks incl. the null block; 0 = "
                        "full reservation parity. The scheduler admits by "
                        "the COMBINED footprint across both pools")
    p.add_argument("--adapter-rank", type=int, default=0,
                   help="multi-tenant LoRA serving: low-rank adapter rank "
                        "r (0 = adapter serving off). Adapter A/B factors "
                        "page into a third block pool next to the KV "
                        "pools; every slot carries its adapter's page rows "
                        "into ONE fused decode dispatch, so slots serving "
                        "DIFFERENT adapters batch together. Adapter '' is "
                        "the null adapter — base-only, bit-identical to "
                        "--adapter-rank 0 output")
    p.add_argument("--adapter-pages", type=int, default=0,
                   help="adapter page pool size incl. the null page; 0 = "
                        "room for 4 adapters. Cold adapters evict under "
                        "pressure (refcounted, like KV blocks) and reload "
                        "CRC-verified from their published artifacts")
    p.add_argument("--adapter", action="append", default=[],
                   metavar="NAME=DIR", dest="adapters",
                   help="register a published adapter artifact at startup "
                        "(repeatable); requests name it via the 'adapter' "
                        "field of a --request-file line. Requires "
                        "--adapter-rank matching the artifact's rank")
    p.add_argument("--prompt-adapter", action="append", default=[],
                   metavar="NAME",
                   help="adapter for the i-th --prompt (repeatable, "
                        "positional; missing entries = '' base-only)")
    p.add_argument("--spec-verify-impl", default="exact",
                   choices=("exact", "chunk"),
                   help="verify-k scoring: 'exact' micro-steps k+1 S=1 "
                        "forwards in one program (greedy streams bit-match "
                        "the non-speculative path by construction); 'chunk' "
                        "runs one (slots, k+1) forward, batching the verify "
                        "FLOPs, but bf16 GEMM accumulation is shape-"
                        "dependent and a one-ulp near-tie can flip an "
                        "argmax vs the S=1 decode program")
    p.add_argument("--spec-tree", default="",
                   help="TREE speculative decoding: comma list of per-depth "
                        "branch fan-outs (e.g. '2,2,1') — the draft's "
                        "k-chain plus free top-k sibling fan-outs, all "
                        "scored by ONE ancestor-masked verify dispatch; an "
                        "accepted sibling rescues a round linear "
                        "speculation would have cut short. '' = linear "
                        "--spec-k rounds. Requires --spec-k; '1,1,...' "
                        "degenerates to the linear chain. With "
                        "--adaptive-spec-k the controller's budget picks a "
                        "sub-shape per round (TreeShape.shrink_to). Under "
                        "--spec-verify-impl exact only the primary chain "
                        "is scored (greedy streams bit-match --spec-k 0 by "
                        "construction); 'chunk' scores every branch")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompt", action="append", default=[],
                   help="repeatable; each becomes one request")
    p.add_argument("--repeat", type=int, default=1,
                   help="submit the prompt set this many times (load gen)")
    p.add_argument("--no-eos", action="store_true",
                   help="ignore EOS; always decode max-new-tokens")
    p.add_argument("--log-frequency", type=int, default=8)
    p.add_argument("--metrics-port", type=int, default=0,
                   help="serve Prometheus /metrics on this port "
                        "(0 = disabled); TTFT, decode-step, slot occupancy")
    p.add_argument("--event-log", default="",
                   help="flight-recorder JSONL path ('' = disabled)")
    p.add_argument("--trace-log", default="",
                   help="request-span trail JSONL (obs/reqtrace.py); "
                        "defaults to trace_<name>.jsonl next to "
                        "--event-log ('' with no --event-log = disabled)")
    p.add_argument("--chaos", default="",
                   help="fault schedule keyed by decode iteration "
                        "('step=<N>:sigusr1' / 'step=<N>:sigterm'; "
                        "chaos/schedule.py grammar) — delivers a real "
                        "drain signal mid-decode; 'step=<N>:reload_signal' "
                        "(keyed by reload ordinal) lands a SIGUSR1 in the "
                        "middle of the Nth hot weight swap; "
                        "'step=<N>:spill_corrupt' (keyed by spill export "
                        "ordinal) flips a payload byte in the Nth spill "
                        "artifact — the restore must CRC-reject it and "
                        "replay")
    p.add_argument("--follow", action="store_true",
                   help="continuous-deployment mode: stay up after the "
                        "initial prompts, tail --request-file for new "
                        "requests and hot-reload each verified publish of "
                        "published.json (deploy/) without dropping "
                        "in-flight requests; SIGUSR1/SIGTERM still drains "
                        "and exits 0")
    p.add_argument("--poll-seconds", type=float, default=1.0,
                   help="published.json / request-file poll interval while "
                        "idle in --follow mode")
    p.add_argument("--request-file", default="",
                   help="JSONL file tailed for requests in --follow mode "
                        "(one {'id','prompt',...} object per line; "
                        "complete lines only)")
    p.add_argument("--journal-dir", default="",
                   help="request-journal directory (inference/journal.py): "
                        "a signal drain persists every unserved queued "
                        "request as a requeue record there, so a fleet "
                        "router (inference/router.py) can re-admit them on "
                        "another host instead of losing them ('' = off)")
    p.add_argument("--adaptive-spec-k", action="store_true",
                   help="tune the speculative round width per request from "
                        "live acceptance (sampler.AdaptiveK): a stale "
                        "draft — e.g. right after a target-only hot swap — "
                        "walks k toward 1 instead of burning --spec-k "
                        "rejected proposals per round")
    return p.parse_args(argv)


def main(argv=None) -> None:
    events.emit_startup(_IMPORTS_DONE_T)
    args = get_serve_args(argv)
    init_logger()
    flag = SignalFlag()
    flag.register()  # before engine build, like train.py
    # Chaos (chaos/): serving supports only the signal faults — a drain
    # delivered mid-decode. Parse errors (or non-serve faults) fail fast,
    # before the expensive engine build.
    chaos = None
    if args.chaos:
        chaos = ChaosInjector(
            parse_schedule(args.chaos, allowed=SERVE_FAULTS),
            seed=args.seed)
        logger.info(f"Chaos schedule | {chaos.describe()}")
    if args.event_log:
        events.configure(args.event_log, job=JOBID or "serve",
                         host=os.getpid())
    trace_log = args.trace_log or (
        reqtrace.derive_trace_path(args.event_log) if args.event_log
        else "")
    if trace_log:
        reqtrace.configure(trace_log, job=JOBID or "serve",
                           host=os.getpid())
    metrics_server = None
    if args.metrics_port:
        metrics_server = MetricsServer(port=args.metrics_port)
        port = metrics_server.start()
        logger.info(f"Metrics | serving /metrics on port {port}")
    events.emit_audit(logger, AUDIT_SERVE_START, "start")

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
        spec_kwargs = {}
        draft_step_restored = None
        draft_cfg = None
        if args.spec_k:
            if not (args.draft_checkpoint_path
                    and args.draft_checkpoint_job_id):
                raise SystemExit(
                    "--spec-k requires --draft-checkpoint-path and "
                    "--draft-checkpoint-job-id")
            draft_cfg = get_config(args.draft_preset, vocab_size=vocab,
                                   layer_impl=args.draft_layer_impl)
            # the draft loads through the SAME cross-topology restore path
            # as the target — any preset, its own training run
            draft_params, draft_step_restored = restore_params(
                args.draft_checkpoint_path, args.draft_checkpoint_job_id,
                draft_cfg, step=args.draft_step)
            spec_kwargs = dict(
                draft_cfg=draft_cfg, draft_params=draft_params,
                spec_k=args.spec_k,
                draft_num_blocks=args.draft_kv_num_blocks or None,
                spec_verify_impl=args.spec_verify_impl,
                spec_tree=args.spec_tree or None)
        elif args.spec_tree:
            raise SystemExit("--spec-tree requires --spec-k (the tree "
                             "widens the speculative rounds)")
        engine = InferenceEngine.from_checkpoint(
            args.checkpoint_path, args.checkpoint_job_id, cfg,
            step=args.step, slots=args.slots,
            max_len=args.max_len or None, prefill_buckets=buckets,
            top_k=args.top_k, kv_layout=args.kv_layout,
            kv_block_size=args.kv_block_size,
            kv_num_blocks=args.kv_num_blocks or None,
            prefix_cache=not args.no_prefix_cache,
            paged_kernel=args.paged_kernel,
            prefill_batch=args.prefill_batch,
            kv_dtype=args.kv_dtype,
            adapter_rank=args.adapter_rank,
            adapter_num_pages=args.adapter_pages,
            **spec_kwargs)
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
        if args.kv_layout == "paged":
            # capacity surface for dashboards: bytes one block costs in
            # the selected storage dtype (scale rows included) and the
            # dtype itself as an info label — with kv_blocks_total these
            # give blocks-per-HBM-budget directly
            bpb = block_bytes(engine.cache)
            _M_KV_BYTES_PER_BLOCK.set(bpb)
            _M_KV_DTYPE.labels(dtype=engine.kv_dtype).set(1)
        _M_ENGINE_ROLE.labels(engine_role="both").set(1)
        if args.spec_k:
            engine.draft_restored_step = draft_step_restored
            logger.info(
                "Speculative decoding | draft=%s step=%s k=%d verify=%s "
                "tree=%s",
                args.draft_preset, draft_step_restored, args.spec_k,
                args.spec_verify_impl, args.spec_tree or "off")
        events.emit_audit(
            logger, AUDIT_SERVE_READY_FMT.format(
                model=args.model, step=engine.restored_step,
                slots=args.slots),
            "ready", step=engine.restored_step, slots=args.slots,
            model=args.model)
        # stop_check lets a chunked prefill see the signal BETWEEN chunks:
        # a mid-prompt SIGUSR1/SIGTERM finishes the current chunk, frees the
        # request's blocks and reports it unserved — exact drain, any
        # prompt length.
        adaptive = (AdaptiveK(args.spec_k)
                    if args.spec_k and args.adaptive_spec_k else None)
        # serve.py is one process: every import of its exports happens
        # here, so a requested mem lane always resolves to mem
        lane = resolve_lane(args.kv_transport, colocated=True)
        transport = make_transport(lane)
        _M_KV_TRANSPORT.labels(lane=lane).set(1)
        if lane != "fs":
            logger.info("KV transport: %s lane (fs artifacts remain the "
                        "durable fallback)", lane)
        sched = Scheduler(engine,
                          eos_token_id=(None if args.no_eos
                                        else tokenizer.eos_token_id),
                          stop_check=lambda: flag.signum is not None,
                          adaptive_k=adaptive,
                          decode_burst=args.decode_burst,
                          prefill_batch=args.prefill_batch,
                          adaptive_burst=args.adaptive_burst,
                          spill_dir=args.spill_dir or None,
                          on_spill=(chaos.on_spill if chaos is not None
                                    else None),
                          kv_store=(BlockStore(args.kv_store_dir,
                                               writer=f"serve_{os.getpid()}")
                                    if args.kv_store_dir else None),
                          transport=transport,
                          kv_store_max_bytes=args.kv_store_max_bytes)
        base_prompts = args.prompt or ([] if args.follow else [_DEMO_PROMPT])
        prompts = base_prompts * args.repeat
        for i, text in enumerate(prompts):
            rid = f"req{i}"
            prompt = tokenizer.encode(text)
            trace_id = reqtrace.mint_trace_id(rid)
            reqtrace.emit(trace_id, rid, "intake",
                          prompt_tokens=len(prompt),
                          max_new_tokens=args.max_new_tokens)
            j = i % len(base_prompts) if base_prompts else 0
            aname = (args.prompt_adapter[j]
                     if j < len(args.prompt_adapter) else "")
            sched.submit(Request(
                id=rid, prompt=prompt,
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature, top_p=args.top_p,
                seed=args.seed + i, trace_id=trace_id,
                adapter=aname))
        watcher = reloader = follower = None
        if args.follow:
            watcher = PointerWatcher(args.checkpoint_path)
            reloader = HotReloader(engine, sched, cfg,
                                   args.checkpoint_path,
                                   draft_cfg=draft_cfg,
                                   adaptive_k=adaptive, chaos=chaos)
            if args.request_file:
                follower = _RequestFollower(args.request_file, tokenizer,
                                            args)
            # catch up to the startup pointer: if it names a different
            # step than we restored (e.g. the trainer published while the
            # engine compiled), swap before taking traffic; if it names
            # the serving step, the poll just primes the watcher's
            # seen-key so the same publish is never re-offered
            ptr0 = watcher.poll()
            if ptr0 is not None and ptr0.step != engine.restored_step:
                reloader.maybe_reload(ptr0)

    drained = False
    while sched.pending() or (args.follow and not drained):
        if args.follow and not drained:
            if follower is not None:
                follower.ingest(sched)
            if not sched.pending() and flag.signum is None:
                # idle follow tick: no requests in flight — absorb any
                # publish now, then wait for work or a signal
                if reloader.maybe_reload(watcher.poll()):
                    continue  # a swap may race a fresh publish; re-poll
                time.sleep(args.poll_seconds)
                continue
        if chaos is not None:
            # keyed by decode iteration: the signal lands here and the
            # flag check just below begins the drain lifecycle mid-decode
            chaos.on_serve_step(sched.iterations)
        update_checkpoint_age_gauge()
        # not admission_open: a chunked prefill may have seen the signal
        # first (scheduler stop_check) and closed admission itself — the
        # audit trail must still record the drain exactly once.
        if flag.signum is not None and not drained:
            events.emit_audit(
                logger, AUDIT_SERVE_DRAINING_FMT.format(
                    signum=flag.signum, active=len(sched.active)),
                "drain", phase="begin", signum=flag.signum,
                active=len(sched.active))
            sched.stop_admission()
            drained = True
        if reloader is not None and not drained:
            # between decode iterations — the in-flight round is finished,
            # so this is exactly the swap's prefill-pause point
            old_step = engine.restored_step
            t_swap = time.monotonic()
            if reloader.maybe_reload(watcher.poll()):
                # a swap stalled every in-flight request for its duration:
                # pin the pause on each active trace so a latency report
                # attributes the decode gap to the reload, not the model
                pause = time.monotonic() - t_swap
                for st in sched.active.values():
                    tid = getattr(st.request, "trace_id", "")
                    if tid:
                        reqtrace.emit(tid, st.request.id, "reload_pause",
                                      dur=pause, old=old_step,
                                      new=engine.restored_step)
        for c in sched.step():
            decoded = c.tokens[:-1] if (not args.no_eos and c.reason == "eos"
                                        ) else c.tokens
            events.emit_audit(
                logger, AUDIT_REQUEST_DONE_FMT.format(
                    id=c.request_id, reason=c.reason,
                    prompt_tokens=c.prompt_len, new_tokens=len(c.tokens),
                    ttft_ms=c.ttft_seconds * 1e3,
                    tps=c.decode_tokens_per_sec),
                "request_done", id=c.request_id, reason=c.reason,
                tokens=len(c.tokens), ttft_ms=c.ttft_seconds * 1e3)
            logger.info("Request %s output: %r", c.request_id,
                        tokenizer.decode(decoded))
            if args.spec_k:
                # drain-audit companion: how many of this request's tokens
                # the verifier emitted that the draft never proposed
                # (bonus/corrected) — with the proposal/accept counts this
                # reconciles the emitted stream exactly
                logger.info(
                    "Request %s spec: proposed=%d accepted=%d "
                    "emitted_not_proposed=%d", c.request_id,
                    c.spec_proposed, c.spec_accepted,
                    c.spec_emitted_not_proposed)
        if sched.iterations and sched.iterations % args.log_frequency == 0:
            events.emit_audit(
                logger, AUDIT_SERVE_STEP_FMT.format(
                    step=sched.iterations, active=len(sched.active),
                    queued=len(sched.queue), done=len(sched.completed)),
                "step", step=sched.iterations, active=len(sched.active),
                queued=len(sched.queue), done=len(sched.completed))

    if flag.signum is not None and not drained:
        # the signal was consumed inside a chunked prefill on the final
        # iteration — the loop exited before the top-of-loop check ran
        events.emit_audit(
            logger, AUDIT_SERVE_DRAINING_FMT.format(
                signum=flag.signum, active=len(sched.active)),
            "drain", phase="begin", signum=flag.signum,
            active=len(sched.active))
        drained = True

    m = sched.metrics()
    logger.info("Serving metrics: %d requests | %d tokens | "
                "%.1f tok/s (%.1f/slot) | decode p50 %.1f ms p95 %.1f ms",
                m["requests_completed"], m["tokens_generated"],
                m["tokens_per_sec"], m["tokens_per_sec_per_slot"],
                m["decode_p50_ms"], m["decode_p95_ms"])
    # the fused-decode win in the drain receipt: per-token decode reads
    # 1.00 dispatches/token; burst n amortizes toward 1/n
    logger.info("Decode dispatch metrics: burst=%d | %d dispatches | "
                "%d host syncs | %d decode tokens | "
                "%.3f dispatches/token | %.3f syncs/token",
                m["decode_burst"], m["decode_dispatches"],
                m["decode_host_syncs"], m["decode_tokens"],
                m["dispatches_per_token"], m["host_syncs_per_token"])
    if args.spec_k:
        logger.info(
            "Spec metrics: k=%d | %d rounds | %d drafted | %d accepted | "
            "acceptance %.3f", m["spec_k"], m["spec_rounds"],
            m["spec_draft_tokens"], m["spec_accepted_tokens"],
            m["spec_acceptance_rate"])
        if args.spec_tree:
            # tree-widening receipt in the drain summary: nodes scored per
            # verify dispatch, accepted tokens per round (the perf claim),
            # and how much of the acceptance came OFF the primary chain —
            # the rescue linear speculation cannot make
            events.emit_audit(
                logger, AUDIT_SERVE_TREE_SPEC_FMT.format(
                    shape=m["spec_tree"], rounds=m["spec_tree_rounds"],
                    nodes=m["spec_tree_nodes"],
                    per_round=m["spec_accepted_per_round"],
                    util=m["spec_tree_branch_utilization"]),
                "tree_spec", shape=m["spec_tree"],
                rounds=m["spec_tree_rounds"], nodes=m["spec_tree_nodes"],
                accepted_per_round=m["spec_accepted_per_round"],
                branch_utilization=m["spec_tree_branch_utilization"])
    if sched.prefill_batch > 1:
        # packed-lane occupancy in the drain receipt: how full the packed
        # prefill dispatches ran, and which kernel their paged reads took
        # (inplace under --paged-kernel pallas — no silent gather)
        events.emit_audit(
            logger, AUDIT_SERVE_PREFILL_FMT.format(
                rounds=m["prefill_packed_rounds"],
                rows=m["prefill_packed_rows"],
                occupancy=m["prefill_packed_occupancy"],
                inplace=m["prefill_inplace_chunks"],
                gather=m["prefill_gather_chunks"]),
            "packed_prefill", rounds=m["prefill_packed_rounds"],
            rows=m["prefill_packed_rows"],
            occupancy=m["prefill_packed_occupancy"],
            inplace_chunks=m["prefill_inplace_chunks"],
            gather_chunks=m["prefill_gather_chunks"])
    if engine.kv_layout == "paged":
        # the --kv-dtype receipt in the drain summary: storage dtype,
        # bytes one block costs (scale rows included), capacity ratio vs
        # the bf16 layout at the same geometry (bf16 reads 1.00)
        bpb = block_bytes(engine.cache)
        ratio = bf16_block_bytes(engine.cache) / bpb
        events.emit_audit(
            logger, AUDIT_KV_QUANT_FMT.format(
                dtype=engine.kv_dtype, bytes_per_block=bpb, ratio=ratio,
                blocks_total=engine.num_blocks),
            "kv_quant", dtype=engine.kv_dtype, bytes_per_block=bpb,
            ratio=ratio, blocks_total=engine.num_blocks)
    if sched.prefix_cache is not None:
        # hit rate rides the drain-summary audit trail: the receipt an
        # operator greps after a drain shows how much prefill the cache
        # absorbed, next to the request/token counts it absorbed it for
        events.emit_audit(
            logger, AUDIT_SERVE_PREFIX_FMT.format(
                lookups=m["prefix_lookups"], rate=m["prefix_hit_rate"],
                hit_tokens=m["prefix_hit_tokens"],
                cached=m["prefix_cached_blocks"],
                cow=m["prefix_cow_copies"], evictions=m["prefix_evictions"]),
            "prefix_cache", lookups=m["prefix_lookups"],
            hit_rate=m["prefix_hit_rate"],
            hit_tokens=m["prefix_hit_tokens"],
            cached_blocks=m["prefix_cached_blocks"],
            cow_copies=m["prefix_cow_copies"],
            evictions=m["prefix_evictions"])
    if sched.adapters is not None:
        # multi-tenant adapter receipt in the drain summary: how many
        # distinct adapters this process served, page-in/eviction churn
        # in the adapter pool, bytes still resident, and rejects (corrupt
        # or unregistered artifacts that never reached the pool)
        events.emit_audit(
            logger, AUDIT_ADAPTER_SUMMARY_FMT.format(
                served=m["adapters_served"],
                pageins=m["adapter_pageins"],
                evictions=m["adapter_evictions"],
                resident_bytes=m["adapter_pages_resident_bytes"],
                rejects=m["adapter_rejects"]),
            "adapter_summary", served=m["adapters_served"],
            pageins=m["adapter_pageins"],
            evictions=m["adapter_evictions"],
            resident_bytes=m["adapter_pages_resident_bytes"],
            rejects=m["adapter_rejects"])
    # Per-request latency audit: the drain summary's SLO receipt — TTFT
    # and TPOT per completed request, keyed by the trace id that joins
    # this process's spans to the router's (obs/reqtrace.py)
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
    # leak guard: with the loop idle, every block must be free or
    # cache-held; violations audit once ([KV LEAK]) but keep the exit-0
    # contract (the strict mode is for tests, via Scheduler.run)
    sched.audit_block_leaks(strict=False)
    if drained:
        unserved = sched.unserved()
        if args.journal_dir and unserved:
            # zero-lost-requests half of the drain contract: what this
            # process will not serve, the journal keeps (params + committed
            # baseline) for a router to re-admit elsewhere
            from .journal import RequestJournal, persist_unserved

            journal = RequestJournal(args.journal_dir,
                                     writer=f"serve_{os.getpid()}")
            persist_unserved(journal, unserved,
                             reason=f"drain_sig{flag.signum}")
        events.emit_audit(
            logger, AUDIT_SERVE_DRAINED_FMT.format(
                completed=len(sched.completed), queued=len(sched.queue)),
            "drain", phase="end", completed=len(sched.completed),
            queued=len(sched.queue))
    if sched.enable_spill:
        # spilled requests were reported unserved above (committed
        # baseline in their requeue records); their artifacts are now
        # dead weight on the host tier
        sched.discard_spilled()
    events.emit_audit(logger, AUDIT_SERVE_COMPLETED, "complete")
    events.flush()
    reqtrace.flush()
    if metrics_server is not None:
        metrics_server.stop()
    # exit 0 always — same contract as training: the exit POLICY is in the
    # logs, not the return code (nonzero would trip Slurm requeue logic)
    sys.exit(0)


if __name__ == "__main__":
    main()
