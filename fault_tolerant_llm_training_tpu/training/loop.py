"""Training orchestration (ref: train.py:12-129).

Setup order mirrors the reference (checkpoint -> data -> model -> optimizer ->
resume bookkeeping, ref train.py:20-84) with the TPU-native differences:

- signal handlers are installed *before* setup and checked at phase
  boundaries, closing the reference's fatal unprotected-setup window
  (SURVEY.md §3.2);
- resume restores the data-iterator position from the checkpoint in O(1)
  instead of replaying N batches (ref: train.py:36-39);
- the hot loop dispatches the jitted step asynchronously with a bounded
  in-flight window (``--inflight``): dispatch stays pipelined (the reference
  blocks on ``loss.item()`` every log step) while "current step" remains
  well-defined within the 120 s preemption budget (SURVEY.md §7.3 #1);
- a non-finite gradient norm raises on the host when the metric is consumed —
  same fault path as the reference's ``error_if_nonfinite`` (utils.py:61),
  shifted out of the jitted region.
"""

import collections
import contextlib
import math
import os
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..chaos.injector import ChaosInjector
from ..checkpoint.manager import CheckpointManager, update_checkpoint_age_gauge
from ..data.collator import CollatorForCLM
from ..data.loader import DataLoader
from ..data.parquet import IterableParquetDataset, ParquetDataset
from ..data.prefetch import DevicePrefetcher
from ..data.tokenizer import load_tokenizer
from ..ft import multihost
from ..ft.multihost import PeerHostError, barrier
from ..ft.signals import SignalFlag, TrainingSignal
from ..models import build_model, get_config
from ..deploy.publish import Publisher
from ..obs import events
from ..obs.registry import REGISTRY
from ..obs.trace import AutoTraceWindow, TraceWindow, profile_options, span
from ..ops.attention import describe_attention_impl
from ..ops.flash_attention import flash_calls, vmem_capacity_bytes
from ..parallel.mesh import make_mesh, use_mesh
from ..parallel.sharding import batch_pspec, param_pspecs, replicated_pspecs
from ..training.state import TrainState
from ..training.step import make_eval_step, make_optimizer, make_train_step
from ..utils.compile_cache import enable_compilation_cache
from ..utils.config import JOBID, TrainConfig
from ..utils.device import describe_device
from ..utils.dtypes import PRECISION_STR_TO_DTYPE
from ..utils.grad_clip import NonFiniteGradientError
from ..utils.logging import (
    AUDIT_RESUME_FMT,
    AUDIT_START,
    AUDIT_STEP_FMT,
    AUDIT_TRACE_AUTO_FMT,
    logger,
)
from ..utils.metrics import (
    Throughput,
    device_memory_report,
    device_peak_flops,
    hbm_usage_str,
    mfu,
    per_device_memory_stats,
    transformer_flops_per_token,
)

# Shared never-set token for watchdog callbacks run directly (single-process
# and re-entrant paths) — they receive a cancellation event they can ignore.
_NEVER_CANCELLED = threading.Event()


class Trainer:
    def __init__(self, cfg: TrainConfig, signal_flag: Optional[SignalFlag] = None):
        self.cfg = cfg
        self.state = None
        self.training_step = 0
        self._resumed = False
        self._last_data_state = None
        # first periodic save blocks to observe real write wall (see _loop)
        self._budget_observed = False
        # True when the raised error is deterministic and hits every host at
        # the same step (injection, non-finite grad from replicated metrics)
        # — only then may the exit handler run a *coordinated* save on a pod.
        self.error_is_replicated = False
        self._mesh_ctx = None
        # Dispatched-but-unfinished steps (filled by _loop; exists from
        # construction so save_checkpoint can drain it on setup-phase saves).
        self._inflight = collections.deque()
        self._batch_iter = None  # live prefetch iterator (fence catch-up)
        self._in_guard = False  # re-entrancy latch for _guarded_wait
        # One long-lived bounded-wait worker: _guarded_wait runs every
        # training step (metric consume), so per-call thread spawn/join
        # (watchdog) would churn a thread per step (ADVICE r5).
        self._waiter = multihost.PersistentWaiter()
        self._fence_done = False  # fence ran; stale err keys must not re-raise
        self._signal_round = 0  # KV signal-agreement round (sync boundaries)
        self._est_save_seconds = None  # startup write-probe estimate

        # Handlers first — signals during the (potentially long) setup are
        # deferred and handled at the next phase boundary instead of killing
        # the process (the reference registers only at train.py:89-90).
        self.signal_flag = signal_flag or SignalFlag()
        if signal_flag is None:
            self.signal_flag.register()

        logger.info(f"Experiment args: {cfg}")  # ref: train.py:14
        # Before the first jit of the process, so the init / restore
        # programs cache too, not only the train step (on by default:
        # utils/compile_cache.py says where it lives).
        cache_dir = enable_compilation_cache(cfg.compile_cache_dir)
        if cache_dir:
            logger.info(f"Compilation cache | {cache_dir}")

        if cfg.distributed:
            # jax.distributed auto-detects Slurm/TPU-pod topologies; outside
            # those (e.g. a hand-launched multi-process CPU run) the JAX_*
            # env vars spell it out explicitly.
            kwargs = {}
            explicit = ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                        "JAX_PROCESS_ID")
            present = [v for v in explicit if v in os.environ]
            if present and len(present) != len(explicit):
                raise ValueError(
                    f"explicit jax.distributed config needs all of "
                    f"{explicit}; missing "
                    f"{sorted(set(explicit) - set(present))}")
            if present:
                # Explicit config must also disable cluster sniffing:
                # jax's Slurm detector triggers on SLURM_JOB_ID alone (set
                # for checkpoint naming even off-Slurm) and then dies on
                # the missing SLURM_LOCALID.
                kwargs = dict(
                    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
                    num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
                    process_id=int(os.environ["JAX_PROCESS_ID"]),
                    cluster_detection_method="deactivate")
            jax.distributed.initialize(**kwargs)
        # Multihost: in-loop signal checks are cluster-wide agreements
        # (ft/multihost.py) so all hosts raise at the same boundary; setup
        # checks are local-only and skipped on pods (see _setup_check).
        self._sync_signals = jax.process_count() > 1

        # Flight recorder (obs/events.py): configured before any phase that
        # can fault, so a signal during setup still leaves a JSONL trail
        # the goodput stitcher can read. Same job-id naming contract as the
        # checkpoints (checkpoint_{JOBID} <-> events_{JOBID}.jsonl).
        self._job_id = JOBID or "local"
        events.configure(cfg.event_log_path(self._job_id),
                         job=self._job_id, host=jax.process_index())
        self._init_metrics()

        # Chaos injectors (chaos/): the parsed --chaos schedule plus the
        # legacy --raise-error alias, seeded by --seed. None = no chaos.
        self.chaos = ChaosInjector.from_config(cfg)
        if self.chaos is not None:
            logger.info(f"Chaos schedule | {self.chaos.describe()}")

        self.mesh = make_mesh(cfg.dp, cfg.fsdp, cfg.sp, cfg.tp, pp=cfg.pp,
                              ep=cfg.ep)
        if cfg.pp > 1:
            if cfg.layer_impl != "scan":
                raise ValueError(
                    "--pp needs --layer-impl scan (pipeline stages shard "
                    "the layer-stacked params; parallel/pipeline.py)")
            if cfg.sp > 1:
                raise ValueError("--pp with --sp is not supported")
            micro = cfg.microbatches or cfg.pp
            if cfg.batch_size % micro:
                raise ValueError(
                    f"--batch-size {cfg.batch_size} not divisible by "
                    f"microbatches {micro}")
        if cfg.grad_accum > 1:
            if cfg.batch_size % cfg.grad_accum:
                raise ValueError(
                    f"--batch-size {cfg.batch_size} not divisible by "
                    f"--grad-accum {cfg.grad_accum}")
            slice_batch = cfg.batch_size // cfg.grad_accum
            data_ways_ = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
            if slice_batch % data_ways_:
                raise ValueError(
                    f"per-slice batch {slice_batch} (= --batch-size / "
                    f"--grad-accum) is not divisible by the data-sharding "
                    f"extent dp*fsdp = {data_ways_}")
            if cfg.pp > 1 and slice_batch % (cfg.microbatches or cfg.pp):
                raise ValueError(
                    f"per-slice batch {slice_batch} is not divisible by "
                    f"the pipeline microbatch count "
                    f"{cfg.microbatches or cfg.pp}")
        data_ways = (self.mesh.shape["data"] * self.mesh.shape["fsdp"])
        if cfg.batch_size % data_ways:
            raise ValueError(
                f"--batch-size {cfg.batch_size} is not divisible by the "
                f"data-sharding extent dp*fsdp = {data_ways} "
                f"(mesh {dict(self.mesh.shape)}); pick a batch size that "
                f"divides evenly or reduce --dp/--fsdp")
        if cfg.sequence_length % self.mesh.shape["sequence"]:
            raise ValueError(
                f"--sequence-length {cfg.sequence_length} is not divisible "
                f"by the sequence-parallel extent sp = "
                f"{self.mesh.shape['sequence']}")
        self._mesh_ctx = use_mesh(self.mesh)
        self._mesh_ctx.__enter__()

        # Resume source (ref: train.py:20-24): the chained job passes the
        # *previous* job's id; its checkpoints live in checkpoint_{id}/.
        read_mngr = None
        if cfg.checkpoint_id:
            logger.info(f"Loading checkpoint from {cfg.checkpoint_path}")
            read_mngr = CheckpointManager(cfg.checkpoint_path, cfg.checkpoint_id)
        self._setup_check()

        # --- data (ref: train.py:27-34) ---
        logger.info("Setting up DataLoaders...")
        self.tokenizer = load_tokenizer(cfg.tokenizer_name_or_path)
        shuffle_seed = cfg.seed if cfg.shuffle else None
        # Automatic eval holdout (VERDICT r4 weak #6): with --eval-frequency
        # but no --eval-dataset, the first batch*eval_batches corpus rows
        # become the eval set and are carved OUT of the training index
        # (both map and packed paths), so "held-out" means held out.
        self._holdout_rows = 0
        if cfg.eval_frequency and not cfg.eval_dataset:
            self._holdout_rows = cfg.batch_size * cfg.eval_batches
            logger.info(f"Eval holdout: first {self._holdout_rows} corpus "
                        f"rows reserved for evaluation and excluded from "
                        f"training")
        if cfg.data_loading == "map":
            dataset = ParquetDataset(cfg.dataset, self.tokenizer,
                                     cfg.sequence_length,
                                     cfg.batch_size * cfg.training_steps,
                                     pretokenize_dir=cfg.pretokenize_dir,
                                     shuffle_seed=shuffle_seed,
                                     holdout_rows=self._holdout_rows,
                                     shuffle_impl=cfg.shuffle_impl)
            collator = CollatorForCLM(cfg.sequence_length,
                                      self.tokenizer.pad_token_id)
            # Pod default: each host tokenizes only its own devices' rows
            # (VERDICT r4 weak #2; bit-identical trajectory to replicated,
            # tests/test_sharded_data.py). Single process: replicated is
            # the same work, skip the indirection unless forced.
            sharded = (cfg.data_sharding == "host"
                       or (cfg.data_sharding == "auto"
                           and jax.process_count() > 1))
            if sharded:
                from ..data.loader import HostShardedDataLoader

                self.loader = HostShardedDataLoader(
                    dataset, cfg.batch_size, collator,
                    NamedSharding(self.mesh, batch_pspec()),
                    cfg.sequence_length)
            else:
                self.loader = DataLoader(dataset, cfg.batch_size, collator)
        else:
            if cfg.data_sharding == "host":
                raise ValueError(
                    "--data-sharding host needs --data-loading map (the "
                    "packed path's token buffer is a sequential walk; "
                    "per-host row sharding is ill-defined there)")
            dataset = IterableParquetDataset(
                cfg.dataset, self.tokenizer, cfg.sequence_length,
                bos_token_id=self.tokenizer.bos_token_id,
                legacy=cfg.legacy_packing, shuffle_seed=shuffle_seed,
                holdout_rows=self._holdout_rows,
                shuffle_impl=cfg.shuffle_impl)
            self.loader = DataLoader(dataset, cfg.batch_size)
        self._setup_check()

        # --- model + optimizer (ref: train.py:42-77) ---
        logger.info("Setting up Model...")
        dtype = PRECISION_STR_TO_DTYPE[cfg.model_dtype]
        param_dtype = (jnp.float32 if cfg.master_weights == "fp32" else dtype)
        vocab = cfg.vocab_size or self.tokenizer.vocab_size
        moe_over = {k: v for k, v in dict(
            moe_experts=cfg.moe_experts, moe_top_k=cfg.moe_top_k,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_aux_weight=cfg.moe_aux_weight,
            moe_impl=cfg.moe_impl).items() if v is not None}
        from ..models.configs import PRESETS, LatentMoEConfig
        self._latent = isinstance(PRESETS.get(cfg.model), LatentMoEConfig)
        if self._latent:
            self.model_config = self._latent_config(cfg, vocab, dtype,
                                                    param_dtype, moe_over)
        else:
            self.model_config = get_config(
                cfg.model, vocab_size=vocab, seq_len=cfg.sequence_length,
                dtype=dtype, param_dtype=param_dtype,
                attention_impl=cfg.attention_impl, embed_impl=cfg.embed_impl,
                sp_layout=cfg.sp_layout, layer_impl=cfg.layer_impl,
                pp_schedule=cfg.pp_schedule,
                pp_stage_unroll=cfg.pp_stage_unroll,
                remat=cfg.remat, **moe_over)
            if cfg.ep > 1 and not self.model_config.moe_experts:
                raise ValueError("--ep needs an MoE model (--model tiny-moe "
                                 "or --moe-experts N)")
        if not self._latent and self.model_config.moe_experts:
            if cfg.pp > 1 and cfg.pp_schedule == "gpipe":
                raise ValueError("--pp-schedule gpipe with an MoE model is "
                                 "not supported (its forward drops the "
                                 "router aux loss); use 1f1b (the default)")
            if self.model_config.moe_experts % max(cfg.ep, 1):
                raise ValueError(
                    f"moe_experts {self.model_config.moe_experts} not "
                    f"divisible by --ep {cfg.ep}")
        self.model = build_model(self.model_config)
        if self._latent:
            # the expert layers' counts of a step, from the packed metrics
            # the loop reads each step anyway (the engine counts the same
            # under phase="decode"|"prefill")
            from ..models.latent_moe import STAT_COUNTERS
            self._m_moe = [REGISTRY.counter(*STAT_COUNTERS[k]).labels(
                phase="train") for k in ("moe_pairs", "moe_touched")]
        # What this run resolved, in its own log: a job that quietly took
        # the CPU, the XLA attention or interpret-mode kernels on a chip
        # host must be visible without a profiler.
        device = describe_device()
        logger.info(f"Device | {device}")
        events.emit("backend_ready", device=device)
        logger.info(f"Attention | requested {cfg.attention_impl} | resolved "
                    f"{describe_attention_impl(cfg.attention_impl)}")
        self.optimizer = make_optimizer(
            cfg.learning_rate, cfg.lr_warmup_steps,
            lr_schedule=cfg.lr_schedule,
            decay_steps=cfg.lr_decay_steps or cfg.training_steps)

        dummy = jnp.zeros((1, cfg.sequence_length), jnp.int32)

        def init_fn(key):
            params = self.model.init(key, dummy)["params"]
            opt_state = self.optimizer.init(params)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt_state)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(cfg.seed))
        specs = (replicated_pspecs(abstract) if self._latent
                 else param_pspecs(abstract))
        self.state_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        self.abstract_state = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abstract, self.state_shardings)
        abstract_sharded = self.abstract_state
        self._warn_if_state_exceeds_hbm(abstract_sharded)
        # MFU denominator (bench.py convention): matmul params exclude the
        # input-embedding gather; attention FLOPs causal-masked.
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(abstract.params))
        if self._latent:    # a token meets k of the routed experts' share
            mc = self.model_config
            held = sum(int(np.prod(l.shape)) for p, l in
                       jax.tree_util.tree_flatten_with_path(abstract.params)[0]
                       if "experts" in jax.tree_util.keystr(p)
                       and "shared" not in jax.tree_util.keystr(p))
            n_params -= held - held * mc.num_experts_per_tok // (
                mc.n_routed_experts)
        self._flops_per_token = transformer_flops_per_token(
            n_params - self.model_config.vocab_size * self.model_config.dim,
            cfg.sequence_length, self.model_config.dim,
            self.model_config.n_layers, causal=True)
        # resolved here, not at the first log line: a TPU whose peak is
        # not on record fails before any training happens
        self._peak_flops = device_peak_flops()

        if read_mngr is not None:
            t_restore = time.perf_counter()
            self.state, data_state, _ = read_mngr.restore(abstract_sharded)
            read_mngr.close()
            self.loader.set_state(data_state)
            self.training_step = int(self.state.step)
            self._last_data_state = data_state
            self._resumed = True
            restore_secs = time.perf_counter() - t_restore
            events.emit("ckpt_restore", step=self.training_step,
                        dur=restore_secs, source_job=cfg.checkpoint_id)
            self._m_restore.set(restore_secs)
            logger.info("Model loaded from checkpoint")  # ref: train.py:58
            logger.info("Optimizer loaded from checkpoint")  # ref: train.py:72
            logger.info("LR Scheduler loaded from checkpoint")  # ref: train.py:77
        else:
            self.state = jax.jit(init_fn,
                                 out_shardings=self.state_shardings)(
                jax.random.PRNGKey(cfg.seed))
            self._last_data_state = self.loader.get_state()
        # Count of step programs this host has dispatched (== state.step on
        # device). The pod fault fence converges on the cluster maximum of
        # this value — training_step lags it inside one loop iteration.
        self._dispatched = self.training_step
        self._setup_check()

        # Save manager for *this* job's id (ref naming: checkpoint_{JOBID},
        # utils.py:80) — files accumulate one dir per preemption, like the
        # reference accumulates one .ckpt per preemption.
        self._save_job_id = self._job_id
        self.ckpt_mngr = CheckpointManager(cfg.checkpoint_path,
                                           self._save_job_id,
                                           max_to_keep=cfg.checkpoint_keep)
        self._log_checkpoint_budget()
        # Deployment pointer (--publish, deploy/publish.py): host 0 commits
        # published.json after each periodic save's integrity sweep. The
        # serving watcher (deploy/reload.py) verifies the manifest before
        # it ever loads, so a torn or corrupted publish cannot take down
        # serving — publishing is fire-and-forget from the trainer's side.
        self._publisher = None
        if cfg.publish and jax.process_index() == 0:
            self._publisher = Publisher(cfg.checkpoint_path,
                                        self._save_job_id, chaos=self.chaos)

        self.batch_sharding = NamedSharding(self.mesh, batch_pspec())
        self._jit_step = jax.jit(
            make_train_step(self.model, self.optimizer, cfg.grad_max_norm,
                            microbatches=cfg.microbatches,
                            grad_accum=cfg.grad_accum),
            donate_argnums=(0,),
            out_shardings=(self.state_shardings, None))
        # AOT-compile now, inside the signal-deferred setup window: a
        # preemption signal interrupting XLA compilation can wedge native
        # code, and compilation is the longest uninterruptible stretch
        # (~35 s model build in the reference, SURVEY.md §3.2). The
        # persistent cache makes a warm restart's compile a disk read; the
        # timed "compile" flight-recorder event is how goodput reports
        # distinguish cold from warm builds.
        batch_struct = jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.sequence_length), jnp.int32,
            sharding=self.batch_sharding)
        t_compile = time.perf_counter()
        traced = self._jit_step.trace(self.abstract_state, batch_struct,
                                      batch_struct)
        self._compiled_step = traced.lower().compile()
        compile_secs = time.perf_counter() - t_compile
        # the attention forward and backward calls a step makes, by kernel
        # family (ops/flash_attention.py picks it per shape from the chip's
        # VMEM): read from the traced program, counted at each consumed step
        self._flash_calls, vmem = flash_calls(traced.jaxpr)
        for direction, calls in self._flash_calls.items():
            if calls:
                logger.info(
                    f"Flash {direction} | " + ", ".join(
                        f"{family} x{n} a step"
                        for family, n in sorted(calls.items()))
                    + f" | vmem limit {vmem / 2**20:g} MiB of "
                      f"{vmem_capacity_bytes() / 2**20:g} MiB")
        # emitted from run(), AFTER the start/resume audit: the flight-
        # recorder trail contract is that a job's first event is
        # start/resume (tests/test_obs.py, goodput stitcher)
        self._compile_event = dict(step=self.training_step,
                                   dur=compile_secs,
                                   cache=("on" if cache_dir else "off"))
        logger.info(f"Train step compiled in {compile_secs:.2f}s "
                    f"(cache {cache_dir or 'off'})")
        self.prefetcher = DevicePrefetcher(
            self.loader, sharding=self.batch_sharding, depth=cfg.prefetch,
            chaos_on_batch=(self.chaos.on_batch if self.chaos else None),
            start_batch=self.training_step)
        self.throughput = Throughput(
            tokens_per_step=cfg.batch_size * cfg.sequence_length)
        if self._resumed:
            # Reset on ckpt_restore: the warmup-exclusion window restarts
            # here so the first post-resume tokens/s excludes the restore/
            # recompile wall instead of mixing it into steady state, and
            # the window is tagged so dashboards don't read the transient
            # as a regression (utils/metrics.py Throughput docstring).
            self.throughput.reset(tag="post_resume")

        # Windowed profiler capture (--trace-steps A:B, obs/trace.py). The
        # window drains the dispatch pipeline before stop_trace so the
        # final steps' async device work lands inside the capture.
        self._trace = None
        if cfg.trace_steps:
            trace_dir = cfg.profile_dir or os.path.join(
                cfg.checkpoint_path or "/tmp",
                f"traces_{self._job_id}")
            self._trace = TraceWindow(
                cfg.trace_steps, trace_dir,
                drain=lambda: self._drain_inflight(check=False))
            logger.info(f"Trace window | steps "
                        f"{self._trace.start_step}:{self._trace.stop_step} "
                        f"-> {trace_dir}")
        # Reactive capture (--auto-trace, obs/trace.py AutoTraceWindow):
        # arms once per run when a step's wall regresses past 2x the
        # rolling median. Mutually exclusive with the explicit window —
        # one profiler owner at a time (utils/config.py).
        self._auto_trace = None
        if cfg.auto_trace and not cfg.trace_steps:
            trace_dir = cfg.profile_dir or os.path.join(
                cfg.checkpoint_path or "/tmp",
                f"traces_{self._job_id}")
            self._auto_trace = AutoTraceWindow(trace_dir)
            logger.info(f"Auto-trace | armed (2x median) -> {trace_dir}")

        # /metrics endpoint (obs/prometheus.py), gated on --metrics-port.
        self._metrics_server = None
        self._heartbeat = None
        if cfg.metrics_port:
            from ..obs.prometheus import MetricsServer

            self._metrics_server = MetricsServer(port=cfg.metrics_port)
            port = self._metrics_server.start()
            logger.info(f"Metrics | serving /metrics on port {port}")
        # Per-host heartbeats run regardless of the scrape endpoint: the
        # age gauges feed the flight recorder and the straggler analysis,
        # and a host without a scraper still publishes its beat for every
        # OTHER host's gauges (utils/config.py heartbeat_seconds).
        if cfg.heartbeat_seconds > 0:
            from ..obs.prometheus import HeartbeatThread

            self._heartbeat = HeartbeatThread(
                lambda: self.training_step,
                interval_seconds=cfg.heartbeat_seconds)
            self._heartbeat.start()

        # --- held-out evaluation (no reference counterpart; SURVEY §5.5
        # notes training loss is the reference's only metric) ---
        self._compiled_eval = None
        if cfg.eval_frequency:
            if cfg.eval_batches < 1:
                raise ValueError(
                    f"--eval-batches {cfg.eval_batches} must be >= 1 when "
                    f"--eval-frequency is set")
            # Without --eval-dataset the eval set is the training corpus's
            # held-out prefix (rows [0, holdout) — see the carve above);
            # with one, it is a separate corpus read from row 0.
            eval_ds = ParquetDataset(
                cfg.eval_dataset or cfg.dataset, self.tokenizer,
                cfg.sequence_length, cfg.batch_size * cfg.eval_batches,
                pretokenize_dir=cfg.pretokenize_dir)
            self.eval_loader = DataLoader(
                eval_ds, cfg.batch_size,
                CollatorForCLM(cfg.sequence_length,
                               self.tokenizer.pad_token_id))
            self._eval_batches_cache = None  # tokenized once, first pass
            self._compiled_eval = jax.jit(
                make_eval_step(self.model,
                               microbatches=cfg.microbatches,
                               grad_accum=cfg.grad_accum)).lower(
                self.abstract_state.params, batch_struct,
                batch_struct).compile()

    def _init_metrics(self) -> None:
        """Registry handles (obs/registry.py) — created once; the hot loop
        only mutates leaf metrics. These replace the ad-hoc log-line-only
        reporting: the same numbers now export at /metrics."""
        r = REGISTRY
        self._m_step_time = r.histogram(
            "ftl_train_step_seconds",
            "Per-step wall time, consume-to-consume (pipelined dispatch "
            "makes this the steady-state step cadence)")
        self._m_tps = r.gauge(
            "ftl_train_tokens_per_sec",
            "Steady-state tokens/s; window label tags post-resume "
            "transients")
        self._m_tokens = r.counter("ftl_train_tokens_total",
                                   "Tokens trained by this process")
        self._m_loss = r.gauge("ftl_train_loss", "Training loss")
        self._m_gnorm = r.gauge("ftl_train_grad_norm",
                                "Global gradient norm")
        self._m_stepg = r.gauge("ftl_train_step",
                                "Last consumed training step")
        self._m_mfu = r.gauge(
            "ftl_train_mfu",
            "Model FLOPs utilization (0-1; TPU backends only — needs a "
            "known peak)")
        self._m_stall = r.counter(
            "ftl_data_stall_seconds_total",
            "Wall time the loop spent blocked on the input pipeline")
        self._m_save = r.histogram(
            "ftl_ckpt_save_seconds",
            "Blocking checkpoint-save wall (fault-path and first periodic)")
        self._m_saves = r.counter("ftl_ckpt_saves_total",
                                  "Checkpoints written")
        self._m_restore = r.gauge("ftl_ckpt_restore_seconds",
                                  "Checkpoint restore wall at setup")
        self._m_eval_loss = r.gauge("ftl_eval_loss",
                                    "Held-out eval loss (token-weighted)")
        self._m_hbm_used = r.gauge(
            "ftl_device_hbm_bytes_in_use",
            "Per-device HBM in use (utils/metrics.py "
            "per_device_memory_stats)")
        self._m_hbm_limit = r.gauge("ftl_device_hbm_bytes_limit",
                                    "Per-device HBM limit")
        self._m_flash = {
            "forward": r.counter(
                "flash_forward_calls_total",
                "Flash attention forward calls of the consumed training "
                "steps, a remat recompute included, by kernel family "
                "(resident = whole K/V rows in VMEM, stream = K/V tiles "
                "streamed by the grid; ops/flash_attention.py)"),
            "backward": r.counter(
                "flash_backward_calls_total",
                "Flash attention backward calls of the consumed training "
                "steps, by kernel family (fused = one dq/dk/dv kernel, split "
                "= the streaming dq and dk/dv kernels; "
                "ops/flash_attention.py)")}
        self._last_consume_t = None
        # (wall clock, last step) already covered by a step event; the next
        # event's dur/steps are deltas against this.
        self._step_window_start = None

    def _latent_config(self, cfg, vocab, dtype, param_dtype, moe_over):
        """The latent / expert class (models/latent_moe.py) as it trains: on
        one device, its whole state replicated. A mesh of more is refused:
        the expert exchange between the chips that share a layer, and
        sharding rules for the class's paths, are not written (ROADMAP
        R1). The Llama class's MoE and layout options do not apply."""
        what = (f"--model {cfg.model} is a LatentMoEConfig preset "
                f"(models/latent_moe.py)")
        if self.mesh.size > 1:
            raise ValueError(
                f"{what}: it trains on one device; a mesh of "
                f"{self.mesh.size} needs the expert exchange and sharding "
                f"rules for its paths, which are not written (ROADMAP R1)")
        if moe_over or cfg.ep > 1 or cfg.grad_accum > 1 or (
                cfg.layer_impl != "loop"):
            raise ValueError(f"{what}: --moe-*, --ep, --grad-accum and "
                             f"--layer-impl scan are not written for it")
        mc = get_config(cfg.model, vocab_size=vocab,
                        seq_len=cfg.sequence_length, dtype=dtype,
                        param_dtype=param_dtype, remat=cfg.remat,
                        attention_impl=cfg.attention_impl,
                        embed_impl=cfg.embed_impl)
        if not mc.trains:
            raise ValueError(
                f"{what}: its schedule has an indexer or sliding layers, "
                f"which are served, not trained (the uncached training "
                f"forward covers full layers without an indexer)")
        return mc

    def _warn_if_state_exceeds_hbm(self, abstract_sharded) -> None:
        """Pre-flight capacity estimate: warn (don't fail — remat and fusion
        change actuals) when the sharded TrainState alone exceeds a device's
        memory, instead of letting XLA die later in a raw OOM dump. No-op on
        backends that expose no memory_stats."""
        from ..utils.metrics import device_memory_stats

        _, limit = device_memory_stats()
        if not limit:
            return
        per_device = 0
        for leaf in jax.tree_util.tree_leaves(abstract_sharded):
            shard = leaf.sharding.shard_shape(leaf.shape)
            per_device += int(np.prod(shard)) * leaf.dtype.itemsize
        if per_device > limit:
            logger.warning(
                f"TrainState needs ~{per_device / 1e9:.1f} GB per device but "
                f"the device reports {limit / 1e9:.1f} GB; expect an OOM — "
                f"shard more (--fsdp/--tp) or pick a smaller --model")

    def _log_checkpoint_budget(self) -> None:
        """The startup deadline check (SURVEY §5.3, §7.3 #2): estimate the
        fault-path save time from this host's state bytes and a one-shot
        write-throughput probe of the checkpoint filesystem, and compare
        it against the scheduler's USR1 lead. The whole framework exists
        to honor that lead — discovering a blown budget at the first
        preemption is too late. Numbers are logged every run so operators
        can track drift (e.g. a slower Lustre mount)."""
        from ..checkpoint.manager import (
            estimate_save_seconds,
            measure_write_throughput,
            state_bytes,
        )

        total = state_bytes(self.abstract_state)
        # Per-host share: every host writes only its own device shards
        # (Orbax per-host parallel writes); even sharding assumed.
        per_host = total // max(jax.process_count(), 1)
        try:
            tput = measure_write_throughput(self.ckpt_mngr.directory)
        except OSError as e:
            logger.warning(f"Checkpoint budget | write probe failed: {e}")
            return
        est = estimate_save_seconds(per_host, tput)
        self._est_save_seconds = est  # sizes the healthy-save watchdog
        lead = self.cfg.signal_lead_seconds
        logger.info(
            f"Checkpoint budget | state {total / 1e9:.2f} GB "
            f"({per_host / 1e9:.2f} GB/host) | disk {tput / 1e9:.2f} GB/s "
            f"| est save {est:.0f} s | signal lead {lead} s")
        if est > lead:
            logger.warning(
                f"Checkpoint budget EXCEEDED: estimated fault-path save "
                f"{est:.0f} s > the {lead} s signal lead — a preemption "
                f"may outrun the save. Shard over more hosts, use faster "
                f"checkpoint storage, or raise --signal-lead-seconds to "
                f"match the scheduler's --signal=USR1@N.")

    def _setup_check(self) -> None:
        """Phase-boundary signal check during setup.

        Single-host: raise now, closing the reference's unprotected-setup
        window (train.py:42-84 runs ~35 s before handlers exist).
        Multihost: never raise *alone* during setup — a lone raise strands
        the other hosts in their next collective, and a collective check
        here hangs survivors if one host's setup fails. The pending signal
        (only possible from the microsecond window before ``deferred()``
        engaged — setup signals are OS-blocked) is instead handled at the
        loop's first synced boundary, with a fully-built trainer that can
        run the coordinated save.
        """
        if not self._sync_signals:
            self.signal_flag.check()

    # ------------------------------------------------------------------ run
    def run(self) -> None:
        cfg = self.cfg
        tokens_per_step = cfg.batch_size * cfg.sequence_length
        self._step_window_start = (time.time(), self.training_step - 1)
        if self._resumed:
            # ref: train.py:81
            events.emit_audit(
                logger, AUDIT_RESUME_FMT.format(step=self.training_step),
                "resume", step=self.training_step,
                tokens_per_step=tokens_per_step)
        else:
            # ref: train.py:84
            events.emit_audit(logger, AUDIT_START, "start", step=0,
                              tokens_per_step=tokens_per_step)
        if self._compile_event is not None:
            events.emit("compile", **self._compile_event)
            self._compile_event = None

        whole_run_trace = (cfg.profile_dir and not cfg.trace_steps
                           and self._auto_trace is None)
        if whole_run_trace:
            # bare --profile-dir keeps its whole-run capture; --trace-steps
            # and --auto-trace supersede it with a bounded window
            # (obs/trace.py) — one profiler owner at a time
            jax.profiler.start_trace(cfg.profile_dir,
                                     profiler_options=profile_options())
        try:
            self._loop()
        except Exception as e:
            # A host-local fault must be announced AS THE EXCEPTION UNWINDS
            # (before the exit handler runs the fence): the peers' per-
            # dispatch poll sees the key within one iteration, bounding how
            # far ahead they dispatch. Agreed signals, replicated errors and
            # peer echoes are cluster-visible already.
            if (self._sync_signals and not self.error_is_replicated
                    and not isinstance(e, (TrainingSignal, PeerHostError))):
                multihost.announce_local_error(self._dispatched)
            raise
        finally:
            if whole_run_trace:
                jax.profiler.stop_trace()
            if self._trace is not None:
                self._trace.close()
            if self._auto_trace is not None:
                self._auto_trace.close()

    def _loop(self) -> None:
        cfg = self.cfg
        it = self._batch_iter = iter(self.prefetcher)
        sync_freq = max(1, cfg.signal_sync_frequency)
        first_iteration = True
        while self.training_step < cfg.training_steps:
            with span("ftl:train.step", step=self.training_step):
                self._iteration(it, sync_freq, first_iteration)
            first_iteration = False
        self._drain_inflight()
        self._emit_tail_window()
        if (self._compiled_eval is not None
                and self.training_step % cfg.eval_frequency != 0):
            self._evaluate()  # final eval unless the last step just ran one

    def _iteration(self, it, sync_freq: int, first_iteration: bool) -> None:
        """One pass of the step loop: boundary checks, next batch, dispatch,
        metric consumption, periodic save / eval."""
        cfg = self.cfg
        if self.chaos is not None:
            # Sync-boundary faults (kv_delay / kv_fail) fire BEFORE the
            # real agreement round below, modeling a slow or failed
            # KV-store round at the exact point one would hurt.
            self.chaos.on_sync_boundary(self, self.training_step)
        with span("ftl:train.signal_check"):
            if self._sync_signals:
                # Host-side non-blocking poll FIRST: a peer's announced
                # local fault must stop this host before it dispatches
                # further steps the faulted peer will never join (pod fault
                # fence, ft/multihost.py). One KV round trip per iteration,
                # no device work, no drain.
                if multihost.peer_error_pending():
                    raise PeerHostError()
                # Cluster-wide signal agreement at sync boundaries, over
                # the KV store (ft/multihost.py agree_on_signal): pure
                # host-side gRPC — no device collective, so the dispatch
                # pipeline keeps flowing through the boundary, and a peer
                # that faults or dies mid-agreement cannot wedge this
                # host's device queue (review r5; the old allgather form
                # both forced a drain per boundary and could strand a
                # survivor's queued programs behind a dead collective).
                # Off-boundary local raises are still skipped — a host
                # raising alone would deadlock the others in their next
                # step collectives. The first iteration always syncs so a
                # signal pending since before setup (see _setup_check) is
                # handled immediately even when the resumed step is
                # off-boundary. Round ids advance identically on every
                # host: boundaries are a pure function of training_step.
                if first_iteration or self.training_step % sync_freq == 0:
                    self._signal_round += 1
                    verdict = multihost.agree_on_signal(
                        self.signal_flag.signum,
                        round_id=self._signal_round,
                        timeout_seconds=self.cfg.peer_timeout_seconds,
                        logger=logger)
                    if verdict is not None:
                        self.signal_flag.signum = None
                        raise TrainingSignal(verdict)
            else:
                self.signal_flag.check()
        t_fetch = time.perf_counter()
        with span("ftl:train.fetch"):
            inputs, labels, data_state = next(it)
        # Data-stall accounting: with the prefetcher healthy this is
        # ~0; a growing counter at /metrics means the input pipeline,
        # not the TPU, is the bottleneck.
        self._m_stall.inc(time.perf_counter() - t_fetch)
        if self._trace is not None:
            self._trace.on_step_start(self.training_step)
        with (self._trace.annotate(self.training_step)
              if self._trace is not None else contextlib.nullcontext()):
            with span("ftl:train.dispatch"):
                self.state, metrics = self._compiled_step(self.state,
                                                          inputs, labels)
        self._dispatched += 1
        self._last_data_state = data_state
        # The jitted step pre-packs (loss, grad_norm) into one array so
        # _consume pays ONE device-to-host transfer (and one sync) per
        # step, not one per metric.
        self._inflight.append((self.training_step, metrics["packed"]))
        while len(self._inflight) >= max(1, cfg.inflight):
            with span("ftl:train.consume"):
                self._consume(*self._inflight.popleft())
        # Deterministic fault injection (ref: train.py:112-113): the
        # single training-loop injection site, fired while the counter
        # still equals the entry's step, after the update. The legacy
        # --raise-error flag is an alias for one 'exception' entry
        # (chaos/injector.py from_config); signal, exception and
        # checkpoint-corruption faults all originate here.
        if self.chaos is not None:
            self.chaos.on_train_step(self, self.training_step)
        if self._trace is not None:
            self._trace.on_step_end(self.training_step)
        self.training_step += 1
        if (cfg.checkpoint_frequency
                and self.training_step % cfg.checkpoint_frequency == 0):
            # The FIRST periodic save blocks to measure the real
            # write wall against the signal lead (the startup budget
            # line only extrapolates a 128 MiB probe — ADVICE r3:
            # on filesystems with throughput cliffs the estimate is
            # optimistic and the operator must learn BEFORE the first
            # preemption, not during it). Later saves are async.
            first = not self._budget_observed
            self._budget_observed = True
            saved = self.save_checkpoint(wait=first, stop_prefetch=False)
            if self._publisher is not None:
                # The pointer must never point at a step without its
                # integrity manifest (the watcher would reject it), so
                # an async save drains before publishing. That trades
                # the async overlap for a durable deployment point —
                # the cadence that wants both is a higher
                # --checkpoint-frequency, not a torn publish.
                self.ckpt_mngr.wait_until_finished()
                self._publisher.publish(saved)
        if (self._compiled_eval is not None
                and self.training_step % cfg.eval_frequency == 0):
            self._evaluate()

    def _emit_tail_window(self) -> None:
        """Close the step-window accounting. Steps drained with
        ``check=False`` (pre-save drains) skip metric consumption by design,
        so a run whose last act was a periodic save would leave its final
        window unrecorded — the goodput stitcher would count those steps'
        wall as lost. One synthetic window event covers the gap."""
        if self._step_window_start is None:
            return
        prev_t, prev_step = self._step_window_start
        last = self.training_step - 1
        if last <= prev_step:
            return
        now_wall = time.time()
        n = last - prev_step
        events.emit(kind="step", step=last, dur=now_wall - prev_t, steps=n,
                    tokens=n * self.throughput.tokens_per_step, tail=True)
        self._step_window_start = (now_wall, last)

    def _evaluate(self) -> None:
        """One held-out pass: ``--eval-batches`` batches, token-weighted mean
        NLL + perplexity. The eval set is fixed and rewound each pass, so
        evaluation is deterministic, independent of the training data
        position, and adds no checkpoint state; its tokenized batches are
        cached after the first pass, and all forward calls are dispatched
        before any result is fetched (no host/device serialization)."""
        if self._eval_batches_cache is None:
            self.eval_loader.set_state({"kind": "map", "next_index": 0})
            self._eval_batches_cache = list(self.eval_loader)
        packed = []
        for inputs, labels in self._eval_batches_cache:
            inputs = jax.device_put(inputs, self.batch_sharding)
            labels = jax.device_put(labels, self.batch_sharding)
            packed.append(self._compiled_eval(self.state.params, inputs,
                                              labels))
        t0 = time.perf_counter()
        totals = np.sum([np.asarray(p) for p in packed], axis=0)
        loss = float(totals[0]) / max(float(totals[1]), 1.0)
        ppl = math.exp(min(loss, 700.0))
        self._m_eval_loss.set(loss)
        logger.info(f"Eval | step {self.training_step} | loss {loss:.4f} | "
                    f"ppl {ppl:.2f} | tokens {int(totals[1])}")
        events.emit(kind="eval", step=self.training_step,
                    dur=time.perf_counter() - t0, loss=loss, ppl=ppl,
                    tokens=int(totals[1]))

    def _drain_inflight(self, check: bool = True, cancelled=None) -> None:
        """Consume every dispatched-but-unfinished step.

        Must run before ANY host-thread collective (signal agreement,
        pre-save barrier): a dispatched step's collectives execute on
        runtime threads, and a collective issued concurrently from the host
        thread can interleave in different orders on different hosts
        (observed as a gloo payload-size mismatch on multi-process CPU
        runs). With the pipeline empty the host's collective is the only
        one in flight anywhere.

        ``check=False`` (exit-handler saves): wait for completion but skip
        the metric consumption — after a fault the remaining steps' metrics
        may be non-finite too, and re-raising inside the save would abort
        the checkpoint the handler exists to write.

        ``cancelled`` (watchdog runs): once set, this thread has been
        abandoned by its watchdog — stop touching the shared deque and
        issue nothing further; the fence owns the drain from here."""
        while self._inflight:
            if cancelled is not None and cancelled.is_set():
                return
            step_no, packed = self._inflight.popleft()
            if check:
                self._consume(step_no, packed)
            else:
                np.asarray(packed)  # completion only

    def _guarded_wait(self, fn, what: str):
        """Run a blocking multihost wait under the fence watchdog
        (ft/multihost.py). On timeout: a pending peer-fault announcement
        means the peer stopped dispatching on purpose — raise
        ``PeerHostError`` so the exit handler runs the fence and the
        coordinated save; no announcement means the peer is dead (SIGKILL,
        node loss) — degrade to a clean no-save exit instead of hanging
        until the scheduler shoots this host too. Single-process (and
        re-entrant) calls run ``fn`` directly. Runs on the persistent
        waiter — this is the per-step path, and a fresh watchdog thread
        per step is pure churn."""
        if not self._sync_signals or self._in_guard:
            return fn(_NEVER_CANCELLED)  # direct execution
        self._in_guard = True
        try:
            ok, result = self._waiter.run(fn,
                                          self.cfg.peer_timeout_seconds)
        finally:
            self._in_guard = False
        if ok:
            return result
        # After the fence the err keys are stale (every host is already in
        # its exit handler) — a timeout there means a peer died mid-save;
        # re-raising inside the exit handler would break the exit-0
        # contract, so degrade instead.
        if (not self._fence_done and multihost.peer_error_pending()
                and not multihost.peer_dead_pending()):
            raise PeerHostError()
        multihost.die_uncoordinated(
            logger, f"{what} exceeded --peer-timeout-seconds "
                    f"{self.cfg.peer_timeout_seconds:g} with no live peer")

    def _consume(self, step_no: int, packed: jnp.ndarray) -> None:
        """Pull one step's packed (loss, grad_norm) to the host — the only
        D2H sync point (the reference syncs via loss.item() at
        train.py:116), and a single transfer. On a pod the wait is
        watchdogged: a step whose collectives a faulted peer never joined
        would otherwise block forever (the finiteness check of a step
        abandoned this way is skipped — the run is ending either way)."""
        vals = self._guarded_wait(lambda _cancelled: np.asarray(packed),
                                  f"metric wait for step {step_no}")
        loss, grad_norm = float(vals[0]), float(vals[1])
        if len(vals) > 2:   # the latent / expert class's (pairs, touched)
            for counter, v in zip(self._m_moe, vals[2:]):
                counter.inc(float(v))
        for direction, calls in self._flash_calls.items():
            for family, n in calls.items():
                self._m_flash[direction].labels(kernel=family).inc(n)
        if not math.isfinite(grad_norm):
            # ref: utils.py:61 error_if_nonfinite -> routed as code error (-1)
            # grad_norm is a replicated global value: every host raises here
            self.error_is_replicated = True
            raise NonFiniteGradientError(
                f"non-finite gradient norm {grad_norm} at step {step_no}")
        self.throughput.step()
        now = time.perf_counter()
        if self._last_consume_t is not None:
            dt = now - self._last_consume_t
            self._m_step_time.observe(dt)
            if self._auto_trace is not None:
                ratio = self._auto_trace.observe(step_no, dt)
                if ratio is not None:
                    events.emit_audit(
                        logger,
                        AUDIT_TRACE_AUTO_FMT.format(ratio=ratio,
                                                    step=step_no),
                        "trace_auto", step=step_no, ratio=ratio,
                        trace_dir=self._auto_trace.trace_dir)
        else:
            # the restart's far boundary: this process has a finished step
            events.emit("first_step_done", step=step_no,
                        resumed=self._resumed)
        self._last_consume_t = now
        self.last_loss = loss
        self._m_loss.set(loss)
        self._m_gnorm.set(grad_norm)
        self._m_stepg.set(step_no)
        self._m_tokens.inc(self.throughput.tokens_per_step)
        if step_no == 1 or step_no % self.cfg.logging_frequency == 0:
            # ref: train.py:115-116 (exact format), plus throughput extras.
            # The audit string stays byte-identical; the paired event
            # carries the window accounting goodput stitching needs.
            prev_t, prev_step = (self._step_window_start
                                 or (time.time(), step_no - 1))
            steps_in_window = max(1, step_no - prev_step)
            now_wall = time.time()
            events.emit_audit(
                logger, AUDIT_STEP_FMT.format(step=step_no,
                                              loss=self.last_loss),
                "step", step=step_no, dur=now_wall - prev_t,
                steps=steps_in_window,
                tokens=steps_in_window * self.throughput.tokens_per_step,
                loss=loss, grad_norm=grad_norm)
            self._step_window_start = (now_wall, step_no)
            # Staleness gauge ages on the logging cadence; save/restore
            # reset it to 0 (checkpoint/manager.py).
            update_checkpoint_age_gauge()
            tps = self.throughput.tokens_per_sec
            if tps:
                window = self.throughput.window_tag or "steady"
                self._m_tps.labels(window=window).set(tps)
                if self._peak_flops:
                    self._m_mfu.set(mfu(tps / max(jax.process_count(), 1)
                                        / max(jax.local_device_count(), 1),
                                        self._flops_per_token,
                                        self._peak_flops))
                for dev, used, limit in per_device_memory_stats():
                    self._m_hbm_used.labels(device=dev).set(used)
                    if limit:
                        self._m_hbm_limit.labels(device=dev).set(limit)
                hbm = hbm_usage_str()
                logger.info(
                    f"Metrics | step {step_no} | grad_norm "
                    f"{grad_norm:.3f} | tokens/s {tps:,.0f}"
                    + (f" | hbm {hbm}" if hbm else "")
                    + (" | window post_resume"
                       if self.throughput.window_tag else ""))
                if self.throughput.window_tag:
                    # the transient window has now been reported once,
                    # tagged; subsequent windows are steady-state again
                    self.throughput.clear_tag()

    # ---------------------------------------------------------- fault fence
    def coordinate_local_error(self) -> bool:
        """Pod fault fence (ft/multihost.py module docstring): converge
        every host on the cluster-maximum dispatched step so the exit
        handler's −1 save can run *coordinated* — the reference's "always
        save on error" guarantee (ref: utils.py:69-81) at pod scale.

        Returns True when converged (the caller then runs the coordinated
        save). On an unreachable peer it does not return: the degraded
        path logs and exits 0 without a checkpoint. Single-process:
        trivially True."""
        if not self._sync_signals:
            return True
        timeout = self.cfg.peer_timeout_seconds
        multihost.publish_stop(self._dispatched)
        # 2x: a peer can spend one full watchdog period blocked in a device
        # wait before its own timeout routes it here to publish its stop.
        stops = multihost.gather_stops(2 * timeout)
        if stops is None:
            multihost.die_uncoordinated(
                logger, "a peer never published its stop step")
        target = max(stops.values())
        if self._dispatched < target:
            logger.info(f"Fault fence: catching up from dispatched step "
                        f"{self._dispatched} to agreed step {target}")
            try:
                self._catch_up_to(target)
            except Exception:
                logger.exception("Fault fence: catch-up failed")
                multihost.publish_dead()
                multihost.die_uncoordinated(
                    logger, f"cannot reach agreed step {target}")
        # poll=peer_dead_pending: a host that declared itself unable to
        # catch up will never complete these steps — degrade within the
        # poll interval instead of burning the whole timeout.
        ok, _ = multihost.watchdog(
            lambda c: self._drain_inflight(check=False, cancelled=c),
            timeout, poll=multihost.peer_dead_pending)
        if not ok:
            multihost.die_uncoordinated(
                logger, "peer unresponsive while draining at the fence")
        self._fence_done = True
        return True

    def _catch_up_to(self, target: int) -> None:
        """Dispatch real steps until this host reaches the fence's agreed
        step. Every host dispatched at most ``target`` programs, so each
        catch-up step completes the peers' already-pending collectives —
        no garbage data, no divergence: the saved state is the one an
        uninterrupted run would have produced."""
        it = self._batch_iter
        if it is None:
            it = self._batch_iter = iter(self.prefetcher)
        while self._dispatched < target:
            inputs, labels, data_state = next(it)
            self.state, metrics = self._compiled_step(self.state, inputs,
                                                      labels)
            self._dispatched += 1
            self.training_step = self._dispatched
            self._last_data_state = data_state
            self._inflight.append((self._dispatched - 1, metrics["packed"]))

    # --------------------------------------------------------------- saving
    def save_checkpoint(self, wait: bool = True,
                        stop_prefetch: bool = True,
                        coordinated: bool = True,
                        fault: bool = False) -> int:
        """Checkpoint the state of every *dispatched* step plus the matching
        data position. All dispatched XLA work completes by construction, so
        zero steps are lost (the reference's guarantee: saved @427, resumed
        @427 — BASELINE.md).

        ``coordinated=False`` (exit handler, error of unknown provenance)
        skips the pre-save barrier — on a pod the other hosts may still be
        stepping and would never reach it."""
        if stop_prefetch:
            self.prefetcher.stop()
        if coordinated:
            # The barrier is a host-thread collective: the dispatch pipeline
            # must be empty first (see _drain_inflight). No-op when the
            # caller (signal check, injection, loop end) already drained;
            # check=False so a post-fault save cannot re-raise on the
            # remaining steps' (possibly also non-finite) metrics. On a pod
            # the whole sequence is watchdogged: a peer dying between the
            # fence and here must not hang the save forever.
            def _pre_save(cancelled):
                self._drain_inflight(check=False, cancelled=cancelled)
                if cancelled.is_set():
                    return  # abandoned: no fresh collectives
                barrier("ftl:pre-save")  # all hosts drained, same step

            self._guarded_wait(_pre_save, "pre-save drain/barrier")
        step = int(jax.device_get(self.state.step))
        data_state = self._last_data_state or self.loader.get_state()
        if self._sync_signals and wait:
            # The sharded write is itself a cross-host collective — a peer
            # dying mid-write must not hang the survivors until the
            # scheduler shoots them (that would break the exit-0
            # never-mark-failed contract). FAULT-path bound: the larger of
            # the peer watchdog and 2x the signal lead (a fault save
            # slower than the lead is lost to the scheduler anyway).
            # HEALTHY blocking saves (the first periodic write, which
            # exists to measure the real filesystem) get a bound scaled to
            # the startup write-probe estimate with a 10x margin — a slow
            # but live filesystem warns, only a genuinely wedged
            # collective degrades (review r5, both directions). Orbax's
            # atomic commit makes an abandoned partial write invisible.
            bound = max(self.cfg.peer_timeout_seconds,
                        2.0 * self.cfg.signal_lead_seconds)
            if not fault:
                est = self._est_save_seconds
                bound = max(bound, 10.0 * est if est else 3600.0, 600.0)
            ok, _ = multihost.watchdog(
                lambda _c: self.ckpt_mngr.save(step, self.state, data_state,
                                               wait=True), bound)
            if not ok:
                multihost.die_uncoordinated(
                    logger, "collective checkpoint write stalled")
        else:
            self.ckpt_mngr.save(step, self.state, data_state, wait=wait)
        self._m_saves.inc()
        if wait and self.ckpt_mngr.last_save_seconds is not None:
            self._m_save.observe(self.ckpt_mngr.last_save_seconds)
        events.emit(kind="ckpt_save", step=step,
                    dur=(self.ckpt_mngr.last_save_seconds
                         if wait else None),
                    blocking=bool(wait), fault=bool(fault))
        if wait and self.ckpt_mngr.last_save_seconds is not None:
            # observed wall for blocking (fault-path) saves: the number the
            # startup budget estimate exists to predict
            from ..checkpoint.manager import state_bytes

            secs = self.ckpt_mngr.last_save_seconds
            total = state_bytes(self.state)
            logger.info(f"Checkpoint write | {total / 1e9:.2f} GB in "
                        f"{secs:.1f} s ({total / 1e9 / max(secs, 1e-6):.2f} "
                        f"GB/s)")
            # Re-check the budget against OBSERVED reality (ADVICE r3):
            # the startup estimate extrapolates a 128 MiB probe, which can
            # be optimistic on network filesystems with throughput cliffs
            # at multi-GB writes or uneven host shards. A measured save
            # that blows the lead is the ground truth the warning exists
            # for.
            lead = self.cfg.signal_lead_seconds
            if secs > lead:
                logger.warning(
                    f"Checkpoint budget EXCEEDED (observed): this save took "
                    f"{secs:.0f} s > the {lead} s signal lead — the startup "
                    f"estimate was optimistic for this filesystem; a "
                    f"preemption may outrun the save.")
        return step

    def close(self) -> None:
        """Stop and JOIN everything this trainer started, and finish
        Orbax's background work, while the interpreter is still whole —
        the exit-0 contract (train.py) dies with any thread left inside
        native code at finalization (data/prefetch.py ``close``)."""
        memory = device_memory_report()
        if memory:
            logger.info(f"Device memory | {memory}")
        self.prefetcher.close()
        self.ckpt_mngr.close()
        if self._trace is not None:
            self._trace.close()
        if self._auto_trace is not None:
            self._auto_trace.close()
        if self._heartbeat is not None:
            self._heartbeat.stop()
            self._heartbeat = None
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        events.flush()
        if self._mesh_ctx is not None:
            self._mesh_ctx.__exit__(None, None, None)
            self._mesh_ctx = None
