"""Config / flag system (ref: utils.py:112-203 + env at utils.py:11-12).

Every reference flag is kept with the same name, type, and default so that the
reference's ``TRAINING_CMD`` lines (ref: train.sh:16-27) parse unchanged.
TPU-specific flags (mesh shape, attention impl, checkpointing cadence, ...)
are additive.

Environment contract (ref: utils.py:11-12, train.py:16):
- ``WORKDIR``       — job working dir, used for self-resubmit (``sbatch $WORKDIR/train.sh``)
- ``SLURM_JOB_ID``  — names the checkpoint of *this* job (``checkpoint_{JOBID}``)
"""

import argparse
import dataclasses
import os
from typing import Optional

WORKDIR = os.getenv("WORKDIR", "")
JOBID = os.environ.get("SLURM_JOB_ID")


@dataclasses.dataclass
class TrainConfig:
    """Typed view over the parsed flags (the reference passes the raw Namespace)."""

    # --- reference flags (ref: utils.py:114-201) ---
    dataset: str = ""
    checkpoint_path: str = ""
    checkpoint_id: str = ""
    tokenizer_name_or_path: str = "unsloth/Mistral-Nemo-Base-2407-bnb-4bit"
    sequence_length: int = 4096
    batch_size: int = 1
    fused_optimizer: bool = False  # no-op on TPU: XLA fuses the optax update
    learning_rate: float = 1e-5
    lr_warmup_steps: int = 10
    training_steps: int = 1000
    logging_frequency: int = 5
    grad_max_norm: float = 1.0
    model_dtype: str = "bf16"
    compile: bool = False  # no-op on TPU: the train step is always jitted
    raise_error: bool = False  # legacy alias for --chaos "step=N:exception"
    error_step: int = 100
    # Declarative fault schedule (chaos/schedule.py grammar or a JSON file):
    # "step=<N>:<fault>[=<arg>][@rank=<R>];..." — seeded by --seed.
    chaos: str = ""
    # Restrict --raise-error to one process index (a host-LOCAL fault, the
    # pod fence's test shape); -1 = raise on every process (replicated,
    # the reference's single-process semantics).
    error_local_rank: int = -1
    # --- model selection (reference hard-codes Llama-3-8B in train.py:43-53) ---
    model: str = "gpt2-125m"
    vocab_size: int = 0  # 0 -> from tokenizer (ref: train.py:51)
    # --- TPU-native additions ---
    seed: int = 0
    dp: int = -1  # data-parallel mesh size; -1 = fill remaining devices
    fsdp: int = 1  # FSDP (param/optimizer sharding) mesh size
    tp: int = 1  # tensor-parallel mesh size
    sp: int = 1  # sequence-parallel (ring attention) mesh size
    pp: int = 1  # pipeline-parallel mesh size (needs --layer-impl scan)
    microbatches: int = 0  # pipeline microbatches (0 = one per stage)
    pp_schedule: str = "1f1b"  # 1f1b (O(pp) activation memory) | gpipe
    pp_stage_unroll: bool = True  # unroll each stage's layer loop (models/configs.py)
    ep: int = 1  # expert-parallel mesh size (needs an MoE model)
    # MoE overrides; None = keep the model preset's values
    moe_experts: Optional[int] = None
    moe_top_k: Optional[int] = None
    moe_capacity_factor: Optional[float] = None
    moe_aux_weight: Optional[float] = None
    moe_impl: Optional[str] = None
    attention_impl: str = "auto"  # auto | xla | pallas | ring
    sp_layout: str = "zigzag"  # zigzag (causal-balanced ring) | contiguous
    embed_impl: str = "auto"  # auto | gather | one_hot (one_hot: TP-friendly)
    layer_impl: str = "loop"  # loop | scan (scan: O(1) compile time in depth)
    remat: bool = False  # jax.checkpoint each block (trade FLOPs for HBM)
    master_weights: str = "same"  # same | fp32 (fp32 optimizer master copy)
    data_loading: str = "map"  # map (ParquetDataset path) | packed (iterable)
    # Pod data path: host = each process tokenizes only its own devices'
    # batch rows (map path; O(1) in host count); replicated = every host
    # builds the full global batch; auto = host on pods, replicated alone.
    data_sharding: str = "auto"
    shuffle: bool = False  # seeded per-epoch shuffle (default: reference's strict doc order)
    # exact = np.permutation per epoch (O(corpus) memory per host);
    # feistel = keyed bijection computed per sample (O(1) memory — the
    # pod-scale form; resume state is identical in shape either way)
    shuffle_impl: str = "exact"
    pretokenize_dir: str = ""  # cache dir for one-time tokenization (map path)
    legacy_packing: bool = True  # reproduce reference packing quirks (dataset.py:78,93)
    checkpoint_frequency: int = 0  # 0 = fault-triggered only (reference behavior)
    checkpoint_keep: int = 2  # Orbax max_to_keep (older steps GC'd)
    # Deployment loop (deploy/): after each periodic save's integrity
    # manifest commits, host 0 atomically points published.json at the
    # step so a --follow serving process hot-reloads it.
    publish: bool = False
    eval_dataset: str = ""  # held-out parquet; empty = use --dataset
    eval_frequency: int = 0  # evaluate every N steps (0 = off)
    eval_batches: int = 8  # batches per evaluation pass
    prefetch: int = 2  # host->device prefetch depth (reference has none)
    inflight: int = 2  # max dispatched-but-unfinished steps (bounds signal latency)
    grad_accum: int = 1  # gradient-accumulation slices per step (memory/batch)
    lr_schedule: str = "constant"  # constant (reference) | cosine
    lr_decay_steps: int = 0  # cosine horizon (0 = --training-steps)
    # Multihost: steps between cluster-wide signal agreements. The
    # agreement is a host-side KV-store round (ft/multihost.py) — it no
    # longer drains the dispatch pipeline, but it is still a cluster
    # rendezvous (every host waits for the slowest), so every N steps
    # bounds signal latency to N*step_time (vs the 120 s USR1 lead)
    # without paying the rendezvous each step.
    signal_sync_frequency: int = 5
    # Bound (seconds) on every blocking multihost wait (metric fetch, the
    # KV signal-agreement round, fence stop-gather, pre-save barrier/
    # drain; the collective checkpoint write uses a derived, larger
    # bound). A wait outliving it with a peer-fault announcement pending
    # routes to the fault fence; with none, the peer is presumed dead and
    # the host degrades to a clean no-save exit 0. Must exceed the
    # slowest legitimate step + drain on the target pod.
    peer_timeout_seconds: float = 300.0
    # The scheduler's pre-termination warning lead (seconds): Slurm arms
    # SIGUSR1 this long before the time limit (ref train.sh:12,
    # --signal=USR1@120). The trainer checks its estimated checkpoint
    # save time against this budget at startup (checkpoint/manager.py).
    signal_lead_seconds: int = 120
    profile_dir: str = ""  # jax.profiler trace output; "" = off
    # Windowed profiler capture "A:B" (steps A..B inclusive; obs/trace.py).
    # Traces land in --profile-dir (or <checkpoint-path>/traces when unset).
    # Unlike bare --profile-dir, the capture is bounded — usable mid-run on
    # long jobs.
    trace_steps: str = ""
    # Reactive profiler window (obs/trace.py AutoTraceWindow): arm a
    # bounded capture automatically, once per run, when a step's wall
    # time exceeds 2x the rolling median. Ignored when --trace-steps is
    # set (one profiler owner at a time).
    auto_trace: bool = False
    # Structured JSONL flight-recorder output dir (obs/events.py); "" =
    # <checkpoint-path>/events, "off" = disabled. One events_<jobid>.jsonl
    # per job; scripts/goodput_report.py stitches them across restarts.
    event_log_dir: str = ""
    # Serve the metric registry at http://host:PORT/metrics (Prometheus
    # text format, obs/prometheus.py); 0 = off.
    metrics_port: int = 0
    # Per-host heartbeat publish interval through the ft/multihost.py KV
    # store (exported as ftl_host_heartbeat_* gauges); 0 = off. Every
    # host publishes and sweeps regardless of --metrics-port — the age
    # gauges also feed the flight recorder, not just a scraper.
    heartbeat_seconds: float = 10.0
    # JAX persistent compilation cache directory (utils/compile_cache.py):
    # None = the fixed in-checkout default, "" = off; the
    # JAX_COMPILATION_CACHE_DIR env var beats both. A warm cache turns the
    # restart-after-preemption compile into a disk read — the build time
    # lands in the flight recorder either way, so goodput reports show
    # cold vs warm directly.
    compile_cache_dir: Optional[str] = None
    resubmit_command: str = ""  # override for tests; default: sbatch $WORKDIR/train.sh
    distributed: bool = False  # call jax.distributed.initialize() (multi-host pods)

    def event_log_path(self, job_id: str) -> str:
        """Resolved flight-recorder path for this job; '' = disabled."""
        if self.event_log_dir == "off":
            return ""
        base = self.event_log_dir or (
            os.path.join(self.checkpoint_path, "events")
            if self.checkpoint_path else "")
        return os.path.join(base, f"events_{job_id}.jsonl") if base else ""


def get_args(argv: Optional[list] = None) -> TrainConfig:
    """Parse flags. Mirrors ref utils.py:112-203 plus TPU additions."""
    parser = argparse.ArgumentParser(description="TPU-native fault-tolerant LLM training")
    # --- reference flag set, names/defaults preserved (ref: utils.py:114-201) ---
    parser.add_argument(
        "--dataset",
        type=str,
        default=os.path.join(WORKDIR, "data", "train_data.parquet") if WORKDIR else "",
        help="Parquet source with a 'text' column: one file, a directory "
             "of *.parquet shards, or a glob pattern",
    )
    parser.add_argument(
        "--checkpoint-path",
        type=str,
        default=f"{WORKDIR}/checkpoints",
        help="Directory where checkpoints are saved/loaded",
    )
    parser.add_argument(
        "--checkpoint-id",
        type=str,
        default="",
        help="Job id whose checkpoint_{id} directory to resume from",
    )
    parser.add_argument(
        "--tokenizer-name-or-path",
        type=str,
        default="unsloth/Mistral-Nemo-Base-2407-bnb-4bit",
        help="HF tokenizer name/path, or 'byte' for the built-in offline byte tokenizer",
    )
    parser.add_argument("--sequence-length", type=int, default=4096)
    parser.add_argument("--batch-size", type=int, default=1)
    parser.add_argument(
        "--fused-optimizer",
        action="store_true",
        help="Accepted for CLI parity; XLA always fuses the optimizer update on TPU",
    )
    parser.add_argument("--learning-rate", type=float, default=1e-5)
    parser.add_argument("--lr-warmup-steps", type=int, default=10)
    parser.add_argument("--training-steps", type=int, default=1000)
    parser.add_argument("--logging-frequency", type=int, default=5,
                        help="Log every --logging-frequency steps")
    parser.add_argument("--grad-max-norm", type=float, default=1)
    parser.add_argument("--model-dtype", type=str, default="bf16",
                        help="Dtype for parameters, gradients and optimizer states")
    parser.add_argument(
        "--compile",
        action="store_true",
        help="Accepted for CLI parity; the train step is always jitted on TPU",
    )
    parser.add_argument("--raise-error", action="store_true",
                        help="Raise an error in the training loop at "
                             "--error-step (legacy alias for --chaos "
                             "'step=N:exception')")
    parser.add_argument("--chaos", type=str, default="",
                        help="Declarative fault schedule: "
                             "'step=<N>:<fault>[=<arg>][@rank=<R>]' entries "
                             "separated by ';' (faults: sigusr1, sigterm, "
                             "exception, ckpt_corrupt, loader_stall, "
                             "kv_delay, kv_fail), or a JSON schedule file "
                             "path. Injections are seeded by --seed.")
    parser.add_argument("--error-step", type=int, default=100,
                        help="Step at which to raise an error if --raise-error is set")
    parser.add_argument("--error-local-rank", type=int, default=-1,
                        help="Raise the --raise-error injection only on "
                             "this process index (a host-local fault, "
                             "exercising the pod fault fence); -1 = all "
                             "processes")
    # --- model selection ---
    parser.add_argument("--model", type=str, default="gpt2-125m",
                        help="Model preset: gpt2-125m | llama3-8b | tiny")
    parser.add_argument("--vocab-size", type=int, default=0,
                        help="0 = take vocab size from the tokenizer")
    # --- TPU-native additions ---
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--dp", type=int, default=-1, help="data-parallel size (-1: infer)")
    parser.add_argument("--fsdp", type=int, default=1, help="FSDP shard size")
    parser.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    parser.add_argument("--sp", type=int, default=1, help="sequence-parallel (ring) size")
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline-parallel size (needs --layer-impl scan)")
    parser.add_argument("--microbatches", type=int, default=0,
                        help="pipeline microbatches (0 = one per stage)")
    parser.add_argument("--pp-schedule", type=str, default="1f1b",
                        choices=["1f1b", "gpipe"],
                        help="pipeline schedule: 1f1b interleaves each "
                             "microbatch's backward (O(pp) activation "
                             "memory); gpipe stores all microbatches")
    parser.add_argument("--no-pp-stage-unroll", dest="pp_stage_unroll",
                        action="store_false",
                        help="Scan (rather than unroll) each pipeline "
                             "stage's layer loop: O(1) compile time in "
                             "stage depth, ~22%% slower (the unrolled "
                             "default's pattern measured on-chip, "
                             "BASELINE.md round 4)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel size (needs an MoE model, "
                             "e.g. --model tiny-moe or --moe-experts N)")
    parser.add_argument("--moe-experts", type=int, default=None,
                        help="Mixture-of-Experts expert count (overrides "
                             "the preset; 0 = dense FFN)")
    parser.add_argument("--moe-top-k", type=int, default=None)
    parser.add_argument("--moe-capacity-factor", type=float, default=None)
    parser.add_argument("--moe-aux-weight", type=float, default=None,
                        help="weight of the router load-balancing loss")
    parser.add_argument("--moe-impl", type=str, default=None,
                        choices=["auto", "capacity", "sorted"],
                        help="MoE dispatch: capacity = GShard slots (drops "
                             "overflow, expert-parallel capable); sorted = "
                             "dropless ragged-dot grouped GEMMs")
    parser.add_argument("--attention-impl", type=str, default="auto",
                        choices=["auto", "xla", "pallas", "ring"])
    parser.add_argument("--sp-layout", type=str, default="zigzag",
                        choices=["zigzag", "contiguous"],
                        help="Sequence layout under --sp: zigzag balances "
                             "causal work around the ring (~2x fewer FLOPs)")
    parser.add_argument("--embed-impl", type=str, default="auto",
                        choices=["auto", "gather", "one_hot"],
                        help="Token-embedding lookup; one_hot contracts a "
                             "vocab-sharded table on the MXU (auto: one_hot "
                             "iff tensor-parallel)")
    parser.add_argument("--layer-impl", type=str, default="loop",
                        choices=["loop", "scan"],
                        help="Trunk form: loop unrolls each block; scan "
                             "compiles one block body over layer-stacked "
                             "params (O(1) compile time in depth)")
    parser.add_argument("--remat", action="store_true",
                        help="Rematerialize each transformer block (saves HBM)")
    parser.add_argument("--master-weights", type=str, default="same",
                        choices=["same", "fp32"])
    parser.add_argument("--data-loading", type=str, default="map",
                        choices=["map", "packed"])
    parser.add_argument("--data-sharding", type=str, default="auto",
                        choices=["auto", "host", "replicated"],
                        help="host: each process tokenizes only the batch "
                             "rows its devices consume (map path; removes "
                             "the O(hosts) redundant-tokenization cliff); "
                             "replicated: every host builds the full "
                             "batch; auto: host on multi-process runs")
    parser.add_argument("--shuffle", action="store_true",
                        help="Deterministic per-epoch data shuffling keyed "
                             "on --seed; iterator state stays a single "
                             "position, so bit-exact O(1) resume is "
                             "preserved (the reference trains in strict "
                             "document order, which produces order "
                             "artifacts in multi-epoch runs)")
    parser.add_argument("--shuffle-impl", type=str, default="exact",
                        choices=["exact", "feistel"],
                        help="exact: np.permutation per epoch (O(corpus) "
                             "host memory); feistel: keyed 4-round Feistel "
                             "bijection per sample (O(1) memory, the "
                             "pod-scale form; each row still appears "
                             "exactly once per epoch)")
    parser.add_argument("--pretokenize-dir", type=str, default="",
                        help="Tokenize the corpus once into a memmap cache "
                             "here; steady-state loading becomes a row "
                             "read (map path only). On multi-host pods this "
                             "MUST be on a filesystem shared by all hosts: "
                             "process 0 builds, the others poll for the "
                             "finished cache file")
    parser.add_argument("--no-legacy-packing", dest="legacy_packing",
                        action="store_false",
                        help="Fix the reference packing quirks (buffer discard / doc re-read)")
    parser.add_argument("--checkpoint-frequency", type=int, default=0,
                        help="Save every N steps; 0 = fault-triggered only (reference behavior)")
    parser.add_argument("--checkpoint-keep", type=int, default=2,
                        help="Orbax max_to_keep: retained checkpoint steps "
                             "(older ones are garbage-collected). Raise it "
                             "when --publish serves older steps (a "
                             "published step must outlive the pointer)")
    parser.add_argument("--publish", action="store_true",
                        help="After each periodic save's integrity manifest "
                             "commits, atomically point published.json at "
                             "the step (deploy/publish.py, host 0) so a "
                             "serve.py --follow process hot-reloads it")
    parser.add_argument("--eval-dataset", type=str, default="",
                        help="Held-out parquet (file/dir/glob) for --eval-frequency; "
                             "empty = evaluate on --dataset")
    parser.add_argument("--eval-frequency", type=int, default=0,
                        help="Evaluate every N steps (0 = off)")
    parser.add_argument("--eval-batches", type=int, default=8,
                        help="Batches per evaluation pass")
    parser.add_argument("--lr-schedule", type=str, default="constant",
                        choices=["constant", "cosine"],
                        help="constant = the reference's warmup-constant "
                             "LambdaLR; cosine decays to 10 percent over "
                             "--lr-decay-steps")
    parser.add_argument("--lr-decay-steps", type=int, default=0,
                        help="cosine decay horizon (0 = --training-steps)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="Accumulate gradients over N batch slices per "
                             "step (token-weighted; peak activation memory "
                             "drops ~N-fold)")
    parser.add_argument("--prefetch", type=int, default=2)
    parser.add_argument("--inflight", type=int, default=2)
    parser.add_argument("--signal-sync-frequency", type=int, default=5)
    parser.add_argument("--peer-timeout-seconds", type=float, default=300.0,
                        help="Watchdog bound on blocking multihost waits; "
                             "on expiry the host either routes a peer's "
                             "announced fault to the fence or, with no "
                             "announcement, presumes the peer dead and "
                             "exits 0 cleanly without a checkpoint")
    parser.add_argument("--signal-lead-seconds", type=int, default=120,
                        help="scheduler pre-termination warning lead (the "
                             "USR1@N contract); the startup checkpoint-"
                             "budget check warns when the estimated save "
                             "exceeds it")
    parser.add_argument("--profile-dir", type=str, default="")
    parser.add_argument("--trace-steps", type=str, default="",
                        help="Windowed jax.profiler capture 'A:B' (steps A "
                             "through B inclusive, obs/trace.py); bounded, "
                             "so usable mid-run on long jobs. Output: "
                             "--profile-dir or <checkpoint-path>/traces")
    parser.add_argument("--auto-trace", action="store_true",
                        help="Arm a bounded profiler capture automatically "
                             "(once per run) when a step's wall time "
                             "regresses past 2x the rolling median "
                             "(obs/trace.py AutoTraceWindow); ignored when "
                             "--trace-steps is set")
    parser.add_argument("--event-log-dir", type=str, default="",
                        help="Flight-recorder JSONL dir (obs/events.py): "
                             "one events_<jobid>.jsonl per job, stitched "
                             "across restarts by scripts/goodput_report.py."
                             " '' = <checkpoint-path>/events, 'off' = "
                             "disabled")
    parser.add_argument("--metrics-port", type=int, default=0,
                        help="Serve Prometheus /metrics on this port "
                             "(obs/prometheus.py); 0 = off")
    parser.add_argument("--heartbeat-seconds", type=float, default=10.0,
                        help="Per-host heartbeat publish interval (KV "
                             "store; ftl_host_heartbeat_* gauges); 0 = off")
    parser.add_argument("--compile-cache-dir", type=str, default=None,
                        help="JAX persistent compilation cache directory "
                             "(default: .jax_compile_cache in the checkout; "
                             "'' = off; the JAX_COMPILATION_CACHE_DIR env "
                             "var wins over this flag). Warm restarts skip "
                             "the train-step XLA compile; build time is "
                             "logged cold vs warm through the flight "
                             "recorder")
    parser.add_argument("--resubmit-command", type=str, default="",
                        help="Override the self-resubmit command (tests); "
                             "default: sbatch $WORKDIR/train.sh $SLURM_JOB_ID")
    parser.add_argument("--distributed", action="store_true",
                        help="jax.distributed.initialize() for multi-host pods")
    args = parser.parse_args(argv)
    fields = {f.name for f in dataclasses.fields(TrainConfig)}
    return TrainConfig(**{k: v for k, v in vars(args).items() if k in fields})
