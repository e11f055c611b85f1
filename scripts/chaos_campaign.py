"""Seeded chaos survival campaign: inject -> die/drain -> resume -> verify.

Runs each fault class end-to-end through the real CLI (train.py): a fresh
tiny-model job takes one scheduled fault (chaos/schedule.py grammar), the
exit policy runs (save / no-save / requeue), a chained job resumes from the
survivors' checkpoints, and the audit trail + flight-recorder event logs are
machine-checked — the same strings the reference's README greps for, plus
the integrity/fallback trail this repo adds. Per-scenario goodput % and MTTR
come from stitching the scenario's event logs (obs/goodput.py).

Usage:
    python scripts/chaos_campaign.py --seed 0
    python scripts/chaos_campaign.py --scenarios ckpt_corrupt,loader_stall \
        --out logs/chaos_campaign.txt

Scenario matrix (all seeded; faults land at step 12 of a 30-step run,
periodic checkpoints every 5 steps):

  sigusr1      SIGUSR1 via os.kill at step 12 -> save @13 + requeue
               attempt -> resume @13
  sigterm      SIGTERM at step 12 -> NO save -> resume from periodic @10
               (steps 11-12 are replayed, visible in the goodput report)
  exception    the reference's simulated error -> save @13, no requeue ->
               resume @13
  ckpt_corrupt error -> fault save @13 -> injector flips a seeded byte in
               the committed step-13 state -> the resume DETECTS it
               (integrity manifest), falls back to @10 audited, resumes
  loader_stall 2 s prefetch-worker stall at step 15; the run completes
               with every one of its 30 full-precision losses bit-equal
               to the clean baseline's (no token replayed, none skipped)
  deploy       continuous-deployment loop (deploy/): a publishing train
               run commits steps 5..30; a live serve.py --follow process
               starts on a rolled-back publish of step 10, absorbs hot
               swaps to 20 and 30 WITHOUT dropping its in-flight
               requests, rejects a chaos-corrupted publish of step 15
               (verify-before-load) while continuing to serve on 30, and
               its post-swap output streams bit-match a fresh serve
               restored directly at step 30
  fleet        serving-fleet migration (inference/fleet.py + router.py):
               two fleet hosts register heartbeat leases; the router
               admits 4 requests (3 greedy + 1 sampled) from an intake
               file; host h0 is SIGKILLed mid-decode (host_kill, no
               drain), the router's lease sweep declares it dead,
               tombstones it and migrates its in-flight requests onto
               h1, which replays each journaled committed prefix; h1
               also absorbs a heartbeat_delay SHORTER than the ttl
               (slow-but-alive must not trip the verdict). Zero lost
               requests, survivor drains leak-clean, and every stream —
               including the migrated, mid-decode ones — bit-matches an
               unfailed single-host reference serve

  kvstore      fleet-global KV-block store (inference/kvstore.py): two
               fleet hosts share a content-addressed store; h0 publishes
               the four requests' shared prefix train, chaos poisons
               exactly that artifact (store_corrupt, manifest spared)
               and later SIGKILLs h0 mid-decode; cache-affinity routing
               still lands the second request on h0 while the overflow
               goes to h1, whose one fetch CRC-rejects and degrades to
               local recompute. Exactly one publish, exactly one reject,
               zero lost, no torn store state, and every stream
               bit-matches an unfailed single-host reference serve

  disagg       disaggregated prefill/decode serving (inference/fleet.py
               --role): two dedicated prefill engines stream committed
               KV blocks to one dedicated decode engine over the
               checksummed artifact path; chaos SIGKILLs prefill host
               pre0 mid-prompt (prefill_kill, between chunk commits) so
               the router re-prefills its requests on pre1, and flips a
               payload byte in one of pre1's shipments (ship_corrupt,
               manifest spared) so the router CRC-rejects exactly that
               shipment and hands the request to decode as a committed-
               prefix replay. Zero requests lost, every engine drains
               leak-clean, and all four decode streams bit-match an
               unfailed colocated reference serve

  transport    pluggable KV transport (inference/transport.py): an
               in-process prefill/decode scheduler pair shares a
               MemFabric; every exported train is pushed over the mem
               lane, and chaos poisons the FIRST push's fabric manifest
               metadata (mem_corrupt, push ordinal 0) while a payload
               byte flip also corrupts the SAME request's fs artifact —
               its whole ladder fails down to the committed-prefix
               replay; a second request gets only the mem poison and
               degrades one rung to the fs artifact. Every remaining
               train lands on the mem lane, zero requests are lost, no
               blocks leak, and all streams bit-match an unfailed
               colocated reference — the full mem -> fs -> replay
               degradation with nothing dropped at any rung

Bit-exactness evidence: full-precision ``loss`` floats from the step
events, compared against a clean baseline run with the same seed; for
ckpt_corrupt, additionally the integrity manifest of the fallback step dir
is compared CRC-for-CRC against the exception scenario's same-step dir —
two independent runs, identical bytes.

Resumed jobs on some CPU containers die in a known post-restore native
crash (see ROADMAP.md) AFTER the restore/fallback audits land; the
campaign treats those exit codes as survivable-with-note and verifies on
the audit trail, which is durable by the flight-recorder flush contract.
"""

import argparse
import json
import os
import re
import shutil
import signal as _signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fault_tolerant_llm_training_tpu.obs.goodput import (  # noqa: E402
    load_chain,
    stitch,
)
from fault_tolerant_llm_training_tpu.obs import reqtrace  # noqa: E402
from scripts import fleet_timeline  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENARIOS = ("sigusr1", "sigterm", "exception", "ckpt_corrupt",
             "loader_stall", "deploy", "fleet", "tiered", "disagg",
             "kvstore", "transport")
# Known container-level post-restore native crash codes (SIGABRT/SIGSEGV,
# as rc or negative signal): the resumed process dies after the restore
# audits are flushed. Survival is then judged on the audit trail.
CRASH_RCS = {134, 139, -6, -11}


def _env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = env.get(
        "JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_compile_cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # serve.py and deploy/publish.py run as -m modules
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _make_parquet(path: str, seed: int) -> None:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    words = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot"]
    docs = [" ".join(rng.choice(words, size=int(rng.integers(20, 120))))
            for _ in range(128)]
    pq.write_table(pa.table({"text": docs}), path)


def _train_argv(parquet: str, ckpt_path: str, seed: int, **over):
    base = {
        "--dataset": parquet,
        "--checkpoint-path": ckpt_path,
        "--tokenizer-name-or-path": "byte",
        "--model": "tiny",
        "--sequence-length": "128",
        "--batch-size": "2",
        "--training-steps": "30",
        "--lr-warmup-steps": "5",
        "--learning-rate": "1e-3",
        "--logging-frequency": "1",
        "--checkpoint-frequency": "5",
        "--seed": str(seed),
    }
    base.update({k: str(v) for k, v in over.items()})
    argv = [sys.executable, os.path.join(REPO, "train.py")]
    for k, v in base.items():
        argv.append(k)
        if v != "":
            argv.append(v)
    return argv


def _run(argv, job_id: str, timeout: int = 300):
    env = _env()
    env["SLURM_JOB_ID"] = job_id
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.send_signal(_signal.SIGABRT)
        try:
            out, _ = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        return 124, out
    return proc.returncode, out


class _ServeDriver:
    """Background serve.py with line tailing.

    The deploy scenario interleaves publishes with a LIVE decode stream,
    so the serve process's stdout is pumped on a thread and the driver
    blocks on specific audit lines (``wait_for``) to sequence its moves —
    the same reader-thread pattern the serve e2e tests use."""

    def __init__(self, argv, job_id: str):
        env = _env()
        env["SLURM_JOB_ID"] = job_id
        self.proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT, text=True,
                                     env=env)
        self.lines = []
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self):
        for line in self.proc.stdout:
            with self._lock:
                self.lines.append(line.rstrip("\n"))

    def wait_for(self, pattern: str, timeout: float = 240.0):
        """Block until any output line so far matches ``pattern``;
        returns the re.Match or None on timeout / process exit. Every
        call scans the whole buffer (the scenario's patterns are all
        distinct), so out-of-order completions are never skipped."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                snapshot = list(self.lines)
            for line in snapshot:
                m = rx.search(line)
                if m:
                    return m
            if time.monotonic() >= deadline:
                return None
            if (self.proc.poll() is not None
                    and len(snapshot) == len(self.lines)):
                return None
            time.sleep(0.05)

    def output(self) -> str:
        with self._lock:
            return "\n".join(self.lines)

    def finish(self, timeout: int = 90) -> int:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._thread.join(timeout=5)
        return self.proc.returncode


def _serve_argv(ckpts: str, job_id: str, extra):
    return [sys.executable, "-m",
            "fault_tolerant_llm_training_tpu.inference.serve",
            "--checkpoint-path", ckpts, "--checkpoint-job-id", job_id,
            "--model", "tiny", "--tokenizer-name-or-path", "byte",
            "--slots", "2", "--max-len", "256", "--no-eos",
            "--log-frequency", "2"] + list(extra)


def _event_losses(events_dir: str, job_id: str) -> dict:
    """step -> full-precision loss from the job's step events (stronger
    than the 2-decimal log lines for bit-exact comparison)."""
    path = os.path.join(events_dir, f"events_{job_id}.jsonl")
    losses = {}
    if not os.path.isfile(path):
        return losses
    with open(path) as fh:
        for line in fh:
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if ev.get("kind") == "step" and "loss" in ev:
                losses[int(ev["step"])] = ev["loss"]
    return losses


def _state_digest(ckpt_root: str, job_id: str, step: int):
    """Per-array (dtype, shape, crc32-of-bytes) list for a saved step.

    The integrity manifest's file-level CRCs detect corruption WITHIN one
    checkpoint, but Orbax's ocdbt container is not byte-deterministic
    across runs (content-addressed data-file names, timestamped
    metadata), so cross-run identity has to be checked at the restored
    array-value level."""
    import zlib

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    d = os.path.join(ckpt_root, f"checkpoint_{job_id}")
    if not os.path.isdir(os.path.join(d, str(step))):
        return None
    mngr = ocp.CheckpointManager(d)
    try:
        r = mngr.restore(step, args=ocp.args.Composite(
            state=ocp.args.PyTreeRestore()))
    finally:
        mngr.close()
    digest = []
    for leaf in jax.tree_util.tree_leaves(r["state"]):
        arr = np.asarray(leaf)
        digest.append((str(arr.dtype), tuple(arr.shape),
                       zlib.crc32(arr.tobytes()) & 0xFFFFFFFF))
    return digest


class Result:
    def __init__(self, name):
        self.name = name
        self.survived = True
        self.notes = []
        self.goodput_pct = None
        self.mttr_seconds = None
        self.replayed_steps = None

    def check(self, cond: bool, what: str):
        if cond:
            self.notes.append(f"ok: {what}")
        else:
            self.survived = False
            self.notes.append(f"FAIL: {what}")
        return cond

    def note(self, what: str):
        self.notes.append(f"note: {what}")


def _write_postmortem(name: str, work: str) -> str:
    """Fold a scenario's event/trace/journal trails into one HLC-ordered,
    anomaly-annotated timeline (scripts/fleet_timeline.py) and write it
    next to the scenario's workdir as ``postmortem_<name>.txt``. Returns
    the timeline text ('' when the scenario left no trails)."""
    base = os.path.join(work, name)
    if not os.path.isdir(base):
        return ""
    files = fleet_timeline.collect([base])
    entries = fleet_timeline.build_timeline(files)
    if not entries:
        return ""
    text = fleet_timeline.format_timeline(entries)
    out = os.path.join(work, f"postmortem_{name}.txt")
    with open(out, "w") as fh:
        fh.write(text)
    print(f"   post-mortem timeline -> {out}")
    return text


def _check_fleet_postmortem(res: Result, timeline: str) -> None:
    """The fleet drill's causal chain, read off the post-mortem: chaos
    SIGKILLs h0, the router renders the fence verdict, then migrates —
    in HLC order, spanning both hosts' trails plus the router's."""
    if not res.check(bool(timeline),
                     "post-mortem timeline generated from the scenario's "
                     "event/trace/journal trails"):
        return
    lines = timeline.splitlines()

    def first_idx(pred):
        return next((i for i, ln in enumerate(lines) if pred(ln)), None)

    kill = first_idx(lambda ln: "[CHAOS]" in ln and "host_kill" in ln)
    fence = first_idx(lambda ln: "[FENCE]" in ln and "fleet_dead" in ln)
    migrate = first_idx(lambda ln: "[MIGRATE]" in ln)
    res.check(kill is not None and fence is not None
              and migrate is not None,
              "post-mortem annotates the chaos kill, the fence verdict "
              "and the migration")
    if None in (kill, fence, migrate):
        return
    res.check(kill < fence < migrate,
              "SIGKILL -> fence -> migrate chain appears in HLC (causal) "
              "order in the post-mortem timeline")
    res.check("h0" in lines[kill],
              "the annotated kill belongs to host h0's trail")
    res.check("fleet_h1" in timeline or " h1 " in timeline,
              "the timeline spans the surviving host's trail too")


def _resume_rc_ok(res: Result, rc: int, out: str) -> bool:
    if rc == 0:
        return True
    if rc in CRASH_RCS and "Resuming training from training_step" in out:
        res.note(f"resumed job hit the known container post-restore crash "
                 f"(rc={rc}) after the restore audits landed")
        return True
    return False


def _stitch_scenario(res: Result, events_dir: str):
    events = load_chain([events_dir])
    if not events:
        res.note("no event logs found for goodput stitching")
        return
    rep = stitch(events)
    res.goodput_pct = rep.goodput_pct
    res.mttr_seconds = rep.mttr_seconds
    res.replayed_steps = sum(r.replayed_steps for r in rep.restarts)


def run_scenario(name: str, work: str, parquet: str, seed: int,
                 baseline_losses: dict, sbatch: str = "") -> Result:
    res = Result(name)
    ckpts = os.path.join(work, name, "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(ckpts, exist_ok=True)
    job_a, job_b = f"{name}_a", f"{name}_b"

    if name == "loader_stall":
        # checkpoint-frequency 0 to match the baseline oracle: pre-save
        # drains consume steps without emitting their step events, so a
        # checkpointing run records fewer loss events (by design, not loss
        # of determinism) and the 30-vs-30 comparison would be unfair.
        rc, out = _run(_train_argv(
            parquet, ckpts, seed,
            **{"--chaos": "step=15:loader_stall=2s",
               "--checkpoint-frequency": "0"}), job_a)
        res.check(rc == 0, f"run completed rc=0 (got {rc})")
        res.check("[CHAOS] Injected loader_stall at step 15" in out,
                  "stall injection audited")
        res.check("Training completed" in out, "run trained to completion")
        losses = _event_losses(events_dir, job_a)
        res.check(len(losses) == 30, f"all 30 step losses recorded "
                                     f"(got {len(losses)})")
        res.check(losses == baseline_losses,
                  "every loss bit-equals the clean baseline (no token "
                  "replayed or skipped across the stall)")
        _stitch_scenario(res, events_dir)
        return res

    fault_over = {"--chaos": f"step=12:{name}"}
    if name == "sigusr1":
        marker = os.path.join(work, name, "resubmitted")
        fault_over["--resubmit-command"] = (
            sbatch or f"touch {marker}")
    rc, out = _run(_train_argv(parquet, ckpts, seed, **fault_over), job_a)
    res.check(rc == 0, f"fault job exits 0 (got {rc})")
    res.check(f"[CHAOS] Injected {name} at step 12" in out,
              "injection audited")

    if name == "sigusr1":
        res.check("[EXIT HANDLER] Job timed out, saving checkpoint." in out,
                  "USR1 routed to the timeout save policy")
        res.check("Checkpoint saved at step 13" in out, "fault save @13")
        res.check("sbatch requeued" in out, "requeue attempted")
        if not sbatch:
            res.check(os.path.isfile(marker), "resubmit command ran")
        expect_resume = 13
    elif name == "sigterm":
        res.check("[EXIT HANDLER] Job cancelled, terminating." in out,
                  "SIGTERM routed to the no-save cancel policy")
        res.check("Checkpoint saved at step" not in out,
                  "cancel writes no checkpoint")
        expect_resume = 10  # newest periodic save (freq 5, steps 5+10 kept)
    elif name == "exception":
        res.check("[EXIT HANDLER] Error during training encountered, "
                  "saving checkpoint." in out,
                  "error routed to the save-no-requeue policy")
        res.check("Checkpoint saved at step 13" in out, "fault save @13")
        res.check("sbatch requeued" not in out, "code error never requeues")
        expect_resume = 13
    else:  # ckpt_corrupt
        res.check("Checkpoint saved at step 13" in out, "fault save @13")
        res.check("[CHAOS] Corrupted checkpoint step 13" in out,
                  "committed checkpoint corrupted post-manifest")
        expect_resume = 10  # verified fallback target

    rc2, out2 = _run(_train_argv(parquet, ckpts, seed,
                                 **{"--checkpoint-id": job_a}), job_b)
    res.check(_resume_rc_ok(res, rc2, out2),
              f"resume job survives (rc={rc2})")
    if name == "ckpt_corrupt":
        res.check("[CKPT VERIFY] Checkpoint step 13 failed integrity check"
                  in out2, "corruption detected at restore")
        res.check("[CKPT VERIFY] Falling back to checkpoint step 10" in out2,
                  "audited automatic fallback to newest passing step")
    m = re.search(r"Resuming training from training_step (\d+)", out2)
    res.check(m is not None and int(m.group(1)) == expect_resume,
              f"resumed at step {expect_resume} "
              f"(got {m.group(1) if m else 'none'})")

    resumed_losses = _event_losses(events_dir, job_b)
    if resumed_losses:
        mismatch = [s for s, l in resumed_losses.items()
                    if baseline_losses.get(s) != l]
        res.check(not mismatch,
                  f"{len(resumed_losses)} post-resume losses bit-equal the "
                  f"baseline (mismatched steps: {mismatch or 'none'})")
    else:
        res.note("no post-resume step events (container crash window); "
                 "bit-exactness evidenced by the audit trail and the "
                 "cross-scenario checkpoint CRC comparison")
    _stitch_scenario(res, events_dir)
    return res


def run_deploy_scenario(work: str, parquet: str, seed: int) -> Result:
    """Deployment-loop scenario: train-with-publish, then a live serve
    absorbs 2 hot swaps with requests in flight, rejects a corrupt
    publish, and bit-matches a fresh restore (module docstring)."""
    from fault_tolerant_llm_training_tpu.deploy.publish import (
        Publisher,
        read_pointer,
    )

    res = Result("deploy")
    ckpts = os.path.join(work, "deploy", "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(ckpts, exist_ok=True)
    job = "deploy_a"

    # 1. publishing train run: every periodic manifest commit (steps
    # 5..30, keep 6 so none is GC'd) moves published.json, ending at 30
    rc, out = _run(_train_argv(parquet, ckpts, seed,
                               **{"--checkpoint-frequency": "5",
                                  "--checkpoint-keep": "6",
                                  "--publish": ""}), job)
    res.check(rc == 0, f"publishing train run exits 0 (got {rc})")
    res.check("[DEPLOY] Published checkpoint step 30" in out,
              "trainer published the final periodic save")
    ptr = read_pointer(ckpts)
    res.check(ptr is not None and ptr.step == 30,
              "published.json points at step 30 after training")
    if not res.survived:
        return res

    # 2. roll the pointer BACK to step 10 through the operator CLI so the
    # serve under test starts two publishes behind the trainer's tip
    rc, _ = _run([sys.executable, "-m",
                  "fault_tolerant_llm_training_tpu.deploy.publish",
                  "--checkpoint-path", ckpts, "--job-id", job,
                  "--step", "10"], "deploy_pub10")
    ptr = read_pointer(ckpts)
    res.check(rc == 0 and ptr is not None and ptr.step == 10,
              "publish CLI re-pointed the deployment at step 10")

    # 3. live serve on the step-10 publish, tailing a request file
    reqs = os.path.join(work, "deploy", "requests.jsonl")
    open(reqs, "w").close()
    serve_events = os.path.join(work, "deploy", "serve_events.jsonl")
    drv = _ServeDriver(_serve_argv(ckpts, job, [
        "--step", "10", "--seed", str(seed), "--follow",
        "--poll-seconds", "0.2", "--request-file", reqs,
        "--event-log", serve_events]), "deploy_serve")
    outputs = {}
    w3 = [("w3a", "india juliett kilo lima"),
          ("w3b", "mike november oscar papa quebec")]
    try:
        res.check(drv.wait_for(r"Serving ready \| model tiny \| "
                               r"checkpoint step 10",
                               timeout=420) is not None,
                  "serve restored the published step-10 checkpoint")

        # wave 1: long greedy requests that stay in flight across BOTH
        # swaps (the publishes below land a few decode iterations in)
        with open(reqs, "a") as fh:
            for rid in ("w1a", "w1b"):
                fh.write(json.dumps({
                    "id": rid,
                    "prompt": "alpha bravo charlie delta echo foxtrot "
                              "golf hotel",
                    "max_new_tokens": 96, "temperature": 0.0}) + "\n")
        res.check(drv.wait_for(r"Serve step: \d+ \| Active: [12]")
                  is not None, "wave-1 requests admitted and decoding")

        publisher = Publisher(ckpts, job)
        for old, new in ((10, 20), (20, 30)):
            publisher.publish(new)
            m = drv.wait_for(rf"\[DEPLOY\] Weights reloaded: "
                             rf"step {old} -> {new} \| (\d+) in-flight")
            res.check(m is not None, f"publish of step {new} hot-swapped "
                                     f"into the running engine")
            res.check(m is not None and int(m.group(1)) >= 1,
                      f"swap {old}->{new} carried in-flight requests "
                      f"(active={m.group(1) if m else '?'})")

        # the swaps must not have dropped or truncated wave 1
        for rid in ("w1a", "w1b"):
            m = drv.wait_for(rf"Request {rid} done \| length \| "
                             rf"prompt \d+ tok \| generated (\d+) tok")
            res.check(m is not None and int(m.group(1)) == 96,
                      f"{rid} ran to its full 96 tokens across both swaps")

        # 4. corrupt publish: chaos flips a committed byte of step 15
        # AFTER the pointer moves; verify-before-load must reject it
        rc, out = _run([sys.executable, "-m",
                        "fault_tolerant_llm_training_tpu.deploy.publish",
                        "--checkpoint-path", ckpts, "--job-id", job,
                        "--step", "15",
                        "--chaos", "step=15:publish_corrupt",
                        "--seed", str(seed)], "deploy_pub15")
        res.check(rc == 0 and
                  "[CHAOS] Injected publish_corrupt at step 15" in out,
                  "chaos-corrupted publish of step 15 committed")
        res.check(drv.wait_for(r"\[DEPLOY\] Publish of step 15 rejected: "
                               r".*; serving continues on step 30")
                  is not None,
                  "corrupt publish rejected before load; serving "
                  "continues on step 30")

        # wave 3: decoded WHOLLY on the swapped step-30 weights — these
        # output reprs are the bit-match reference
        with open(reqs, "a") as fh:
            for rid, prompt in w3:
                fh.write(json.dumps({"id": rid, "prompt": prompt,
                                     "max_new_tokens": 24,
                                     "temperature": 0.0}) + "\n")
        for rid, _ in w3:
            m = drv.wait_for(rf"Request {rid} output: (.+)$")
            res.check(m is not None,
                      f"{rid} completed on the swapped step-30 weights")
            if m is not None:
                outputs[rid] = m.group(1)

        # drain exactly like training: SIGUSR1 finishes in-flight, exit 0
        drv.proc.send_signal(_signal.SIGUSR1)
        rc = drv.finish()
    finally:
        if drv.proc.poll() is None:
            drv.proc.kill()
            drv.finish(timeout=10)
    out = drv.output()
    res.check(rc == 0, f"serve drained and exited 0 (got {rc})")
    res.check("[EXIT HANDLER] Drained;" in out, "drain audited")

    # flight recorder agrees with the log lines
    kinds = []
    if os.path.isfile(serve_events):
        with open(serve_events) as fh:
            for line in fh:
                try:
                    kinds.append(json.loads(line).get("kind"))
                except json.JSONDecodeError:
                    pass
    res.check(kinds.count("weights_reload") == 2 and
              kinds.count("weights_reload_rejected") == 1,
              "flight recorder: exactly 2 swaps + 1 rejection")

    # 5. fresh serve restored directly at step 30, same prompts/knobs:
    # greedy streams must be bit-identical to the hot-swapped process's
    argv = _serve_argv(ckpts, job, ["--step", "30", "--seed", str(seed),
                                    "--max-new-tokens", "24"])
    for _, prompt in w3:
        argv += ["--prompt", prompt]
    rc, out2 = _run(argv, "deploy_fresh", timeout=600)
    res.check(rc == 0, f"fresh step-30 serve exits 0 (got {rc})")
    fresh = dict(re.findall(r"Request (req\d+) output: (.+)", out2))
    res.check(len(outputs) == 2 and
              fresh.get("req0") == outputs.get("w3a") and
              fresh.get("req1") == outputs.get("w3b"),
              "post-swap streams bit-identical to a fresh restore of "
              "step 30")
    _stitch_scenario(res, events_dir)
    return res


def run_fleet_scenario(work: str, parquet: str, seed: int) -> Result:
    """Serving-fleet migration scenario: SIGKILL one of two fleet hosts
    mid-decode and prove the router migrates its in-flight requests onto
    the survivor with zero loss and bit-exact continuations (module
    docstring)."""
    res = Result("fleet")
    base = os.path.join(work, "fleet")
    ckpts = os.path.join(base, "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(base, exist_ok=True)
    job = "fleet_a"

    # 1. a checkpoint for the fleet to serve (short run; the scenario is
    # about serving faults, not training ones)
    rc, out = _run(_train_argv(parquet, ckpts, seed,
                               **{"--training-steps": "10",
                                  "--checkpoint-frequency": "5"}), job)
    if not res.check(rc == 0, f"fleet training checkpoint committed "
                              f"(got rc {rc})"):
        return res

    store = os.path.join(base, "store")
    jdir = os.path.join(base, "journal")
    intake = os.path.join(base, "intake.jsonl")
    reqs = [
        {"id": "req0", "prompt": "alpha bravo charlie delta",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 11},
        {"id": "req1", "prompt": "echo foxtrot golf hotel",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 12},
        {"id": "req2", "prompt": "india juliett kilo lima",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 13},
        {"id": "req3", "prompt": "mike november oscar papa",
         "max_new_tokens": 48, "temperature": 0.8, "seed": seed + 14},
    ]
    with open(intake, "w") as fh:
        for r in reqs:
            fh.write(json.dumps(r) + "\n")

    def host_argv(hid, chaos):
        return [sys.executable, "-m",
                "fault_tolerant_llm_training_tpu.inference.fleet",
                "--host-id", hid, "--store", store, "--journal-dir", jdir,
                "--checkpoint-path", ckpts, "--checkpoint-job-id", job,
                "--model", "tiny", "--tokenizer-name-or-path", "byte",
                "--slots", "2", "--max-len", "256", "--no-eos",
                "--lease-ttl", "2.0", "--max-run-seconds", "240",
                "--seed", str(seed), "--chaos", chaos,
                "--event-log", os.path.join(base, f"events_{hid}.jsonl")]

    # 2. two hosts: h0 takes a SIGKILL at decode iteration 12 (mid-decode,
    # committed tokens already journaled); h1 takes a 1 s heartbeat stall —
    # SHORTER than the 2 s ttl, so it must NOT be declared dead
    h0 = _ServeDriver(host_argv("h0", "step=12:host_kill"), "fleet_h0")
    h1 = _ServeDriver(host_argv("h1", "step=3:heartbeat_delay=1s"),
                      "fleet_h1")
    router = None
    try:
        res.check(h0.wait_for(r"\[FLEET\] Host h0 joined", timeout=420)
                  is not None, "host h0 joined the fleet with a lease")
        res.check(h1.wait_for(r"\[FLEET\] Host h1 joined", timeout=420)
                  is not None, "host h1 joined the fleet with a lease")

        # 3. router admits the intake and supervises the leases
        router = _ServeDriver(
            [sys.executable, "-m",
             "fault_tolerant_llm_training_tpu.inference.router",
             "--store", store, "--journal-dir", jdir, "--intake", intake,
             "--expected", "4", "--max-seconds", "180",
             "--poll-seconds", "0.1",
             "--event-log", os.path.join(base, "events_router.jsonl")],
            "fleet_router")
        rrc = router.finish(timeout=200)
        res.check(rrc == 0, f"router completed and exited 0 (got {rrc})")
        rc0 = h0.finish(timeout=15)
        # 4. drain the survivor exactly like a single serve
        h1.proc.send_signal(_signal.SIGUSR1)
        rc1 = h1.finish(timeout=120)
    finally:
        for drv in (h0, h1, router):
            if drv is not None and drv.proc.poll() is None:
                drv.proc.kill()
                drv.finish(timeout=10)
    rout = router.output()
    out0, out1 = h0.output(), h1.output()

    res.check(rc0 == -9 and "[CHAOS] Injected host_kill" in out0,
              f"host h0 SIGKILLed mid-decode by chaos (rc {rc0})")
    res.check("[FLEET] Host h0 declared dead" in rout
              and "fencing and migrating" in rout,
              "router declared h0 dead and fenced it")
    migrs = [int(n) for n in re.findall(
        r"\[FLEET\] Migrating request req\d+: h0 -> h1 \(gen \d+, (\d+) "
        r"committed token\(s\) replayed\)", rout)]
    res.check(bool(migrs) and any(n >= 1 for n in migrs),
              f"migration replayed a committed prefix onto the survivor "
              f"(committed counts {migrs})")
    res.check(re.search(r"Fleet router complete: 4 request\(s\) done, "
                        r"\d+ migrated, 0 lost", rout) is not None,
              "zero requests lost: all 4 served")
    res.check("Injected heartbeat_delay" in out1
              and "Host h1 declared dead" not in rout,
              "heartbeat-delayed h1 stayed under its ttl (no false dead "
              "verdict)")
    res.check(rc1 == 0 and "Fleet drain leak guard: clean" in out1,
              f"survivor drained leak-clean and exited 0 (got rc {rc1})")

    # flight recorder agrees with the log lines: one dead verdict, at
    # least one migration, no verdict against the slow-but-alive host
    kinds = []
    ev_path = os.path.join(base, "events_router.jsonl")
    if os.path.isfile(ev_path):
        with open(ev_path) as fh:
            for line in fh:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                kinds.append((ev.get("kind"), ev.get("host")))
    res.check(kinds.count(("fleet_dead", "h0")) == 1
              and ("fleet_dead", "h1") not in kinds
              and sum(1 for k, _ in kinds if k == "fleet_migrate") >= 1,
              "flight recorder: exactly one dead verdict (h0) + the "
              "migrations")

    # 5. unfailed reference: ONE serve.py tails the same intake (same ids,
    # seeds, sampling params) — every fleet stream, including the
    # migrated mid-decode ones, must bit-match it
    ref_reqs = os.path.join(base, "ref_requests.jsonl")
    shutil.copy(intake, ref_reqs)
    ref = _ServeDriver(_serve_argv(ckpts, job, [
        "--seed", str(seed), "--follow", "--poll-seconds", "0.2",
        "--request-file", ref_reqs]), "fleet_ref")
    try:
        for r in reqs:
            res.check(ref.wait_for(rf"Request {r['id']} output: ",
                                   timeout=420) is not None,
                      f"reference serve completed {r['id']}")
        ref.proc.send_signal(_signal.SIGUSR1)
        ref_rc = ref.finish()
    finally:
        if ref.proc.poll() is None:
            ref.proc.kill()
            ref.finish(timeout=10)
    res.check(ref_rc == 0, f"reference serve exited 0 (got {ref_rc})")
    fleet_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                    out0 + "\n" + out1))
    ref_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                  ref.output()))
    res.check(
        len(fleet_outputs) == 4 and all(
            fleet_outputs.get(f"req{i}") == ref_outputs.get(f"req{i}")
            for i in range(4)),
        "migrated streams bit-identical to the unfailed reference serve")

    # 6. request-trace stitch (obs/reqtrace.py): every process wrote a
    # trace_<name>.jsonl next to its event log; joined by trace_id, the
    # migrated request must show ONE trail that spans both hosts, and its
    # migration span's replayed count must equal the journal committed
    # prefix the router logged
    migr_by_id = {rid: int(n) for rid, n in re.findall(
        r"\[FLEET\] Migrating request (req\d+): h0 -> h1 \(gen \d+, (\d+) "
        r"committed token\(s\) replayed\)", rout)}
    traced = {r["request_id"]: r
              for r in reqtrace.stitch([base]) if r["request_id"]}
    trace_ok = bool(migr_by_id)
    for rid, committed in migr_by_id.items():
        tr = traced.get(rid)
        trace_ok = (trace_ok and tr is not None and tr["migrated"]
                    and {"h0", "h1"} <= set(tr["hosts"])
                    and tr["replayed"] == committed)
    res.check(trace_ok,
              "stitched trace: migrated request spans h0 and h1, replay "
              "count matches the journal committed prefix")
    _stitch_scenario(res, events_dir)
    return res


def run_tiered_scenario(work: str, parquet: str, seed: int) -> Result:
    """Tiered KV-block lifecycle scenario: a ``--handoff`` drain ships
    in-flight requests' committed blocks as checksummed artifacts, chaos
    corrupts the FIRST one (``handoff_corrupt``), and the survivor — run
    with a pool too small for its own two requests, so the spill tier
    fires, with ``spill_corrupt`` poisoning its first spill artifact —
    must finish all four streams bit-identical to an unfailed single-host
    reference: verified artifacts import, corrupt ones CRC-reject into
    committed-prefix replay, and the drain leak guard stays strict-clean
    across the device pool and the spill tier."""
    res = Result("tiered")
    base = os.path.join(work, "tiered")
    ckpts = os.path.join(base, "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(base, exist_ok=True)
    job = "tiered_a"

    rc, out = _run(_train_argv(parquet, ckpts, seed,
                               **{"--training-steps": "10",
                                  "--checkpoint-frequency": "5"}), job)
    if not res.check(rc == 0, f"tiered training checkpoint committed "
                              f"(got rc {rc})"):
        return res

    store = os.path.join(base, "store")
    jdir = os.path.join(base, "journal")
    intake = os.path.join(base, "intake.jsonl")
    reqs = [
        {"id": "req0", "prompt": "alpha bravo charlie delta",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 11},
        {"id": "req1", "prompt": "echo foxtrot golf hotel",
         "max_new_tokens": 48, "temperature": 0.7, "top_p": 0.9,
         "seed": seed + 12},
        {"id": "req2", "prompt": "india juliett kilo lima",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 13},
        {"id": "req3", "prompt": "mike november oscar papa",
         "max_new_tokens": 48, "temperature": 0.8, "seed": seed + 14},
    ]
    with open(intake, "w") as fh:
        for r in reqs:
            fh.write(json.dumps(r) + "\n")

    def host_argv(hid, chaos, extra=()):
        return [sys.executable, "-m",
                "fault_tolerant_llm_training_tpu.inference.fleet",
                "--host-id", hid, "--store", store, "--journal-dir", jdir,
                "--checkpoint-path", ckpts, "--checkpoint-job-id", job,
                "--model", "tiny", "--tokenizer-name-or-path", "byte",
                "--slots", "2", "--max-len", "256", "--no-eos",
                "--lease-ttl", "2.0", "--max-run-seconds", "240",
                "--seed", str(seed), "--chaos", chaos,
                "--event-log",
                os.path.join(base, f"events_{hid}.jsonl")] + list(extra)

    # h0: unconstrained pool, --handoff, a SIGUSR1 drain at decode
    # iteration 10 and a byte flip in its FIRST handoff artifact.
    # h1 (the survivor): 8 usable blocks against two requests needing 5
    # each — the second admission MUST spill the first — plus a byte flip
    # in its first spill artifact, so one restore CRC-rejects into replay.
    h0 = _ServeDriver(host_argv(
        "h0", "step=10:sigusr1;step=0:handoff_corrupt", ["--handoff"]),
        "tiered_h0")
    h1 = _ServeDriver(host_argv(
        "h1", "step=0:spill_corrupt",
        ["--kv-num-blocks", "9",
         "--spill-dir", os.path.join(base, "spill_h1")]), "tiered_h1")
    router = None
    try:
        res.check(h0.wait_for(r"\[FLEET\] Host h0 joined", timeout=420)
                  is not None, "host h0 joined the fleet with a lease")
        res.check(h1.wait_for(r"\[FLEET\] Host h1 joined", timeout=420)
                  is not None, "host h1 joined the fleet with a lease")
        router = _ServeDriver(
            [sys.executable, "-m",
             "fault_tolerant_llm_training_tpu.inference.router",
             "--store", store, "--journal-dir", jdir, "--intake", intake,
             "--expected", "4", "--max-seconds", "180",
             "--poll-seconds", "0.1",
             "--event-log", os.path.join(base, "events_router.jsonl")],
            "tiered_router")
        rrc = router.finish(timeout=200)
        res.check(rrc == 0, f"router completed and exited 0 (got {rrc})")
        rc0 = h0.finish(timeout=60)
        h1.proc.send_signal(_signal.SIGUSR1)
        rc1 = h1.finish(timeout=120)
    finally:
        for drv in (h0, h1, router):
            if drv is not None and drv.proc.poll() is None:
                drv.proc.kill()
                drv.finish(timeout=10)
    rout = router.output()
    out0, out1 = h0.output(), h1.output()

    # --- handoff half: exports on h0, verify-or-replay at the router
    exports = re.findall(r"\[HANDOFF\] Block-shipment export request "
                         r"(req\d+)", out0)
    res.check(rc0 == 0 and len(exports) == 2,
              f"h0 drained via --handoff and exported both in-flight "
              f"requests' blocks (rc {rc0}, exports {exports})")
    res.check("[CHAOS] Injected handoff_corrupt" in out0,
              "chaos flipped a payload byte in h0's first handoff "
              "artifact (manifest spared)")
    rejects = re.findall(r"\[HANDOFF\] Block-shipment reject request "
                         r"(req\d+)", rout)
    ships = re.findall(r"\[HANDOFF\] Block-shipment ship request "
                       r"(req\d+)", rout)
    res.check(len(rejects) == 1 and len(ships) == 1
              and set(rejects) | set(ships) == set(exports),
              f"router CRC-rejected exactly the corrupt artifact and "
              f"shipped the other (rejects {rejects}, ships {ships})")
    imports = re.findall(r"\[HANDOFF\] Block-shipment import request "
                         r"(req\d+)", out1)
    res.check(imports == ships,
              f"survivor imported the verified artifact's blocks instead "
              f"of replaying (imports {imports})")
    res.check(re.search(r"Fleet router complete: 4 request\(s\) done, "
                        r"\d+ migrated, 0 lost", rout) is not None,
              "zero requests lost: all 4 served")

    # --- spill half: h1's pool forces a preemption, chaos poisons it
    res.check("[KV TIER] Spill export" in out1
              and "[CHAOS] Injected spill_corrupt" in out1,
              "survivor's constrained pool spilled a request to the host "
              "tier and chaos corrupted the artifact")
    res.check("[KV TIER] Spill reject" in out1,
              "poisoned spill artifact CRC-rejected at restore and fell "
              "back to committed-prefix replay")
    res.check(rc1 == 0 and "Fleet drain leak guard: clean" in out1,
              f"survivor drained leak-clean across device pool + spill "
              f"tier and exited 0 (got rc {rc1})")

    # --- bit-exactness: every stream (handoff-imported, CRC-reject
    # replayed, spill-restored) vs ONE unfailed single-host serve
    ref_reqs = os.path.join(base, "ref_requests.jsonl")
    shutil.copy(intake, ref_reqs)
    ref = _ServeDriver(_serve_argv(ckpts, job, [
        "--seed", str(seed), "--follow", "--poll-seconds", "0.2",
        "--request-file", ref_reqs]), "tiered_ref")
    try:
        for r in reqs:
            res.check(ref.wait_for(rf"Request {r['id']} output: ",
                                   timeout=420) is not None,
                      f"reference serve completed {r['id']}")
        ref.proc.send_signal(_signal.SIGUSR1)
        ref_rc = ref.finish()
    finally:
        if ref.proc.poll() is None:
            ref.proc.kill()
            ref.finish(timeout=10)
    res.check(ref_rc == 0, f"reference serve exited 0 (got {ref_rc})")
    tier_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                   out0 + "\n" + out1))
    ref_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                  ref.output()))
    res.check(
        len(tier_outputs) == 4 and all(
            tier_outputs.get(f"req{i}") == ref_outputs.get(f"req{i}")
            for i in range(4)),
        "all streams (imported, replayed, spill-restored) bit-identical "
        "to the unfailed reference serve")
    _stitch_scenario(res, events_dir)
    return res


def run_disagg_scenario(work: str, parquet: str, seed: int) -> Result:
    """Disaggregated prefill/decode scenario: two dedicated prefill
    engines stream committed KV blocks to one dedicated decode engine
    over the checksummed artifact path; chaos kills one prefill host
    mid-prompt and poisons one of the survivor's shipments (module
    docstring)."""
    res = Result("disagg")
    base = os.path.join(work, "disagg")
    ckpts = os.path.join(base, "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(base, exist_ok=True)
    job = "disagg_a"

    rc, out = _run(_train_argv(parquet, ckpts, seed,
                               **{"--training-steps": "10",
                                  "--checkpoint-frequency": "5"}), job)
    if not res.check(rc == 0, f"disagg training checkpoint committed "
                              f"(got rc {rc})"):
        return res

    store = os.path.join(base, "store")
    jdir = os.path.join(base, "journal")
    intake = os.path.join(base, "intake.jsonl")
    # Long prompts (70+ byte-tokens against 32-token prefill chunks):
    # every prefill takes >= 3 chunk commits, so the prefill_kill at
    # chunk ordinal 1 lands MID-PROMPT and the incremental pipeline
    # ships more than one artifact per request.
    prompts = [
        "alpha bravo charlie delta echo foxtrot golf hotel india "
        "juliett kilo lima",
        "mike november oscar papa quebec romeo sierra tango uniform "
        "victor whiskey",
        "zulu yankee xray whiskey victor uniform tango sierra romeo "
        "quebec papa oscar",
        "one two three four five six seven eight nine ten eleven "
        "twelve thirteen fourteen",
    ]
    reqs = []
    for i, prompt in enumerate(prompts):
        r = {"id": f"req{i}", "prompt": prompt, "max_new_tokens": 48,
             "temperature": 0.0, "seed": seed + 21 + i}
        if i == 3:
            r["temperature"] = 0.8
        reqs.append(r)
    with open(intake, "w") as fh:
        for r in reqs:
            fh.write(json.dumps(r) + "\n")

    def host_argv(hid, role, extra=()):
        return [sys.executable, "-m",
                "fault_tolerant_llm_training_tpu.inference.fleet",
                "--host-id", hid, "--store", store, "--journal-dir", jdir,
                "--checkpoint-path", ckpts, "--checkpoint-job-id", job,
                "--model", "tiny", "--tokenizer-name-or-path", "byte",
                "--max-len", "256", "--prefill-buckets", "16,32",
                "--no-eos", "--lease-ttl", "2.0",
                "--max-run-seconds", "240", "--seed", str(seed),
                "--role", role,
                "--event-log",
                os.path.join(base, f"events_{hid}.jsonl")] + list(extra)

    # pre0: SIGKILLed between its 2nd chunk's commit and its shipment
    # export — shipments stop mid-prompt, the router must re-prefill on
    # pre1. pre1: chaos flips a payload byte in its 5th shipment export
    # (manifest spared) — the router must CRC-reject exactly that
    # shipment and degrade that request to a committed-prefix replay.
    pre0 = _ServeDriver(host_argv(
        "pre0", "prefill",
        ["--slots", "2", "--chaos", "step=1:prefill_kill"]), "disagg_pre0")
    pre1 = _ServeDriver(host_argv(
        "pre1", "prefill",
        ["--slots", "2", "--chaos", "step=4:ship_corrupt"]), "disagg_pre1")
    d0 = _ServeDriver(host_argv("d0", "decode", ["--slots", "4"]),
                      "disagg_d0")
    router = None
    try:
        res.check(pre0.wait_for(r"\[FLEET\] Host pre0 joined", timeout=420)
                  is not None, "prefill host pre0 joined the fleet")
        res.check(pre1.wait_for(r"\[FLEET\] Host pre1 joined", timeout=420)
                  is not None, "prefill host pre1 joined the fleet")
        res.check(d0.wait_for(r"\[FLEET\] Host d0 joined", timeout=420)
                  is not None, "decode host d0 joined the fleet")
        router = _ServeDriver(
            [sys.executable, "-m",
             "fault_tolerant_llm_training_tpu.inference.router",
             "--store", store, "--journal-dir", jdir, "--intake", intake,
             "--expected", "4", "--max-seconds", "180",
             "--poll-seconds", "0.1",
             "--event-log", os.path.join(base, "events_router.jsonl")],
            "disagg_router")
        rrc = router.finish(timeout=200)
        res.check(rrc == 0, f"router completed and exited 0 (got {rrc})")
        rc_pre0 = pre0.finish(timeout=15)
        pre1.proc.send_signal(_signal.SIGUSR1)
        rc_pre1 = pre1.finish(timeout=120)
        d0.proc.send_signal(_signal.SIGUSR1)
        rc_d0 = d0.finish(timeout=120)
    finally:
        for drv in (pre0, pre1, d0, router):
            if drv is not None and drv.proc.poll() is None:
                drv.proc.kill()
                drv.finish(timeout=10)
    rout = router.output()
    out_pre0, out_pre1, out_d0 = pre0.output(), pre1.output(), d0.output()

    # --- prefill-side faults
    res.check(rc_pre0 == -9
              and "[CHAOS] Injected prefill_kill" in out_pre0,
              f"prefill host pre0 SIGKILLed mid-prompt by chaos "
              f"(rc {rc_pre0})")
    res.check("[FLEET] Host pre0 declared dead" in rout
              and "fencing and migrating" in rout,
              "router declared pre0 dead and fenced it")
    res.check(re.search(r"\[FLEET\] Migrating request req\d+: "
                        r"pre0 -> pre1", rout) is not None,
              "dead host's mid-prompt requests re-prefilled on the "
              "surviving prefill peer")
    res.check("[CHAOS] Injected ship_corrupt" in out_pre1
              and "Corrupted block shipment" in out_pre1,
              "chaos flipped a payload byte in one of pre1's shipments "
              "(manifest spared)")

    # --- the CRC gate: exactly the poisoned shipment rejected, its
    # request degraded to replay; every request still reached decode
    rejects = re.findall(r"\[DISAGG\] Shipment reject request (req\d+) "
                         r"seq (\d+)", rout)
    res.check(len(rejects) == 1,
              f"router CRC-rejected exactly the poisoned shipment "
              f"(rejects {rejects})")
    places = re.findall(r"\[DISAGG\] Placement decode request (req\d+)",
                        rout)
    res.check(sorted(places) == [r["id"] for r in reqs],
              f"every request handed to the decode engine exactly once "
              f"(placements {sorted(places)})")
    res.check(re.search(r"Fleet router complete: 4 request\(s\) done, "
                        r"\d+ migrated, 0 lost", rout) is not None,
              "zero requests lost: all 4 served")

    # --- decode side: imports for the clean shipments, replay for the
    # rejected one, and the streams all come off the decode engine
    res.check(len(re.findall(r"Request req\d+ output: ", out_d0)) == 4
              and "Request req" not in
              "\n".join(l for l in out_pre1.splitlines()
                        if "output:" in l),
              "all four streams decoded on the dedicated decode engine")
    res.check(rc_pre1 == 0
              and "Fleet drain leak guard: clean" in out_pre1,
              f"prefill survivor drained leak-clean and exited 0 "
              f"(got rc {rc_pre1})")
    res.check(rc_d0 == 0 and "Fleet drain leak guard: clean" in out_d0,
              f"decode engine drained leak-clean and exited 0 "
              f"(got rc {rc_d0})")

    # --- bit-exactness: one unfailed COLOCATED serve, same prompts,
    # seeds and prefill chunking — every disaggregated stream must match
    ref_reqs = os.path.join(base, "ref_requests.jsonl")
    shutil.copy(intake, ref_reqs)
    ref = _ServeDriver(_serve_argv(ckpts, job, [
        "--prefill-buckets", "16,32", "--seed", str(seed), "--follow",
        "--poll-seconds", "0.2", "--request-file", ref_reqs]),
        "disagg_ref")
    try:
        for r in reqs:
            res.check(ref.wait_for(rf"Request {r['id']} output: ",
                                   timeout=420) is not None,
                      f"reference serve completed {r['id']}")
        ref.proc.send_signal(_signal.SIGUSR1)
        ref_rc = ref.finish()
    finally:
        if ref.proc.poll() is None:
            ref.proc.kill()
            ref.finish(timeout=10)
    res.check(ref_rc == 0, f"reference serve exited 0 (got {ref_rc})")
    disagg_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                     out_d0))
    ref_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                  ref.output()))
    res.check(
        len(disagg_outputs) == 4 and all(
            disagg_outputs.get(f"req{i}") == ref_outputs.get(f"req{i}")
            for i in range(4)),
        "disaggregated streams (shipped-block imports and the CRC-reject "
        "replay alike) bit-identical to the unfailed colocated reference")

    # --- request-trace stitch: every trail crosses into the decode host
    # and is flagged disaggregated (block_ship/decode_placement spans)
    traced = {r["request_id"]: r
              for r in reqtrace.stitch([base]) if r["request_id"]}
    trace_ok = len(traced) == 4
    for r in reqs:
        tr = traced.get(r["id"])
        trace_ok = (trace_ok and tr is not None
                    and bool(tr.get("disaggregated"))
                    and "d0" in set(tr.get("hosts", ())))
    res.check(trace_ok,
              "stitched trace: all four requests flagged disaggregated "
              "with the decode host on the critical path")
    _stitch_scenario(res, events_dir)
    return res


def run_kvstore_scenario(work: str, parquet: str, seed: int) -> Result:
    """Fleet-global KV store scenario: poison the one published train
    (store_corrupt) and SIGKILL the publishing host mid-decode — the
    fetching host CRC-rejects exactly once, degrades to local recompute,
    the router's cache-affinity placement still lands the second request
    on the publisher, zero requests are lost, and every stream
    bit-matches an unfailed single-host reference serve (module
    docstring)."""
    res = Result("kvstore")
    base = os.path.join(work, "kvstore")
    ckpts = os.path.join(base, "ckpts")
    events_dir = os.path.join(ckpts, "events")
    os.makedirs(base, exist_ok=True)
    job = "kvstore_a"

    rc, out = _run(_train_argv(parquet, ckpts, seed,
                               **{"--training-steps": "10",
                                  "--checkpoint-frequency": "5"}), job)
    if not res.check(rc == 0, f"kvstore training checkpoint committed "
                              f"(got rc {rc})"):
        return res

    store = os.path.join(base, "store")
    jdir = os.path.join(base, "journal")
    kvstore_dir = os.path.join(base, "kvstore")
    intake = os.path.join(base, "intake.jsonl")
    # all four prompts share every FULL 16-token block (34-char shared
    # prefix, <=13-char tails keep the block boundary inside the shared
    # region), so they share ONE content-addressed train
    shared = "alpha bravo charlie delta echo fox"
    reqs = [
        {"id": "req0", "prompt": shared + " a1",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 11},
        {"id": "req1", "prompt": shared + " b2",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 12},
        {"id": "req2", "prompt": shared + " c3",
         "max_new_tokens": 48, "temperature": 0.0, "seed": seed + 13},
        {"id": "req3", "prompt": shared + " d4",
         "max_new_tokens": 48, "temperature": 0.8, "seed": seed + 14},
    ]

    def host_argv(hid, chaos):
        return [sys.executable, "-m",
                "fault_tolerant_llm_training_tpu.inference.fleet",
                "--host-id", hid, "--store", store, "--journal-dir", jdir,
                "--kv-store-dir", kvstore_dir,
                "--checkpoint-path", ckpts, "--checkpoint-job-id", job,
                "--model", "tiny", "--tokenizer-name-or-path", "byte",
                "--slots", "2", "--max-len", "256", "--no-eos",
                "--lease-ttl", "2.0", "--max-run-seconds", "240",
                "--seed", str(seed), "--chaos", chaos,
                "--event-log", os.path.join(base, f"events_{hid}.jsonl")]

    # h0 is the publisher: its first (and only) put is poisoned at
    # publish ordinal 0, then a SIGKILL at decode iteration 40 takes it
    # out mid-decode — the kill after a committed put is what the
    # manifest-commits-last ordering must make indistinguishable from a
    # clean put, and the torn-tail fold must absorb its journal
    h0 = _ServeDriver(host_argv(
        "h0", "step=0:store_corrupt;step=40:host_kill"), "kvstore_h0")
    h1 = _ServeDriver(host_argv("h1", ""), "kvstore_h1")
    router = None
    try:
        res.check(h0.wait_for(r"\[FLEET\] Host h0 joined", timeout=420)
                  is not None, "host h0 joined the fleet with a lease")
        res.check(h1.wait_for(r"\[FLEET\] Host h1 joined", timeout=420)
                  is not None, "host h1 joined the fleet with a lease")

        # stage the intake: req0 alone first, so h0 publishes the shared
        # train (poisoned) BEFORE the affinity-relevant requests arrive
        with open(intake, "w") as fh:
            fh.write(json.dumps(reqs[0]) + "\n")
        router = _ServeDriver(
            [sys.executable, "-m",
             "fault_tolerant_llm_training_tpu.inference.router",
             "--store", store, "--journal-dir", jdir, "--intake", intake,
             "--kv-store-dir", kvstore_dir,
             "--expected", "4", "--max-seconds", "180",
             "--poll-seconds", "0.1",
             "--event-log", os.path.join(base, "events_router.jsonl")],
            "kvstore_router")
        res.check(h0.wait_for(r"\[KV STORE\] publish", timeout=120)
                  is not None,
                  "h0 published the shared train to the fleet store")
        res.check(h0.wait_for(r"\[CHAOS\] Injected store_corrupt",
                              timeout=30) is not None,
                  "chaos poisoned the published store artifact "
                  "(manifest spared)")
        with open(intake, "a") as fh:
            for r in reqs[1:]:
                fh.write(json.dumps(r) + "\n")
        rrc = router.finish(timeout=200)
        res.check(rrc == 0, f"router completed and exited 0 (got {rrc})")
        rc0 = h0.finish(timeout=15)
        h1.proc.send_signal(_signal.SIGUSR1)
        rc1 = h1.finish(timeout=120)
    finally:
        for drv in (h0, h1, router):
            if drv is not None and drv.proc.poll() is None:
                drv.proc.kill()
                drv.finish(timeout=10)
    rout = router.output()
    out0, out1 = h0.output(), h1.output()

    res.check(rc0 == -9 and "[CHAOS] Injected host_kill" in out0,
              f"publishing host h0 SIGKILLed mid-decode (rc {rc0})")
    res.check("[FLEET] Host h0 declared dead" in rout,
              "router declared the dead publisher and migrated its work")
    # cache-affinity receipt: req1 arrived while h0 held the only copy
    # of the train AND fewer free blocks than h1 — without the affinity
    # term in pick_host it would have been placed on h1
    assigns = {}
    with open(os.path.join(jdir, "router.jsonl")) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "assign":
                assigns.setdefault(str(rec.get("id")),
                                   str(rec.get("host")))
    res.check(assigns.get("req0") == "h0" and assigns.get("req1") == "h0",
              f"cache-affinity placement: req1 landed with the published "
              f"train on h0 (assigns {sorted(assigns.items())})")
    res.check(assigns.get("req2") == "h1" and assigns.get("req3") == "h1",
              f"free slots dominate affinity: overflow intake landed on "
              f"the cold host h1 (assigns {sorted(assigns.items())})")
    # the SHARED prompt train publishes exactly once fleet-wide
    # (content-address dedup: req2/req3 hash to the same terminal key on
    # h1 and skip the export). Migrated requests legitimately publish
    # NEW trains — their re-prefill covers prompt + committed tokens, a
    # longer chain with a different terminal hash — so the dedup pin is
    # per-key, not a global publish count. Exactly ONE CRC reject (h1's
    # first fetch; the recompute re-seeds its local cache so the next
    # admission never re-fetches).
    m_key = re.search(r"\[KV STORE\] publish key (\w+) request req0", out0)
    shared_key = m_key.group(1) if m_key is not None else ""
    n_shared = (out0 + out1).count(f"[KV STORE] publish key {shared_key}"
                                   ) if shared_key else 0
    n_rej = (out0 + out1).count("[KV STORE] reject")
    res.check(m_key is not None and n_shared == 1,
              f"content-address dedup: shared prompt train published "
              f"exactly once fleet-wide, by h0 (got {n_shared})")
    res.check(n_rej == 1 and "[KV STORE] reject" in out1
              and "falling back to local chunked prefill" in out1,
              f"exactly one CRC reject, on h1, degrading to local "
              f"recompute (got {n_rej})")
    res.check(re.search(r"Fleet router complete: 4 request\(s\) done, "
                        r"\d+ migrated, 0 lost", rout) is not None,
              "zero requests lost: all 4 served")
    res.check(rc1 == 0 and "Fleet drain leak guard: clean" in out1,
              f"survivor drained leak-clean and exited 0 (got rc {rc1})")

    # store post-mortem: the SIGKILL left no torn state — every visible
    # train either CRC-verifies or is the ONE poisoned artifact, and a
    # restarted handle folds the journals (h0's torn tail included)
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KVBlockIntegrityError, verify_block_artifact)
    from fault_tolerant_llm_training_tpu.inference.kvstore import (
        BlockStore)
    post = BlockStore(kvstore_dir, writer="postmortem")
    folded = post.fold()          # raises on journal corruption
    bad = good = 0
    for key in folded:
        if not post.has(key):
            continue              # torn put: invisible by contract
        try:
            verify_block_artifact(post.train_dir(key))
            good += 1
        except KVBlockIntegrityError:
            bad += 1
    res.check(bad == 1,
              f"store post-mortem: exactly the one poisoned train fails "
              f"CRC ({bad} bad, {good} clean), no torn state survives")
    res.check(all(st.refs == 0 for st in folded.values()),
              "no leaked store refcounts: every journaled fetch ref was "
              "released")

    # unfailed single-host reference: every stream — fetched, locally
    # recomputed after the reject, and migrated alike — must bit-match
    ref_reqs = os.path.join(base, "ref_requests.jsonl")
    with open(ref_reqs, "w") as fh:
        for r in reqs:
            fh.write(json.dumps(r) + "\n")
    ref = _ServeDriver(_serve_argv(ckpts, job, [
        "--seed", str(seed), "--follow", "--poll-seconds", "0.2",
        "--request-file", ref_reqs]), "kvstore_ref")
    try:
        for r in reqs:
            res.check(ref.wait_for(rf"Request {r['id']} output: ",
                                   timeout=420) is not None,
                      f"reference serve completed {r['id']}")
        ref.proc.send_signal(_signal.SIGUSR1)
        ref_rc = ref.finish()
    finally:
        if ref.proc.poll() is None:
            ref.proc.kill()
            ref.finish(timeout=10)
    res.check(ref_rc == 0, f"reference serve exited 0 (got {ref_rc})")
    fleet_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                    out0 + "\n" + out1))
    ref_outputs = dict(re.findall(r"Request (req\d+) output: (.+)",
                                  ref.output()))
    res.check(
        len(fleet_outputs) == 4 and all(
            fleet_outputs.get(f"req{i}") == ref_outputs.get(f"req{i}")
            for i in range(4)),
        "store-fetched, reject-recomputed and migrated streams all "
        "bit-identical to the unfailed single-host reference serve")
    _stitch_scenario(res, events_dir)
    return res


def run_transport_scenario(work: str, parquet: str, seed: int) -> Result:
    """KV transport ladder scenario: chaos poisons the first mem-lane
    push's fabric metadata (``mem_corrupt``) AND a payload byte of the
    same request's fs artifact, so that request degrades mem -> fs ->
    committed-prefix replay; a second request takes only the mem poison
    and stops one rung down, on the fs artifact. Every other train lands
    zero-copy on the mem lane. Zero requests lost, no leaked blocks, all
    streams bit-identical to an unfailed colocated reference (module
    docstring). Runs in-process: the mem lane's fabric is process-local
    by design, so the two roles share one address space here just as
    colocated prefill/decode engines on one host would."""
    import glob as _glob
    import logging as _logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fault_tolerant_llm_training_tpu.chaos.injector import (
        ChaosInjector)
    from fault_tolerant_llm_training_tpu.chaos.schedule import (
        parse_schedule)
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.inference.transport import (
        MemFabric, MemTransport)
    from fault_tolerant_llm_training_tpu.models.configs import get_config
    from fault_tolerant_llm_training_tpu.models.llama import Transformer
    from fault_tolerant_llm_training_tpu.obs.registry import MetricRegistry

    res = Result("transport")
    base = os.path.join(work, "transport")
    os.makedirs(base, exist_ok=True)

    cfg = get_config("tiny", vocab_size=64, seq_len=128,
                     layer_impl="loop")
    params = Transformer(cfg).init(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    def build():
        return InferenceEngine(cfg, params, slots=2, max_len=128,
                               prefill_buckets=(16, 32),
                               kv_layout="paged", kv_block_size=8)

    rng = np.random.default_rng(seed + 31)
    reqs = [Request(id=f"req{i}",
                    prompt=rng.integers(3, 64, size=24 + 8 * i).tolist(),
                    max_new_tokens=12,
                    **({} if i % 2 == 0 else
                       {"temperature": 0.8, "top_p": 0.9}),
                    seed=seed + 50 + i)
            for i in range(4)]
    n = len(reqs)

    def clone(r, **extra):
        return Request(id=r.id, prompt=list(r.prompt),
                       max_new_tokens=r.max_new_tokens,
                       temperature=r.temperature, top_p=r.top_p,
                       seed=r.seed, **extra)

    # unfailed colocated reference: the streams every degradation rung
    # must reproduce bitwise
    ref = Scheduler(build(), registry=MetricRegistry())
    for r in reqs:
        ref.submit(clone(r))
    ref.run()
    ref_streams = {c.request_id: c.tokens for c in ref.completed}
    res.check(len(ref_streams) == n,
              f"colocated reference served all {n} requests")

    # capture the frozen [KV XPORT] audit trail the ladder must leave
    audit, handler = [], None

    class _Capture(_logging.Handler):
        def emit(self, record):
            audit.append(record.getMessage())

    sched_logger = _logging.getLogger()    # the scheduler audits to root
    handler = _Capture()
    old_level = sched_logger.level
    sched_logger.setLevel(_logging.INFO)   # audit lines log at INFO
    sched_logger.addHandler(handler)
    try:
        fabric = MemFabric()
        chaos = ChaosInjector(parse_schedule("step=0:mem_corrupt"),
                              seed=seed)
        poisoned = []

        def on_push(fab, handle, ordinal=0):
            hit = chaos.on_mem_push(fab, handle, ordinal)
            if hit:
                poisoned.append(hit)

        ships = {}

        def on_ship(req, art_dir, ordinal, seq, start, end, length):
            ships.setdefault(req.id, []).append(
                {"artifact": art_dir, "seq": seq, "start_block": start,
                 "end_block": end, "length": length, "lane": "mem"})

        pre = Scheduler(build(), role="prefill",
                        ship_dir=os.path.join(base, "ships"),
                        on_ship=on_ship,
                        transport=MemTransport(fabric, on_push=on_push),
                        registry=MetricRegistry())
        for r in reqs:
            pre.submit(clone(r))
        pre.run()
        first = {c.request_id: c.tokens for c in pre.completed}
        res.check(len(first) == n and pre.ship_exports >= n,
                  f"prefill committed and shipped all {n} requests "
                  f"({pre.ship_exports} train(s) exported)")
        res.check(len(poisoned) == 1,
                  "chaos poisoned exactly the first mem push's fabric "
                  "metadata (mem_corrupt, ordinal 0)")
        res.check(len(fabric) == pre.ship_exports,
                  "every exported train was pushed to the shared fabric")

        # rung 3 setup: the poisoned train's request ALSO loses its fs
        # artifact (one payload byte), so its ladder bottoms out at the
        # committed-prefix replay; find which request owns that train
        victim = next(r.id for r in reqs for s in ships[r.id]
                      if s["artifact"] == poisoned[0])
        # a second request takes ONLY the mem poison: one rung down
        second = next(r.id for r in reqs if r.id != victim)
        fabric.poison(ships[second][0]["artifact"])
        blk = sorted(_glob.glob(os.path.join(
            poisoned[0], "block_*.bin")))[0]
        raw = bytearray(open(blk, "rb").read())
        raw[3] ^= 0xFF
        open(blk, "wb").write(bytes(raw))

        dec = Scheduler(build(), role="decode",
                        transport=MemTransport(fabric),
                        registry=MetricRegistry())
        for r in reqs:
            dec.submit(clone(r, committed=tuple(first[r.id])),
                       shipments=ships.get(r.id), ship_gen=0)
        dec.run()
        streams = {c.request_id: c.tokens for c in dec.completed}
    finally:
        sched_logger.removeHandler(handler)
        sched_logger.setLevel(old_level)

    res.check(len(streams) == n,
              f"zero requests lost: decode completed {len(streams)}/{n} "
              f"across all three degradation rungs")
    res.check(streams == ref_streams,
              "all decode streams — mem-landed, fs-degraded and "
              "replayed alike — bit-identical to the unfailed colocated "
              "reference")
    res.check(dec.mem_lane_imports == n - 2,
              f"untouched trains landed zero-copy on the mem lane "
              f"({dec.mem_lane_imports} of {n})")
    res.check(dec.lane_fallbacks == 2 and dec.ship_rejects == 1,
              f"degradation ladder: two mem->fs fallbacks, one of which "
              f"fell through to replay (fallbacks "
              f"{dec.lane_fallbacks}, rejects {dec.ship_rejects})")
    fallbacks = [ln for ln in audit
                 if ln.startswith("[KV XPORT] fallback lane mem")]
    res.check(len(fallbacks) == 2,
              f"audit trail: [KV XPORT] fallback lane mem logged for "
              f"both poisoned trains (got {len(fallbacks)})")
    res.check(any(ln.startswith(f"[DISAGG] Shipment reject request "
                                f"{victim} ") for ln in audit),
              f"audit trail: shipment reject for the doubly-poisoned "
              f"request {victim} (replay rung)")
    res.check(pre.audit_block_leaks(strict=False) == []
              and dec.audit_block_leaks(strict=False) == [],
              "no leaked KV blocks on either role after the ladder")
    return res


def format_report(results, seed: int, wall: float, extra_notes) -> str:
    lines = []
    lines.append("Chaos survival campaign")
    lines.append(f"seed {seed} | scenarios {len(results)} | "
                 f"wall {wall:.0f} s | driver scripts/chaos_campaign.py")
    lines.append("")
    lines.append(f"{'class':<14} {'survived':<9} {'goodput%':>9} "
                 f"{'mttr_s':>8} {'replayed':>9}")
    lines.append("-" * 53)
    for r in results:
        gp = f"{r.goodput_pct:.1f}" if r.goodput_pct is not None else "-"
        mt = (f"{r.mttr_seconds:.1f}" if r.mttr_seconds is not None
              else "-")
        rp = (str(r.replayed_steps) if r.replayed_steps is not None
              else "-")
        lines.append(f"{r.name:<14} {'yes' if r.survived else 'NO':<9} "
                     f"{gp:>9} {mt:>8} {rp:>9}")
    lines.append("")
    for r in results:
        lines.append(f"[{r.name}]")
        for n in r.notes:
            lines.append(f"  {n}")
        lines.append("")
    for n in extra_notes:
        lines.append(n)
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="seeded chaos survival campaign (see module docstring)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--scenarios", default=",".join(SCENARIOS),
                   help=f"comma-separated subset of {SCENARIOS}")
    p.add_argument("--workdir", default="/tmp/ftl_chaos_campaign")
    p.add_argument("--out", default=os.path.join(REPO, "logs",
                                                 "chaos_campaign.txt"))
    p.add_argument("--sbatch", default="",
                   help="resubmit via this sbatch (e.g. scripts/fake_slurm/"
                        "sbatch) instead of a touch-marker command")
    args = p.parse_args(argv)

    wanted = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    bad = [s for s in wanted if s not in SCENARIOS]
    if bad:
        p.error(f"unknown scenario(s) {bad}; known: {SCENARIOS}")

    work = os.path.join(args.workdir, f"seed{args.seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)
    parquet = os.path.join(work, "train_data.parquet")
    _make_parquet(parquet, args.seed)

    t0 = time.monotonic()
    print(f"== baseline (clean 30-step run, seed {args.seed})")
    base_ckpts = os.path.join(work, "baseline", "ckpts")
    rc, out = _run(_train_argv(parquet, base_ckpts, args.seed,
                               **{"--checkpoint-frequency": "0"}),
                   "baseline")
    if rc != 0 or "Training completed" not in out:
        print(out[-4000:])
        print("baseline run failed; aborting campaign", file=sys.stderr)
        return 1
    baseline_losses = _event_losses(os.path.join(base_ckpts, "events"),
                                    "baseline")
    if len(baseline_losses) != 30:
        print(f"baseline produced {len(baseline_losses)} step losses, "
              f"want 30; aborting", file=sys.stderr)
        return 1

    results = []
    for name in wanted:
        print(f"== scenario: {name}")
        if name == "deploy":
            res = run_deploy_scenario(work, parquet, args.seed)
        elif name == "fleet":
            res = run_fleet_scenario(work, parquet, args.seed)
        elif name == "tiered":
            res = run_tiered_scenario(work, parquet, args.seed)
        elif name == "disagg":
            res = run_disagg_scenario(work, parquet, args.seed)
        elif name == "kvstore":
            res = run_kvstore_scenario(work, parquet, args.seed)
        elif name == "transport":
            res = run_transport_scenario(work, parquet, args.seed)
        else:
            res = run_scenario(name, work, parquet, args.seed,
                               baseline_losses, sbatch=args.sbatch)
        timeline = _write_postmortem(name, work)
        if name == "fleet":
            _check_fleet_postmortem(res, timeline)
        results.append(res)
        print(f"   -> {'survived' if res.survived else 'FAILED'}")

    extra = []
    by_name = {r.name: r for r in results}
    if "ckpt_corrupt" in by_name and "exception" in by_name:
        # Two independent jobs, same seed: every array of their periodic
        # step-10 saves must be value-identical — the state the corrupt
        # scenario FELL BACK to is exactly the state an uncorrupted chain
        # had at that step.
        a = _state_digest(os.path.join(work, "ckpt_corrupt", "ckpts"),
                          "ckpt_corrupt_a", 10)
        b = _state_digest(os.path.join(work, "exception", "ckpts"),
                          "exception_a", 10)
        r = by_name["ckpt_corrupt"]
        r.check(a is not None and a == b,
                "fallback step-10 state array-for-array CRC-identical to "
                "the exception scenario's independent step-10 save "
                "(bit-exact state)")
        extra.append(
            "cross-scenario evidence: ckpt_corrupt's fallback source "
            "(step 10) and exception's step 10 were written by independent "
            "processes; every restored array matches CRC-for-CRC — the "
            "verified fallback resumes the exact state a clean run had.")

    wall = time.monotonic() - t0
    report = format_report(results, args.seed, wall, extra)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        fh.write(report + "\n")
    print()
    print(report)
    print(f"\nreport written to {args.out}")
    return 0 if all(r.survived for r in results) else 2


if __name__ == "__main__":
    sys.exit(main())
