"""Inference & serving subsystem (fault_tolerant_llm_training_tpu/inference/).

Three layers of evidence, mirroring how the training side is verified:

1. numerics — cached (prefill + stepwise decode) logits BIT-MATCH the
   uncached teacher-forcing forward, the property that makes serving a
   trained checkpoint trustworthy at all;
2. mechanics — slot-based continuous batching (admit/evict/drain) pinned
   against a fake engine, plus greedy/sampled determinism across engine
   rebuilds (the serving analogue of bit-exact training resume);
3. lifecycle — the real CLI chain: train a tiny model, restore the
   checkpoint in serve.py, run concurrent requests, SIGTERM mid-generation
   and assert the drain audit trail on exit 0 (the same grep-the-.out-file
   discipline as the trainer's exit handler).
"""

import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from _tiny import tiny_cfg

REPO = Path(__file__).resolve().parent.parent
CACHE = "/tmp/jax_test_compile_cache"


# --------------------------------------------------------------- 1. numerics
def _init_params(cfg, seed=0):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    model = Transformer(cfg)
    tokens = jnp.zeros((1, cfg.seq_len), jnp.int32)
    return model, model.init(jax.random.PRNGKey(seed), tokens)["params"]


def test_cached_decode_bitmatches_uncached_forward():
    """Prefill writes the prompt's KV and decode extends it one token at a
    time; at EVERY position the cached logits must equal the teacher-forcing
    forward bitwise — same projections, same RoPE table values, same
    fp32-softmax attention order (ops/attention.py cached_attention).

    A bf16 contract, so it asks the helper for bfloat16 (one slot: XLA:CPU
    executes that dot). A one-token step is a matmul of another shape than
    the 24-token forward, so its float32 accumulation order differs in the
    last place; bf16 products are exact in float32 and every matmul's
    float32 sum is rounded to 8 bits of mantissa, which takes that away.
    At float32 only ``allclose`` would hold."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg(dtype="bfloat16")
    model, params = _init_params(cfg)
    rng = np.random.default_rng(0)
    T = 24
    toks = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(1, T)),
                       jnp.int32)
    full = np.asarray(model.apply({"params": params}, toks))  # (1, T, V)

    from fault_tolerant_llm_training_tpu.inference.kv_cache import init_cache

    cache = init_cache(cfg, slots=1, max_len=32)
    P = 16  # prompt prefix; the rest decodes stepwise
    cached, (k, v) = model.apply(
        {"params": params}, toks[:, :P], cache.k, cache.v,
        jnp.zeros((1,), jnp.int32), method="forward_with_cache")
    np.testing.assert_array_equal(np.asarray(cached), full[:, :P])
    offset = jnp.full((1,), P, jnp.int32)
    for t in range(P, T):
        step, (k, v) = model.apply(
            {"params": params}, toks[:, t:t + 1], k, v, offset,
            method="forward_with_cache")
        np.testing.assert_array_equal(np.asarray(step)[:, 0], full[:, t])
        offset = offset + 1


@pytest.mark.parametrize("layer_impl", ["loop", "scan"])
def test_engine_greedy_matches_uncached_autoregression(layer_impl):
    """The engine end-to-end (AOT prefill bucket + donated decode, scan
    checkpoints converted to the loop trunk) reproduces the greedy
    continuation computed by repeatedly running the full uncached forward."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import InferenceEngine

    cfg = tiny_cfg(layer_impl=layer_impl)
    model, params = _init_params(cfg)
    rng = np.random.default_rng(1)
    prompt = rng.integers(3, cfg.vocab_size, size=9).tolist()
    N = 6

    # reference: argmax-extend with the plain training forward
    seq = list(prompt)
    ref = []
    for _ in range(N):
        logits = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32))
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        ref.append(tok)
        seq.append(tok)

    # default layout is the paged block pool: the raw engine API needs the
    # slot's block-table row (the Scheduler's allocator provides it in
    # production; tests/test_paged_kv.py covers the allocator itself)
    engine = InferenceEngine(cfg, params, slots=2, max_len=32)
    row = np.arange(1, engine.max_blocks_per_slot + 1, dtype=np.int32)
    tables = np.zeros((2, engine.max_blocks_per_slot), np.int32)
    tables[0] = row
    got = [engine.prefill(0, prompt, block_row=row)]
    for step in range(1, N):
        toks = engine.decode_step(
            np.array([got[-1], 0], np.int32), np.array([True, False]),
            np.zeros(2, np.float32), np.ones(2, np.float32),
            np.zeros(2, np.int32), np.full(2, step, np.int32),
            block_tables=tables)
        got.append(int(toks[0]))
    assert got == ref


def test_generation_deterministic_across_engine_rebuilds():
    """Restart determinism (the serving analogue of bit-exact resume): a
    rebuilt engine reproduces greedy AND sampled generations — per-slot
    PRNG is fold_in(seed, step), independent of engine history."""
    from fault_tolerant_llm_training_tpu.inference.engine import InferenceEngine
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    cfg = tiny_cfg()
    _, params = _init_params(cfg)
    prompt = [5, 17, 9, 33]

    def _generate():
        engine = InferenceEngine(cfg, params, slots=2, max_len=32)
        sched = Scheduler(engine, eos_token_id=None)
        for i, temp in enumerate([0.0, 0.8]):
            sched.submit(Request(id=f"r{i}", prompt=prompt, max_new_tokens=5,
                                 temperature=temp, seed=7 + i))
        done = sched.run()
        return {c.request_id: c.tokens for c in done}

    assert _generate() == _generate()


# -------------------------------------------------------------- 2. mechanics
class _FakeEngine:
    """Deterministic engine double: slot s emits 100+s then counts up;
    'eos_at' slots emit the eos token after a set number of steps."""

    def __init__(self, slots=2, max_len=64):
        self.slots = slots
        self.max_len = max_len
        self.prefills = []

    def prefill(self, slot, prompt, temperature=0.0, top_p=1.0, seed=0):
        self.prefills.append((slot, tuple(prompt)))
        return 100 + slot

    def decode_step(self, tokens, active, temperature, top_p, seeds, steps):
        return np.where(active, np.asarray(tokens) + 1, 0).astype(np.int32)


def test_scheduler_admits_evicts_and_refills():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeEngine(slots=2)
    sched = Scheduler(eng, eos_token_id=None)
    for i, n in enumerate([3, 5, 2]):  # staggered lengths force a refill
        sched.submit(Request(id=f"r{i}", prompt=[1, 2], max_new_tokens=n))
    done = sched.run()
    assert {c.request_id for c in done} == {"r0", "r1", "r2"}
    assert all(c.reason == "length" for c in done)
    by_id = {c.request_id: c for c in done}
    assert len(by_id["r0"].tokens) == 3
    assert len(by_id["r1"].tokens) == 5
    assert len(by_id["r2"].tokens) == 2
    # r2 was queued behind the first two and admitted into r0's freed slot
    assert sched.max_concurrent == 2
    assert eng.prefills[0][0] != eng.prefills[1][0]
    m = sched.metrics()
    assert m["requests_completed"] == 3
    assert m["tokens_generated"] == 10
    assert m["decode_p95_ms"] >= 0 and m["iterations"] == sched.iterations


def test_scheduler_eos_and_oversize_rejection():
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeEngine(slots=1)
    sched = Scheduler(eng, eos_token_id=103)  # slot 0 emits 100,101,102,103
    sched.submit(Request(id="r0", prompt=[1], max_new_tokens=32))
    done = sched.run()
    assert done[0].reason == "eos" and done[0].tokens[-1] == 103
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit(Request(id="big", prompt=[1] * 60, max_new_tokens=32))


def test_scheduler_drain_finishes_active_leaves_queue():
    """stop_admission() mid-flight (what serve.py does on SIGTERM): active
    slots run to completion, queued requests stay unserved, pending() goes
    False so the serve loop exits."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    eng = _FakeEngine(slots=1)
    sched = Scheduler(eng, eos_token_id=None)
    for i in range(3):
        sched.submit(Request(id=f"r{i}", prompt=[1], max_new_tokens=4))
    sched.step()  # admits r0 only (1 slot)
    sched.stop_admission()
    while sched.pending():
        sched.step()
    assert [c.request_id for c in sched.completed] == ["r0"]
    assert [r.id for r in sched.unserved()] == ["r1", "r2"]
    assert not sched.pending()


# -------------------------------------------------------------- 3. lifecycle
def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PYTHONFAULTHANDLER"] = "1"
    return env


def _run_serve(argv, timeout=300, send_signal=None, wait_for=None):
    """Run serve.py, optionally signalling once ``wait_for`` appears."""
    import queue as _queue
    import threading as _threading

    proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=_env())
    lines: "_queue.Queue" = _queue.Queue()

    def _reader():
        for line in proc.stdout:
            lines.put(line)
        lines.put(None)

    _threading.Thread(target=_reader, daemon=True).start()
    out, fired = [], False
    deadline = time.time() + timeout
    while True:
        try:
            line = lines.get(timeout=max(0.1, deadline - time.time()))
        except _queue.Empty:
            line = ""
        if line is None:
            break
        if line:
            out.append(line)
            if (send_signal is not None and not fired
                    and wait_for is not None and wait_for in line):
                proc.send_signal(send_signal)
                fired = True
        if time.time() > deadline:
            proc.kill()
            break
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    return proc.returncode, "".join(out), fired


@pytest.fixture(scope="module")
def trained_ckpt(tmp_path_factory):
    """Train tiny for a few steps through the real CLI; returns the
    checkpoint root (job id 'serve_e2e')."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    tmp = tmp_path_factory.mktemp("serve_e2e")
    rng = np.random.default_rng(5)
    words = ["alpha", "bravo", "charlie", "delta", "echo"]
    docs = [" ".join(rng.choice(words, size=int(rng.integers(20, 120))))
            for _ in range(64)]
    parquet = tmp / "train_data.parquet"
    pq.write_table(pa.table({"text": docs}), parquet)

    env = _env()
    env["SLURM_JOB_ID"] = "serve_e2e"
    argv = [sys.executable, str(REPO / "train.py"),
            "--dataset", str(parquet),
            "--checkpoint-path", str(tmp / "ckpts"),
            "--tokenizer-name-or-path", "byte", "--model", "tiny",
            "--sequence-length", "128", "--batch-size", "2",
            "--training-steps", "6", "--checkpoint-frequency", "5",
            "--learning-rate", "1e-3", "--logging-frequency", "1"]
    proc = subprocess.run(argv, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout
    assert "Training completed" in proc.stdout, proc.stdout
    return str(tmp / "ckpts")


def _serve_argv(ckpt, extra):
    return [sys.executable, "-m",
            "fault_tolerant_llm_training_tpu.inference.serve",
            "--checkpoint-path", ckpt, "--checkpoint-job-id", "serve_e2e",
            "--model", "tiny", "--slots", "2", "--max-len", "128",
            "--seed", "3"] + extra


def test_serve_restores_checkpoint_and_completes(cpu_bf16_serving_dot,
                                                 trained_ckpt):
    """Happy path: restore the trained checkpoint, run >= 2 concurrent
    requests through the scheduler, finish every request, exit 0. The
    serve CLI runs at the preset's bf16 (no model-dtype input), hence the
    capability fixture: skipped where XLA:CPU refuses the decode dot."""
    rc, out, _ = _run_serve(_serve_argv(trained_ckpt, [
        "--prompt", "alpha bravo", "--prompt", "charlie delta",
        "--prompt", "echo alpha", "--max-new-tokens", "8"]))
    assert rc == 0, out
    assert "Starting serving!" in out
    assert "Model loaded from checkpoint" in out
    assert "Serving ready | model tiny | checkpoint step 5 | slots 2" in out
    for i in range(3):
        assert f"Request req{i} done" in out, out
    assert "Prefix cache | lookups 3 |" in out  # summary audit, cache on
    assert "Serving completed" in out
    assert "[EXIT HANDLER]" not in out  # no drain on the happy path


def test_serve_sigterm_drains_and_exits_zero(cpu_bf16_serving_dot,
                                             trained_ckpt):
    """The receipt: SIGTERM mid-generation -> admission stops, in-flight
    requests finish, queued ones are reported unserved, process exits 0
    with the audit trail. Transcript saved to logs/serving_e2e.log."""
    rc, out, fired = _run_serve(_serve_argv(trained_ckpt, [
        "--prompt", "alpha bravo charlie", "--repeat", "40",
        "--max-new-tokens", "48", "--no-eos", "--log-frequency", "1"]),
        send_signal=signal.SIGTERM, wait_for="Serve step: 1 |")
    logdir = REPO / "logs"
    logdir.mkdir(exist_ok=True)
    (logdir / "serving_e2e.log").write_text(out)
    assert fired, out
    assert rc == 0, out
    assert "Signal 15 received, draining" in out, out
    assert "admission stopped." in out
    assert "[EXIT HANDLER] Drained;" in out
    assert "queued request(s) not admitted." in out
    # 40 identical prompts: every admission past the first hits the
    # first committed block, so the summary audit shows a nonzero rate
    assert "Prefix cache | lookups" in out
    assert "hit rate 0.000" not in out.split("Prefix cache | ")[1], out
    assert "Serving completed" in out
    # drained means NOT all 40 requests ran; at least the in-flight finished
    done = out.count("done | length")
    assert 0 < done < 40, out
