"""Deployment loop (fault_tolerant_llm_training_tpu/deploy/).

Layers, cheapest first:

- pointer mechanics: atomic ``published.json`` writes (a concurrent
  reader never observes a torn pointer, no tmp litter), publish refuses
  a step without its integrity manifest;
- verify-before-load: a corrupted published step (or a manifest swapped
  after the digest was taken) is rejected WITHOUT loading, the audit +
  counter fire, serving state is untouched;
- watcher dedup: each (job, step, digest) publish is offered exactly once;
- the swap itself, against real tiny engines: in-flight slots survive a
  mid-stream hot reload un-dropped, admission reopens, and a request
  admitted AFTER the swap streams bit-identically to a fresh restore of
  the published step — the property the chaos campaign pins end-to-end;
- the adaptive-k controller: targets stay inside [1, k_max] on any
  observation sequence, walk down under rejection, recover on reset.
"""

import json
import os
import threading
from pathlib import Path

import numpy as np
import pytest

from _tiny import tiny_cfg
from fault_tolerant_llm_training_tpu.deploy.publish import (
    POINTER_NAME,
    Pointer,
    Publisher,
    manifest_digest,
    pointer_path,
    read_pointer,
    verify_pointer,
    write_pointer,
)
from fault_tolerant_llm_training_tpu.deploy.reload import (
    HotReloader,
    PointerWatcher,
)
from fault_tolerant_llm_training_tpu.obs import events as events_mod

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _fresh_recorder():
    events_mod._RECORDER = events_mod.FlightRecorder()
    yield
    events_mod._RECORDER = events_mod.FlightRecorder()


# ------------------------------------------------------------------ pointers
def _ptr(step, job="j", path="p", digest="d", draft=None):
    return Pointer(step=step, job_id=job, path=path,
                   manifest_digest=digest, draft=draft)


def test_pointer_write_read_roundtrip(tmp_path):
    root = str(tmp_path)
    draft = {"job_id": "dj", "step": 3, "path": "dp", "manifest_digest": "x"}
    write_pointer(root, _ptr(10, draft=draft))
    got = read_pointer(root)
    assert (got.step, got.job_id, got.path, got.manifest_digest) == \
        (10, "j", "p", "d")
    assert got.draft == draft
    assert got.version == 1


def test_pointer_reads_tolerate_garbage(tmp_path):
    root = str(tmp_path)
    assert read_pointer(root) is None  # no pointer yet
    Path(pointer_path(root)).write_text("{not json")
    assert read_pointer(root) is None
    Path(pointer_path(root)).write_text('{"version": 1}')  # missing keys
    assert read_pointer(root) is None


def test_pointer_updates_are_atomic_under_concurrent_reads(tmp_path):
    """A reader polling while the publisher rewrites the pointer many
    times must only ever see complete, monotonically-advancing pointers
    (the tmp-rename contract), and the writer leaves no tmp litter."""
    root = str(tmp_path)
    write_pointer(root, _ptr(0))
    stop = threading.Event()
    bad, seen = [], []

    def reader():
        while not stop.is_set():
            ptr = read_pointer(root)
            if ptr is None:
                bad.append("unreadable pointer mid-rewrite")
            else:
                seen.append(ptr.step)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for step in range(1, 200):
            write_pointer(root, _ptr(step))
    finally:
        stop.set()
        t.join()
    assert not bad
    assert seen == sorted(seen), "pointer regressed mid-rewrite"
    assert [p for p in os.listdir(root) if p.startswith(POINTER_NAME)] == \
        [POINTER_NAME], "tmp litter left behind"


# ------------------------------------------------- publish + verify-before-load
def _fake_step_dir(tmp_path, job="pub", step=20):
    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        write_manifest,
    )

    d = tmp_path / f"checkpoint_{job}" / str(step)
    (d / "state").mkdir(parents=True)
    (d / "state" / "arr0.bin").write_bytes(os.urandom(4096))
    (d / "data.json").write_text('{"next_index": 0}')
    write_manifest(str(d), step)
    return d


def test_publish_refuses_step_without_manifest(tmp_path):
    d = tmp_path / "checkpoint_pub" / "10"
    (d / "state").mkdir(parents=True)
    (d / "state" / "arr0.bin").write_bytes(os.urandom(64))
    pub = Publisher(str(tmp_path), "pub")
    assert pub.publish(10) is None
    assert read_pointer(str(tmp_path)) is None


def test_publish_commits_verified_pointer_and_audits(tmp_path):
    d = _fake_step_dir(tmp_path, step=20)
    pub = Publisher(str(tmp_path), "pub")
    ptr = pub.publish(20)
    assert ptr is not None and ptr.step == 20
    assert ptr.manifest_digest == manifest_digest(str(d))
    assert verify_pointer(str(tmp_path), ptr) == (True, "ok")
    got = read_pointer(str(tmp_path))
    assert (got.step, got.job_id) == (20, "pub")
    kinds = [e["kind"] for e in events_mod._RECORDER.ring]
    assert kinds.count("publish") == 1


def test_verify_pointer_rejects_corruption_and_manifest_swap(tmp_path):
    d = _fake_step_dir(tmp_path, step=20)
    ptr = Publisher(str(tmp_path), "pub").publish(20)

    # payload byte flip after publish: the per-file CRC catches it
    target = d / "state" / "arr0.bin"
    raw = bytearray(target.read_bytes())
    raw[100] ^= 0xFF
    target.write_bytes(bytes(raw))
    ok, detail = verify_pointer(str(tmp_path), ptr)
    assert not ok and "crc mismatch" in detail
    raw[100] ^= 0xFF
    target.write_bytes(bytes(raw))
    assert verify_pointer(str(tmp_path), ptr) == (True, "ok")

    # manifest replaced wholesale after the digest was taken: even though
    # the rewritten manifest matches the (also rewritten) files, the
    # pointer's digest pin catches the swap
    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        write_manifest,
    )

    target.write_bytes(os.urandom(4096))
    write_manifest(str(d), 20)
    ok, detail = verify_pointer(str(tmp_path), ptr)
    assert not ok and "digest" in detail


def test_watcher_offers_each_publish_exactly_once(tmp_path):
    _fake_step_dir(tmp_path, step=10)
    _fake_step_dir(tmp_path, step=20)
    pub = Publisher(str(tmp_path), "pub")
    watcher = PointerWatcher(str(tmp_path))
    assert watcher.poll() is None  # nothing published yet
    pub.publish(10)
    assert watcher.poll().step == 10
    assert watcher.poll() is None  # deduped
    pub.publish(10)  # same step, same manifest -> same digest: no new offer
    assert watcher.poll() is None
    pub.publish(20)
    assert watcher.poll().step == 20
    assert watcher.poll() is None


# ------------------------------------------------------------- the swap itself
def _init_params(cfg, seed=0):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    model = Transformer(cfg)
    tokens = jnp.zeros((1, cfg.seq_len), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), tokens)["params"]


def _save_train_checkpoint(tmp_path, job, step, params):
    """Write a real (verified, manifested) training checkpoint holding
    ``params`` — the tree restore_params expects, optimizer state
    included."""
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        CheckpointManager,
    )
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import make_optimizer

    state = TrainState(step=jnp.asarray(step, jnp.int32), params=params,
                       opt_state=make_optimizer(1e-4, 1).init(params))
    mngr = CheckpointManager(str(tmp_path), job, enable_async=False,
                             max_to_keep=4)
    mngr.save(step, state, {"next_index": 0}, wait=True)
    mngr.close()


def _greedy_request(rid, prompt, n):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    return Request(id=rid, prompt=list(prompt), max_new_tokens=n,
                   temperature=0.0)


def _run_to_completion(sched):
    done = []
    while sched.pending():
        done.extend(sched.step())
    return {c.request_id: c.tokens for c in done}


def test_hot_reload_preserves_in_flight_and_bitmatches_fresh_restore(
        tmp_path):
    """The acceptance property at unit scale: a swap mid-stream drops no
    in-flight slot, and a request admitted after the swap streams
    bit-identically to a fresh restore of the published step."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
        restore_params,
    )
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    cfg = tiny_cfg()
    params_a = _init_params(cfg, seed=0)
    params_b = _init_params(cfg, seed=1)
    _save_train_checkpoint(tmp_path, "pub", 20, params_b)
    Publisher(str(tmp_path), "pub").publish(20)

    engine = InferenceEngine(cfg, params_a, slots=2, max_len=48)
    engine.restored_step = 0
    sched = Scheduler(engine)
    reloader = HotReloader(engine, sched, cfg, str(tmp_path))
    watcher = PointerWatcher(str(tmp_path))

    prompt = [5, 9, 2, 14, 7]
    sched.submit(_greedy_request("inflight", prompt, 12))
    for _ in range(4):
        sched.step()
    assert len(sched.active) == 1
    (slot,) = sched.active
    tokens_before = list(sched.active[slot].tokens)
    assert len(tokens_before) >= 4

    assert reloader.maybe_reload(watcher.poll()) is True
    assert reloader.reloads == 1 and reloader.rejects == 0
    assert engine.restored_step == 20
    # PAUSE/RESUME left the in-flight slot intact and admission open
    assert sched.admission_open
    assert list(sched.active) == [slot]
    assert sched.active[slot].tokens[:len(tokens_before)] == tokens_before
    kinds = [e["kind"] for e in events_mod._RECORDER.ring]
    assert kinds.count("weights_reload") == 1

    # post-swap admission runs wholly under the published weights
    sched.submit(_greedy_request("fresh-path", prompt, 8))
    done = _run_to_completion(sched)
    assert len(done["inflight"]) == 12, "in-flight stream was truncated"

    # ground truth: a fresh restore of the published step
    restored, got = restore_params(str(tmp_path), "pub", cfg, step=20)
    assert got == 20
    engine_b = InferenceEngine(cfg, restored, slots=2, max_len=48)
    sched_b = Scheduler(engine_b)
    sched_b.submit(_greedy_request("reference", prompt, 8))
    ref = _run_to_completion(sched_b)
    assert done["fresh-path"] == ref["reference"], (
        "post-swap stream diverged from a fresh restore of the "
        "published step")


def test_reload_rejects_corrupt_publish_and_serving_continues(tmp_path):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    cfg = tiny_cfg()
    params_a = _init_params(cfg, seed=0)
    params_b = _init_params(cfg, seed=1)
    _save_train_checkpoint(tmp_path, "pub", 20, params_b)
    Publisher(str(tmp_path), "pub").publish(20)

    # corrupt AFTER the publish committed (the publish_corrupt shape)
    step_dir = tmp_path / "checkpoint_pub" / "20"
    victim = next(p for p in sorted((step_dir / "state").rglob("*"))
                  if p.is_file() and p.stat().st_size > 0)
    raw = bytearray(victim.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    victim.write_bytes(bytes(raw))

    engine = InferenceEngine(cfg, params_a, slots=2, max_len=48)
    engine.restored_step = 0
    sched = Scheduler(engine)
    reloader = HotReloader(engine, sched, cfg, str(tmp_path))
    watcher = PointerWatcher(str(tmp_path))

    leaf_before = np.asarray(
        next(iter(jax_leaves(engine.params))))  # snapshot one weight
    assert reloader.maybe_reload(watcher.poll()) is False
    assert reloader.rejects == 1 and reloader.reloads == 0
    assert engine.restored_step == 0
    assert sched.admission_open
    np.testing.assert_array_equal(
        np.asarray(next(iter(jax_leaves(engine.params)))), leaf_before)
    kinds = [e["kind"] for e in events_mod._RECORDER.ring]
    assert kinds.count("weights_reload_rejected") == 1
    assert kinds.count("weights_reload") == 0
    # the rejected publish is not re-offered on the next poll
    assert watcher.poll() is None

    # serving still works end-to-end on the current weights
    sched.submit(_greedy_request("r", [5, 9, 2], 4))
    done = _run_to_completion(sched)
    assert len(done["r"]) == 4


def jax_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def test_engine_reload_rejects_mismatched_trees():
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )

    cfg = tiny_cfg()
    engine = InferenceEngine(cfg, _init_params(cfg, seed=0), slots=1,
                             max_len=32)
    bigger = tiny_cfg(vocab_size=96)
    with pytest.raises(ValueError, match="does not match"):
        engine.reload_params(_init_params(bigger, seed=1))
    with pytest.raises(ValueError, match="without a draft"):
        engine.reload_draft_params(_init_params(cfg, seed=1))


# ------------------------------------------------------------ adaptive width
def test_adaptive_k_stays_in_bounds_on_any_observation_sequence():
    from fault_tolerant_llm_training_tpu.inference.sampler import AdaptiveK

    ak = AdaptiveK(k_max=8)
    assert ak.rungs == (1, 2, 4, 8)
    rng = np.random.default_rng(0)
    for _ in range(500):
        k = int(rng.integers(1, 9))
        ak.observe("r", int(rng.integers(0, k + 1)), k)
        assert 1 <= ak.target_k("r") <= 8
        assert ak.target_k("r") in ak.rungs


def test_adaptive_k_walks_down_under_rejection_and_resets_optimistic():
    from fault_tolerant_llm_training_tpu.inference.sampler import AdaptiveK

    ak = AdaptiveK(k_max=8)
    assert ak.target_k("r") == 8, "no evidence -> optimistic"
    for _ in range(10):
        ak.observe("r", 0, 8)  # stale draft: nothing accepted
    assert ak.target_k("r") == 1, "full rejection degrades to plain decode"
    for _ in range(20):
        ak.observe("r", 8, 8)  # perfect acceptance recovers
    assert ak.target_k("r") == 8
    ak.observe("other", 0, 8)
    assert ak.round_k(["r", "other"]) == 1, "least-accepting stream rules"
    assert ak.round_k([]) == 8
    ak.reset()  # fresh draft installed
    assert ak.target_k("other") == 8
    ak.observe("gone", 0, 8)
    ak.forget("gone")
    assert ak.target_k("gone") == 8


def test_adaptive_k_validates_construction():
    from fault_tolerant_llm_training_tpu.inference.sampler import AdaptiveK

    with pytest.raises(ValueError):
        AdaptiveK(k_max=0)
    with pytest.raises(ValueError):
        AdaptiveK(k_max=4, decay=1.0)
    assert AdaptiveK(k_max=1).rungs == (1,)


def test_adaptive_spec_rounds_stream_matches_fixed_width(tmp_path):
    """Numerics guard for the compiled-ladder path: a greedy spec stream
    under the adaptive controller emits the same tokens as the fixed-width
    engine — narrower rounds change the proposal batching, not the
    accepted argmax chain."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.sampler import AdaptiveK
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    cfg = tiny_cfg()
    params = _init_params(cfg, seed=0)
    draft_params = _init_params(cfg, seed=3)
    prompt = [5, 9, 2, 14, 7]

    def stream(adaptive):
        engine = InferenceEngine(cfg, params, slots=2, max_len=48,
                                 draft_cfg=cfg,
                                 draft_params=draft_params, spec_k=4)
        sched = Scheduler(engine, adaptive_k=adaptive)
        sched.submit(_greedy_request("r", prompt, 10))
        return _run_to_completion(sched)["r"]

    fixed = stream(None)
    adaptive = stream(AdaptiveK(k_max=4))
    assert adaptive == fixed
