"""Multi-tenant LoRA adapter serving (inference/adapters.py + the fused
adapter lane through engine/scheduler, deploy/publish.py sub-pointers).

Evidence ladder:

1. pool — adapter pages ride the SAME BlockAllocator discipline as KV
   blocks: page 0 is the reserved null page, exhaustion queues instead of
   crashing, cold adapters evict under pressure and reload CRC-verified,
   double-frees fail loudly;
2. engine/scheduler — K concurrent streams on K DIFFERENT adapters,
   batched through ONE decode dispatch per round, BIT-MATCH K sequential
   single-adapter runs, and the null adapter '' bit-matches an engine
   built with no adapter lane at all (adapter_rank=0);
3. integrity — a corrupt adapter artifact is rejected at page-in
   (request completes with reason ``adapter_rejected``), the pool and the
   base params untouched; verify_pointer rejects a publish whose adapter
   sub-pointer names flipped bytes;
4. hot swap — a new adapter version swapped mid-stream (the deploy
   reload path's mgr.swap) leaves the in-flight stream bit-exact on the
   version it pinned while requests admitted after the swap serve the new
   version.
"""

import json
import os

import numpy as np
import pytest

from _tiny import tiny_cfg


def _init_params(cfg, seed=0):
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    model = Transformer(cfg)
    return model.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]


@pytest.fixture(scope="module")
def cfg_params():
    cfg = tiny_cfg()
    return cfg, _init_params(cfg)


def _engine(cfg, params, rank=4, pages=0, slots=3):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    return InferenceEngine(cfg, params, slots=slots, max_len=32,
                           prefill_buckets=(8, 16), kv_layout="paged",
                           kv_block_size=8, adapter_rank=rank,
                           adapter_num_pages=pages)


def _write_adapter(root, layout, name, seed, step=1, alpha=32.0,
                   scale=0.5):
    from fault_tolerant_llm_training_tpu.inference.adapters import (
        init_adapter_factors, write_adapter_artifact)

    factors = init_adapter_factors(layout, seed=seed, scale=scale)
    ent = write_adapter_artifact(str(root), name, step, factors,
                                 rank=layout.rank, alpha=alpha)
    return os.path.join(str(root), ent["path"])


def _request(rid, prompt, n, adapter=""):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Request

    return Request(id=rid, prompt=prompt, max_new_tokens=n,
                   adapter=adapter)


def _serve(engine, arts, reqs):
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Scheduler)

    for name, art_dir in arts.items():
        engine.adapters.register(name, art_dir)
    sched = Scheduler(engine, eos_token_id=None)
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    engine.reset()
    return {c.request_id: c.tokens for c in done}, sched


# ------------------------------------------------------------------ 1. pool
def test_adapter_pool_reuses_block_allocator_discipline(cfg_params,
                                                        tmp_path):
    cfg, params = cfg_params
    eng = _engine(cfg, params)
    mgr = eng.adapters
    per = mgr.layout.pages_per_adapter
    art = _write_adapter(tmp_path, mgr.layout, "ta", seed=1)
    mgr.register("ta", art)

    assert not mgr.resident("ta")
    assert mgr.resident("")  # the null adapter is always servable
    assert mgr.page_in("ta")
    assert mgr.resident_pages() == per
    # pages came from the allocator, page 0 (null) never handed out
    rec_rows = mgr.acquire("ta", 0)[0]
    assert 0 not in set(int(r) for r in rec_rows)
    # double free fails loudly, same contract as the KV pools
    mgr.release(0)
    pages = list(rec_rows)
    mgr.evict("ta")
    with pytest.raises(ValueError, match="double free"):
        mgr.allocator.free([int(pages[0])])


def test_combined_footprint_eviction_under_pressure(cfg_params, tmp_path):
    """Pool sized for ONE resident adapter: the second tenant's request
    queues behind page-in while the first is pinned, then evicts the cold
    adapter once it drains — everything completes, nothing crashes, and
    the stream served after the evict/reload cycle is still bit-exact."""
    cfg, params = cfg_params
    eng = _engine(cfg, params)
    layout = eng._adapter_layout
    per = layout.pages_per_adapter
    arts = {"ta": _write_adapter(tmp_path, layout, "ta", seed=1),
            "tb": _write_adapter(tmp_path, layout, "tb", seed=2)}

    # room for exactly one adapter beside the null page
    eng_small = _engine(cfg, params, pages=per + 1)
    reqs = [_request("r0", [1, 2, 3], 6, adapter="ta"),
            _request("r1", [4, 5, 6], 6, adapter="tb")]
    conc, sched = _serve(eng_small, arts, reqs)
    m = sched.metrics()
    assert set(conc) == {"r0", "r1"}
    assert m["adapter_evictions"] >= 1  # ta evicted to make room for tb
    assert m["adapter_pageins"] >= 2
    assert m["adapter_rejects"] == 0
    assert m["adapter_waits"] >= 1  # r1 queued behind the busy pool

    # sequential reference runs on a roomy pool: eviction+reload must not
    # have perturbed either stream
    for r in reqs:
        one, _ = _serve(_engine(cfg, params), arts,
                        [_request(r.id, list(r.prompt), 6,
                                  adapter=r.adapter)])
        assert one[r.id] == conc[r.id]


def test_scheduler_admission_validates_adapters(cfg_params):
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Scheduler)

    cfg, params = cfg_params
    eng = _engine(cfg, params)
    sched = Scheduler(eng, eos_token_id=None)
    with pytest.raises(ValueError, match="unregistered adapter"):
        sched.submit(_request("r0", [1, 2], 4, adapter="ghost"))
    eng.reset()

    eng0 = _engine(cfg, params, rank=0)
    sched0 = Scheduler(eng0, eos_token_id=None)
    with pytest.raises(ValueError, match="adapter_rank=0"):
        sched0.submit(_request("r0", [1, 2], 4, adapter="ta"))


# ------------------------------------------------- 2. batched heterogeneous
def test_heterogeneous_batch_bitmatches_sequential(cfg_params, tmp_path):
    """Three slots serving three DIFFERENT adapters (one of them the null
    adapter) in the same fused decode dispatches must produce streams
    bitwise identical to three sequential single-adapter runs — and the
    null stream must bit-match an engine built without the adapter lane."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Scheduler)

    cfg, params = cfg_params
    eng = _engine(cfg, params)
    layout = eng._adapter_layout
    arts = {"ta": _write_adapter(tmp_path, layout, "ta", seed=10),
            "tb": _write_adapter(tmp_path, layout, "tb", seed=11)}
    reqs = [_request("r0", [1, 2, 3], 6, adapter="ta"),
            _request("r1", [4, 5, 6], 6, adapter="tb"),
            _request("r2", [7, 8, 9], 6, adapter="")]

    conc, sched = _serve(eng, arts, reqs)
    m = sched.metrics()
    assert sorted(m["adapters_resident"]) == ["ta", "tb"]
    assert m["adapters_served"] == 2
    # one page-in an adapter however many dispatches gather its pages, and
    # the default pool holds both: nothing evicted, nothing rejected
    assert (m["adapter_pageins"], m["adapter_evictions"],
            m["adapter_rejects"]) == (2, 0, 0)

    for r in reqs:
        one, _ = _serve(_engine(cfg, params), arts,
                        [_request(r.id, list(r.prompt), 6,
                                  adapter=r.adapter)])
        assert one[r.id] == conc[r.id], (
            f"{r.id} ({r.adapter or 'null'}) diverged from its "
            f"sequential single-adapter run")

    # adapter-0 == no-adapter baseline, bitwise
    eng_base = _engine(cfg, params, rank=0)
    sched_base = Scheduler(eng_base, eos_token_id=None)
    sched_base.submit(_request("r2", [7, 8, 9], 6))
    base = {c.request_id: c.tokens for c in sched_base.run()}
    assert base["r2"] == conc["r2"], (
        "the null adapter must be bit-identical to adapter_rank=0")


# --------------------------------------------------------------- 3. integrity
def _corrupt_one_factor(art_dir):
    victim = sorted(f for f in os.listdir(art_dir)
                    if f.endswith(".npy"))[0]
    path = os.path.join(art_dir, victim)
    with open(path, "r+b") as fh:
        fh.seek(-1, os.SEEK_END)
        byte = fh.read(1)
        fh.seek(-1, os.SEEK_END)
        fh.write(bytes([byte[0] ^ 0xFF]))


def test_corrupt_adapter_rejected_pool_and_params_untouched(cfg_params,
                                                            tmp_path):
    cfg, params = cfg_params
    eng = _engine(cfg, params)
    layout = eng._adapter_layout
    arts = {"ta": _write_adapter(tmp_path, layout, "ta", seed=10),
            "evil": _write_adapter(tmp_path, layout, "evil", seed=66)}
    _corrupt_one_factor(arts["evil"])

    reqs = [_request("r0", [1, 2, 3], 6, adapter="evil"),
            _request("r1", [4, 5, 6], 6, adapter="ta")]
    done, sched = _serve(eng, arts, reqs)
    m = sched.metrics()
    # the corrupt tenant is REJECTED (no tokens), never paged in; the
    # healthy tenant on the same pool serves normally
    assert done["r0"] == []
    assert m["adapter_rejects"] == 1
    assert m["adapters_resident"] == ["ta"]
    by_id = {c.request_id: c for c in sched.completed}
    assert by_id["r0"].reason == "adapter_rejected"
    assert len(done["r1"]) == 6

    # ... and r1's stream equals a run where the corrupt artifact never
    # existed — the rejected page-in left pool AND params untouched
    clean, _ = _serve(_engine(cfg, params),
                      {"ta": arts["ta"]},
                      [_request("r1", [4, 5, 6], 6, adapter="ta")])
    assert clean["r1"] == done["r1"]


def test_verify_pointer_rejects_corrupt_adapter_publish(tmp_path):
    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        write_manifest)
    from fault_tolerant_llm_training_tpu.deploy.publish import (
        Publisher, adapter_pointer, verify_pointer)
    from fault_tolerant_llm_training_tpu.inference.adapters import (
        AdapterLayout)

    # a fake manifested checkpoint step for the main pointer target
    step_dir = tmp_path / "checkpoint_pub" / "20"
    step_dir.mkdir(parents=True)
    (step_dir / "payload.bin").write_bytes(b"weights" * 64)
    write_manifest(str(step_dir), 20)

    layout = AdapterLayout.from_cfg(tiny_cfg(), 4)
    art = _write_adapter(tmp_path, layout, "ta", seed=3)
    sub = adapter_pointer(str(tmp_path), "ta", art)
    assert sub is not None and sub["rank"] == 4

    pub = Publisher(str(tmp_path), "pub")
    ptr = pub.publish(20, adapters={"ta": sub})
    assert ptr is not None
    assert verify_pointer(str(tmp_path), ptr) == (True, "ok")
    # published.json carries the tenant -> adapter map
    with open(tmp_path / "published.json") as fh:
        assert "ta" in json.load(fh)["adapters"]

    _corrupt_one_factor(art)
    ok, detail = verify_pointer(str(tmp_path), ptr)
    assert not ok and "adapter ta" in detail


# ----------------------------------------------------------------- 4. hot swap
def test_hot_swap_midstream_preserves_inflight_slots(cfg_params, tmp_path):
    """Swap a NEW version of an adapter in mid-decode (what the deploy
    reload does inside its prefill-pause): the in-flight stream must keep
    decoding the version it pinned, bit-exact end to end, while a request
    admitted after the swap serves the new version."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Scheduler)

    cfg, params = cfg_params
    eng = _engine(cfg, params)
    layout = eng._adapter_layout
    art_v1 = _write_adapter(tmp_path / "v1", layout, "ta", seed=10,
                            step=1)
    art_v2 = _write_adapter(tmp_path / "v2", layout, "ta", seed=99,
                            step=2, scale=0.7)

    # reference streams: all-v1 and all-v2 sequential runs
    ref_v1, _ = _serve(_engine(cfg, params), {"ta": art_v1},
                       [_request("r0", [1, 2, 3], 8, adapter="ta")])
    ref_v2, _ = _serve(_engine(cfg, params), {"ta": art_v2},
                       [_request("r1", [4, 5, 6], 6, adapter="ta")])
    assert ref_v1["r0"][:6] != ref_v2["r1"]  # the versions really differ

    eng.adapters.register("ta", art_v1)
    sched = Scheduler(eng, eos_token_id=None)
    sched.submit(_request("r0", [1, 2, 3], 8, adapter="ta"))
    for _ in range(3):  # r0 prefills and decodes a few tokens on v1
        sched.step()
    assert eng.adapters.active_slots().get("ta", 0) == 1

    assert eng.adapters.swap("ta", art_v2)  # both versions now resident
    sched.submit(_request("r1", [4, 5, 6], 6, adapter="ta"))
    done = {c.request_id: c.tokens for c in sched.run()}

    assert done["r0"] == ref_v1["r0"], (
        "the in-flight slot must finish on the version it pinned")
    assert done["r1"] == ref_v2["r1"], (
        "a request admitted after the swap must serve the new version")
    # the drained v1 pages were reclaimed — no stale-version leak
    assert eng.adapters.stats()["stale_versions"] == 0
    sched.audit_block_leaks(strict=True)
