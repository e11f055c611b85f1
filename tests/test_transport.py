"""Pluggable KV transport (inference/transport.py): the zero-copy
in-memory push lane for block trains, sub-train (partial prefix)
addressability in the fleet store, and decode-gauge prefill pacing.

Evidence ladder:

1. lanes — the mem-lane disaggregated pipeline reproduces the colocated
   stream BITWISE for bf16 and int8 pools, and the fabric-resident
   device arrays are byte-identical to the fs artifact's payload files
   (the two lanes carry the same train);
2. sub-train addressability — a prompt that is a proper PREFIX of a
   longer published train is served partially: exactly the covered
   blocks land on device, the rest of the train stays on disk, and the
   stream matches the no-store reference bitwise;
3. fallback ladder — poisoned mem metadata (the ``mem_corrupt`` shape)
   degrades that train to the fs artifact with the stream intact;
   poisoning the fs payload too degrades to the committed-prefix
   replay — mem -> fs -> replay, nothing lost at any rung;
4. mixed dtype — a bf16 train is geometry-rejected by an int8 pool on
   BOTH lanes before any device write;
5. pacing — a starved decode fleet (pacing() below the prompt's block
   need) defers prefill admission without reordering the queue, a
   recovered fleet admits normally, and pacing() -> None (no decode
   peers visible) never stalls.
"""

import glob
import os

import numpy as np
import pytest

from _tiny import tiny_cfg


@pytest.fixture(scope="module")
def xport_setup():
    """One tiny model, builders per kv-dtype, and the bf16 colocated
    reference streams the transported pipelines must reproduce."""
    import jax
    import jax.numpy as jnp

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg(seq_len=128)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]

    def build(kv_dtype="bf16", slots=4):
        return InferenceEngine(cfg, params, slots=slots, max_len=128,
                               prefill_buckets=(16, 32), kv_layout="paged",
                               kv_block_size=8, kv_dtype=kv_dtype)

    rng = np.random.default_rng(23)
    reqs = [
        Request(id="g", prompt=rng.integers(3, 64, size=41).tolist(),
                max_new_tokens=16, seed=1),
        Request(id="s", prompt=rng.integers(3, 64, size=37).tolist(),
                max_new_tokens=12, temperature=0.8, top_p=0.9, seed=2),
    ]

    def reference(kv_dtype="bf16"):
        sched = Scheduler(build(kv_dtype))
        for r in reqs:
            sched.submit(r)
        sched.run()
        return {c.request_id: c.tokens for c in sched.completed}

    return {"build": build, "reqs": reqs, "reference": reference,
            "ref": reference("bf16"), "Request": Request,
            "Scheduler": Scheduler}


def _mem_pipeline(setup, tmp_path, kv_dtype="bf16", poison=None,
                  corrupt_fs=None):
    """Run prefill -> decode over a shared MemFabric; returns
    (pre, dec, streams, ships). ``poison(fabric, ships)`` runs between
    the roles (the mem_corrupt window), ``corrupt_fs(ships)`` too."""
    from fault_tolerant_llm_training_tpu.inference.transport import (
        MemFabric, MemTransport)

    Request, Scheduler = setup["Request"], setup["Scheduler"]
    fabric = MemFabric()
    ships = {}

    def on_ship(req, art_dir, ordinal, seq, start, end, length):
        ships.setdefault(req.id, []).append(
            {"artifact": art_dir, "seq": seq, "start_block": start,
             "end_block": end, "length": length, "lane": "mem"})

    pre = Scheduler(setup["build"](kv_dtype), role="prefill",
                    ship_dir=str(tmp_path / f"ships_{kv_dtype}"),
                    on_ship=on_ship, transport=MemTransport(fabric))
    for r in setup["reqs"]:
        pre.submit(r)
    pre.run()
    if poison is not None:
        poison(fabric, ships)
    if corrupt_fs is not None:
        corrupt_fs(ships)
    first = {c.request_id: c.tokens for c in pre.completed}
    dec = Scheduler(setup["build"](kv_dtype), role="decode",
                    transport=MemTransport(fabric))
    for r in setup["reqs"]:
        dec.submit(Request(id=r.id, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens,
                           temperature=r.temperature, top_p=r.top_p,
                           seed=r.seed, committed=tuple(first[r.id])),
                   shipments=ships.get(r.id), ship_gen=0)
    dec.run()
    return pre, dec, {c.request_id: c.tokens for c in dec.completed}, ships


# ----------------------------------------------------------------- 1. lanes
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_mem_lane_bitmatch(xport_setup, tmp_path, kv_dtype):
    """The tentpole guarantee, per storage dtype: trains pushed through
    the mem lane land the EXACT colocated stream, and the fabric holds
    byte-identical payloads to the fs artifacts it rides with."""
    ref = (xport_setup["ref"] if kv_dtype == "bf16"
           else xport_setup["reference"](kv_dtype))
    pre, dec, out, ships = _mem_pipeline(xport_setup, tmp_path, kv_dtype)
    assert out == ref, "mem-lane stream diverged from colocated"
    # every export was pushed; every import landed on the mem lane
    assert len(pre.transport.fabric) == pre.ship_exports >= 2
    assert dec.mem_lane_imports == len(xport_setup["reqs"])
    assert dec.lane_fallbacks == 0 and dec.ship_rejects == 0
    assert dec.transport.land_seconds["mem"] > 0.0
    assert dec.transport.lane_bytes["mem"] > 0
    m = dec.metrics()
    assert m["kv_transport_lane"] == "mem"
    assert m["kv_transport_mem_imports"] == len(xport_setup["reqs"])
    # lane equivalence down to the bytes: each pushed train's device
    # arrays re-serialize to the artifact's per-block payload files
    for lst in ships.values():
        for s in lst:
            train = pre.transport.fabric.get(s["artifact"])
            files = sorted(glob.glob(os.path.join(s["artifact"],
                                                  "block_*.bin")))
            assert len(files) == s["end_block"] - s["start_block"]
            for j, path in enumerate(files):
                mem_bytes = b"".join(np.asarray(a[j]).tobytes()
                                     for a in train.arrays)
                assert mem_bytes == open(path, "rb").read(), (
                    f"{os.path.basename(s['artifact'])} block {j}: mem "
                    f"payload != fs payload")
    assert dec.audit_block_leaks(strict=True) == []


# -------------------------------------------- 2. sub-train addressability
def test_partial_prefix_hit_lands_covered_blocks_only(xport_setup,
                                                      tmp_path):
    """Publish a 5-block train; a prompt covering only its first 2
    blocks must fetch partially: depth < train blocks, exactly the
    covered rows written, stream bit-exact vs the no-store run."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        block_layout, block_payload)
    from fault_tolerant_llm_training_tpu.inference.kvstore import (
        BlockStore)
    from fault_tolerant_llm_training_tpu.inference.prefix_cache import (
        chain_hashes)

    Request, Scheduler = xport_setup["Request"], xport_setup["Scheduler"]
    rng = np.random.default_rng(5)
    prompt_a = rng.integers(3, 64, size=40).tolist()   # 5 full blocks
    prompt_b = prompt_a[:20]                           # 2 full blocks + 4
    store_dir = str(tmp_path / "store")

    pub = Scheduler(xport_setup["build"](),
                    kv_store=BlockStore(store_dir, writer="pub"))
    pub.submit(Request(id="a", prompt=prompt_a, max_new_tokens=4, seed=3))
    pub.run()
    assert pub.store_publishes == 1

    store = BlockStore(store_dir, writer="probe")
    hit = store.match(chain_hashes(prompt_b, 8))
    assert hit is not None and hit.partial
    assert (hit.depth, hit.blocks) == (2, 5)

    # landing surface: exactly the covered rows change, nothing else
    eng = xport_setup["build"]()
    layout_before = [np.asarray(seg["array"])
                     for seg in block_layout(eng.cache)]
    manifest = eng.import_pool_block_batch(
        [(hit.art_dir, [1, 2])], allow_partial=True)[0]
    assert len(manifest["blocks"]) == 5   # the train is longer on disk
    for row, src in ((1, 0), (2, 1)):
        want = open(os.path.join(hit.art_dir,
                                 f"block_{src:05d}.bin"), "rb").read()
        assert block_payload(eng.cache, row) == want
    for si, seg in enumerate(block_layout(eng.cache)):
        got = np.asarray(seg["array"])
        assert np.array_equal(got[3:], layout_before[si][3:]), (
            "rows beyond the covered prefix changed")

    # end to end: the partial fetch feeds the prefix cache and the
    # stream still matches the storeless reference bitwise
    ref = Scheduler(xport_setup["build"]())
    ref.submit(Request(id="b", prompt=prompt_b, max_new_tokens=8, seed=4))
    ref.run()
    want = {c.request_id: c.tokens for c in ref.completed}

    fetch = Scheduler(xport_setup["build"](),
                      kv_store=BlockStore(store_dir, writer="fetch"))
    fetch.submit(Request(id="b", prompt=prompt_b, max_new_tokens=8,
                         seed=4))
    fetch.run()
    got = {c.request_id: c.tokens for c in fetch.completed}
    assert got == want
    assert fetch.store_fetches == 1
    assert fetch.store_partial_hits == 1
    assert fetch.metrics()["kv_store_partial_hits"] == 1
    assert fetch.audit_block_leaks(strict=True) == []


# ------------------------------------------------------ 3. fallback ladder
def test_mem_poison_degrades_to_fs_lane(xport_setup, tmp_path):
    """mem_corrupt shape: poisoning one pushed train's manifest metadata
    fails the digest verify, and that request's WHOLE train degrades to
    the fs artifacts — stream bit-exact, nothing replayed."""
    def poison(fabric, ships):
        assert fabric.poison(ships["g"][0]["artifact"])

    pre, dec, out, _ = _mem_pipeline(xport_setup, tmp_path / "p1",
                                     poison=poison)
    assert out == xport_setup["ref"]
    assert dec.lane_fallbacks == 1 and dec.ship_rejects == 0
    # the untouched request still lands on the mem lane
    assert dec.mem_lane_imports == 1
    assert dec.metrics()["kv_transport_lane_fallbacks"] == 1
    assert dec.audit_block_leaks(strict=True) == []


def test_mem_and_fs_poison_degrade_to_replay(xport_setup, tmp_path):
    """Both rungs poisoned: mem digest mismatch AND a flipped fs payload
    byte. The ladder bottoms out at the committed-prefix replay and the
    stream is still bit-exact — the full mem -> fs -> replay contract."""
    def poison(fabric, ships):
        assert fabric.poison(ships["g"][0]["artifact"])

    def corrupt_fs(ships):
        p = sorted(glob.glob(os.path.join(
            ships["g"][0]["artifact"], "block_*.bin")))[0]
        raw = bytearray(open(p, "rb").read())
        raw[7] ^= 0xFF
        open(p, "wb").write(bytes(raw))

    pre, dec, out, _ = _mem_pipeline(xport_setup, tmp_path / "p2",
                                     poison=poison, corrupt_fs=corrupt_fs)
    assert out == xport_setup["ref"], "replay rung lost the stream"
    assert dec.lane_fallbacks >= 1
    assert dec.ship_rejects == 1
    assert dec.audit_block_leaks(strict=True) == []


# -------------------------------------------------------- 4. mixed dtype
def test_mixed_dtype_rejected_on_both_lanes(xport_setup, tmp_path):
    """A bf16 train cannot land in an int8 pool: geometry-rejected on
    the mem lane AND the fs lane, before any device write."""
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        KVBlockIntegrityError)
    from fault_tolerant_llm_training_tpu.inference.transport import (
        MemTransport)

    xport = MemTransport()
    src = xport_setup["build"]("bf16", slots=2)
    art = str(tmp_path / "mixed_train")
    xport.export(src.cache, [1, 2], art, length=16,
                 meta={"kind": "ship", "request_id": "x"})
    dst = xport_setup["build"]("int8", slots=2)
    before = [np.asarray(a.q if hasattr(a, "q") else a)
              for a in (*dst.cache.k, *dst.cache.v)]
    for lane in ("mem", "fs"):
        with pytest.raises(KVBlockIntegrityError, match="geometry"):
            xport.import_batch(dst, [(art, [1, 2])], lane=lane)
    after = [np.asarray(a.q if hasattr(a, "q") else a)
             for a in (*dst.cache.k, *dst.cache.v)]
    for b, a in zip(before, after):
        assert np.array_equal(b, a), "rejected import touched the pool"


# ------------------------------------------------------------- 5. pacing
def test_pacing_defers_prefill_under_starved_decode_pool(xport_setup,
                                                         tmp_path):
    """ROADMAP item 2's control plane: pacing() below the head prompt's
    block need defers admission (queue intact, FIFO preserved); restored
    capacity admits; pacing() -> None never stalls."""
    Scheduler = xport_setup["Scheduler"]
    state = {"free": 0}
    pre = Scheduler(xport_setup["build"](), role="prefill",
                    ship_dir=str(tmp_path / "paced_ships"),
                    pacing=lambda: state["free"])
    for r in xport_setup["reqs"]:
        pre.submit(r)
    for _ in range(4):
        pre.step()
    assert not pre.active and not pre.completed
    assert len(pre.queue) == len(xport_setup["reqs"])  # nothing dropped
    assert pre.prefill_paced >= 4  # every deferred round counted
    assert pre.metrics()["prefill_paced"] == pre.prefill_paced

    state["free"] = 10_000  # the decode fleet drained its backlog
    pre.run()
    assert {c.request_id for c in pre.completed} == {"g", "s"}
    assert all(c.reason == "prefill" for c in pre.completed)
    assert pre.ship_exports >= 2
    assert pre.audit_block_leaks(strict=True) == []

    # no decode peers visible yet (pacing None): admission proceeds —
    # a lone prefill host must not deadlock before the fleet assembles
    lone = Scheduler(xport_setup["build"](), role="prefill",
                     ship_dir=str(tmp_path / "lone_ships"),
                     pacing=lambda: None)
    lone.submit(xport_setup["reqs"][0])
    lone.run()
    assert lone.prefill_paced == 0
    assert [c.request_id for c in lone.completed] == ["g"]
