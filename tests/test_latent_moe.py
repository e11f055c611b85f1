"""The latent-attention / indexer / window / expert class
(``models/latent_moe.py``) against the plain float32 reference of its family
file (``perfbench/families/dots3.py``), at the tiny sizes of
``perfbench/configs/tiny-dots3.json``, on the CPU, weights from a seed
through ``weights.make_leaf``:

(a) prefill + decode through the ENGINE's caches against the reference's
    full forward, logits, with contexts past the tiny ``index_topk`` (8)
    and the tiny window (9), over a turn served from cached blocks (the
    window rebuild) and over a restart from the base context; and the same
    through ``Scheduler`` and its prefix cache, tokens;
(b) each mixer and the expert layer alone against the reference's;
(c) the shares of an expert layer add up to the uncut layer;
(d) dropless under the worst routing (every token to one held expert);
(e) a router width, top-k, ``index_topk`` or window other than the
    configuration's is caught;
plus what the engine refuses for this class, by name.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from perfbench.lib import reference as R  # noqa: E402
from perfbench.lib import weights  # noqa: E402

TOL = 2e-4   # float32 program against float32 reference, logits of |x| < 10
KEY = jax.random.PRNGKey(11)


def _family(**over):
    config = json.loads((REPO / "perfbench" / "configs"
                         / "tiny-dots3.json").read_text())
    config.update(over)
    d = weights.dims_of(config)
    fam = weights.family_of(d)
    cfg = fam.preset(config, dtype=jnp.float32, param_dtype=jnp.float32)
    return config, d, fam, cfg


@pytest.fixture(scope="module")
def tiny():
    config, d, fam, cfg = _family()
    params = weights.make_param_tree(KEY, d, jnp.float32)
    return d, fam, cfg, params


@pytest.fixture(scope="module")
def engine(tiny):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    d, fam, cfg, params = tiny
    return InferenceEngine(cfg, params, slots=3, max_len=128,
                           prefill_buckets=(8, 32), kv_block_size=8,
                           kv_num_blocks=3 * 16 + 1)


def _ref_logits(tiny, seq, positions):
    d, fam, _, _ = tiny
    return fam.forward_logits(KEY, d, np.asarray(seq, np.int32),
                              np.asarray(positions), R.mm_f32, jnp.float32)


def _peek_logits(engine, tables, tokens):
    """Logits of the next decode round, without committing it: the decode
    program's own forward over the engine's caches, nothing donated."""
    cache = engine.cache
    logits, _, _ = engine.model.apply(
        {"params": engine.params}, jnp.asarray(tokens, jnp.int32)[:, None],
        cache, cache.lengths, jnp.asarray(tables, jnp.int32),
        jnp.ones((engine.slots, 1), jnp.bool_),
        jnp.arange(engine.slots, dtype=jnp.int32), cache.win_from,
        method="forward_with_cache")
    return np.asarray(logits[:, 0])


def _greedy_args(engine):
    n = engine.slots
    return (np.zeros(n, np.float32), np.ones(n, np.float32),
            np.zeros(n, np.int32))


# --------------------------------------------- (a) the engine's caches, logits
def test_engine_prefill_and_decode_match_the_reference_logits(tiny, engine):
    """One session: its 50-token context prefilled in chunks (buckets 8 and
    32: three programs, the window ring carried between them), 10 tokens
    decoded; then a TURN in another slot whose first 56 positions are the
    first slot's blocks (resumed at 56: the engine recomputes the 24
    positions before it to rebuild three sliding layers' windows and writes
    none of them); then a RESTART from the base context at its last whole
    block. Every step's logits against the reference's full forward."""
    engine.reset()
    rng = np.random.default_rng(3)
    base = rng.integers(3, 512, size=50).astype(np.int32)
    bs, per = engine.block_size, engine.max_blocks_per_slot
    tables = np.zeros((engine.slots, per), np.int32)
    tables[0] = 1 + np.arange(per)
    temp, top_p, seeds = _greedy_args(engine)

    def decode(slot, seq, first, steps):
        seq = list(seq) + [first]
        active = np.zeros(engine.slots, bool)
        active[slot] = True
        for i in range(steps):
            toks = np.zeros(engine.slots, np.int32)
            toks[slot] = seq[-1]
            got = _peek_logits(engine, tables, toks)[slot]
            want = _ref_logits(tiny, seq, [len(seq) - 1])[0]
            assert np.abs(got - want).max() < TOL, (slot, i)
            out = engine.decode_step(toks, active, temp, top_p, seeds,
                                     np.full(engine.slots, i, np.int32),
                                     block_tables=tables)
            assert out[slot] == int(want.argmax())
            seq.append(int(out[slot]))
        return seq

    first = engine.prefill(0, base, block_row=tables[0])
    assert first == int(_ref_logits(tiny, base, [49])[0].argmax())
    hist = decode(0, base, first, 10)            # 61 tokens, 60 rows cached

    # a turn: history + 6 new tokens into slot 1, over slot 0's first 7
    # blocks (56 positions) and fresh blocks after them
    turn = np.concatenate([np.asarray(hist, np.int32),
                           rng.integers(3, 512, size=6).astype(np.int32)])
    tables[1, :7] = tables[0, :7]
    tables[1, 7:] = 17 + np.arange(per - 7)
    rows = (tables[0, :7, None] * bs + np.arange(bs)).reshape(-1)
    shared_before = [np.asarray(p[rows]) for p in engine.cache.latent]
    first = engine.prefill(1, turn, block_row=tables[1], start_pos=7 * bs)
    assert first == int(_ref_logits(tiny, turn, [len(turn) - 1])[0].argmax())
    for before, pool in zip(shared_before, engine.cache.latent):
        assert np.array_equal(before, np.asarray(pool[rows])), (
            "a resumed prefill wrote into the blocks it resumed from")
    assert int(np.asarray(engine.cache.win_from)[1]) == 7 * bs - 24
    decode(1, turn, first, 6)

    # a restart: the base context + 5 other tokens into slot 2, over the
    # base's 6 whole blocks (48 positions)
    again = np.concatenate([base, rng.integers(3, 512, size=5).astype(
        np.int32)])
    tables[2, :6] = tables[0, :6]
    tables[2, 6:] = 33 + np.arange(per - 6)
    first = engine.prefill(2, again, block_row=tables[2], start_pos=6 * bs)
    assert first == int(_ref_logits(tiny, again,
                                    [len(again) - 1])[0].argmax())
    decode(2, again, first, 4)


def test_scheduler_serves_turns_and_restarts_from_the_prefix_cache(tiny,
                                                                  engine):
    """Through ``Scheduler`` and its prefix cache, as the benchmark's closed
    loop does: a context, a turn appended to its history (a cache hit), a
    restart from the base. Every served token is the reference's choice and
    the hits were taken."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    engine.reset()
    sched = Scheduler(engine, eos_token_id=None)
    rng = np.random.default_rng(5)
    base = rng.integers(3, 512, size=50).astype(np.int32)

    def serve(rid, prompt, n):
        sched.submit(Request(id=rid, prompt=prompt, max_new_tokens=n))
        done = []
        while sched.pending():
            done += sched.step()
        (c,) = [c for c in done if c.request_id == rid]
        seq = np.concatenate([prompt, np.asarray(c.tokens[:-1], np.int32)])
        ref = _ref_logits(tiny, seq, np.arange(len(prompt) - 1, len(seq)))
        assert list(c.tokens) == [int(t) for t in ref.argmax(-1)], rid
        return np.concatenate([prompt, np.asarray(c.tokens, np.int32)])

    hist = serve("ctx", base, 12)
    hits0 = sched.prefix_cache.hit_tokens
    serve("turn", np.concatenate(
        [hist, rng.integers(3, 512, size=6).astype(np.int32)]), 12)
    # the cache holds what a request PREFILLED (its prompt's whole blocks):
    # a turn resumes from its predecessor's prompt, not from its output
    assert sched.prefix_cache.hit_tokens - hits0 == 48
    hits1 = sched.prefix_cache.hit_tokens
    serve("restart", np.concatenate(
        [base, rng.integers(3, 512, size=5).astype(np.int32)]), 8)
    assert sched.prefix_cache.hit_tokens - hits1 == 48


def test_window_layers_hold_the_window_not_the_context(tiny, engine):
    d, fam, cfg, _ = tiny
    rings = engine.cache.window
    assert len(rings) == 3 and all(
        r.shape == (engine.slots, cfg.window_ring,
                    d["sliding"]["kv_rank"] + d["sliding"]["rope"])
        for r in rings)
    assert cfg.sliding_window <= cfg.window_ring < cfg.sliding_window + 16
    held = engine.cache.resident_bytes()
    assert held["sliding"] == 3 * engine.slots * cfg.window_ring * 32 * 4
    # the full layers' pools are what grows with the pool's tokens
    assert held["full"] == 2 * engine.num_blocks * engine.block_size * (
        24 + 16) * 4


# ------------------------------------------------ (b) the layers, one by one
def _layer_weights(fam, d, layer):
    return {p: weights.make_leaf(KEY, f"layers_{layer}/{p}", shape, kind,
                                 jnp.float32, "dots3")
            for p, (shape, kind) in fam.layer_leaves(d, layer).items()}


@pytest.mark.parametrize("kind,layer", [("full", 1), ("sliding", 2)])
def test_mixer_alone_matches_the_reference(tiny, kind, layer):
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_latent_cache)
    from fault_tolerant_llm_training_tpu.models.latent_moe import (
        LatentAttention)

    d, fam, cfg, _ = tiny
    w = fam.sub(_layer_weights(fam, d, layer), "attention/")
    s = 40                          # past the top-k of 8 and the window of 9
    u = jax.random.normal(jax.random.PRNGKey(2), (s, d["dim"]), jnp.float32)
    want = np.asarray(fam.mixer(w, u, d, R.mm_f32, kind))
    cache = init_latent_cache(cfg, 1, 8, 6)
    part = ((cache.latent[0], cache.rope[0], cache.index[0])
            if kind == "full" else (cache.window[0],))
    valid = jnp.ones((1, s), jnp.bool_)
    got, _ = LatentAttention(cfg, kind).apply(
        {"params": weights.nest(w)}, u[None], jnp.zeros((1,), jnp.int32),
        part, jnp.arange(1, 6, dtype=jnp.int32)[None], valid, valid,
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


def _expert_layer(cfg, w, u, valid=None):
    from fault_tolerant_llm_training_tpu.models.latent_moe import ExpertLayer

    valid = jnp.ones(u.shape[:1], jnp.bool_) if valid is None else valid
    routed, shared, pairs, touched = ExpertLayer(cfg).apply(
        {"params": weights.nest(w)}, u[None], valid[None], method="parts")
    return np.asarray(routed[0]), np.asarray(shared[0]), int(pairs), int(
        touched)


def test_expert_layer_alone_matches_the_reference(tiny):
    d, fam, cfg, _ = tiny
    w = fam.sub(_layer_weights(fam, d, 1), "feed_forward/")
    u = jax.random.normal(jax.random.PRNGKey(4), (37, d["dim"]), jnp.float32)
    routed, shared, pairs, touched = _expert_layer(cfg, w, u)
    want = np.asarray(fam.expert_layer(w, u, d, R.mm_f32))
    assert np.abs(routed + shared - want).max() < TOL
    assert 0 < pairs <= 37 * d["top_k"] and 0 < touched <= d["held"]
    # a token the program is told is padding is routed nowhere
    valid = jnp.arange(37) < 30
    routed_v, _, pairs_v, _ = _expert_layer(cfg, w, u, valid)
    assert np.abs(routed_v[:30] - routed[:30]).max() < 1e-6
    assert not routed_v[30:].any() and pairs_v < pairs


# ----------------------------------------------------- (c) the shares add up
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """All 8 routed experts drawn once; the uncut reference runs them all.
    Each of the 2 shares of 4 (and each of the 4 shares of 2) is the
    program's layer told it holds that range, given that range's slices:
    the routed parts summed and the shared expert counted once are the
    uncut layer."""
    _, d_all, fam, _ = _family(n_routed_experts=8, published={})
    assert (d_all["routed"], d_all["held"]) == (8, 8)
    w_all = {p: weights.make_leaf(KEY, f"layers_1/feed_forward/{p}", shape,
                                  kind, jnp.float32, "dots3")
             for p, (shape, kind) in fam.ffn_leaves(d_all, 1).items()}
    u = jax.random.normal(jax.random.PRNGKey(6), (33, d_all["dim"]),
                          jnp.float32)
    uncut = np.asarray(fam.expert_layer(w_all, u, d_all, R.mm_f32))
    for n_shares in (2, 4):
        m = 8 // n_shares
        total, pairs = 0.0, 0
        for j in range(n_shares):
            _, _, _, cfg = _family(
                n_routed_experts=m, published={"n_routed_experts": 8},
                program={"family": "dots3", "held_first": j * m})
            assert cfg.held_experts == (j * m, m)
            w = {p: (v[j * m:(j + 1) * m] if p.startswith("experts/") else v)
                 for p, v in w_all.items()}
            routed, shared, n_pairs, _ = _expert_layer(cfg, w, u)
            total, pairs = total + routed, pairs + n_pairs
        assert np.abs(total + shared - uncut).max() < TOL, n_shares
        assert pairs == 33 * d_all["top_k"]     # every pair, exactly once


# ------------------------------------------------ (d) dropless, worst routing
def test_expert_layer_is_dropless_when_every_token_takes_one_expert(tiny):
    """The router's selection bias sends every token to held expert 2 (and
    to one other): all 64 pairs of that expert are computed, none dropped,
    and the result is the reference's."""
    d, fam, cfg, _ = tiny
    w = fam.sub(_layer_weights(fam, d, 1), "feed_forward/")
    w["router/bias"] = w["router/bias"].at[2].set(100.0)
    u = jax.random.normal(jax.random.PRNGKey(8), (64, d["dim"]), jnp.float32)
    routed, shared, pairs, touched = _expert_layer(cfg, w, u)
    want = np.asarray(fam.expert_layer(w, u, d, R.mm_f32))
    assert np.abs(routed + shared - want).max() < TOL
    score = jax.nn.sigmoid(u @ w["router/kernel"])
    _, choice = jax.lax.top_k(score + w["router/bias"], d["top_k"])
    assert bool(jnp.all(jnp.any(choice == 2, axis=-1)))
    assert pairs == int(jnp.sum(choice < d["held"])) >= 64
    only = np.asarray(fam.expert_layer(
        {**w, "experts/w1/kernel": w["experts/w1/kernel"][2:3],
         "experts/w2/kernel": w["experts/w2/kernel"][2:3],
         "experts/w3/kernel": w["experts/w3/kernel"][2:3]},
        u, d, R.mm_f32, held_first=2, shared=False))
    assert np.abs(only).max() > 0.01    # the crowded expert's part is there


# ------------------------------- (e) another width, top-k or window is caught
@pytest.mark.parametrize("field,value", [
    ("sliding_window", 8), ("sliding_window", 10), ("index_topk", 7),
    ("index_topk", 9), ("num_experts_per_tok", 1),
    ("num_experts_per_tok", 3), ("held_experts", (0, 3)),
    ("n_routed_experts", 16)])
def test_a_size_other_than_the_configurations_is_caught(tiny, field, value):
    """The reference runs the configuration's sizes; a program built with
    another window, ``index_topk``, experts per token, held range or router
    width either cannot take the seed's weights or gives other logits."""
    d, fam, cfg, params = tiny
    model = fam.model_class()(cfg.replace(**{field: value}))
    tokens = np.random.default_rng(9).integers(3, 512, size=(1, 40)).astype(
        np.int32)
    want = _ref_logits(tiny, tokens[0], np.arange(40))
    try:
        got = np.asarray(model.apply({"params": params},
                                     jnp.asarray(tokens)))[0]
    except Exception as e:      # the leaves no longer fit the modules
        assert field in ("held_experts", "n_routed_experts"), e
        return
    assert field not in ("held_experts", "n_routed_experts")
    assert np.abs(got - want).max() > 100 * TOL
    same = np.asarray(fam.model_class()(cfg).apply(
        {"params": params}, jnp.asarray(tokens)))[0]
    assert np.abs(same - want).max() < TOL


def test_check_dims_and_the_leaves_table_hold_the_family_to_its_sizes(tiny):
    d, fam, cfg, params = tiny
    assert fam.check_dims(d) == []
    assert fam.check_dims({**d, "layer_types": d["layer_types"][:4]})
    assert fam.check_dims({**d, "held": 9})
    model = fam.model_class()(cfg)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    got = {p: (tuple(v.shape), v.dtype) for p, v in weights.flatten(
        tree).items()}
    want = {p: (tuple(s), jnp.dtype(jnp.float32))
            for p, (s, _) in fam.all_leaves(d).items()}
    assert got == want
    assert weights.param_count(d) == sum(
        int(np.prod(s)) for s, _ in got.values())


# ------------------------------------------------------- what is refused
@pytest.mark.parametrize("kwargs,named", [
    (dict(kv_dtype="int8"), "int8"),
    (dict(kv_layout="ring"), "ring"),
    (dict(spec_k=2), "speculative"),
    (dict(spec_tree="2,1"), "speculative"),
    (dict(adapter_rank=4), "adapters"),
    (dict(prefill_batch=2), "prefill_batch"),
    (dict(paged_kernel="pallas"), "pallas")])
def test_engine_refuses_by_name_what_it_cannot_do_for_this_class(
        tiny, kwargs, named):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)

    _, _, cfg, params = tiny
    with pytest.raises(ValueError, match=f"LatentMoEConfig.*{named}"):
        InferenceEngine(cfg, params, slots=2, max_len=32,
                        prefill_buckets=(8,), **kwargs)


def test_block_movers_and_scheduler_tiers_refuse_this_class(tiny, engine,
                                                            tmp_path):
    from fault_tolerant_llm_training_tpu.inference.scheduler import Scheduler

    with pytest.raises(ValueError, match="LatentMoEConfig"):
        engine.export_slot_blocks([1], str(tmp_path), slot=0)
    with pytest.raises(ValueError, match="LatentMoEConfig"):
        engine.import_pool_blocks(str(tmp_path), [1])
    with pytest.raises(ValueError, match="K/V model"):
        engine.decode_logits(np.zeros(3, np.int32), np.ones(3, bool),
                             block_tables=np.zeros((3, 16), np.int32))
    for kw, named in ((dict(enable_spill=True), "spill"),
                      (dict(role="prefill"), "role"),
                      (dict(kv_store=object()), "kv_store"),
                      (dict(decode_burst=4), "decode_burst")):
        with pytest.raises(ValueError, match=f"LatentMoEConfig.*{named}"):
            Scheduler(engine, **kw)


def test_the_llama_class_is_still_found_from_its_configuration():
    sys.path.insert(0, str(REPO / "tests"))
    from _tiny import tiny_cfg

    from fault_tolerant_llm_training_tpu.models import (
        Transformer, build_model, get_config)
    from fault_tolerant_llm_training_tpu.models.latent_moe import (
        LatentMoETransformer)

    assert isinstance(build_model(tiny_cfg()), Transformer)
    assert isinstance(build_model(get_config("tiny-latent-moe")),
                      LatentMoETransformer)


# ------------------------------------------------ the chunk read's two forms
@pytest.mark.parametrize("n_keys", [256, 100, 17])
def test_masked_flash_kernel_matches_the_key_block_loop(n_keys):
    """A chunk's full-layer read has two forms — the Mosaic kernel (a TPU,
    real widths) and the XLA key-block loop (elsewhere) — of one result:
    the kernel in interpret mode against the loop, at head widths that fill
    its tiles, under a random mask, with key blocks past ``n_keys``
    skipped."""
    from fault_tolerant_llm_training_tpu.ops import latent_attention as la

    h, s, t, dn, dr, dv, r, bs = 2, 64, 256, 128, 64, 128, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (s, h, dn + dr), jnp.float32)
    latent = jax.random.normal(ks[1], (t + bs, r), jnp.float32)
    rope_keys = jax.random.normal(ks[2], (t + bs, dr), jnp.float32)
    w = jax.random.normal(ks[3], (r, h, dn + dv), jnp.float32) / np.sqrt(r)
    members = (jax.random.uniform(ks[4], (s, t)) < 0.3).at[:, 0].set(True)
    members = members & (jnp.arange(t)[None] < n_keys)
    table = jnp.arange(1, t // bs + 1, dtype=jnp.int32)
    args = (q, members, latent, rope_keys, table, w, jnp.int32(n_keys), dn,
            0.07, bs)
    loop = la.latent_chunk_attention(*args, kernel=False)
    kern = la.latent_chunk_attention(*args, kernel=True)
    assert float(jnp.abs(loop - kern).max()) < 1e-5
    assert not la.chunk_kernel_fits(64, 128, 64, 128)      # no TPU here
    assert la._block_of(19456, 512) == 512 and la._block_of(96, 512) == 96


def test_packed_rows_round_trip():
    from fault_tolerant_llm_training_tpu.ops import latent_attention as la

    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 24)).astype(
        jnp.bfloat16)
    packed = la.pack_rows(x)
    assert packed.shape == (5, 3, 12) and packed.dtype == jnp.uint32
    assert np.array_equal(
        np.asarray(la.unpack_rows(packed, jnp.bfloat16), np.float32),
        np.asarray(x, np.float32))
    f = x.astype(jnp.float32)
    assert la.pack_rows(f) is f and la.unpack_rows(f, jnp.float32) is f
