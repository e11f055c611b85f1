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

from perfbench.lib import program_records  # noqa: E402
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
    # the cache holds what the finished request's slot WROTE (50 prompt +
    # 11 fed tokens = 61 positions, 7 whole blocks): a turn resumes from
    # the last whole block of its history, not from its predecessor's
    # prompt
    assert sched.prefix_cache.hit_tokens - hits0 == 56
    hits1 = sched.prefix_cache.hit_tokens
    serve("restart", np.concatenate(
        [base, rng.integers(3, 512, size=5).astype(np.int32)]), 8)
    assert sched.prefix_cache.hit_tokens - hits1 == 48


# ------------------------------- a session's next turn over the slot's rings
def _counts():
    return program_records.counters()


def _moved(before):
    """The program's counters that moved since ``before``, by the names
    the benchmark's readers see them under."""
    return {k: v for k, v in program_records.change(
        before, program_records.counters()).items() if v}


def _session(rng, turns=3, new=6):
    """A 50-token context and the new tokens of ``turns`` turns after it."""
    return (rng.integers(3, 512, size=50).astype(np.int32),
            [rng.integers(3, 512, size=new).astype(np.int32)
             for _ in range(turns)])


def _serve_session(tiny, engine, how, base, news, gen=12):
    """The context and its turns through a fresh ``Scheduler`` over
    ``engine``, each turn = history + the last answer + its new tokens:
    ``held`` as the scheduler serves them, ``rebuilt`` with the slots'
    records cleared before every turn, ``uncached`` with no prefix cache.
    Returns [(slot, start_pos, rings_held, tokens, the logits of the round
    after the prefill)] a request."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    engine.reset()
    sched = Scheduler(engine, eos_token_id=None)
    if how == "uncached":
        sched.prefix_cache = None
    seen, inner = [], engine.prefill

    def prefill(slot, ids, **kw):
        first = inner(slot, ids, **kw)
        toks = np.zeros(engine.slots, np.int32)
        toks[slot] = first
        seen.append((slot, kw.get("start_pos", 0), kw.get("rings_held"),
                     _peek_logits(engine, sched.block_tables, toks)[slot]))
        return first

    engine.prefill = prefill
    try:
        out, prompt = [], base
        for i, extra in enumerate([None, *news]):
            if extra is not None:
                prompt = np.concatenate([prompt, extra])
            if how == "rebuilt":
                sched.held_rings.clear()
            sched.submit(Request(id=f"t{i}", prompt=prompt,
                                 max_new_tokens=gen))
            done = []
            while sched.pending():
                done += sched.step()
            (c,) = done
            out.append(seen[-1][:3] + (list(c.tokens), seen[-1][3]))
            prompt = np.concatenate([prompt, np.asarray(c.tokens, np.int32)])
    finally:
        del engine.prefill
    assert sched.audit_block_leaks(strict=True) == []
    return out


def test_a_sessions_turns_over_held_rings_are_the_rebuilt_and_uncached_turns(
        tiny, engine):
    """Three turns after a context: served over the slot's held rings, with
    the windows rebuilt, and with no cache at all, every turn gives the
    reference's tokens and, after its prefill, the same next-round logits
    to float32 noise. The held turns go back into their stream's slot,
    resume at the last whole block the slot wrote with that request's
    ``win_from``, and recompute no row; the rebuilt ones recompute 24."""
    base, news = _session(np.random.default_rng(17))
    before = _counts()
    held = _serve_session(tiny, engine, "held", base, news)
    moved_held = _moved(before)
    before = _counts()
    rebuilt = _serve_session(tiny, engine, "rebuilt", base, news)
    moved_rebuilt = _moved(before)
    plain = _serve_session(tiny, engine, "uncached", base, news)

    prompt = base
    for i, (h, r, u) in enumerate(zip(held, rebuilt, plain)):
        if i:
            prompt = np.concatenate([prompt, news[i - 1]])
        seq = np.concatenate([prompt, np.asarray(h[3][:-1], np.int32)])
        ref = _ref_logits(tiny, seq, np.arange(len(prompt) - 1, len(seq)))
        assert h[3] == r[3] == u[3] == [int(t) for t in ref.argmax(-1)], i
        # the round after the prefill: position len(prompt), fed the first
        want = _ref_logits(tiny, seq, [len(prompt)])[0]
        for got in (h[4], r[4], u[4]):
            assert np.abs(got - want).max() < TOL, i
        prompt = np.concatenate([prompt, np.asarray(h[3], np.int32)])
    # the context wrote 50 + 11 = 61 positions: turn 1 resumes at 56; each
    # turn adds 6 + 12: 79 -> 72, 97 -> 96
    assert [(t[1], t[2]) for t in held] == [
        (0, None), (56, (61, 0)), (72, (79, 0)), (96, (97, 0))]
    assert len({t[0] for t in held}) == 1, "a turn left its stream's slot"
    assert [(t[1], t[2]) for t in rebuilt] == [
        (0, None), (56, None), (72, None), (96, None)]
    assert all(t[1] == 0 and t[2] is None for t in plain)
    assert moved_held["ftl_serve_window_resumes_total{how=held}"] == 3
    assert "ftl_serve_window_resumes_total{how=rebuilt}" not in moved_held
    assert "ftl_serve_prefill_rows_total{kind=recomputed}" not in moved_held
    # 50 context rows, then what each turn's prompt (the last + 12 + 6
    # tokens) holds past its hit
    assert moved_held["ftl_serve_prefill_rows_total{kind=new}"] == (
        50 + (68 - 56) + (86 - 72) + (104 - 96))
    assert moved_rebuilt["ftl_serve_window_resumes_total{how=rebuilt}"] == 3
    assert moved_rebuilt["ftl_serve_prefill_rows_total{kind=recomputed}"] == (
        3 * 24)
    assert (moved_rebuilt["ftl_serve_prefill_rows_total{kind=new}"]
            == moved_held["ftl_serve_prefill_rows_total{kind=new}"])


@pytest.mark.parametrize("what", ["another_request", "drain_roll_back",
                                  "shorter_hit"])
def test_a_slots_record_is_dropped_and_the_turn_rebuilds(tiny, engine, what):
    """The record of what a free slot's rings hold goes when another
    request is prefilled into the slot, when a drain rolls the turn's own
    prefill back, and does not apply to a hit that ends before the last
    whole block the slot wrote: the turn is then served with the windows
    rebuilt (in a slot that holds no stream's rings, where there is one),
    and its tokens are the reference's."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    rng = np.random.default_rng(23)
    base, (extra,) = _session(rng, turns=1, new=40)
    engine.reset()
    stop = {"on": False}
    sched = Scheduler(engine, eos_token_id=None,
                      stop_check=lambda: stop["on"])
    starts, inner = [], engine.prefill

    def prefill(slot, ids, **kw):
        starts.append((slot, kw.get("start_pos", 0), kw.get("rings_held")))
        if what == "drain_roll_back" and kw.get("rings_held"):
            stop["on"] = True       # the signal lands inside this prefill
        return inner(slot, ids, **kw)

    engine.prefill = prefill

    def serve(rid, prompt, n=12, also=()):
        for i, other in enumerate([prompt, *also]):
            sched.submit(Request(id=f"{rid}{i or ''}", prompt=other,
                                 max_new_tokens=n))
        done = []
        while sched.pending():
            done += sched.step()
        return done

    try:
        (c,) = serve("ctx", base)
        hist = np.concatenate([base, np.asarray(c.tokens, np.int32)])
        (slot,) = sched.held_rings
        assert sched.held_rings[slot].length == 61
        turn = np.concatenate([hist, extra])
        if what == "another_request":
            # three at once: the first two go where no stream's rings are
            # held, the third has only the stream's slot left and leaves a
            # record of its own there
            others = rng.integers(3, 512, size=(3, 20)).astype(np.int32)
            assert len(serve("other", others[0], also=others[1:])) == 3
            assert [s for s, _, _ in starts[-3:]] == [
                s for s in range(engine.slots) if s != slot] + [slot]
            assert sched.held_rings[slot].length == 20 + 11
        elif what == "shorter_hit":
            assert sched.prefix_cache.evict(1) == 1     # the deepest block
        before = _counts()
        done = serve("turn", turn)
        if what == "drain_roll_back":
            # the held attempt stopped between its chunks and was rolled
            # back: the request is unserved, the slot's record gone
            assert done == [] and starts[-1] == (slot, 56, (61, 0))
            assert [r.id for r in sched.unserved()] == ["turn"]
            assert sched.held_rings == {}
            stop["on"] = False
            sched.resume_admission()
            while sched.pending():
                done += sched.step()
        (c,) = done
    finally:
        del engine.prefill
    seq = np.concatenate([turn, np.asarray(c.tokens[:-1], np.int32)])
    ref = _ref_logits(tiny, seq, np.arange(len(turn) - 1, len(seq)))
    assert list(c.tokens) == [int(t) for t in ref.argmax(-1)]
    if what == "shorter_hit":
        # rebuilt where no stream's rings were held: the record stays
        assert starts[-1] == (slot + 1, 48, None)
        assert sched.held_rings[slot].length == 61
    else:
        assert starts[-1] == (slot, 56, None)
    assert _moved(before)["ftl_serve_window_resumes_total{how=rebuilt}"] == 1
    assert sched.audit_block_leaks(strict=True) == []


def test_engine_refuses_rings_that_do_not_cover_the_resume(tiny, engine):
    """``rings_held`` is checked, not trusted: rings written 8 positions
    past the resume point have lost the first row the resumed call reads
    (window 9, ring 16: 7 is the most), a record that ends before it holds
    nothing of the rows in between, and a ``win_from`` past it is no
    request's."""
    engine.reset()
    per = engine.max_blocks_per_slot
    row = 1 + np.arange(per, dtype=np.int32)
    ids = np.random.default_rng(29).integers(3, 512, size=70).astype(np.int32)
    assert engine.rings_cover(63, 56) and not engine.rings_cover(64, 56)
    assert not engine.rings_cover(55, 56)
    for held in [(64, 0), (55, 0), (60, 57)]:
        with pytest.raises(ValueError, match="rings_held"):
            engine.prefill(0, ids, block_row=row, start_pos=56,
                           rings_held=held)


# ------------------------------------------------ the chunk loop's cover rule
def _stand_in_cost(bucket):
    """(flops, bytes) of a chunk program that reads its weights whatever
    its rows: a fixed part and a part a row, the fixed part large in bytes
    (12 GB of weights beside 24 MB a row) and small in flops."""
    return 0.09 + 0.0033 * bucket, 11.7 + 0.0243 * bucket


_LADDERS = {"cell": [64, 2048], "longdecode": [32, 256, 2048],
            "chat": [64, 128, 256, 512, 1024, 2048]}
# rows -> the calls, by ladder: a remainder just over the smallest bucket
# of a ladder WITHOUT the next rung runs as small calls (2 x 13.3 GB under
# the 61.5 GB of a 2,048-row call), a large one as one large call (6 x 13.3
# GB for 336 rows would read more; 25-30 small calls more by both counts);
# a ladder WITH the rung is covered as it always was
_COVERS = {
    "cell": {1: [64], 64: [64], 65: [64, 64], 80: [64, 64], 336: [2048],
             1600: [2048], 1872: [2048], 2048: [2048], 2049: [2048, 64]},
    "longdecode": {1: [32], 64: [256], 65: [256], 80: [256],
                   336: [256, 256], 1600: [2048], 1872: [2048],
                   2048: [2048], 2049: [2048, 32]},
    "chat": {1: [64], 64: [64], 65: [128], 80: [128], 336: [512],
             1600: [2048], 1872: [2048], 2048: [2048], 2049: [2048, 64]},
}


@pytest.mark.parametrize("rows", [1, 64, 65, 80, 336, 1600, 1872, 2048, 2049])
@pytest.mark.parametrize("ladder", list(_LADDERS))
def test_the_chunk_loop_covers_a_remainder_by_its_cheapest_calls(ladder,
                                                                 rows):
    from fault_tolerant_llm_training_tpu.inference.engine import cover_plan

    buckets = _LADDERS[ladder]
    cost = {b: _stand_in_cost(b) for b in buckets}
    plan = cover_plan(rows, buckets, cost)
    assert plan == _COVERS[ladder][rows]
    # it covers the rows and no call of it is idle
    assert sum(plan) >= rows > sum(plan) - plan[-1]
    # never dearer than the plain cover, by either count; with no costs it
    # IS the plain cover: whole largest chunks, then the first bucket that
    # holds the rest
    plain = cover_plan(rows, buckets)
    whole, rest = divmod(rows, buckets[-1])
    assert plain == [buckets[-1]] * whole + (
        [next(b for b in buckets if b >= rest)] if rest else [])
    for i in (0, 1):
        assert (sum(cost[b][i] for b in plan)
                <= sum(cost[b][i] for b in plain))
    # cheaper by ONE count only is no reason: with flops alone lower (a
    # program that reads 60 GB whatever its rows) the large call stays
    lopsided = {b: (cost[b][0], 60.0) for b in buckets}
    assert cover_plan(rows, buckets, lopsided) == plain


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
