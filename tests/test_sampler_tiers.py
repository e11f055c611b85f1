"""The sampling epilogue does what its batch asks for, and no slot can tell.

``inference/sampler.py`` branches once a batch on ``epilogue_tier``: an
all-greedy batch takes the argmax, a batch that samples without a nucleus
skips the sort, and only a nucleus request pays for the whole epilogue.
Three layers of evidence:

(a) token for token against a plain reference kept HERE — the formula the
    sampler ran unconditionally before the tiers, with the one stated
    amendment (``top_p >= 1`` is the identity);
(b) a slot's token is the same alone, beside greedy slots and beside a
    nucleus slot, so the tier is a cost and never an answer;
(c) an engine: a greedy stream is bit-equal whether or not sampled
    requests share its rounds, and ``sample_epilogue_rounds_total`` counts
    each round under the tier the device's branch takes.

Where the branch sits in the compiled program (outside the ``vmap``: a
``conditional``, not a ``select`` that runs the sort anyway) is held by
``tests/test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import tiny_cfg

TIERS = ("greedy", "sampled", "nucleus")


def _sampler():
    """The module under test, imported inside the tests (collecting the
    suite imports no ``inference/`` code)."""
    from fault_tolerant_llm_training_tpu.inference import sampler

    return sampler


# ------------------------------------------------------------ the reference
def _ref_token(logits, key, temperature, top_p, top_k):
    """One row, everything computed whatever the row asks for."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][-1]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    filtered = _parent_top_p_filter(scaled, top_p)
    scaled = jnp.where(top_p >= 1.0, scaled, filtered)  # the amendment
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _parent_top_p_filter(logits, top_p):
    sorted_logits = jnp.sort(logits)[::-1]
    probs = jax.nn.softmax(sorted_logits)
    cum = jnp.cumsum(probs)
    keep = jnp.sum((cum - probs < top_p).astype(jnp.int32))
    cutoff = sorted_logits[jnp.maximum(keep - 1, 0)]
    return jnp.where(logits >= cutoff, logits, -jnp.inf)


def _ref_slot_tokens(logits, seeds, steps, temperature, top_p, top_k):
    keys = jax.vmap(_sampler().slot_key)(seeds, steps)
    return jax.vmap(_ref_token, in_axes=(0, 0, 0, 0, None))(
        logits, keys, temperature, top_p, top_k)


# (temperature, top_p) a slot, and the tier the batch must take
BATCHES = {
    "all_greedy": ([0.0, 0.0, 0.0, 0.0], [1.0, 1.0, 0.5, 1.0], "greedy"),
    "all_temperature": ([0.7, 1.0, 1.3, 0.2], [1.0, 1.0, 1.0, 1.5],
                        "sampled"),
    "all_nucleus": ([0.7, 1.0, 1.3, 0.2], [0.9, 0.5, 0.99, 0.1], "nucleus"),
    "mixed": ([0.0, 0.9, 0.0, 1.1], [1.0, 1.0, 0.3, 0.8], "nucleus"),
    "greedy_and_temperature": ([0.0, 0.9, 0.0, 1.1], [0.4, 1.0, 1.0, 1.0],
                               "sampled"),
}


def _rows(vocab, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, vocab)).astype(np.float32) * 3.0)


@pytest.mark.parametrize("vocab", [256, 259], ids=["v256", "v259"])
@pytest.mark.parametrize("top_k", [0, 5], ids=["k0", "k5"])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_slot_tokens_match_the_unconditional_reference(batch, top_k, vocab):
    temperature, top_p, tier = BATCHES[batch]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_p = jnp.asarray(top_p, jnp.float32)
    assert TIERS[int(_sampler().epilogue_tier(temperature, top_p))] == tier
    got = jax.jit(_sampler().sample_slot_tokens, static_argnums=5)
    ref = jax.jit(_ref_slot_tokens, static_argnums=5)
    seeds = jnp.asarray([3, 1, 4, 1], jnp.int32)
    for step in range(6):
        logits = _rows(vocab, seed=step)
        steps = jnp.full((4,), step, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(got(logits, seeds, steps, temperature, top_p, top_k)),
            np.asarray(ref(logits, seeds, steps, temperature, top_p, top_k)))


@pytest.mark.parametrize("vocab", [256, 259], ids=["v256", "v259"])
@pytest.mark.parametrize("top_k", [0, 5], ids=["k0", "k5"])
@pytest.mark.parametrize("temperature,top_p,tier", [
    (0.0, 1.0, "greedy"), (0.0, 0.5, "greedy"), (0.8, 1.0, "sampled"),
    (0.8, 0.6, "nucleus")], ids=["greedy", "greedy_p", "sampled", "nucleus"])
def test_one_row_token_matches_the_unconditional_reference(
        temperature, top_p, tier, top_k, vocab):
    """``sample_token``: the prefill programs' one-row epilogue takes the
    same rule on its scalars."""
    t, p = jnp.float32(temperature), jnp.float32(top_p)
    assert TIERS[int(_sampler().epilogue_tier(t, p))] == tier
    got = jax.jit(_sampler().sample_token, static_argnums=4)
    ref = jax.jit(_ref_token, static_argnums=4)
    for step in range(6):
        row = _rows(vocab, n=1, seed=10 + step)[0]
        key = _sampler().slot_key(jnp.int32(7), jnp.int32(step))
        assert int(got(row, key, t, p, top_k)) == int(
            ref(row, key, t, p, top_k))


def test_the_rule_reads_the_same_on_the_host_and_on_the_device():
    """NumPy arrays (the engine's counter) and traced arrays (the
    program's branch) through one function, over every kind of batch and
    at the float32 edge of ``top_p``."""
    on_device = jax.jit(_sampler().epilogue_tier)
    cases = [(t, p) for t, p, _ in BATCHES.values()]
    # 1 - 2**-25 rounds to 1.0 in float32: no nucleus on either side
    cases += [([0.5], [1.0 - 2.0 ** -25]), ([0.5], [1.0 - 2.0 ** -24]),
              ([-1.0, 0.0], [0.2, 0.2]), ([1e-9], [1.0])]
    for t, p in cases:
        t, p = np.asarray(t, np.float32), np.asarray(p, np.float32)
        host = _sampler().epilogue_tier(t, p)
        assert isinstance(host, np.integer)
        assert int(on_device(jnp.asarray(t), jnp.asarray(p))) == int(host)
        want = (any(t > 0) + any((t > 0) & (p < 1)))
        assert int(host) == want
    assert _sampler().TIERS == TIERS


# -------------------------------------------- a slot cannot tell its tier
def _peaked_row(vocab):
    """One token holds all but ~1e-8 of the mass: the float32 ``cumsum``
    reaches 1.0 at once, and the unamended filter at ``top_p == 1.0``
    drops the whole tail."""
    row = np.zeros((vocab,), np.float32)
    row[17] = 24.0
    return jnp.asarray(row)


def test_top_p_of_one_is_the_identity_where_the_old_filter_dropped_the_tail():
    new = _sampler()._top_p_filter
    row = _peaked_row(259)
    one = jnp.float32(1.0)
    old = np.asarray(_parent_top_p_filter(row, one))
    assert np.isneginf(old).sum() == 258           # why the amendment
    np.testing.assert_array_equal(np.asarray(new(row, one)), np.asarray(row))
    np.testing.assert_array_equal(np.asarray(new(row, jnp.float32(1.5))),
                                  np.asarray(row))
    # under 1 the filter is the old one, bit for bit
    row = _rows(259)[0]
    for p in (0.999, 0.5, 0.0):
        np.testing.assert_array_equal(
            np.asarray(new(row, jnp.float32(p))),
            np.asarray(_parent_top_p_filter(row, jnp.float32(p))))


@pytest.mark.parametrize("top_k", [0, 5], ids=["k0", "k5"])
@pytest.mark.parametrize("row", ["random", "peaked"])
def test_a_slots_token_is_the_same_in_every_tier(row, top_k):
    """Slot 0 under three batches of one compiled program: beside idle
    greedy slots, beside greedy slots that decode, beside a nucleus slot.
    Its token is the same for a greedy, a temperature-only (``top_p`` 1.0
    and above) and a nucleus slot 0 alike."""
    vocab = 259
    fn = jax.jit(_sampler().sample_slot_tokens, static_argnums=5)
    seeds = jnp.asarray([11, 5, 6, 7], jnp.int32)
    mine = {"greedy": (0.0, 1.0), "temperature": (0.8, 1.0),
            "temperature_p_above_1": (0.8, 1.25), "nucleus": (0.8, 0.7)}
    beside = {"alone": ([0.0, 0.0, 0.0], [1.0, 1.0, 1.0]),
              "temperature": ([0.0, 1.2, 0.6], [1.0, 1.0, 1.0]),
              "nucleus": ([0.0, 1.2, 0.6], [1.0, 0.4, 1.0])}
    for step in range(8):
        logits = _rows(vocab, seed=100 + step)
        if row == "peaked":
            logits = logits.at[0].set(_peaked_row(vocab))
        steps = jnp.full((4,), step, jnp.int32)
        for kind, (t0, p0) in mine.items():
            toks, tiers = {}, set()
            for who, (t, p) in beside.items():
                t = jnp.asarray([t0] + t, jnp.float32)
                p = jnp.asarray([p0] + p, jnp.float32)
                tiers.add(int(_sampler().epilogue_tier(t, p)))
                toks[who] = int(fn(logits, seeds, steps, t, p, top_k)[0])
            assert len(set(toks.values())) == 1, (kind, step, toks)
            # the batches did take different branches
            assert len(tiers) >= (2 if kind != "nucleus" else 1)


# ------------------------------------------------------------------ engine
@pytest.fixture(scope="module")
def engine():
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine)
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg()
    params = Transformer(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.seq_len), jnp.int32))["params"]
    return InferenceEngine(cfg, params, slots=3, max_len=32,
                           prefill_buckets=(8, 16), kv_block_size=8)


def _tier_counts():
    from fault_tolerant_llm_training_tpu.obs.registry import default_registry

    family = default_registry().snapshot().get(
        "sample_epilogue_rounds_total", {"series": {}})
    return dict(family["series"])


def _moved(before):
    return {k: v - before.get(k, 0.0) for k, v in _tier_counts().items()
            if v != before.get(k, 0.0)}


def _serve(engine, requests, monkeypatch):
    """Run ``requests`` to completion; returns (streams by id, the tier of
    every decode round as plain Python reads its arrays)."""
    from fault_tolerant_llm_training_tpu.inference.scheduler import (
        Request, Scheduler)

    rounds = []
    real = engine.decode_step

    def spy(tokens, active, temperature, top_p, *a, **kw):
        t = np.asarray(temperature, np.float32)
        p = np.asarray(top_p, np.float32)
        sampling = [float(x) > 0.0 for x in t]
        nucleus = [s and float(y) < 1.0 for s, y in zip(sampling, p)]
        rounds.append(TIERS[int(any(sampling)) + int(any(nucleus))])
        # the rule, evaluated on the device arrays the program is handed
        assert TIERS[int(jax.jit(_sampler().epilogue_tier)(
            jnp.asarray(t), jnp.asarray(p)))] == rounds[-1]
        return real(tokens, active, temperature, top_p, *a, **kw)

    monkeypatch.setattr(engine, "decode_step", spy)
    engine.reset()
    sched = Scheduler(engine, eos_token_id=None)
    for r in requests:
        sched.submit(Request(**r))
    done = {c.request_id: list(c.tokens) for c in sched.run()}
    monkeypatch.setattr(engine, "decode_step", real)
    return done, rounds


def test_engine_counts_each_round_under_the_tier_its_batch_takes(
        engine, monkeypatch):
    """A greedy request, a temperature-only one and a nucleus one of
    different lengths: the rounds go nucleus -> sampled -> greedy as the
    requests leave. The greedy stream does not move, the temperature-only
    stream is the one it draws alone, and the counter's series move by the
    rounds plain Python assigns to each tier."""
    greedy = dict(id="g", prompt=[5, 17, 9, 33], max_new_tokens=12)
    sampled = dict(id="s", prompt=[7, 2, 40], max_new_tokens=8,
                   temperature=0.8, seed=3)
    nucleus = dict(id="n", prompt=[9, 9, 21, 4, 8], max_new_tokens=4,
                   temperature=0.9, top_p=0.6, seed=5)

    before = _tier_counts()
    alone, rounds = _serve(engine, [greedy], monkeypatch)
    assert set(rounds) == {"greedy"}
    assert _moved(before) == {"tier=greedy": len(rounds)}

    before = _tier_counts()
    s_alone, rounds = _serve(engine, [sampled], monkeypatch)
    assert set(rounds) == {"sampled"}
    assert _moved(before) == {"tier=sampled": len(rounds)}

    before = _tier_counts()
    mixed, rounds = _serve(engine, [greedy, sampled, nucleus], monkeypatch)
    tally = {t: rounds.count(t) for t in TIERS}
    assert all(tally.values()), tally          # every tier engaged
    # in this order: the costliest request leaves first
    assert rounds == sorted(rounds, key=TIERS.index, reverse=True)
    assert _moved(before) == {f"tier={t}": n for t, n in tally.items()}
    assert mixed["g"] == alone["g"]
    assert mixed["s"] == s_alone["s"]
    assert len(mixed["n"]) == 4


def test_engine_burst_counts_one_round_a_dispatch(engine):
    """``decode_burst``: the branch runs inside the loop's body (a
    ``cond`` in a ``fori_loop``), the count is one a dispatched round."""
    row = np.arange(1, 5, dtype=np.int32)
    tables = np.stack([row, np.zeros_like(row), np.zeros_like(row)])
    active = np.array([True, False, False])
    seeds, steps = np.zeros(3, np.int32), np.ones(3, np.int32)

    def run(temperature, top_p, n):
        engine.reset()
        tok = engine.prefill(0, [5, 17, 9, 33], block_row=row,
                             temperature=temperature, top_p=top_p)
        return engine.decode_burst(
            np.array([tok, 0, 0], np.int32), active,
            np.array([temperature, 0, 0], np.float32),
            np.array([top_p, 1, 1], np.float32), seeds, steps, n,
            block_tables=tables)[0]

    for temperature, top_p, tier in ((0.0, 1.0, "greedy"),
                                     (0.8, 1.0, "sampled"),
                                     (0.8, 0.5, "nucleus")):
        before = _tier_counts()
        burst = run(temperature, top_p, 2)
        assert _moved(before) == {f"tier={tier}": 1}
        # two single rounds draw the burst's tokens
        first = run(temperature, top_p, 1)
        assert first[0] == burst[0]
