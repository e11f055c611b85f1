"""The one general traffic generator. A traffic mix is a data file of
parameters (``perfbench/traffic/<name>.json``); this file turns it and a
seed into requests. A new mix is a new data file, never new code.

Every seed gets the same sizes at the same arrival times: both are drawn
from the mix's own fixed stream (``shape_seed`` in the file) and the run's
seed draws only the token ids. (Shuffling the order by seed was tried first:
in a 45 s window of ~54 requests the order alone moved ``serve_tok_s`` by
10 % between seeds — the seed was changing the work.)
"""

import math

import numpy as np


def draw_lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Poisson arrivals at ``rate_rps`` on the wall clock for ``seconds``:
    [{id, t_due, prompt, max_new_tokens}], sorted by due time."""
    base = int(mix.get("shape_seed", 0))
    rate = float(mix["rate_rps"])
    # fixed streams, one each, so that a longer horizon extends the same
    # schedule; the horizon fixes how many arrivals are used
    cap = int(rate * seconds * 2) + 16
    gaps = np.random.default_rng([base, 1]).exponential(1.0 / rate, cap)
    n = int(np.searchsorted(np.cumsum(gaps), seconds))
    p_len = draw_lengths(mix["prompt_len"], n,
                         np.random.default_rng([base, 2]))
    o_len = draw_lengths(mix["output_len"], n,
                         np.random.default_rng([base, 3]))
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    due = np.cumsum(gaps[:n])
    out = []
    for j in range(n):
        prompt = rng.integers(3, vocab, size=int(p_len[j])).astype(np.int32)
        out.append({"id": f"r{j}", "t_due": float(due[j]),
                    "prompt": prompt, "max_new_tokens": int(o_len[j])})
    return out


def closed_loop(mix: dict, seed: int, vocab: int) -> list:
    """Sessions of a closed loop: each has a base context (built in
    set-up) and a stream of turns; a turn's prompt is the session's
    history plus ``turn_prompt`` new tokens. After ``turns_per_session``
    turns the session starts over from its base context."""
    rng = np.random.default_rng([int(seed), 0x5E5510])
    shape = np.random.default_rng(mix.get("shape_seed", 0))
    n = int(mix["sessions"])
    turns = int(mix["turns_per_session"])
    out = []
    for s in range(n):
        base = rng.integers(3, vocab, size=int(mix["context_len"])).astype(
            np.int32)
        p = draw_lengths(mix["turn_prompt"], turns, shape)
        o = draw_lengths(mix["turn_output"], turns, shape)
        new = [rng.integers(3, vocab, size=int(k)).astype(np.int32)
               for k in p]
        out.append({"id": f"s{s}", "base": base, "turn_new": new,
                    "turn_out": [int(k) for k in o]})
    return out


def lengths_needed(mix: dict) -> dict:
    """The longest prompt + output the mix can make (server capacity and
    the bucket ladder have to cover it)."""
    if mix["loop"] == "open":
        return {"prompt": mix["prompt_len"].get("max",
                                               mix["prompt_len"].get("value")),
                "total": mix["prompt_len"].get("max", 0)
                + mix["output_len"].get("max", 0)}
    p = mix["turn_prompt"].get("max", mix["turn_prompt"].get("value"))
    o = mix["turn_output"].get("max", mix["turn_output"].get("value"))
    return {"prompt": mix["context_len"] + mix["turns_per_session"] * (p + o),
            "total": mix["context_len"] + mix["turns_per_session"] * (p + o)}
