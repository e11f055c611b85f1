"""What decides ``correct`` for a serving cell: a sample, drawn from the
seed, of the requests the window finished — the longest always in it —
is run once through the plain float32 reference (prompt with its served
tokens), and the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at that position.
Greedy decoding only (the mixes are greedy). 0 means the served token is
the reference's own choice; bfloat16 serving flips near-ties, so the limit
sits above what sound runs read and below what the int8 control reads
(``limits/<workload>.json``).
"""

import numpy as np

PAD_TO = 1024


def pick_sample(finished: list, seed: int, want_tokens: int,
                max_requests: int) -> list:
    """The longest request, then others in an order drawn from the seed,
    until the sample holds ``want_tokens`` served tokens."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"])
                                               + len(r["tokens"])))
    sample = [by_len[0]]
    rest = [r for r in finished if r is not by_len[0]]
    for j in rng.permutation(len(rest)):
        if (sum(len(r["tokens"]) for r in sample) >= want_tokens
                or len(sample) >= max_requests):
            break
        sample.append(rest[j])
    return sample


def gaps_of(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """Per position: reference's best logit minus its logit of ``tokens``."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), np.asarray(tokens)]


def reference_logits(key, d, sample, mm, dtype):
    """Logits at every served position of every sampled request, by the
    family's forward pass over the padded sequences."""
    from . import weights

    seqs, wanted = [], []
    for r in sample:
        seq = np.concatenate([np.asarray(r["prompt"], np.int32),
                              np.asarray(r["tokens"][:-1], np.int32)])
        first = len(r["prompt"]) - 1
        wanted.append(np.arange(first, first + len(r["tokens"])))
        pad = (-len(seq)) % PAD_TO
        seqs.append(np.concatenate([seq, np.zeros((pad,), np.int32)]))
    return weights.family_of(d).batch_logits(key, d, seqs, wanted, mm, dtype)


def run(cell, seed: int, d: dict, finished: list, control: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from . import reference as R
    from .result import compared_entry

    limits = cell.limits
    dtype = (jnp.float32 if cell.traffic["server"].get("dtype") == "fp32"
             else jnp.bfloat16)
    sample = pick_sample(finished, seed, limits.get("sample_tokens", 400),
                         limits.get("max_requests", 6))
    key = jax.random.PRNGKey(seed)
    notes = {"sample_requests": len(sample),
             "sample_tokens": sum(len(r["tokens"]) for r in sample),
             "sample_longest": max((len(r["prompt"]) + len(r["tokens"])
                                    for r in sample), default=0)}
    if not sample:
        return {"compared": {"logit_gap_max": compared_entry(None, limits.get(
            "logit_gap_max"), ok=False)}, "notes": notes}
    ref = reference_logits(key, d, sample, R.mm_f32, dtype)
    gaps = np.concatenate([gaps_of(l, r["tokens"])
                           for l, r in zip(ref, sample)])
    notes["logit_gap_mean"] = float(gaps.mean())
    notes["tokens_off_reference_best"] = int((gaps > 0).sum())
    value = float(gaps.max())
    if control:
        # A control run: the reference computed in the lower precision
        # stands in the program's place — at each position of the same
        # prompts and tokens, the gap of the token it puts first — and goes
        # through the same comparison; ``correct`` has to come out false.
        ctl = reference_logits(key, d, sample, R.MATMULS[control], dtype)
        cgaps = np.concatenate([gaps_of(l, c.argmax(axis=-1))
                                for l, c in zip(ref, ctl)])
        notes["control"] = control
        notes["program_logit_gap_max"] = value
        notes["control_logit_gap_mean"] = float(cgaps.mean())
        notes["control_tokens_off"] = int((cgaps > 0).sum())
        value = float(cgaps.max())
    limit = limits.get("logit_gap_max")
    # a cell whose limit is not set yet (a study run) is not judged by it
    compared = {"logit_gap_max": compared_entry(
        value, limit, ok=True if limit is None else None)}
    return {"compared": compared, "notes": notes}
