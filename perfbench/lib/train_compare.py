"""What decides ``correct`` for a training cell's arithmetic: the readings
the program's own step gave in its first steps (``train_child`` took them
from the compiled step the window then ran) against the plain float32
reference following the same steps on the same rows.

Numbers, each with a limit of its own (``limits/<workload>.json``):

- ``loss_gap``: worst over the followed steps of |loss - ref| / |ref|;
- ``grad_norm_gap``: worst leaf of |norm - ref norm| of the first gradient
  as the optimizer gets it (from Adam's first moment after one step),
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- ``change_norm_gap``: the same measure on the norm of each leaf's change
  after the followed steps. Left out, each by a rule on the reference and
  none by name: leaves whose reference gradient is under a thousandth of
  the median leaf's (they move by round-off alone under Adam), and leaves
  *under bfloat16 resolution* — more than half of the reference's change
  sits on elements whose step is under half a bfloat16 ulp of their value
  (``reference.TrainReference.change_norms``), so that the bfloat16
  parameters the configuration states cannot move them;
- ``frozen_unexpected``: how many of the leaves that ``change_norm_gap``
  keeps the program left unmoved (under a hundredth of the reference's
  change). Exact: the limit is 0, whatever the leaf's size.
"""

import statistics


def gaps(program: dict, ref: dict) -> dict:
    steps = min(len(program["loss"]), len(ref["loss"]))
    loss_gap = max(abs(program["loss"][i] - ref["loss"][i])
                   / abs(ref["loss"][i]) for i in range(steps))

    def by_leaf(prog_norms, ref_norms, keep):
        med = statistics.median(ref_norms.values())
        return {leaf: abs(prog_norms[leaf] - ref_norms[leaf]) / max(
            ref_norms[leaf], med) for leaf in keep}

    def worst(prog_norms, ref_norms, keep):
        worst_gap, worst_leaf = 0.0, None
        for leaf, gap in by_leaf(prog_norms, ref_norms, keep).items():
            if gap >= worst_gap:
                worst_gap, worst_leaf = gap, leaf
        return worst_gap, worst_leaf

    leaves = sorted(ref["grad_norms"])
    g_gap, g_leaf = worst(program["grad_norms"], ref["grad_norms"], leaves)
    g_med = statistics.median(ref["grad_norms"].values())
    under = [p for p in leaves if ref.get("stuck_share", {}).get(p, 0) > 0.5]
    moved = [p for p in leaves if ref["grad_norms"][p] >= 1e-3 * g_med
             and p not in under]
    c_gap, c_leaf = worst(program["change_norms"], ref["change_norms"],
                          moved)
    frozen = [p for p in leaves if ref["change_norms"][p] > 0
              and program["change_norms"][p]
              <= 0.01 * ref["change_norms"][p]]
    return {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
            "change_norm_gap": c_gap,
            "frozen_unexpected": len(set(frozen) & set(moved)),
            "worst_grad_leaf": g_leaf, "worst_change_leaf": c_leaf,
            "left_out": sorted(set(leaves) - set(moved) - set(under)),
            "under_bf16_resolution": under, "frozen": frozen,
            "top_change_gaps": sorted(
                by_leaf(program["change_norms"], ref["change_norms"],
                        moved).items(), key=lambda kv: -kv[1])[:3]}


def judge(g: dict, limits: dict) -> dict:
    from .result import compared_entry

    out = {k: compared_entry(g[k], limits.get(k))
           for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")
           if limits.get(k) is not None}
    if limits.get("frozen_unexpected") is not None:
        out["frozen_unexpected"] = compared_entry(
            g["frozen_unexpected"], limits["frozen_unexpected"], exact=True)
    return out


def batches_of(spec: dict, steps: int):
    """The first ``steps`` batches, made again from the seed (nothing is
    read from what the program loaded)."""
    from . import train_parent as tp

    tr = spec["traffic"]
    seq, batch = tr["sequence_length"], spec["batch_size"]
    rows = tp.corpus_rows(spec["seed"], tr["docs"], seq)
    toks = [tp.expected_tokens(rows, s, batch, seq) for s in range(steps)]
    return [(t[:, :-1], t[:, 1:]) for t in toks]


def run(spec: dict, d: dict) -> dict:
    """In the last child, after the program's state is freed."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from . import reference

    with open(os.path.join(spec["work_dir"], "window.json")) as fh:
        program = json.load(fh)["readings"]
    tr = spec["traffic"]
    key = jax.random.PRNGKey(spec["seed"])
    dtype = jnp.bfloat16 if tr.get("model_dtype", "bf16") == "bf16" \
        else jnp.float32
    batches = batches_of(spec, tr["warm_steps"])
    out = {}
    ref = reference.run_train_reference(
        key, d, tr["learning_rate"], tr["lr_warmup_steps"], batches,
        dtype=dtype)
    g = gaps(program, ref)
    out["gaps"] = g
    out["reference"] = {"loss": ref["loss"],
                        "grad_norm_raw": ref["grad_norm_raw"]}
    out["program"] = {"loss": program["loss"],
                      "grad_norm_raw": program["grad_norm_raw"]}
    if spec.get("control"):
        ctl = reference.run_train_reference(
            key, d, tr["learning_rate"], tr["lr_warmup_steps"], batches,
            mm=reference.MATMULS[spec["control"]], dtype=dtype)
        out["control_gaps"] = gaps(ctl, ref)
        out["control"] = {"loss": ctl["loss"]}
    # a control run judges the control in the program's place, through the
    # same comparison: ``correct`` has to come out false
    out["compared"] = judge(out.get("control_gaps", g),
                            spec.get("limits") or {})
    return out
