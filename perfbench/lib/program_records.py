"""Two tables of the program's own, taken where the program is imported
anyway (the training child, the serving cell's one process) and handed on
as plain data, so that the benchmark keeps no copy of either and the
training cells' parent and the readers stay off JAX:

- the device scopes: ``obs/trace.py`` ``SCOPES`` in its order (the order a
  reader gives an op to a bucket) and the subset the program opens itself,
  written beside a traced run's trace as ``program_scopes.json`` in the
  cell's work directory, where ``metrics/_program_trace.py`` finds its
  bucket list. A scope a program PR adds to its table is a bucket by that
  alone;
- the counters: every series of kind ``counter`` in ``obs/registry.py``'s
  registry, as ``{"name" | "name{label=value,...}": value}``. The change of
  each over a window reaches the readers as
  ``ctx["train"]["window"]["counters"]`` and
  ``ctx["serve"]["program_counters"]``.
"""

import json
import os

SCOPES_NAME = "program_scopes.json"


def scopes() -> dict:
    """``{"scopes": [...], "opened": [...]}`` as the imported program has
    them. A program that renames either table fails the traced run here,
    loudly, and not every device share silently."""
    from fault_tolerant_llm_training_tpu.obs.trace import (
        _OPENED_HERE,
        SCOPES,
    )

    return {"scopes": list(SCOPES), "opened": list(_OPENED_HERE)}


def write_scopes(work_dir: str) -> None:
    os.makedirs(work_dir, exist_ok=True)
    with open(os.path.join(work_dir, SCOPES_NAME), "w") as fh:
        json.dump(scopes(), fh)


def read_scopes(work_dir: str):
    """What :func:`write_scopes` left there, or None. Imports nothing of
    the program."""
    try:
        with open(os.path.join(work_dir, SCOPES_NAME)) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def counters() -> dict:
    """Every counter series of the program's registry, now."""
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    out = {}
    for name, family in REGISTRY.snapshot().items():
        if family["kind"] != "counter":
            continue
        for labels, value in family["series"].items():
            out[f"{name}{{{labels}}}" if labels else name] = value
    return out


def change(before: dict, after: dict) -> dict:
    """Each counter's change between two :func:`counters`; a series born
    in between started at 0."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}
