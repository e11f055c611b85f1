"""The run's last words: each compared number beside its limit on stderr,
then the one JSON line on stdout."""

import json
import sys


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device, as the backend
    reports it (0 where it reports none)."""
    import jax

    return max((int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for dev in jax.local_devices()), default=0)


def compared_entry(value, limit, ok=None, exact=False) -> dict:
    if ok is None:
        ok = (value is not None and limit is not None
              and (value == limit if exact else value <= limit))
    return {"value": value, "limit": limit, "ok": bool(ok)}


def emit(correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, compared: dict, breakdown=None, notes=None) -> None:
    sys.stdout.flush()
    for k, v in (notes or {}).items():
        print(f"perfbench note | {k}: {v}", file=sys.stderr)
    print(f"perfbench compared | correct={bool(correct)}", file=sys.stderr)
    for name, c in compared.items():
        mark = "ok" if c["ok"] else "FAIL"
        print(f"perfbench compared | {name} = {c['value']} | limit "
              f"{c['limit']} | {mark}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v[0], "unit": v[1]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()


def read_per_layer(cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read: {name: (value, unit)}. A reader that returns None is left out."""
    from . import manifest

    out = {}
    for m in cell.per_layer():
        value = manifest.load_reader(m["name"], cell.bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out
