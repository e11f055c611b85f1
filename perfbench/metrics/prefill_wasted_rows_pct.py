"""Share of the rows the prefill chunk programs ran in the window that a
request did not need: (recomputed + padding) / all rows of the program's
``ftl_serve_prefill_rows_total{kind}`` — ``new`` = real rows at or past the
position a call resumed at, ``recomputed`` = real rows before it (a window
rebuild), ``padding`` = a call's bucket less its real rows. A program
without the counter, or a window without a prefill call, has no reading."""

NAME = "ftl_serve_prefill_rows_total"


def read(ctx):
    counters = (ctx.get("serve") or {}).get("program_counters") or {}
    rows = {kind: counters.get(f"{NAME}{{kind={kind}}}", 0.0)
            for kind in ("new", "recomputed", "padding")}
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * (rows["recomputed"] + rows["padding"]) / total
