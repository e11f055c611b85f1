"""``prefill_wasted_rows_pct`` (PR 34) over the door to the program's
counters: the share of the chunk programs' rows that were recomputed or
padding; a program without the counter (the parent of PR 34 under this
benchmark), or a window without a prefill call, gives no reading and does
not raise; and the real registry's series come through the door under the
names the reader looks for."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import manifest, program_records  # noqa: E402

NAME = "ftl_serve_prefill_rows_total"


def _read(counters):
    reader = manifest.load_reader("prefill_wasted_rows_pct")
    return reader({"serve": {"program_counters": counters}})


@pytest.mark.parametrize("rows,want", [
    # the dots3 cell before PR 34: a turn's one 2,048-row call
    ({"new": 336, "recomputed": 1536, "padding": 176}, 100 * 1712 / 2048),
    # after it: turn 0 (one 64-row call), a later turn (two)
    ({"new": 64, "recomputed": 0, "padding": 0}, 0.0),
    ({"new": 80, "recomputed": 0, "padding": 48}, 37.5),
    # a Llama cell: new and padding only, other counters beside them
    ({"new": 48, "padding": 208, "other_total": 9}, 100 * 208 / 256),
])
def test_the_share_is_recomputed_plus_padding_over_all_rows(rows, want):
    counters = {(f"{NAME}{{kind={k}}}" if k != "other_total" else k): v
                for k, v in rows.items()}
    assert _read(counters) == pytest.approx(want)


@pytest.mark.parametrize("ctx", [
    {}, {"serve": None}, {"serve": {}}, {"serve": {"program_counters": {}}},
    {"serve": {"program_counters": {"decode_dispatches_total": 7.0}}},
    {"serve": {"program_counters": {f"{NAME}{{kind=new}}": 0.0}}},
    {"train": {"window": {"counters": {}}}},
])
def test_no_counter_or_no_prefill_call_is_no_reading(ctx):
    assert manifest.load_reader("prefill_wasted_rows_pct")(ctx) is None


def test_the_programs_series_come_through_the_door_under_these_names():
    from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY

    before = program_records.counters()
    rows = REGISTRY.counter(NAME)
    rows.labels(kind="new").inc(80)
    rows.labels(kind="padding").inc(48)
    moved = program_records.change(before, program_records.counters())
    assert moved[f"{NAME}{{kind=new}}"] == 80
    assert _read(moved) == pytest.approx(37.5)
