"""The four readers of the latent / expert layers (PR 33) on a small
synthetic trace: seconds and counts are taken from the same rounds, the
compiler's scope-less ragged-dot calls count as ``moe_experts`` by name, and
a program without the stats spans gives no reading at all."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import manifest, program_records, weights  # noqa: E402
from perfbench.metrics import _latent_trace as lt  # noqa: E402
from perfbench.metrics import _program_trace as pt  # noqa: E402

MS = 1_000_000
SCOPES = {"scopes": ["kv_write", "kv_read", "rope", "sample", "loss_head",
                     "grad_clip", "optimizer", "moe_route", "moe_experts",
                     "moe_shared", "index_select", "feed_forward",
                     "attention", "tok_embeddings", "output",
                     "attention_norm", "ffn_norm", "norm"],
          "opened": ["kv_write", "kv_read", "rope", "sample", "loss_head",
                     "grad_clip", "optimizer", "moe_route", "moe_experts",
                     "moe_shared", "index_select"]}
STATS = {"moe_pairs": 256, "moe_touched": 100, "index_keys": 2_000_000,
         "latent_rows": 262_144, "window_rows": 98_304}


def _raw(with_stats=True):
    """Two decode rounds of 20 ms (ops in the first 12 ms of each) and one
    prefill of 40 ms, on one device and one host thread."""
    path = "jit(f)/layers_1/"
    ops, spans, t = [], [["pb:window", 0, 100 * MS, "0.0", {}]], 0
    for _ in range(2):
        spans.append(["ftl:engine.decode", t, t + 20 * MS, "0.0",
                      {"live_tokens": 1, "slots_active": 64, "n": 1}])
        if with_stats:
            spans.append(["ftl:engine.decode.stats", t + 19 * MS,
                          t + 19 * MS + 1000, "0.0", dict(STATS)])
        ops += [["fusion.1", path + "attention/index_select/dot", t, 2 * MS],
                ["fusion.2", path + "attention/kv_read/gather", t + 2 * MS,
                 3 * MS],
                ["pallas:ragged-dot-none.1 bf16[512,1536]", "", t + 5 * MS,
                 4 * MS],
                ["fusion.3", path + "feed_forward/moe_route/top_k",
                 t + 9 * MS, 1 * MS],
                ["fusion.4", path + "feed_forward/moe_shared/dot",
                 t + 10 * MS, 2 * MS]]
        t += 20 * MS
    spans.append(["ftl:engine.prefill", t, t + 40 * MS, "0.0",
                  {"new_tokens": 100}])
    if with_stats:
        spans.append(["ftl:engine.prefill.stats", t + 39 * MS,
                      t + 39 * MS + 1000, "0.0",
                      {**STATS, "moe_pairs": 2048, "moe_touched": 128}])
    ops += [["pallas:ragged-dot-none.2 bf16[16384,1536]", "", t, 10 * MS],
            ["pallas:latent_chunk_attention.1",
             path + "attention/kv_read/latent_chunk_attention", t + 10 * MS,
             20 * MS]]
    return {"device_ops": {"/device:TPU:0": ops}, "spans": spans}


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    cell = manifest.Cell("dots3-d5-ep8.sessions16k", ROOT)
    monkeypatch.setattr(cell, "work_dir", lambda: str(tmp_path))
    program_records_file = os.path.join(str(tmp_path),
                                        program_records.SCOPES_NAME)
    with open(program_records_file, "w") as fh:
        import json
        json.dump(SCOPES, fh)
    box = {"raw": _raw()}
    monkeypatch.setattr(lt, "_raw_of", lambda path: box["raw"])
    monkeypatch.setattr(lt.trace_reduce, "newest_xplane", lambda d: "x")
    monkeypatch.setattr(pt, "summary_of",
                        lambda c: pt.reduce(box["raw"], SCOPES))
    d = weights.dims_of(cell.config)
    return box, {"cell": cell, "dims": d, "serve": {"x": 1},
                 "peaks": {"bf16_flops": 197e12,
                           "hbm_bytes_per_s": 819e9}}


def _read(name, ctx):
    return manifest.load_reader(name)(ctx)


def test_shares_count_the_ragged_dot_calls_by_name(ctx):
    _, c = ctx
    busy = 2 * 12 + 30          # ms of the 100 ms window
    moe = 2 * (4 + 1 + 2) + 10
    assert _read("moe_dev_share_pct", c) == pytest.approx(100 * moe / busy)
    assert _read("index_select_dev_share_pct", c) == pytest.approx(
        100 * 4 / busy)


def test_rooflines_take_seconds_and_counts_from_the_same_rounds(ctx):
    _, c = ctx
    d = c["dims"]
    fam = weights.family_of(d)
    # experts: the ragged-dot seconds inside decode + prefill rounds
    pairs, touched = 2 * 256 + 2048, 2 * 100 + 128
    least = max(fam.moe_expert_flops(d, pairs) / 197e12,
                fam.moe_expert_bytes(d, touched) / 819e9)
    assert _read("moe_expert_roofline", c) == pytest.approx(
        100 * least / 0.018)
    # the reads: kv_read + index_select inside the DECODE rounds only
    need = fam.latent_read_bytes(d, 2 * 2_000_000, 2 * 262_144, 2 * 98_304)
    assert _read("latent_read_roofline", c) == pytest.approx(
        100 * need / 819e9 / 0.010)
    assert 0 < _read("latent_read_roofline", c) < 100


def test_a_program_without_the_stats_spans_gives_no_roofline(ctx):
    box, c = ctx
    box["raw"] = _raw(with_stats=False)
    assert _read("moe_expert_roofline", c) is None
    assert _read("latent_read_roofline", c) is None
    # the shares need only scopes
    assert _read("moe_dev_share_pct", c) > 0
    # ... and a trace under another program's names, nothing
    for op in box["raw"]["device_ops"]["/device:TPU:0"]:
        op[1] = ""
    assert _read("moe_dev_share_pct", c) is None
    assert _read("index_select_dev_share_pct", c) is None
