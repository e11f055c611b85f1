"""The kanana2 cell's benchmark files (PR 36): its two readers on a small
synthetic training trace, the family's counts against hand arithmetic at
the cell's widths, ``check_dims`` refusing a cut width, and the cell's CPU
stand-in (``tiny-kanana2.tiny-preempt1``) rehearsed through the one
command."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_rehearsal as R  # noqa: E402
from perfbench.lib import manifest, program_records, weights  # noqa: E402
from perfbench.metrics import _latent_trace as lt  # noqa: E402
from perfbench.metrics import _program_trace as pt  # noqa: E402

CELL = "kanana2-d6-ep8.moe8k"
MS = 1_000_000
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SCOPES = {"scopes": ["kv_write", "kv_read", "rope", "sample", "loss_head",
                     "grad_clip", "optimizer", "moe_route", "moe_experts",
                     "moe_shared", "index_select", "feed_forward",
                     "attention", "tok_embeddings", "output",
                     "attention_norm", "ffn_norm", "norm"],
          "opened": ["kv_write", "kv_read", "rope", "sample", "loss_head",
                     "grad_clip", "optimizer", "moe_route", "moe_experts",
                     "moe_shared", "index_select"]}
# the window's counters: 10 steps consumed, 3 of them traced
PAIRS, TOUCHED = 10 * 5 * 24_576, 10 * 5 * 16


def _raw():
    """Three traced steps of 100 ms, ops in the first 60 ms of each: flash
    attention, the router, the grouped matmuls (scope-less, told by name)
    and their combine, the shared experts, the head."""
    layer = "jit(train_step)/jvp(LatentMoETransformer)/layers_1/"
    ops, t = [], 0
    for _ in range(3):
        ops += [["pallas:attention.3", layer + "attention/", t, 20 * MS],
                ["fusion.1", layer + "feed_forward/moe_route/top_k",
                 t + 20 * MS, 2 * MS],
                ["pallas:ragged-dot-none.4 bf16[196608,768]", "",
                 t + 22 * MS, 15 * MS],
                ["fusion.2", layer + "feed_forward/moe_experts/mul",
                 t + 37 * MS, 3 * MS],
                ["fusion.3", layer + "feed_forward/moe_shared/dot",
                 t + 40 * MS, 5 * MS],
                ["fusion.4", "jit(train_step)/loss_head/dot",
                 t + 45 * MS, 15 * MS]]
        t += 100 * MS
    return {"device_ops": {"/device:TPU:0": ops},
            "spans": [["pb:window", 0, 300 * MS, "0.0", {}]]}


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    cell = manifest.Cell(CELL, ROOT)
    monkeypatch.setattr(cell, "work_dir", lambda: str(tmp_path))
    with open(os.path.join(str(tmp_path), program_records.SCOPES_NAME),
              "w") as fh:
        json.dump(SCOPES, fh)
    box = {"raw": _raw()}
    monkeypatch.setattr(lt, "_raw_of", lambda path: box["raw"])
    monkeypatch.setattr(lt.trace_reduce, "newest_xplane", lambda d: "x")
    monkeypatch.setattr(pt, "summary_of",
                        lambda c: pt.reduce(box["raw"], SCOPES))
    window = {"steps": 10, "traced_steps": 3, "done_t": list(range(10)),
              "counters": {'moe_pairs_total{phase=train}': PAIRS,
                           'moe_experts_touched_total{phase=train}':
                               TOUCHED,
                           'moe_pairs_total{phase=decode}': 7}}
    return box, {"cell": cell, "dims": weights.dims_of(cell.config),
                 "traffic": cell.traffic, "chips": 1, "peaks": PEAKS,
                 "train": {"window": window, "batch": 4, "cycles": []}}


def _read(name, ctx):
    return manifest.load_reader(name)(ctx)


def test_the_expert_share_counts_the_ragged_dot_calls_by_name(ctx):
    _, c = ctx
    busy = 3 * 60
    assert _read("moe_train_dev_share_pct", c) == pytest.approx(
        100.0 * 3 * (2 + 15 + 3 + 5) / busy)


def test_the_grouped_matmuls_roofline_scales_the_counters_to_the_trace(ctx):
    _, c = ctx
    d = c["dims"]
    fam = weights.family_of(d)
    per = 3 / 10
    least = max(fam.moe_train_expert_flops(d, PAIRS * per) / 197e12,
                fam.moe_train_expert_bytes(d, TOUCHED * per) / 819e9)
    got = _read("expert_gmm_roofline", c)
    assert got == pytest.approx(100.0 * least / (3 * 0.018))
    assert 0 < got <= 100


def test_a_program_that_trains_no_expert_layer_reads_nothing(ctx):
    box, c = ctx
    del c["train"]["window"]["counters"]['moe_pairs_total{phase=train}']
    assert _read("expert_gmm_roofline", c) is None
    for op in box["raw"]["device_ops"]["/device:TPU:0"]:
        op[1] = op[1].replace("moe_", "mlp_")
        op[0] = op[0].replace("ragged-dot", "dot")
    assert _read("moe_train_dev_share_pct", c) is None


def test_the_familys_counts_at_the_cells_widths_by_hand():
    cell = manifest.Cell(CELL, ROOT)
    d = weights.dims_of(cell.config)
    fam = weights.family_of(d)
    assert fam.check_dims(d) == []
    # attention 12.58 + 1.18 + 4.19 + 8.39 M; dense FFN 37.75 M; an expert
    # layer's shared 9.44 M, router 0.26 M and 16 x 4.72 M held experts;
    # the vocabulary slice 2 x 16,032 x 2,048
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert attn == 26_345_472
    expert = 3 * 2048 * 768
    moe = 2048 * 128 + 128 + 3 * 2048 * 1536 + 16 * expert   # + its bias
    norms = 512 + 2 * 2048                 # the latent's and the block's
    total = (2 * 16032 * 2048 + 2048 + 6 * (attn + norms)
             + 3 * 2048 * 6144 + 5 * moe)
    assert weights.param_count(d) == total
    assert total == pytest.approx(687.5e6, rel=1e-3)
    # a token's matmul parameters: 6 attentions, the dense FFN, 5 x (the
    # router, the shared experts, 6 x 16 / 128 experts), the head: 295 M
    active = (6 * attn + 3 * 2048 * 6144 + 2048 * 16032
              + 5 * (2048 * 128 + 3 * 2048 * 1536 + 6 * 16 / 128 * expert))
    assert fam.active_matmul_params(d) == pytest.approx(active)
    assert active == pytest.approx(295e6, rel=3e-3)
    # causal attention over widths 192 + 128: 2 x 32 x 320 a pair
    attn_fwd = 6 * 2 * 32 * 320 * 8192 * 8193 / 2
    assert fam.train_flops_per_token(d, 8192) == pytest.approx(
        3 * (2 * active + attn_fwd / 8192))
    assert 32768 * fam.train_flops_per_token(d, 8192) == pytest.approx(
        107e12, rel=0.01)
    assert fam.flash_attn_flops(d, 4, 8192) == pytest.approx(3 * 4 * attn_fwd)
    qk = 4 * 8192 * 32 * 192 * 2
    v = 4 * 8192 * 32 * 128 * 2
    assert fam.flash_attn_bytes(d, 4, 8192) == 6 * (6 * qk + 6 * v)
    assert fam.moe_train_expert_flops(d, 1536) == 6 * 1536 * expert
    assert fam.moe_train_expert_bytes(d, 16) == 3 * 16 * expert * 2


@pytest.mark.parametrize("key,value", [
    ("moe_intermediate_size", 384), ("qk_rope_head_dim", 32),
    ("kv_lora_rank", 256), ("num_experts_per_tok", 4),
    ("q_lora_rank", 768)])
def test_check_dims_refuses_a_cut_width(key, value):
    cell = manifest.Cell(CELL, ROOT)
    config = dict(cell.config, **{key: value})
    d = weights.dims_of(config)
    assert weights.family_of(d).check_dims(d)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return R.make_checkout(tmp_path_factory.mktemp("pb_kanana"))


def test_the_stand_in_trains_resumes_and_matches_its_reference(checkout):
    stand_in = R.stand_ins(ROOT)[CELL]
    assert stand_in == ["tiny-kanana2.tiny-preempt1"]
    proc, line = R.run_cell(checkout, stand_in[0], "--rehearsal", trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"
    for k in ("resume_state_breaks", "loss_gap", "grad_norm_gap",
              "change_norm_gap", "frozen_unexpected"):
        assert line["compared"][k]["ok"], k
    assert len(json.loads(R.notes_of(proc)["cycles"])) == 1
    work = os.path.join(checkout, ".perfbench_work", stand_in[0])
    with open(os.path.join(work, "window.json")) as fh:
        counters = json.load(fh)["counters"]
    assert counters['moe_pairs_total{phase=train}'] > 0
    assert counters['moe_experts_touched_total{phase=train}'] > 0
