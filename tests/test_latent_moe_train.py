"""The latent-attention / expert class as it trains (models/latent_moe.py's
uncached forward, training/step.py, training/loop.py), against the plain
float32 reference of its benchmark family (``perfbench/families/
kanana2.py``) at the tiny Kanana shape (``perfbench/configs/
tiny-kanana2.json``: no query latent, two shared experts, scaling 2.448,
experts 0-3 of 16 held, top-3). Everything float32 on the CPU.

Tolerances, with their reasons: program and reference are the same float32
equations in another order of summation (the program's fused projections,
its blocked expert grouping, XLA:CPU's reductions), so they agree to a few
float32 ulps of the largest terms — 1e-5 relative on the loss, 1e-4 on a
leaf's gradient norm and on a leaf's change after two AdamW steps. The int8
control (``perfbench/lib/reference.py`` ``mm_int8``, the nearest precision
below the configurations' bfloat16) moves them by 1e-3 and more, so it
fails each."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from perfbench.lib import reference, weights  # noqa: E402

from fault_tolerant_llm_training_tpu.models.latent_moe import (  # noqa: E402
    ExpertLayer,
)
from fault_tolerant_llm_training_tpu.ops import flash_attention as fa  # noqa
from fault_tolerant_llm_training_tpu.training.state import TrainState  # noqa
from fault_tolerant_llm_training_tpu.training.step import (  # noqa: E402
    loss_and_stats,
    make_optimizer,
    make_train_step,
)

F32 = jnp.float32
LOSS_TOL, LEAF_TOL = 1e-5, 1e-4


@pytest.fixture(scope="module")
def tiny():
    config = json.loads((REPO / "perfbench" / "configs"
                         / "tiny-kanana2.json").read_text())
    d = weights.dims_of(config)
    fam = weights.family_of(d)
    cfg = fam.preset(config, dtype=F32, param_dtype=F32, seq_len=64)
    return config, d, fam, cfg


def _batch(seed, rows=2, seq=64, vocab=512):
    toks = np.random.default_rng(seed).integers(0, vocab, (rows, seq + 1))
    return jnp.asarray(toks[:, :-1], jnp.int32), jnp.asarray(toks[:, 1:],
                                                             jnp.int32)


def _program_loss_and_grads(fam, cfg, params, inputs, labels):
    model = fam.model_class()(cfg)

    def loss(p):
        return loss_and_stats(model, p, inputs, labels)[0]

    value, grads = jax.value_and_grad(loss)(params)
    return float(value), weights.flatten(grads)


def _gaps(prog, ref):
    """Per leaf |‖a‖ - ‖b‖| and ‖a - b‖, each over max(‖b‖, median ‖b‖)."""
    med = float(np.median([float(jnp.linalg.norm(v)) for v in ref.values()]))
    out = {}
    for p, b in ref.items():
        a = prog[p]
        den = max(float(jnp.linalg.norm(b)), med)
        out[p] = float(jnp.linalg.norm(a - b)) / den
    return out


def test_loss_and_every_leafs_gradient_match_the_reference(tiny):
    config, d, fam, cfg = tiny
    key = jax.random.PRNGKey(11)
    params = weights.make_param_tree(key, d, F32)
    inputs, labels = _batch(1)
    loss, grads = _program_loss_and_grads(fam, cfg, params, inputs, labels)
    flat = weights.flatten(params)
    ref_loss, ref_grads = fam.LossAndGrads(d, reference.mm_f32)(
        flat, np.asarray(inputs), np.asarray(labels))
    assert set(grads) == set(ref_grads) == set(weights.all_leaves(d))
    assert abs(loss - ref_loss) / abs(ref_loss) < LOSS_TOL
    gaps = _gaps(grads, ref_grads)
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] < LEAF_TOL, (worst, gaps[worst])
    # the router's selection bias moves which experts are chosen, never a
    # weight: no gradient reaches it, in the program as in the reference
    for p in grads:
        if p.endswith("router/bias"):
            assert float(jnp.max(jnp.abs(grads[p]))) == 0.0, p
    # the control, one precision below the configurations' bfloat16, fails
    ctl_loss, ctl_grads = fam.LossAndGrads(d, reference.mm_int8)(
        flat, np.asarray(inputs), np.asarray(labels))
    ctl = _gaps(ctl_grads, ref_grads)
    assert (abs(ctl_loss - ref_loss) / abs(ref_loss) > LOSS_TOL
            or max(ctl.values()) > LEAF_TOL)
    assert max(ctl.values()) > 10 * LEAF_TOL


def test_two_optimizer_steps_change_every_leaf_as_the_reference(tiny):
    config, d, fam, cfg = tiny
    key = jax.random.PRNGKey(5)
    lr = 1e-3
    opt = make_optimizer(lr, 0)
    params = weights.make_param_tree(key, d, F32)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    step = jax.jit(make_train_step(fam.model_class()(cfg), opt, 1.0))
    batches = [_batch(21), _batch(22)]
    losses = []
    for inputs, labels in batches:
        state, metrics = step(state, inputs, labels)
        packed = np.asarray(metrics["packed"])
        losses.append(float(packed[0]))
        assert packed.shape == (4,)     # loss, grad norm, pairs, touched
    ref = reference.run_train_reference(
        key, d, lr, 0, [(np.asarray(i), np.asarray(l)) for i, l in batches],
        dtype=F32)
    for got, want in zip(losses, ref["loss"]):
        assert abs(got - want) / abs(want) < LOSS_TOL
    p0 = weights.flatten(params)
    change = {p: float(jnp.linalg.norm(v - p0[p]))
              for p, v in weights.flatten(state.params).items()}
    med = float(np.median(list(ref["change_norms"].values())))
    for p, want in ref["change_norms"].items():
        assert abs(change[p] - want) / max(want, med) < LEAF_TOL, p


def _plain_attention(q, k, v):
    s = q.shape[2]
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                    precision="highest") / np.sqrt(q.shape[-1])
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("s,stream", [(64, False), (1024, False),
                                      (2560, False), (1024, True)])
def test_flash_kernel_of_two_widths_matches_plain_attention(
        s, stream, monkeypatch):
    """192-wide query/key, 128-wide value, in interpret mode: the forward
    and all three gradients. The resident family — the kernels the
    cell's 8,192 rows take, where its VMEM fits the chip — at 64 rows (one
    tile), 1,024 (2 x 2 tiles) and 2,560 (5 q-tiles over a k-loop of 5,
    past the 2,048 rows where a row count once made the forward stream);
    ``stream``: on a chip with no VMEM to spare, the streaming family at
    1,024 rows — the streamed forward (2 q-tiles x 1 k-step) and the split
    backward (2 x 2 dq, 1 x 2 dk/dv: the causal grid bounds and fetch
    clamps)."""
    if stream:
        monkeypatch.setattr(fa, "vmem_capacity_bytes", lambda: 0)
    resident = fa._fused_bwd_vmem_limit(s, 192, 128, False, 4) is not None
    assert resident is not stream
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    b, h = 1, 2
    q = jax.random.normal(ks[0], (b, h, s, 192), F32)
    k = jax.random.normal(ks[1], (b, h, s, 192), F32)
    v = jax.random.normal(ks[2], (b, h, s, 128), F32)
    g = jax.random.normal(ks[3], (b, h, s, 128), F32)
    out = fa.flash_attention_bhsd(q, k, v)
    assert out.shape == (b, h, s, 128)
    np.testing.assert_allclose(out, _plain_attention(q, k, v), atol=2e-5)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * g),
                        argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(grads(fa.flash_attention_bhsd),
                         grads(_plain_attention)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-4)


_COUNT_BACKWARDS = r"""
import sys
sys.path.insert(0, sys.argv[1])
import train as entry
from fault_tolerant_llm_training_tpu.obs.registry import REGISTRY
from fault_tolerant_llm_training_tpu.ops import flash_attention as fa
from fault_tolerant_llm_training_tpu.utils.config import get_args
from fault_tolerant_llm_training_tpu.utils.logging import init_logger

init_logger()
if sys.argv[2] == "split":      # a chip with no VMEM to spare
    fa.vmem_capacity_bytes = lambda: 0
try:
    entry.train(get_args(sys.argv[3:]))
except SystemExit as e:
    assert e.code in (0, None), e.code
print({d: dict(REGISTRY.snapshot().get(f"flash_{d}_calls_total",
                                       {"series": {}})["series"])
       for d in ("forward", "backward")})
"""


@pytest.mark.parametrize("family", ["fused", "split"])
def test_flash_backward_counter_reads_layers_times_steps(tmp_path, family):
    """Two training steps of the 3-layer ``tiny-latent-train`` under
    ``--remat`` with the Pallas kernels (interpreted here): the start-up
    lines name the forward and backward families the VMEM rule picked,
    ``flash_backward_calls_total`` advances by layers x steps under that
    family alone, and ``flash_forward_calls_total`` by layers x steps x 2
    under the forward of the same family (remat runs a layer's forward
    again in the backward)."""
    import ast

    import pyarrow as pa
    import pyarrow.parquet as pq

    parquet = str(tmp_path / "d.parquet")
    pq.write_table(pa.table({"text": ["alpha bravo charlie delta"] * 16}),
                   parquet)
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLURM_JOB_ID="kf1")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _COUNT_BACKWARDS, str(REPO), family,
         "--dataset", parquet, "--checkpoint-path", str(tmp_path / "ck"),
         "--tokenizer-name-or-path", "byte", "--model", "tiny-latent-train",
         "--vocab-size", "512", "--model-dtype", "fp32",
         "--attention-impl", "pallas", "--remat", "--sequence-length", "64",
         "--batch-size", "2", "--training-steps", "2",
         "--compile-cache-dir", ""],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    forward = "resident" if family == "fused" else "stream"
    assert f"Flash backward | {family} x3 a step" in proc.stdout, (
        proc.stdout[-3000:])
    assert f"Flash forward | {forward} x6 a step" in proc.stdout
    got = ast.literal_eval(proc.stdout.strip().splitlines()[-1])
    assert got == {"forward": {f"kernel={forward}": 3 * 2 * 2},
                   "backward": {f"kernel={family}": 3 * 2}}


# ------------------------------------------------------ the expert layer
def _expert_params(cfg, key):
    layer = ExpertLayer(cfg)
    x = jnp.zeros((1, 8, cfg.dim), F32)
    return layer, layer.init(key, x, jnp.ones((1, 8), bool))["params"]


def _share(params, first, count):
    out = jax.tree_util.tree_map(lambda a: a, params)
    out["experts"] = {n: {"kernel": v["kernel"][first:first + count]}
                      for n, v in params["experts"].items()}
    return out


@pytest.mark.parametrize("shares", [2, 4])
def test_shares_of_the_held_range_add_up_to_the_uncut_layer(tiny, shares):
    """All 16 experts held, against ``shares`` chips holding 16 / shares
    each: the routed parts add up, the shared experts counted once, and so
    do the gradients of the weights and of the input."""
    _, _, _, cfg = tiny
    whole = cfg.replace(held_experts=(0, 16))
    layer, params = _expert_params(whole, jax.random.PRNGKey(3))
    params["router"]["bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (16,), F32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.dim), F32)
    valid = jnp.ones((2, 32), bool)
    n = 16 // shares
    cut = [ExpertLayer(cfg.replace(held_experts=(i * n, n)))
           for i in range(shares)]

    def uncut(p, x):
        return layer.apply({"params": p}, x, valid)[0]

    def shared_sum(p, x):
        total = 0.0
        for i, part in enumerate(cut):
            routed, shared, _, _ = part.apply(
                {"params": _share(p, i * n, n)}, x, valid,
                method=ExpertLayer.parts)
            total = total + routed + (shared if i == 0 else 0.0)
        return total

    np.testing.assert_allclose(shared_sum(params, x), uncut(params, x),
                               atol=1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(6), x.shape, F32)
    g_cut = jax.grad(lambda p, x: jnp.sum(shared_sum(p, x) * probe),
                     argnums=(0, 1))(params, x)
    g_whole = jax.grad(lambda p, x: jnp.sum(uncut(p, x) * probe),
                       argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_cut),
                    jax.tree_util.tree_leaves(g_whole)):
        np.testing.assert_allclose(a, b, atol=1e-4)


def _undefined_past_the_groups(monkeypatch):
    """``jax.lax.ragged_dot`` as a TPU leaves it: the rows past the groups
    hold whatever was there, in the forward and in the backward's
    activation gradient alike (here NaN, the worst they may hold: one NaN
    that reaches a product spreads to every row it is summed with)."""
    plain = jax.lax.ragged_dot

    def fill(y, sizes):
        rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        return jnp.where(rows[:, None], y, jnp.nan)

    @jax.custom_vjp
    def rd(x, w, sizes):
        return fill(plain(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return rd(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        dx, dw = jax.vjp(lambda a, b: plain(a, b, sizes), x, w)[1](g)
        return fill(dx, sizes), dw, None

    rd.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda x, w, sizes, **kw: rd(x, w, sizes))


def test_no_undefined_grouped_row_reaches_a_gradient(tiny, monkeypatch):
    """The training path's gradients are those of a ``ragged_dot`` that
    zeroes the rows past its groups, when it leaves them undefined as on
    a TPU (PR 36's first chip run: every gradient off by ~96 % until the
    training path masked them); the serving path's forward is unaffected
    and stays as it was."""
    _, _, _, cfg = tiny
    layer, params = _expert_params(cfg, jax.random.PRNGKey(12))
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 32, cfg.dim), F32)
    valid = jnp.ones((2, 32), bool)
    probe = jax.random.normal(jax.random.PRNGKey(14), x.shape, F32)

    def grads(train):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            {"params": p}, x, valid, train)[0] * probe),
            argnums=(0, 1))(params, x)

    want = grads(True)
    out_want = layer.apply({"params": params}, x, valid)[0]
    _undefined_past_the_groups(monkeypatch)
    for a, b in zip(jax.tree_util.tree_leaves(grads(True)),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # unmasked, the undefined rows reach the input's gradient
    bad = grads(False)[1]
    assert not np.all(np.abs(np.asarray(bad - want[1])) <= 1.0)
    np.testing.assert_allclose(layer.apply({"params": params}, x, valid)[0],
                               out_want, atol=1e-6)


def _reference_layer(fam, d, params, u):
    w = {p: v for p, v in weights.flatten(params).items()}
    return fam.expert_layer(w, u, d, reference.mm_f32)


def test_dropless_when_every_token_goes_to_one_held_expert(tiny):
    _, d, fam, cfg = tiny
    layer, params = _expert_params(cfg, jax.random.PRNGKey(7))
    # expert 2 (held) outbids every other: each token chooses it
    params["router"]["bias"] = jnp.zeros((16,), F32).at[2].set(100.0)
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 64, cfg.dim), F32)
    out, pairs, touched = layer.apply({"params": params}, u,
                                      jnp.ones((1, 64), bool))
    want = _reference_layer(fam, d, params, u[0])
    np.testing.assert_allclose(out[0], want, atol=1e-5)
    choice, _ = fam.route(weights.flatten(params), u[0], d, reference.mm_f32)
    assert bool(jnp.all(jnp.any(choice == 2, axis=-1)))
    held = np.asarray((choice >= 0) & (choice < 4))
    assert int(pairs) == int(held.sum()) >= 64


@pytest.mark.parametrize("wrong", [
    {"routed_scaling_factor": 1.0}, {"num_experts_per_tok": 2},
    {"n_shared_experts": 1}, {"held_experts": (4, 4)}])
def test_a_wrong_expert_layer_is_caught(tiny, wrong):
    """The program told a wrong scaling, top-k, shared width or held range
    misses the reference by far more than the tolerance."""
    _, d, fam, cfg = tiny
    layer, params = _expert_params(cfg, jax.random.PRNGKey(9))
    u = jax.random.normal(jax.random.PRNGKey(10), (1, 64, cfg.dim), F32)
    want = _reference_layer(fam, d, params, u[0])
    bad = cfg.replace(**wrong)
    p = dict(params)
    if "n_shared_experts" in wrong:
        p["shared"] = {"w1": {"kernel": params["shared"]["w1"]["kernel"][:, :32]},
                       "w3": {"kernel": params["shared"]["w3"]["kernel"][:, :32]},
                       "w2": {"kernel": params["shared"]["w2"]["kernel"][:32]}}
    out, _, _ = ExpertLayer(bad).apply({"params": p}, u,
                                       jnp.ones((1, 64), bool))
    err = float(jnp.max(jnp.abs(out[0] - want)))
    assert err > 100 * 1e-5, err


# ------------------------------------------------- state, checkpoint, mesh
def test_the_state_tree_saves_and_restores_bit_exact(tiny, tmp_path):
    from fault_tolerant_llm_training_tpu.checkpoint.manager import (
        CheckpointManager,
    )

    _, d, fam, cfg = tiny
    opt = make_optimizer(1e-3, 0)
    params = weights.make_param_tree(jax.random.PRNGKey(1), d, jnp.bfloat16)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    state, _ = jax.jit(make_train_step(
        fam.model_class()(cfg.replace(dtype=jnp.bfloat16,
                                      param_dtype=jnp.bfloat16)), opt, 1.0))(
        state, *_batch(2))
    paths = weights.flatten(state.params)
    assert any("experts/w1" in p for p in paths)
    assert any(p.endswith("router/bias") for p in paths)
    assert any(p.endswith("kv_norm/scale") for p in paths)
    mngr = CheckpointManager(str(tmp_path), "kj1")
    mngr.save(1, state, {"pos": 7}, wait=True)
    mngr.close()
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        state)
    back = CheckpointManager(str(tmp_path), "kj1")
    restored, data_state, step = back.restore(abstract)
    back.close()
    assert step == 1 and data_state == {"pos": 7}
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_mesh_of_several_devices_is_refused_by_name(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    parquet = str(tmp_path / "d.parquet")
    pq.write_table(pa.table({"text": ["alpha bravo charlie"] * 32}), parquet)
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLURM_JOB_ID="km4",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "train.py", "--dataset", parquet,
         "--checkpoint-path", str(tmp_path / "ck"),
         "--tokenizer-name-or-path", "byte", "--model", "tiny-latent-train",
         "--sequence-length", "64", "--batch-size", "4",
         "--training-steps", "2", "--compile-cache-dir", ""],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    text = proc.stdout + proc.stderr
    assert "LatentMoEConfig preset" in text and "ROADMAP R1" in text, (
        text[-3000:])
    assert "Starting training!" not in text
