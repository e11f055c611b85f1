"""Compile the main path's kernels for a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed with libtpu and compiles for a topology that is
only described (``v5e:2x2``), so what Mosaic or the SPMD partitioner would
refuse on the chip — a vector layout it cannot infer, a kernel it cannot
partition — is refused here, in tier-1, at no chip time. Interpret-mode tests
cannot see any of that. A compile that passes is NOT a chip run: nothing
executes, and no result or time comes out of this file.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the tests
steer ``_interpret()`` themselves (never through a program option), and the
persistent compile cache is switched off around them: a TPU executable written
to it cannot be read back without a chip and would only warn.
"""

import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep the compiler quiet

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from fault_tolerant_llm_training_tpu.ops import flash_attention as fa
from fault_tolerant_llm_training_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / topology not describable here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def compiled_kernels_no_cache(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    # both modules hold their own reference to the rule
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest pins "highest" for the CPU numerics oracles; the program runs
    # at the default, and Mosaic refuses an fp32 contraction of bf16 tiles
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _shapes_on(device):
    """ShapeDtypeStruct factory pinned to one described device."""
    one = SingleDeviceSharding(device)
    return lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    return compiled


# (heads, kv_heads, head_dim): gpt2-125m, a small GQA, and llama3-8b widths
TRAIN_WIDTHS = [(12, 12, 64), (32, 8, 128)]
SERVE_WIDTHS = [(12, 12, 64), (4, 2, 64), (32, 8, 128)]
_ids = lambda w: f"h{w[0]}-kv{w[1]}-d{w[2]}"


@pytest.mark.parametrize("rope_fused", [False, True],
                         ids=["flash", "rope_fused"])
@pytest.mark.parametrize("width", TRAIN_WIDTHS, ids=_ids)
def test_flash_fwd_bwd_compiles_for_v5e(v5e, width, rope_fused):
    h, kv, d = width
    b, s = 2, 2048
    sds = _shapes_on(v5e.devices[0])
    if rope_fused:
        def loss(q, k, v, cos2, sin2):
            return fa.flash_attention_rope(q, k, v, cos2, sin2, True).astype(
                jnp.float32).sum()
        args = (sds((b, h, s, d)), sds((b, kv, s, d)), sds((b, kv, s, d)),
                sds((s, d), jnp.float32), sds((s, d), jnp.float32))
    else:
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, True).astype(
                jnp.float32).sum()
        args = (sds((b, s, h, d)), sds((b, s, kv, d)), sds((b, s, kv, d)))
    _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)


@pytest.mark.parametrize("kernel", ["decode", "chunk", "int8_decode",
                                    "int8_chunk", "tree_verify"])
@pytest.mark.parametrize("width", SERVE_WIDTHS, ids=_ids)
def test_paged_kernel_compiles_for_v5e(v5e, width, kernel):
    from fault_tolerant_llm_training_tpu.inference.kv_cache import QuantPool

    h, kv, d = width
    slots, block, blocks_per_slot, pool_blocks = 8, 16, 32, 300
    sds = _shapes_on(v5e.devices[0])
    pool = sds((pool_blocks, kv, block, d))
    if kernel.startswith("int8"):
        pool = QuantPool(q=sds(pool.shape, jnp.int8),
                         scale=sds((pool_blocks, kv), jnp.float32))
    tables = sds((slots, blocks_per_slot), jnp.int32)
    offsets = sds((slots,), jnp.int32)
    if kernel == "tree_verify":
        nodes = 7
        _compile(pa.paged_tree_chunk_attention, sds((slots, nodes, h, d)),
                 pool, pool, tables, offsets, sds((nodes, nodes), jnp.int32))
    elif kernel.endswith("decode"):
        _compile(pa.paged_decode_attention, sds((slots, 1, h, d)), pool,
                 pool, tables, offsets)
    else:
        _compile(pa.paged_chunk_attention, sds((slots, 16, h, d)), pool,
                 pool, tables, offsets)


def test_train_step_lowers_on_four_chip_fsdp_mesh(v5e):
    """The Mosaic-partition guard: the real train step at gpt2-125m widths
    (2 layers) must LOWER for a 4-device fsdp mesh with the Pallas flash
    kernel inside — issued bare under jit it raises 'Mosaic kernels cannot
    be automatically partitioned' before step 1 on any multi-chip host."""
    from fault_tolerant_llm_training_tpu.models import Transformer, get_config
    from fault_tolerant_llm_training_tpu.parallel.mesh import (
        make_mesh,
        use_mesh,
    )
    from fault_tolerant_llm_training_tpu.parallel.sharding import (
        batch_pspec,
        param_pspecs,
    )
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer,
        make_train_step,
    )

    seq, batch = 2048, 8
    cfg = dataclasses.replace(
        get_config("gpt2-125m", vocab_size=50257, seq_len=seq,
                   attention_impl="pallas"), n_layers=2)
    mesh = make_mesh(fsdp=4, devices=v5e.devices)
    with use_mesh(mesh):
        model, opt = Transformer(cfg), make_optimizer(1e-4, 10)

        def init_fn(key):
            params = model.init(key, jnp.zeros((1, seq), jnp.int32))["params"]
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt.init(params))

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), param_pspecs(abstract),
            is_leaf=lambda x: isinstance(x, P))
        state = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abstract, shardings)
        tokens = jax.ShapeDtypeStruct(
            (batch, seq), jnp.int32,
            sharding=NamedSharding(mesh, batch_pspec()))
        lowered = jax.jit(make_train_step(model, opt, 1.0),
                          donate_argnums=(0,),
                          out_shardings=(shardings, None)).lower(
            state, tokens, tokens)
    assert "tpu_custom_call" in lowered.as_text()


def test_d4_train_step_keeps_scope_names_and_flash_call_names(v5e):
    """The benchmark's training program (mistral-7b-v0.3-d4, seq 4096 x 3
    rows) compiled for one chip of the described v5e: after XLA's fusion
    every training scope of ``obs/trace.py`` ``SCOPES`` is still the
    ``op_name`` of some instruction (the profiler reads device time by
    model part from it), and the flash attention Mosaic calls are still
    named ``attention.N`` — the name the benchmark's accepted kernel
    readers find them by, which a scope opened between the ``attention``
    module and the ``pallas_call`` would change."""
    import json
    import re
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from perfbench.lib import weights

    from fault_tolerant_llm_training_tpu.models import Transformer
    from fault_tolerant_llm_training_tpu.models import configs as mc
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer,
        make_train_step,
    )

    bench = root / "perfbench"
    config = json.loads(
        (bench / "configs" / "mistral-7b-v0.3-d4.json").read_text())
    mix = json.loads((bench / "traffic" / "preempt.json").read_text())
    seq, rows = mix["sequence_length"], mix["rows_per_chip"]
    d = weights.dims_of(config)
    cfg = mc.TransformerConfig(**weights.preset_kwargs(config), seq_len=seq,
                               attention_impl="pallas")
    model = Transformer(cfg)
    opt = make_optimizer(mix["learning_rate"], mix["lr_warmup_steps"])

    def init_fn(key):
        params = weights.make_param_tree(key, d, jnp.bfloat16)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    one = SingleDeviceSharding(v5e.devices[0])
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
    hlo = jax.jit(make_train_step(model, opt, 1.0),
                  donate_argnums=(0,)).lower(
        state, tokens, tokens).compile().as_text()
    words = {w for name in re.findall(r'op_name="([^"]+)"', hlo)
             for w in re.findall(r"[A-Za-z_][A-Za-z0-9_]*", name)}
    want = {"loss_head", "grad_clip", "optimizer", "attention",
            "feed_forward", "tok_embeddings", "attention_norm", "ffn_norm",
            "norm"}
    assert want <= words, want - words
    calls = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # forward + backward kernels of each of the 4 layers
    assert len(calls) >= 2 * d["n_layers"]
    assert all(re.fullmatch(r"attention(\.\d+)?", c) for c in calls), calls
