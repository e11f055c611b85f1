"""Compile each cell's timed program at its real widths for a DESCRIBED TPU
v5e (no chip attached), so that a shape that does not fit, or that Mosaic
refuses, is found before chip time is spent. Nothing runs here: a compile
that passes is not a chip run, and no time or result comes out of this file.

The topology is described inside a fixture (never at import), as
``tests/test_chip_compile.py`` does; both files then want libtpu in their
own worker. Where that cannot be had this file's tests skip.
"""

import os
import sys
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.lib import manifest, weights  # noqa: E402

HBM = 16e9


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture()
def for_the_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fault_tolerant_llm_training_tpu.ops import flash_attention as fa
    from fault_tolerant_llm_training_tpu.ops import paged_attention as pa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def program_config(name, **over):
    """Sizes and the program's model configuration, as the cells build
    them: through the configuration's model family."""
    cfg = manifest.load_json(os.path.join(ROOT, "perfbench", "configs",
                                          name + ".json"))
    d = weights.dims_of(cfg)
    return d, weights.family_of(d).preset(cfg, **over)


def on(device, tree):
    one = SingleDeviceSharding(device)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)


def test_preempt_train_step_fits_one_chip(v5e, for_the_chip):
    from fault_tolerant_llm_training_tpu.models import Transformer
    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer,
        make_train_step,
    )

    mix = manifest.load_json(os.path.join(ROOT, "perfbench", "traffic",
                                          "preempt.json"))
    seq, rows = mix["sequence_length"], mix["rows_per_chip"]
    d, cfg = program_config("mistral-7b-v0.3-d4", seq_len=seq,
                            attention_impl="pallas")
    assert cfg.ffn_hidden_dim == d["hidden"] == 14336
    model = Transformer(cfg)
    opt = make_optimizer(mix["learning_rate"], mix["lr_warmup_steps"])

    def init_fn(key):
        params = weights.make_param_tree(key, d, jnp.bfloat16)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    dev = v5e.devices[0]
    state = on(dev, jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                                  sharding=SingleDeviceSharding(dev))
    compiled = jax.jit(make_train_step(model, opt, 1.0),
                       donate_argnums=(0,)).lower(
        state, tokens, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the flash kernel
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6.8e9        # params + 2 moments
    assert need < HBM, f"{need / 1e9:.1f} GB does not fit one chip"


@pytest.mark.parametrize("mix_name", ["longdecode", "chat"])
def test_serve_decode_program_fits_one_chip(v5e, for_the_chip, mix_name):
    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        blocks_per_slot,
        init_paged_cache,
    )
    from fault_tolerant_llm_training_tpu.models import Transformer

    mix = manifest.load_json(os.path.join(ROOT, "perfbench", "traffic",
                                          mix_name + ".json"))
    server = mix["server"]
    d, cfg = program_config("internlm2-1.8b")
    cfg = cfg.replace(remat=False)      # as the engine sets it
    slots, block = server["slots"], 16
    num_blocks = server["pool_tokens"] // block + 1
    per_slot = blocks_per_slot(server["max_len"], block)
    dev = v5e.devices[0]
    params = on(dev, jax.eval_shape(
        lambda k: weights.make_param_tree(k, d, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on(dev, jax.eval_shape(
        lambda: init_paged_cache(cfg, slots, server["max_len"], block,
                                 num_blocks)))
    one = SingleDeviceSharding(dev)
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=one)  # noqa
    tables = jax.ShapeDtypeStruct((slots, per_slot), jnp.int32, sharding=one)
    # the engine's own decode function over a stand-in that carries what it
    # reads of ``self`` (building a whole engine would place real arrays)
    stub = types.SimpleNamespace(model=Transformer(cfg), top_k=0,
                                 _adapter_operand=lambda *a: None)
    compiled = jax.jit(
        lambda *a: InferenceEngine._paged_decode_fn(stub, *a),
        donate_argnums=(1,)).lower(
        params, cache, tables, vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.float32), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.int32)).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # weights 3.78 GB + pool 8.05 GB are the arguments
    assert mem.argument_size_in_bytes > 11.5e9
    assert need < HBM, f"{need / 1e9:.1f} GB does not fit one chip"
    assert traffic_fits(mix)


def traffic_fits(mix) -> bool:
    from perfbench.lib import traffic

    need = traffic.lengths_needed(mix)
    server = mix["server"]
    return (need["total"] <= server["max_len"]
            and server["slots"] * server["max_len"] <= server["pool_tokens"])
