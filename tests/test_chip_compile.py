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
from fault_tolerant_llm_training_tpu.ops import latent_attention as la
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
    monkeypatch.setattr(la, "_interpret", lambda: False)
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


@pytest.mark.parametrize("b,h,kv,s,d,dv", [(4, 32, 32, 8192, 192, 128),
                                            (3, 32, 8, 4096, 128, 128)],
                         ids=["kanana2-moe8k", "mistral7b-preempt"])
def test_fused_flash_backward_compiles_at_the_training_cells_shapes(
        v5e, monkeypatch, b, h, kv, s, d, dv):
    """The training cells' attention, forward and backward, compiled by
    Mosaic for one described v5e: the resident family — the resident
    forward and the ONE fused backward — compiles under the VMEM limit its
    calls ask for. At kanana-2's shape that limit is what makes it
    compile: held to XLA's default scoped 16 MiB, the compiler runs out of
    VMEM."""
    sds = _shapes_on(v5e.devices[0])
    grad = jax.grad(lambda q, k, v: fa.flash_attention_bhsd(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))
    args = (sds((b, h, s, d)), sds((b, kv, s, d)), sds((b, kv, s, dv)))
    calls, vmem = fa.flash_calls(jax.jit(grad).trace(*args).jaxpr)
    assert calls == {"forward": {"resident": 1}, "backward": {"fused": 1}}
    assert vmem == fa._fused_bwd_vmem_limit(s, d, dv, False, 2) <= (
        fa.CALIBRATION_VMEM_BYTES * 3 // 4)
    compiled = _compile(grad, *args)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    if d == 192:
        monkeypatch.setattr(fa, "_fused_bwd_vmem_limit",
                            lambda *a: fa.DEFAULT_SCOPED_VMEM_BYTES)
        jax.clear_caches()      # or the backward traced above is reused
        with pytest.raises(Exception, match="vmem"):
            jax.jit(grad).lower(*args).compile()


def test_resident_flash_forward_compiles_at_the_kanana_cells_shape(
        v5e, monkeypatch):
    """One kanana-2 layer's attention forward alone (4 rows x 32 heads x
    8,192, 192 / 128), compiled by Mosaic for one described v5e: the
    resident forward — whole K and V rows of a head in VMEM, no K/V tile
    streamed — compiles under the limit its call asks for (the family's,
    54 MiB), and that limit is what makes it compile: its blocks need
    about 18 MiB, so held to XLA's default scoped 16 MiB the compiler runs
    out of VMEM."""
    import re

    b, h, s, d, dv = 4, 32, 8192, 192, 128
    sds = _shapes_on(v5e.devices[0])
    args = (sds((b, h, s, d)), sds((b, h, s, d)), sds((b, h, s, dv)))
    forward = lambda q, k, v: fa.flash_attention_bhsd(q, k, v)  # noqa: E731
    calls, vmem = fa.flash_calls(jax.jit(forward).trace(*args).jaxpr)
    assert calls == {"forward": {"resident": 1}, "backward": {}}
    assert vmem == fa._fused_bwd_vmem_limit(s, d, dv, False, 2) == 54 * 2**20
    text = _compile(forward, *args).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(rf"f32\[{b},{h},{s // 128},128\]", text)  # blocked lse
    monkeypatch.setattr(fa, "_fused_bwd_vmem_limit",
                        lambda *a: fa.DEFAULT_SCOPED_VMEM_BYTES)
    jax.clear_caches()
    with pytest.raises(Exception, match="vmem"):
        jax.jit(forward).lower(*args).compile()


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


@pytest.mark.parametrize("cell,slots,blocks_per_slot",
                         [("longdecode", 8, 640), ("chat", 32, 160)])
def test_paged_decode_compiles_at_the_serving_cells_shapes(
        v5e, cell, slots, blocks_per_slot):
    """The S=1 kernel as the benchmark's serving cells call it: InternLM2
    heads (16 over 8 of 128), the 5,121-block pool, each cell's slots and
    table width — the page group its shape rule picks there (32 pages, 4
    MiB of buffers) has to fit what a v5e kernel may scope."""
    h, kv, d = 16, 8, 128
    assert pa._decode_pages_per_step(blocks_per_slot, kv, POOL_BLOCK, d,
                                     2) == 32
    sds = _shapes_on(v5e.devices[0])
    pool = sds((POOL_BLOCKS, kv, POOL_BLOCK, d))
    _compile(pa.paged_decode_attention, sds((slots, 1, h, d)), pool, pool,
             sds((slots, blocks_per_slot), jnp.int32),
             sds((slots,), jnp.int32))


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


def _d4_train_step_lowered(v5e):
    """The benchmark's training program (mistral-7b-v0.3-d4, seq 4096 x 3
    rows) lowered for one chip of the described v5e, and its sizes."""
    import json
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
    return jax.jit(make_train_step(model, opt, 1.0),
                   donate_argnums=(0,)).lower(state, tokens, tokens), d


def test_d4_train_step_keeps_scope_names_and_flash_call_names(v5e):
    """The benchmark's training program compiled for one chip of the
    described v5e: after XLA's fusion
    every training scope of ``obs/trace.py`` ``SCOPES`` is still the
    ``op_name`` of some instruction (the profiler reads device time by
    model part from it), and the flash attention Mosaic calls are still
    named ``attention.N`` — the name the benchmark's accepted kernel
    readers find them by, which a scope opened between the ``attention``
    module and the ``pallas_call`` would change."""
    import re

    lowered, d = _d4_train_step_lowered(v5e)
    hlo = lowered.compile().as_text()
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


# InternLM2-1.8B as the serving cells run it (perfbench/configs), cut for
# time where the pool is not involved: 2 of 24 layers, vocab 1024 of 92544
# (the sampler's sort over the vocabulary is most of a 40 s compile).
POOL_LAYERS, POOL_BLOCKS, POOL_BLOCK = 2, 5121, 16


def _serving_program_hlo(v5e, program, vocab=1024):
    """The engine's own ``program`` body at InternLM2-1.8B widths, compiled
    for one described v5e as ``longdecode``'s server shapes it (8 slots of
    640 table entries, the 5,121-block pool); returns (HLO text, cfg).
    ``vocab=92544`` is the real epilogue (a scratch reading, not a test)."""
    import json
    import sys
    import types
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from perfbench.lib import weights

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
        TreeShape,
    )
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_paged_cache,
    )
    from fault_tolerant_llm_training_tpu.models import Transformer
    from fault_tolerant_llm_training_tpu.models import configs as mc

    config = json.loads((root / "perfbench" / "configs"
                         / "internlm2-1.8b.json").read_text())
    config.update(num_hidden_layers=POOL_LAYERS, vocab_size=vocab)
    d = weights.dims_of(config)
    cfg = mc.TransformerConfig(**weights.preset_kwargs(config)).replace(
        remat=False)
    assert (cfg.kv_heads, cfg.head_dim) == (8, 128)
    slots, per_slot = 8, 640                        # longdecode's server
    sds = _shapes_on(v5e.devices[0])
    on = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    params = on(jax.eval_shape(
        lambda k: weights.make_param_tree(k, d, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(
        cfg, slots, per_slot * POOL_BLOCK, POOL_BLOCK, POOL_BLOCKS)))
    i32, f32 = jnp.int32, jnp.float32
    vec = lambda dt, n=slots: sds((n,), dt)         # noqa: E731
    model = Transformer(cfg)
    # the engine's own program bodies over a stand-in that carries what
    # they read of ``self`` (a whole engine would place real arrays)
    stub = types.SimpleNamespace(model=model, top_k=0, slots=slots, cfg=cfg,
                                 spec_verify_impl="chunk",
                                 _adapter_operand=lambda *a: None)
    if program == "decode":
        fn = lambda *a: InferenceEngine._paged_decode_fn(stub, *a)  # noqa
        args = (sds((slots, per_slot), i32), vec(i32), vec(jnp.bool_),
                vec(f32), vec(f32), vec(i32), vec(i32))
    elif program == "prefill_1x512":
        fn = lambda *a: InferenceEngine._paged_prefill_fn(  # noqa: E731
            stub, model, *a)
        args = (sds((per_slot,), i32), sds((1, 512), i32), sds((), i32),
                sds((), i32), sds((), i32), sds((), f32), sds((), f32),
                sds((), i32))
    elif program == "packed_4x512":
        fn = lambda *a: InferenceEngine._packed_prefill_fn(  # noqa: E731
            stub, model, *a)
        args = (sds((4, per_slot), i32), sds((4, 512), i32), vec(i32, 4),
                vec(i32, 4), vec(i32, 4), vec(jnp.bool_, 4), vec(f32, 4),
                vec(f32, 4), vec(i32, 4))
    else:
        shape = TreeShape((2, 2, 1))
        fn = lambda *a: InferenceEngine._tree_verify_fn(  # noqa: E731
            stub, shape, *a)
        args = (sds((slots, per_slot), i32), sds((slots, shape.size), i32),
                sds((slots, shape.size, cfg.vocab_size), f32), vec(i32),
                vec(jnp.bool_), vec(f32), vec(f32), vec(i32), vec(i32))
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile().as_text(), cfg


@pytest.mark.parametrize("program", ["decode", "prefill_1x512",
                                     "packed_4x512", "tree_verify"])
def test_serving_program_writes_the_pool_in_its_own_layout(v5e, program):
    """No serving program that writes the paged KV pool may hold a ``copy``
    of the pool's shape, and every pool it returns aliases its input.

    The pool is stored ``(N, K, bs, D)``, N outermost. A write indexed on
    dims 0 and 2 at once (``pool.at[blk, :, off, :]``) makes the TPU scatter
    take the operand as ``(N, bs, K, D)``: XLA then transposes each layer's
    whole K and V pool into that layout and back — 4 copies of 168 MB a
    layer, 96 a decode round at 24 layers, 53 ms of a 118 ms round on the
    chip (PERF.md section 6, PR 27). Donation alone does not show it: the
    aliases were there all along. ``write_paged_kv`` and
    ``remap_paged_path`` therefore gather and scatter whole blocks."""
    import re

    hlo, cfg = _serving_program_hlo(v5e, program)
    pool = re.escape(f"[{POOL_BLOCKS},{cfg.kv_heads},{POOL_BLOCK},"
                     f"{cfg.head_dim}]")
    copies = re.findall(rf"= \w+{pool}\S* copy\(", hlo)
    assert not copies, f"{len(copies)} pool-sized copies in {program}"
    # the pool is still written there, in place: K and V of every layer,
    # and lengths, come back in the buffers they were donated in
    assert len(re.findall(rf"= \w+{pool}\S* fusion\(", hlo)) >= (
        2 * POOL_LAYERS)
    aliased = re.findall(r"\(\d+, \{[^}]*\}, (?:may|must)-alias\)", hlo)
    assert len(aliased) == 2 * POOL_LAYERS + 1, aliased


def test_decode_program_reads_the_pool_in_place(v5e, monkeypatch):
    """On a TPU the default ``paged_kernel="auto"`` resolves the decode
    program's S=1 read to the in-place kernel: its HLO holds no gather,
    copy or transpose of a slot-table's worth of the pool — the ``slots x
    max_len`` view ``[8,640,8,16,128]`` the gather assembled and the
    ``[5120,8,16,128]`` it was transposed through, 48 + 48 a round and
    ~50 of every ~61 ms of ``kv_read`` (PERF.md section 6, PR 30) — and
    one Mosaic call a layer, under an ``op_name`` that has ``kv_read`` in
    its path: that path is what puts the call's device time in the
    ``kv_read`` bucket of the benchmark's scope reader."""
    import re

    # the described chip is not the default backend here: say it is, so
    # that the rule reads what it would read on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, cfg = _serving_program_hlo(v5e, "decode")
    assert cfg.paged_kernel == "auto"
    views = (r"\[8,640,8,16,128\]", r"\[5120,8,16,128\]")
    moved = [ln.strip()[:120] for ln in hlo.splitlines()
             if re.search(rf"= \w+(?:{'|'.join(views)})\S* "
                          rf"(?:gather|copy|transpose|fusion)\(", ln)]
    assert not moved, moved
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == POOL_LAYERS, len(calls)
    assert all(re.search(r'op_name="[^"]*kv_read[^"]*"', ln)
               for ln in calls), calls


def _hlo_computations(hlo):
    """{name: [instruction lines]} of an HLO module's text, and the entry
    computation's name."""
    import re

    comps, entry, cur = {}, None, None
    for ln in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", ln)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(ln)
    return comps, entry


def _reached(comps, root, through_conditionals):
    """Computations ``root`` runs: those it calls (fusions, loops' bodies,
    reducers), and a ``conditional``'s branches only if asked."""
    import re

    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for ln in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", ln)
            if through_conditionals:
                todo += re.findall(
                    r"(?:true|false)_computation=%?([\w.\-]+)", ln)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        ln):
                    todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


@pytest.mark.parametrize("program", ["decode", "prefill_1x512"])
def test_sampling_epilogue_branches_once_outside_the_vmap(v5e, program):
    """What a greedy round or a greedy prefill runs holds no sort over the
    vocabulary and draws no noise over it: the epilogue's tiers
    (``inference/sampler.py`` ``epilogue_tier``) are the branches of a
    ``conditional`` in the entry computation, and the ``sort`` and the
    Gumbel draws stand only in computations that ``conditional`` calls.

    A branch taken under the ``vmap`` with a batched predicate lowers to a
    ``select`` that runs every side — ``sort f32[32,92544]`` was 3.7 ms of
    ``chat``'s 13.5 ms decode round with all 32 slots greedy (PERF.md
    section 6, PR 32) — and this is where such an edit fails."""
    import re

    hlo, _ = _serving_program_hlo(v5e, program)
    comps, entry = _hlo_computations(hlo)
    switch = [re.search(r"branch_computations=\{([^}]*)\}", ln)
              for ln in comps[entry] if " conditional(" in ln]
    tiers = [[b.strip().lstrip("%") for b in m.group(1).split(",")]
             for m in switch if m]
    assert [len(t) for t in tiers] == [3], (
        "the epilogue's three-way switch is not in the entry computation")
    always = _reached(comps, entry, through_conditionals=False)
    branches = _reached(comps, entry, through_conditionals=True) - always

    def holding(names, pattern):
        return sorted(n for n in names
                      if any(re.search(pattern, ln) for ln in comps[n]))

    # the Gumbel noise a categorical draws over the vocabulary (a key's
    # derivation, ``slot_key``: two scalar hashes, may stand anywhere)
    draws = r"rng-bit-generator|random_bits|_gumbel|_uniform"
    assert not holding(always, r" sort\(")
    assert not holding(always, draws)
    assert holding(branches, r" sort\(")
    assert holding(branches, draws)
    # and the first branch, the greedy tier, holds neither
    greedy = _reached(comps, tiers[0][0], through_conditionals=True)
    assert not holding(greedy, r" sort\(") and not holding(greedy, draws)


# ------------------------------------ the accepted cells' programs, unchanged
# sha256 of the LOWERED (StableHLO, no source locations) paged decode and
# 1 x 64 prefill programs of the Llama class, recorded at commit e7a1277
# (PR 32) by this same function: ``tiny`` as the CPU lowers it, InternLM2-1.8B
# widths (2 layers, vocab 1024, longdecode's server shapes) as the described
# v5e does. A PR that brings another model class through the engine (PR 33:
# models/latent_moe.py) must leave these programs as they were; a PR that
# means to change them, or a JAX upgrade, records the constants anew and says
# so.
SERVING_PROGRAMS_AS_RECORDED = {
    "tiny": {
        "decode": "a83e0353bfae5787df17214d3481bdb7078397c4be22a0acd2333b4c9c"
                  "75429f",
        "prefill": "0d30670fa1b69fff62daf6134a6726b5281d9ad86c5cbc6660d41613c"
                   "e3f3d3b"},
    "internlm2": {
        "decode": "c277a36b8ea8c660e5c32c7aef55ed2dd83604f21ae23c867988f512fc"
                  "a04b55",
        "prefill": "0cb5064611870dadd96f990fc1ee28fea4928e3f11ad21a800b0824ad"
                   "eb952da"},
    # the second class (test_dots3_serving_programs_are_as_recorded), at
    # commit edfc238 (PR 33)
    "dots3": {
        "decode": "fb17c2630eadcdcbd115082885dd0d491904c9d2bc950971da74a4deafe5"
                  "6035",
        "prefill": "ce1fc9015c94c391a83007118052ee5030d0c103ca3a3cf1e6a7ad76353"
                   "0aecd"}}
# and the training cell's step (mistral-7b-v0.3-d4, seq 4096 x 3 rows), at
# commit edfc238 (PR 33), by test_d4_train_step_is_as_recorded. All five
# held at 4a708ba (PR 34) and hold with PR 36's training path for the
# latent / expert class beside them (same constants). The training step is
# recorded anew since its attention backward became one fused flash call a
# layer (dq, dk and dv, under the VMEM limit the call asks for), where it
# was the split dq and dk/dv calls; and anew since its 4,096-row forward is
# the resident kernel (the VMEM rule picks the family for the forward too),
# asking the family's limit and writing the blocked (3, 32, 32, 128) lse
# plane, where it streamed K and V and wrote the packed (3, 32, 1, 4096)
# row. The three serving programs hold as recorded.
TRAIN_PROGRAM_AS_RECORDED = (
    "918e61de64837f1bd1f54c434be0b937ab565467ae564987349a81ead6953398")


def _lowered_serving_hashes(cfg, params, slots, per_slot, bs, blocks, sds):
    import hashlib
    import types

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_paged_cache,
    )
    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cache = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: init_paged_cache(cfg, slots, per_slot * bs, bs, blocks)))
    model = Transformer(cfg)
    stub = types.SimpleNamespace(model=model, top_k=0, slots=slots, cfg=cfg,
                                 spec_verify_impl="chunk",
                                 _adapter_operand=lambda *a: None)
    i32, f32 = jnp.int32, jnp.float32
    vec = lambda dt: sds((slots,), dt)                      # noqa: E731
    texts = {
        "decode": jax.jit(
            lambda *a: InferenceEngine._paged_decode_fn(stub, *a),
            donate_argnums=(1,)).lower(
            params, cache, sds((slots, per_slot), i32), vec(i32),
            vec(jnp.bool_), vec(f32), vec(f32), vec(i32),
            vec(i32)).as_text(),
        "prefill": jax.jit(
            lambda *a: InferenceEngine._paged_prefill_fn(stub, model, *a),
            donate_argnums=(1,)).lower(
            params, cache, sds((per_slot,), i32), sds((1, 4 * bs), i32),
            sds((), i32), sds((), i32), sds((), i32), sds((), f32),
            sds((), f32), sds((), i32)).as_text()}
    return {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in texts.items()}


def test_tiny_serving_programs_are_as_recorded():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from _tiny import tiny_cfg

    from fault_tolerant_llm_training_tpu.models.llama import Transformer

    cfg = tiny_cfg().replace(remat=False)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt)         # noqa: E731
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: Transformer(cfg).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
                "params"]))
    assert _lowered_serving_hashes(cfg, params, 2, 4, 8, 9, sds) == (
        SERVING_PROGRAMS_AS_RECORDED["tiny"])


def test_internlm2_serving_programs_are_as_recorded(v5e):
    import json
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from perfbench.lib import weights

    from fault_tolerant_llm_training_tpu.models import configs as mc

    config = json.loads((root / "perfbench" / "configs"
                         / "internlm2-1.8b.json").read_text())
    config.update(num_hidden_layers=POOL_LAYERS, vocab_size=1024)
    d = weights.dims_of(config)
    cfg = mc.TransformerConfig(**weights.preset_kwargs(config)).replace(
        remat=False)
    on = _shapes_on(v5e.devices[0])
    sds = lambda s, dt: on(s, dt)                           # noqa: E731
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda k: weights.make_param_tree(k, d, jnp.bfloat16),
            jax.random.PRNGKey(0)))
    assert _lowered_serving_hashes(cfg, params, 8, 640, POOL_BLOCK,
                                   POOL_BLOCKS, sds) == (
        SERVING_PROGRAMS_AS_RECORDED["internlm2"])


# one full and one sliding layer of the dots3 configuration, 2 held experts
_DOTS3_TWO_LAYERS = dict(
    num_hidden_layers=2, vocab_size=1024, n_routed_experts=2,
    first_k_dense_replace=0,
    layer_types=["full_attention", "sliding_attention"])


def _dots3_programs(v5e, slots, per_slot, blocks, buckets, **cut):
    """The decode program and the prefill chunk programs (one a bucket) of
    the latent / indexer / window / expert class as the ``dots3`` cell's
    configuration file states it, ``cut`` overriding keys of it, LOWERED
    for one described v5e: ``(decode, {bucket: prefill}, the cache's
    shapes)``."""
    import json
    import sys
    import types
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from perfbench.lib import weights

    from fault_tolerant_llm_training_tpu.inference.engine import (
        InferenceEngine,
    )
    from fault_tolerant_llm_training_tpu.inference.kv_cache import (
        init_latent_cache,
    )

    config = json.loads((root / "perfbench" / "configs"
                         / "dots3-note-prev-d5-ep8.json").read_text())
    config.update(cut)
    d = weights.dims_of(config)
    fam = weights.family_of(d)
    cfg = fam.preset(config)
    sds = _shapes_on(v5e.devices[0])
    on = lambda tree: jax.tree_util.tree_map(               # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    params = on(jax.eval_shape(
        lambda k: weights.make_param_tree(k, d, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(lambda: init_latent_cache(
        cfg, slots, POOL_BLOCK, blocks)))
    stub = types.SimpleNamespace(model=fam.model_class()(cfg), top_k=0,
                                 slots=slots, cfg=cfg)
    i32, f32 = jnp.int32, jnp.float32
    vec = lambda dt: sds((slots,), dt)                      # noqa: E731
    one = lambda dt: sds((), dt)                            # noqa: E731
    decode = jax.jit(
        lambda *a: InferenceEngine._latent_decode_fn(stub, *a),
        donate_argnums=(1,)).lower(
        params, cache, sds((slots, per_slot), i32), vec(i32),
        vec(jnp.bool_), vec(f32), vec(f32), vec(i32), vec(i32))
    prefill = {
        b: jax.jit(
            lambda *a: InferenceEngine._latent_prefill_fn(stub, *a),
            donate_argnums=(1,)).lower(
            params, cache, sds((per_slot,), i32), sds((1, b), i32),
            one(i32), one(i32), one(i32), one(i32), one(i32), one(f32),
            one(f32), one(i32))
        for b in buckets}
    return decode, prefill, cache


def test_dots3_serving_programs_are_as_recorded(v5e):
    """PR 34 changed which chunk programs the host calls and with what
    positions, not the programs: at the widths of the in-place test below
    (one full and one sliding layer, 2 held experts, vocab 1024, 8 slots)
    the lowered decode and 1 x 64 prefill programs are the parent's."""
    import hashlib

    decode, prefill, _ = _dots3_programs(v5e, 8, 640, POOL_BLOCKS, (64,),
                                         **_DOTS3_TWO_LAYERS)
    got = {"decode": decode.as_text(), "prefill": prefill[64].as_text()}
    assert {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in got.items()} == SERVING_PROGRAMS_AS_RECORDED["dots3"]


def test_d4_train_step_is_as_recorded(v5e):
    """The training cell's step lowers to the parent's program. A Mosaic
    call's serialized body holds the source locations of its kernel, the
    checkout's path among them, so the bodies are cut out of the hashed
    text: what they hold is ``ops/flash_attention.py``, which is held
    where it runs (the tests of the kernel, the cell's ``correct``)."""
    import hashlib
    import re

    lowered, _ = _d4_train_step_lowered(v5e)
    text, cut = re.subn(r'(\\22body\\22: \\22)[^\\]*(\\22)',
                        r"\1\2", lowered.as_text())
    assert cut >= 8                 # forward + backward of each of 4 layers
    assert hashlib.sha256(text.encode()).hexdigest() == (
        TRAIN_PROGRAM_AS_RECORDED)


def test_latent_chunk_loop_covers_the_cells_remainders_by_its_own_costs(v5e):
    """The two chunk programs of ``dots3-d5-ep8.sessions16k`` at the cell's
    own sizes (5 layers, 32 held experts, 64 slots of 19,456 positions, a
    pool of 77,825 blocks; ladder ``[64, 2048]``), compiled for one
    described v5e, and the engine's cover rule over the costs their
    compiler counts: a turn's 64 new rows are one 64-row call, a later
    turn's 80 are two, and what a window rebuild leaves (1,600-1,872 rows,
    or 336 with the windows held and the output not cached) stays one
    2,048-row call — never 25 to 30 small ones. A compile, not a chip run:
    the counts are the compiler's, no time comes out of this."""
    from fault_tolerant_llm_training_tpu.inference.engine import (
        cover_plan,
        program_cost,
    )

    slots, max_len, buckets = 64, 19456, (64, 2048)
    _, prefill, _ = _dots3_programs(v5e, slots, max_len // POOL_BLOCK,
                                    1245184 // POOL_BLOCK + 1, buckets)
    cost = {b: program_cost(p.compile()) for b, p in prefill.items()}
    assert all(cost.values()), cost
    # the small program is the cheaper one by both counts, and not by the
    # ratio of the rows: it reads the same weights
    for small, large in zip(cost[64], cost[2048]):
        assert large / 32 < small < large / 2, cost
    assert cover_plan(64, buckets, cost) == [64]
    assert cover_plan(80, buckets, cost) == [64, 64]
    for rows in (336, 1600, 1616, 1872, 2048):
        assert cover_plan(rows, buckets, cost) == [2048], rows
    assert cover_plan(2048 + 80, buckets, cost) == [2048, 64, 64]


def test_latent_decode_program_accesses_its_pools_in_place(v5e):
    """The decode program of the latent / indexer / window / expert class
    at the published widths (one full and one sliding layer, 2 held
    experts, vocab 1024, 8 slots over longdecode's 5,121 blocks), compiled
    for one described v5e: every pool and ring it returns aliases its input
    and the program holds no ``copy`` of a pool's shape. A latent pool
    stored by block, or with rows of 288 words, cost a pool-sized relayout
    copy for every row access (ops/latent_attention.py)."""
    import re

    slots = 8
    decode, _, cache = _dots3_programs(v5e, slots, 640, POOL_BLOCKS, (),
                                       **_DOTS3_TWO_LAYERS)
    compiled = decode.compile()
    hlo = compiled.as_text()
    rows = POOL_BLOCKS * POOL_BLOCK
    # (the rope-key pool, 32 words a row and an eighth of the latent pool's
    # bytes, is the compiler's to lay out: at this pool size it copies it,
    # at the cell's 1,245,200 rows it does not)
    pools = [rf"u32\[{rows},256\]",
             rf"bf16\[{POOL_BLOCKS},1,{POOL_BLOCK},128\]",
             rf"bf16\[{slots},528,1088\]"]
    for shape in pools:
        assert re.search(shape, hlo), shape      # the pool is in the program
        assert not re.search(rf"= {shape}\S* copy\(", hlo), (
            f"the decode program copies a whole {shape}")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(cache)
        if a.ndim > 1)          # every pool and ring is updated in place


def test_masked_flash_kernel_compiles_at_the_cells_widths(v5e):
    """The full layers' chunk read at the published widths (128 heads of
    128 + 64 / 128, a 2,048-query chunk over a 19,456-row table: the
    cell's own shapes), compiled by Mosaic for one described v5e."""
    sds = _shapes_on(v5e.devices[0])
    h, s, t = 128, 2048, 19456
    _compile(
        lambda qn, qr, kn, kr, v, keep, n: la.masked_flash_attention(
            qn, qr, kn, kr, v, keep, n, 0.0722),
        sds((h, s, 128)), sds((h, s, 64)), sds((h, t, 128)), sds((t, 64)),
        sds((h, t, 128)), sds((s, t), jnp.int8), sds((), jnp.int32))


def test_kanana_train_step_compiles_at_the_cells_widths(v5e):
    """The ``kanana2-d6-ep8.moe8k`` cell's training step as the trainer
    builds it (6 layers at the published widths, 16 held experts, vocab
    16,032, 4 rows of 8,192 tokens, ``--remat``), compiled for one
    described v5e: it fits the chip's 16 GiB with room; the attention is
    the Mosaic kernels of ``flash_attention_bhsd`` named ``attention.N``
    (what the accepted readers' ``^pallas:attention`` finds), three a
    layer — two forwards under remat and the one fused backward — and
    the held experts' grouped matmuls are the compiler's ragged-dot calls;
    the forwards are the resident kernel, whose lse is the blocked
    ``(4, 32, 64, 128)`` plane; the compiler's peak is not above the
    14,625,897,472 B it counted when the forwards streamed (the same count
    with them resident); and the
    only float32 array as wide as the vocabulary that the program holds
    outside a fusion is the loss head's logits."""
    import json
    import re
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from perfbench.lib import weights

    from fault_tolerant_llm_training_tpu.training.state import TrainState
    from fault_tolerant_llm_training_tpu.training.step import (
        make_optimizer,
        make_train_step,
    )

    bench = root / "perfbench"
    config = json.loads(
        (bench / "configs" / "kanana-2-30b-a3b-d6-ep8.json").read_text())
    mix = json.loads((bench / "traffic" / "moe8k.json").read_text())
    seq, rows = mix["sequence_length"], mix["rows_per_chip"]
    assert "--remat" in mix["mesh_args"]
    d = weights.dims_of(config)
    fam = weights.family_of(d)
    cfg = fam.preset(config, seq_len=seq, attention_impl="pallas",
                     remat=True)
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
    compiled = jax.jit(make_train_step(fam.model_class()(cfg), opt, 1.0),
                       donate_argnums=(0,)).lower(
        state, tokens, tokens).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15 * 2**30
    assert mem.peak_memory_in_bytes <= 14_625_897_472
    hlo = compiled.as_text()
    assert f"f32[{rows},{d['heads']},{seq // 128},128]" in hlo
    assert f"f32[{rows},{d['heads']},1,{seq}]" not in hlo
    calls = [re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
             for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    flash = [c for c in calls if re.fullmatch(r"attention(\.\d+)?", c)]
    # two forwards a layer under remat, one backward returning dq, dk, dv
    assert len(flash) == 3 * d["n_layers"]
    assert all(c.startswith("ragged-dot") for c in calls
               if c not in flash), calls
    assert any(c.startswith("ragged-dot") for c in calls)
    wide = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", hlo):
        head = comp.split(" ", 2)[:2]
        if comp.startswith("%fused") or "fused" in head[0] or (
                "wrapped" in head[0]):
            continue
        for line in comp.splitlines()[1:]:
            if re.search(rf"= \(?[^=]*f32\[[0-9,]*{d['vocab']}\]", line):
                wide.append(line)
    assert wide and all("loss_head" in line for line in wide), wide[:4]
