"""The one tiny model the serving tests build their engines from.

Every test that decodes through an ``InferenceEngine`` (or the model's
``forward_with_cache``) on this CPU takes its configuration from
:func:`tiny_cfg`; ``tests/test_audit_contract.py`` holds the files to it.
"""

import jax.numpy as jnp

# The dot this XLA:CPU refuses, as ops/attention.py cached_attention states
# it at S = 1 (probabilities x V: bf16 in, float32 out). SHAPES is the
# smallest pair seen to fail here (one slot passes; two do not).
REFUSED_DOT = "bkgqt,bktd->bqkgd"
REFUSED_DOT_SHAPES = ((2, 2, 2, 1, 32), (2, 2, 32, 16))
REFUSED_DOT_ERRORS = ("DotThunk", "Unsupported element type")


def tiny_cfg(dtype="float32", **over):
    """``get_config("tiny", vocab_size=64, seq_len=64, layer_impl="loop")``
    with ``dtype = param_dtype = float32`` — the dtype an engine-level CPU
    test runs in. ``over`` overrides any field; ``dtype`` names both dtypes.

    Why float32: the program's default is bf16, and this XLA:CPU refuses the
    bf16 x bf16 -> float32 einsum of ``cached_attention`` (``REFUSED_DOT``,
    "Unsupported element type for DotThunk::Execute: BF16 x BF16 = F32"), so
    a bf16 engine dies in its first decode round before any assertion.

    Why that is not a weaker test: each serving test compares two paths of
    the SAME program at the SAME dtype (paged against ring, burst against
    sequential, shipped against local, fused sampler against host sampler,
    cached against uncached), so the property holds at any dtype the backend
    executes. What is specific to bf16 — accumulation order at near-ties,
    the kernels' ulps — is held where bf16 runs: the benchmark cells'
    ``logit_gap_max``, ``tests/test_chip_compile.py`` and
    ``scripts/kernel_checks.py --paged-only`` on the chip. A test that
    states a bf16 contract asks for ``dtype="bfloat16"`` and says why.
    """
    from fault_tolerant_llm_training_tpu.models.configs import get_config

    dt = getattr(jnp, dtype)  # "float32" | "bfloat16", jax.numpy's names
    fields = dict(vocab_size=64, seq_len=64, layer_impl="loop", dtype=dt,
                  param_dtype=dt)
    fields.update(over)
    return get_config("tiny", **fields)
