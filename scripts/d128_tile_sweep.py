"""D=128 tile mini-sweep (VERDICT r4 next-step #7).

Every tile constant in ops/flash_attention.py was tuned at D=64 (the
gpt2-125m bench head width). The flagship llama3-8b preset runs D=128 —
this sweep times the resident family's fwd+bwd at a llama-shaped GQA
config (h:kv = 4:1, D=128, S=2048 — the resident forward's longest S,
with the fused backward engaged as the flagship would) across tile
candidates, on the chip, to decide whether the D=64 constants transfer
or need a D=128 dispatch branch.

A second section covers the SERVING kernels (ops/paged_attention.py).
The S>1 chunk kernel's head-tile knob ``CHUNK_HEAD_TILE``: it grids over
kv heads one at a time by default — at D=128 with 4 kv heads a wider
per-dispatch head tile may amortize the grid's scalar-prefetch overhead
(timed at the S=6 tree-verify/chunk window, knob restored after; 1 stays
the recorded default unless the chip says otherwise). The S=1 decode
kernel has no knob: ``_decode_rule_sweep`` times a 24-layer round of it
against the gather at the benchmark's two serving shapes, at the page
group its shape rule picks and at the neighbours, so that the rule can be
checked against the chip.

Run on the TPU:  python scripts/d128_tile_sweep.py [--paged-only]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    if "--paged-only" in sys.argv[1:]:
        return _paged_sweep()
    if "--decode-only" in sys.argv[1:]:
        return _decode_rule_sweep()
    import jax
    import jax.numpy as jnp

    import fault_tolerant_llm_training_tpu.ops.flash_attention as fa

    b, s, h, kv, d = 4, 2048, 8, 2, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True).astype(
            jnp.float32) ** 2)

    defaults = dict(FWD_BLOCK_Q=fa.FWD_BLOCK_Q, FWD_BLOCK_K=fa.FWD_BLOCK_K,
                    DQ_BLOCK_Q=fa.DQ_BLOCK_Q, DQ_BLOCK_K=fa.DQ_BLOCK_K,
                    DKV_BLOCK_Q=fa.DKV_BLOCK_Q, DKV_BLOCK_K=fa.DKV_BLOCK_K)

    combos = [
        ("default D64 tiles (512,512|512,512|512,1024)", {}),
        ("fwd 256x512", dict(FWD_BLOCK_Q=256, FWD_BLOCK_K=512)),
        ("fwd 512x256", dict(FWD_BLOCK_Q=512, FWD_BLOCK_K=256)),
        ("fwd 256x256", dict(FWD_BLOCK_Q=256, FWD_BLOCK_K=256)),
        ("fwd 1024x512", dict(FWD_BLOCK_Q=1024, FWD_BLOCK_K=512)),
        ("dq 256x512", dict(DQ_BLOCK_Q=256, DQ_BLOCK_K=512)),
        ("dq 512x256", dict(DQ_BLOCK_Q=512, DQ_BLOCK_K=256)),
        ("dkv 512x512", dict(DKV_BLOCK_Q=512, DKV_BLOCK_K=512)),
        ("dkv 1024x512", dict(DKV_BLOCK_Q=1024, DKV_BLOCK_K=512)),
        ("dkv 256x1024", dict(DKV_BLOCK_Q=256, DKV_BLOCK_K=1024)),
    ]

    results = []
    for tag, over in combos:
        for name, val in {**defaults, **over}.items():
            setattr(fa, name, val)
        try:
            g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            best = _timed(g, q, k, v, reps=2)
            results.append((best, tag))
            print(f"{tag:48s} {best * 1000:8.2f} ms", flush=True)
        except Exception as e:
            print(f"{tag:48s} FAILED: {str(e)[:120]}", flush=True)
    for name, val in defaults.items():
        setattr(fa, name, val)
    results.sort()
    print(f"\nbest: {results[0][1]} ({results[0][0] * 1000:.2f} ms); "
          f"default at {[r for r in results if 'default' in r[1]][0][0] * 1000:.2f} ms")

    _paged_sweep()


def _timed(fn, *args, reps=3, inner=20):
    """Best mean seconds a call of jitted ``fn`` over ``reps`` runs."""
    from fault_tolerant_llm_training_tpu.utils.sync import hard_sync

    hard_sync(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        hard_sync(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _paged_sweep():
    """Serving kernels at D=128: the chunk kernel's CHUNK_HEAD_TILE, and
    the S=1 decode kernel's shape rule at the benchmark cells' shapes."""
    import jax
    import jax.numpy as jnp

    import fault_tolerant_llm_training_tpu.ops.paged_attention as pa

    slots, kv, h, bs, nb, d, s_q = 8, 4, 8, 16, 16, 128, 6
    rng = np.random.default_rng(5)
    n_pool = slots * nb + 1
    pool_k = jnp.asarray(rng.standard_normal((n_pool, kv, bs, d)),
                         jnp.bfloat16)
    pool_v = jnp.asarray(rng.standard_normal((n_pool, kv, bs, d)),
                         jnp.bfloat16)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, slots * nb + 1)).reshape(slots, nb)
        .astype(np.int32))
    offsets = jnp.asarray(
        rng.integers(bs, nb * bs - s_q, size=slots).astype(np.int32))
    qs = jnp.asarray(rng.standard_normal((slots, s_q, h, d)), jnp.bfloat16)

    print(f"\npaged chunk head-tile sweep (slots={slots} kv={kv} h={h} "
          f"d={d} S={s_q})")
    default = pa.CHUNK_HEAD_TILE
    for tile in (1, 2, 4):
        pa.CHUNK_HEAD_TILE = tile
        try:
            fn = jax.jit(pa.paged_chunk_attention)  # fresh: knob baked in
            best = _timed(fn, qs, pool_k, pool_v, tables, offsets, inner=50)
            print(f"  CHUNK_HEAD_TILE={tile}   {best * 1e6:9.1f} us",
                  flush=True)
        except Exception as e:
            print(f"  CHUNK_HEAD_TILE={tile}   FAILED: {str(e)[:100]}",
                  flush=True)
    pa.CHUNK_HEAD_TILE = default
    _decode_rule_sweep()


def _decode_rule_sweep(layers: int = 6):
    """The S=1 kernel against the gather it replaced, ``layers`` calls on
    end (6 of the model's 24: a round is 4 x what this prints) at the
    serving cells' shapes (InternLM2-1.8B: 16 heads over 8 kv
    heads of 128, pool 5,121 blocks of 16), and whether the span that
    ``_decode_pages_per_step`` sizes its page groups from is the one the
    chip prefers. The rule has no knob: the sweep patches the module's
    private span, and restores it."""
    import jax
    import jax.numpy as jnp

    import fault_tolerant_llm_training_tpu.ops.paged_attention as pa
    from fault_tolerant_llm_training_tpu.ops.attention import (
        paged_cached_attention,
    )

    h, kv, bs, d, n_pool = 16, 8, 16, 128, 5121
    rng = np.random.default_rng(7)
    pools = [jax.random.normal(key, (n_pool, kv, bs, d), jnp.bfloat16)
             for key in jax.random.split(jax.random.PRNGKey(7), 4)]

    def round_of(attend):
        def run(q, tables, offsets, pools):
            for i in range(layers):   # each layer's q hangs on the last's
                q = attend(q, pools[i % 2], pools[2 + i % 2], tables,
                           offsets)
            return q
        return jax.jit(run)

    # (name, slots, table entries a slot, live tokens a slot)
    cells = (("longdecode", 8, 640, (8192, 9800)),
             ("chat", 32, 160, (0, 300)))
    for name, slots, nb, (lo, hi) in cells:
        tables = jnp.asarray(rng.integers(
            1, n_pool, size=(slots, nb)).astype(np.int32))
        offsets = jnp.asarray(rng.integers(lo, hi, size=slots)
                              .astype(np.int32))
        live = int(jnp.sum(offsets + 1))
        floor_s = layers * live * 2 * kv * d * 2 / 819e9
        q = jnp.asarray(rng.standard_normal((slots, 1, h, d)), jnp.bfloat16)
        print(f"\npaged decode, {name}: slots {slots} x {nb} entries, "
              f"{live} live tokens, {layers} layers "
              f"(live bytes at 819 GB/s: {floor_s * 1e3:.2f} ms)")
        one = jax.jit(paged_cached_attention)
        want = one(q, pools[0], pools[2], tables, offsets)
        t = _timed(one, q, pools[0], pools[2], tables, offsets, inner=10)
        print(f"  gather                 {t * layers * 1e3:8.2f} ms "
              f"({layers} x one call's {t * 1e3:.3f} ms)", flush=True)
        default = pa._DECODE_SPAN
        for span in (128, 256, 512, 1024):
            pa._DECODE_SPAN = span
            try:
                got = jax.jit(lambda *a: pa.paged_decode_attention(*a))(
                    q, pools[0], pools[2], tables, offsets)
                err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                            - want.astype(jnp.float32))))
                fn = round_of(pa.paged_decode_attention)
                t = _timed(fn, q, tables, offsets, pools, inner=5)
                pages = pa._decode_pages_per_step(nb, kv, bs, d, 2)
                mark = "  <- the rule" if span == default else ""
                print(f"  in place, {pages:3d} pages    {t * 1e3:8.2f} ms   "
                      f"max|d| vs gather {err:.4f}{mark}", flush=True)
            except Exception as e:
                print(f"  in place, span {span}   FAILED: {str(e)[:200]}",
                      flush=True)
        pa._DECODE_SPAN = default


if __name__ == "__main__":
    main()
