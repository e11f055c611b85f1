"""A/B one train-step variant at the headline bench shape and print tokens/s.

Same workload and sync as bench.py (mesh, donation, hard_sync; best-of-N passes)
but parameterized so MFU experiments can be compared on the chip:

    python scripts/mfu_sweep.py --set fused_qkv=1
    python scripts/mfu_sweep.py --set rope_impl=xla qkv_layout=bhsd
    python scripts/mfu_sweep.py --ce-block 8192
    python scripts/mfu_sweep.py --force-fused-ce

NOTE: qkv_layout only matters under rope_impl=xla — the default fused
rope supersedes it (models/configs.py).

Prints one line: ``variant=<tag> tokens_per_sec=<N> ms_per_step=<N>``.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_val(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--set", nargs="*", default=[], metavar="KEY=VAL",
                   help="TransformerConfig overrides (int/float/str coerced)")
    p.add_argument("--model", default="gpt2-125m")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--passes", type=int, default=2)
    p.add_argument("--ce-block", type=int, default=None,
                   help="force the vocab-blocked CE with this block size")
    p.add_argument("--force-fused-ce", action="store_true",
                   help="force the fused head+CE dispatch (AUTO_MIN_BYTES=0)")
    p.add_argument("--tiles", default=None,
                   help="flash tile override 'fq,fk,dqq,dqk,dkq,dkk'")
    args = p.parse_args()

    import jax
    from jax.sharding import NamedSharding

    from fault_tolerant_llm_training_tpu.models import get_config
    from fault_tolerant_llm_training_tpu.parallel.mesh import make_mesh, use_mesh
    from fault_tolerant_llm_training_tpu.parallel.sharding import batch_pspec
    from fault_tolerant_llm_training_tpu.utils.harness import (
        synthetic_batch,
        synthetic_state_and_step,
    )
    from fault_tolerant_llm_training_tpu.utils.sync import hard_sync

    overrides = dict(kv.split("=", 1) for kv in args.set)
    overrides = {k: parse_val(v) for k, v in overrides.items()}
    if args.ce_block is not None:
        import functools

        from fault_tolerant_llm_training_tpu.training import step as step_mod
        orig = step_mod.cross_entropy_loss
        step_mod.cross_entropy_loss = functools.partial(
            orig, ce_block=args.ce_block)
    if args.force_fused_ce:
        from fault_tolerant_llm_training_tpu.ops import fused_ce
        fused_ce.AUTO_MIN_BYTES = 0
        from fault_tolerant_llm_training_tpu.ops import cross_entropy
        cross_entropy.AUTO_THRESHOLD = 0

    if args.tiles:
        from fault_tolerant_llm_training_tpu.ops import flash_attention as fa
        (fa.FWD_BLOCK_Q, fa.FWD_BLOCK_K, fa.DQ_BLOCK_Q, fa.DQ_BLOCK_K,
         fa.DKV_BLOCK_Q, fa.DKV_BLOCK_K) = map(int, args.tiles.split(","))

    base = dict(vocab_size=50257, seq_len=2048)
    base.update(overrides)  # --set may override vocab_size/seq_len too
    cfg = get_config(args.model, **base)
    mesh = make_mesh()
    with use_mesh(mesh):
        state, step_fn = synthetic_state_and_step(cfg, mesh=mesh,
                                                  grad_accum=args.grad_accum)
        toks, labels = synthetic_batch(
            cfg, args.batch_size, sharding=NamedSharding(mesh, batch_pspec()))
        for _ in range(5):
            state, metrics = step_fn(state, toks, labels)
        hard_sync(metrics)
        dt = float("inf")
        for _ in range(args.passes):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, metrics = step_fn(state, toks, labels)
            hard_sync(metrics)
            dt = min(dt, time.perf_counter() - t0)
        loss = float(metrics["loss"])
    assert loss == loss, "nonfinite loss"
    tag = ",".join(args.set) or "base"
    if args.ce_block is not None:
        tag += f",ce_block={args.ce_block}"
    if args.force_fused_ce:
        tag += ",fused_ce"
    if args.tiles:
        tag += f",tiles={args.tiles}"
    if args.grad_accum > 1:
        tag += f",accum={args.grad_accum}"
    tps = args.batch_size * cfg.seq_len * args.steps / dt
    print(f"variant={tag} tokens_per_sec={tps:.0f} "
          f"ms_per_step={dt / args.steps * 1000:.2f} loss={loss:.4f}",
          flush=True)


if __name__ == "__main__":
    main()
