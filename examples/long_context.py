"""Long-context training: sequence parallelism over the ``sequence`` axis.

DeepSpeed-Ulysses analog (blogs/deepspeed-ulysses): activations shard as
[B, S/sp, ...] so the per-device activation footprint drops by the sequence
degree. Two backends, same config knob (``attention_backend``):

- ``ulysses``: head-scatter all-to-all, local full-sequence attention on a
  head slice (the reference's only long-context mechanism).
- ``ring``: blockwise ring attention over ``ppermute`` — the
  context-parallel strategy the reference lacks; O(S/sp) resident KV.

`DSTPU_FORCE_CPU=1 python examples/long_context.py --backend ring --seq 2048`
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# DSTPU_FORCE_CPU=1: run on 8 virtual CPU devices
if os.environ.get("DSTPU_FORCE_CPU"):
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--backend", default="ring", choices=["ring", "ulysses"])
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--sp", type=int, default=4, help="sequence-parallel degree")
    p.add_argument("--steps", type=int, default=5)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        TINY_LLAMA, LlamaConfig, LlamaForCausalLM, random_tokens)

    import dataclasses

    n_dev = len(jax.devices())
    if n_dev % args.sp:
        raise SystemExit(f"{n_dev} devices not divisible by sp={args.sp}")
    if args.seq % args.sp:
        raise SystemExit(f"seq {args.seq} not divisible by sp={args.sp}")
    dp = n_dev // args.sp
    cfg = dataclasses.replace(TINY_LLAMA, max_seq_len=args.seq,
                              attention_backend=args.backend,
                              dtype=jnp.float32)
    config = {
        "train_batch_size": 2 * dp,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": {"data": dp, "sequence": args.sp},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config=config,
        example_batch=random_tokens(2, args.seq,
                                    vocab_size=cfg.vocab_size))
    batch = random_tokens(2 * dp, args.seq, vocab_size=cfg.vocab_size, seed=0)
    losses = [float(engine.train_batch(batch=batch))
              for _ in range(args.steps)]
    print(f"{args.backend} sp={args.sp} seq={args.seq}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0] and all(np.isfinite(losses))


if __name__ == "__main__":
    main()
