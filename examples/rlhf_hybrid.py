"""RLHF train<->generate flip on shared weights (DeepSpeed-Chat analog).

The hybrid engine trains (PPO-style update against a toy reward) and
generates rollouts from the SAME weight set — the generation side runs the
FastGen view with LoRA fused in, no weight copies.

`python examples/rlhf_hybrid.py --iters 3`
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
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--rollout_len", type=int, default=8)
    args = p.parse_args()

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        TINY_LLAMA, LlamaForCausalLM, random_tokens)

    n_dev = len(jax.devices())
    config = {
        "train_batch_size": 2 * n_dev,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "hybrid_engine": {"enabled": True},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(TINY_LLAMA), config=config,
        example_batch=random_tokens(2, 32, vocab_size=TINY_LLAMA.vocab_size))

    rng = np.random.default_rng(0)
    for it in range(args.iters):
        # 1) generate rollouts from current weights (FastGen view)
        prompts = [list(rng.integers(0, TINY_LLAMA.vocab_size, size=6))
                   for _ in range(2)]
        rollouts = engine.generate(prompts, max_new_tokens=args.rollout_len)
        # 2) toy "reward-weighted" SFT step on the rollouts (stands in for PPO)
        seqs = [p + r for p, r in zip(prompts, rollouts)]
        width = max(len(s) for s in seqs)
        ids = np.zeros((2 * n_dev, width), np.int32)
        for row in range(ids.shape[0]):
            s = seqs[row % len(seqs)]
            ids[row, :len(s)] = s
        loss = engine.train_batch(batch={"input_ids": ids})
        print(f"iter {it}: rollout lens {[len(r) for r in rollouts]}, "
              f"train loss {float(loss):.4f}")
    # per-phase flip instrumentation (reference hybrid_engine.py:30
    # _t_start/_t_gen family): train->generate view refresh cost
    print(f"rlhf hybrid flip OK: {engine.flip_count} flips, "
          f"mean flip latency "
          f"{engine.latency_report()['flip_mean_s'] * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
