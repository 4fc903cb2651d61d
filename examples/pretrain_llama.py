"""Pretrain a Llama-family model end to end.

DeepSpeedExamples analog (megatron/llama pretraining): config-driven engine,
ZeRO-3 + bf16 + remat + chunked-CE loss, checkpoint/resume, monitoring.
Runs anywhere: `python examples/pretrain_llama.py --steps 20` uses a tiny
model on whatever devices exist (8 virtual CPU devices under the test env;
the real thing on a TPU slice). Scale by swapping the config for LLAMA3_8B
and adding a "mesh" block.
"""

import argparse
import dataclasses
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
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--ckpt_dir", default=None)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--packed", action="store_true",
                   help="pack variable-length synthetic documents into the "
                        "batch (segment_ids masked in-kernel, per-document "
                        "positions, target-gated loss)")
    args = p.parse_args()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        TINY_LLAMA, LlamaForCausalLM, random_tokens)

    n_dev = len(jax.devices())
    cfg = dataclasses.replace(TINY_LLAMA, max_seq_len=args.seq_len,
                              remat=True, loss_chunk_size=args.seq_len)
    config = {
        "train_batch_size": 2 * n_dev * 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3,
                                                  "weight_decay": 0.1}},
        "scheduler": {"type": "WarmupDecayLR",
                      "params": {"warmup_num_steps": 5,
                                 "total_num_steps": args.steps}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10,
        "csv_monitor": {"enabled": bool(args.ckpt_dir),
                        "output_path": args.ckpt_dir or ""},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config=config,
        example_batch=random_tokens(2, args.seq_len,
                                    vocab_size=cfg.vocab_size))
    if args.resume and args.ckpt_dir:
        engine.load_checkpoint(args.ckpt_dir)

    packed_batches = None
    if args.packed:
        import numpy as np
        from deepspeed_tpu.data_pipeline import (pack_sequences,
                                                 packing_efficiency)
        rng = np.random.default_rng(0)
        docs = [rng.integers(1, cfg.vocab_size,
                             size=rng.integers(args.seq_len // 6,
                                               args.seq_len)).astype(np.int32)
                for _ in range(24 * n_dev)]
        packed_batches = pack_sequences(docs, batch_size=2 * n_dev,
                                        seq_len=args.seq_len)
        print(f"packed {len(docs)} docs into {len(packed_batches)} batches "
              f"({packing_efficiency(packed_batches):.0%} slot utilization)")

    for step in range(args.steps):
        if packed_batches is not None:
            import numpy as np
            micro = [packed_batches[(2 * step + g) % len(packed_batches)]
                     for g in range(2)]
            batch = {k: np.stack([m[k] for m in micro]) for k in micro[0]}
        else:
            batch = random_tokens(2 * n_dev, args.seq_len,
                                  vocab_size=cfg.vocab_size, seed=step % 4,
                                  gas=2)
        loss = engine.train_batch(batch=batch, stacked=True)
        if step % 5 == 0 or step == args.steps - 1:
            lr = engine.get_lr()
            lr = lr[0] if isinstance(lr, (list, tuple)) else lr
            print(f"step {step}: loss {float(loss):.4f} lr {lr:.2e}")
    if args.ckpt_dir:
        engine.save_checkpoint(args.ckpt_dir)
        print(f"checkpoint saved to {args.ckpt_dir}")
    return float(loss)


if __name__ == "__main__":
    main()
