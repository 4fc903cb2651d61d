"""ZeRO-Offload / Infinity: optimizer state on the host tier.

DeepSpeedExamples analog (zero-offload configs): optimizer moments live in
host RAM (or NVMe via "device": "nvme" + nvme_path), stepped by the C++ CPU
optimizer; the device holds compute-dtype shadows. Twin-Flow `ratio` keeps a
slice of the update on-device.

Quick sanity run (tiny model):
    python examples/offload_infinity.py --steps 10

The >HBM demo (reference: blogs/deepspeed-offloadpp/README.md:10 — train a
model whose params + optimizer state exceed device HBM on one chip):
    python examples/offload_infinity.py --model 1b --steps 3 --measure
trains a ~1.3B-param llama whose total training state (bf16 params + fp32
grads + fp32 master/m/v ≈ 18 bytes/param ≈ 22 GiB) exceeds a v5e chip's
16 GB HBM — only the bf16 shadow + grads + activations live on device.
--measure prints one JSON line with step time and the effective
host<->device swap bandwidth (fp32 grads D2H + bf16 shadow H2D =
6 bytes/param/step).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# DSTPU_FORCE_CPU=1: run on 8 virtual CPU devices
if os.environ.get("DSTPU_FORCE_CPU"):
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--device", default="cpu", choices=["cpu", "nvme"])
    p.add_argument("--nvme_path", default="/tmp/dstpu_nvme")
    p.add_argument("--model", default="tiny",
                   choices=["tiny", "1b", "3b", "8b"],
                   help="'1b': ~1.3B params — total training state exceeds "
                        "one v5e chip's 16 GB HBM (the ZeRO-Infinity case). "
                        "'8b': ~8B params — bf16 WEIGHTS alone exceed HBM "
                        "(requires --offload_param)")
    p.add_argument("--seq", type=int, default=0,
                   help="override sequence length (default: 32 tiny/1024 1b)")
    p.add_argument("--micro_batch", type=int, default=0)
    p.add_argument("--offload_param", action="store_true",
                   help="ZeRO-Infinity PARAMETER offload: weights live on "
                        "host and stream through HBM layer-group by "
                        "layer-group (runtime/param_offload.py)")
    p.add_argument("--layers_per_group", type=int, default=2)
    p.add_argument("--measure", action="store_true",
                   help="print one JSON line: step time + swap bandwidth")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (
        TINY_LLAMA, LlamaConfig, LlamaForCausalLM, random_tokens)

    sizes = {
        # hidden, intermediate, layers, heads, kv_heads
        "1b": (2048, 5632, 24, 16, 8),
        "3b": (3072, 8192, 28, 24, 8),
        "8b": (4096, 14336, 32, 32, 8),   # llama-3-8B geometry, 32k vocab
    }
    if args.model in sizes:
        h, inter, layers, heads, kv = sizes[args.model]
        seq = args.seq or 1024
        mb = args.micro_batch or 1
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=h, intermediate_size=inter,
            num_layers=layers, num_heads=heads, num_kv_heads=kv,
            max_seq_len=seq,
            dtype=jnp.bfloat16, attention_backend="flash", remat=True,
            remat_policy="dots_with_no_batch_dims_saveable")
        gas = 2
    else:
        cfg, seq, mb, gas = TINY_LLAMA, args.seq or 32, 8, 1

    offload = {"device": args.device, "ratio": 0.8 if args.model == "tiny"
               else 0.0}  # 1b: fully host-resident moments (>HBM is the point)
    if args.device == "nvme":
        os.makedirs(args.nvme_path, exist_ok=True)
        offload["nvme_path"] = args.nvme_path
    zero = {"stage": 2, "offload_optimizer": offload}
    if args.offload_param:
        zero["offload_param"] = {"device": args.device,
                                 "layers_per_group": args.layers_per_group}
        if args.device == "nvme":
            zero["offload_param"]["nvme_path"] = args.nvme_path
        zero["stage"] = 0
    config = {
        "train_batch_size": mb * gas,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": zero,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), config=config,
        example_batch=random_tokens(1, seq, vocab_size=cfg.vocab_size))
    assert engine._offload is not None
    n_params = sum(int(np.prod(np.shape(x)))
                   for x in jax.tree.leaves(engine.get_params()))
    state_gib = n_params * (2 + 4 + 12) / 2**30  # bf16 + grads + fp32 m/v/mst
    print(f"{n_params / 1e9:.2f}B params; total training state "
          f"{state_gib:.1f} GiB (device keeps ~{n_params * 6 / 2**30:.1f})")

    if args.measure and args.steps < 2:
        p.error("--measure needs --steps >= 2 (step 1 is compile+warmup)")
    # stacked contract: [gas, micro_batch, ...] — micro size is mb, not mb*gas
    fixed = random_tokens(mb, seq, vocab_size=cfg.vocab_size, seed=0,
                          gas=gas if gas > 1 else None)
    losses = [float(engine.train_batch(batch=fixed))]   # compile + step 1
    t0 = time.perf_counter()
    for _ in range(args.steps - 1):
        losses.append(float(engine.train_batch(batch=fixed)))
    dt = (time.perf_counter() - t0) / max(args.steps - 1, 1)
    print(f"offload={args.device}: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    if args.measure:
        phases = {}
        if args.offload_param:
            # measured H2D param stream + fp32 grads D2H (once per microbatch)
            po = engine._param_offload
            swap_bytes = po.bytes_streamed + 4 * n_params * gas
            metric = "zero_infinity_param_offload_step_time"
            phases = po.phase_seconds
        else:
            swap_bytes = 6 * n_params        # fp32 grads D2H + bf16 H2D
            metric = "zero_infinity_step_time"
        print(json.dumps({
            "metric": metric, "value": round(dt, 3),
            "unit": "s/step", "model_params_b": round(n_params / 1e9, 3),
            "state_gib": round(state_gib, 1), "offload_device": args.device,
            "offload_param": bool(args.offload_param),
            "swap_gib_per_step": round(swap_bytes / 2**30, 2),
            "effective_swap_gibps": round(swap_bytes / 2**30 / dt, 2),
            "seq_len": seq, "tokens_per_sec": round(mb * gas * seq / dt, 1),
            **({"phase_seconds": phases} if phases else {}),
        }))


if __name__ == "__main__":
    main()
