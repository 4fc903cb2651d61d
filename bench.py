"""Benchmark driver — prints ONE JSON line with the headline metric.

Measures sustained Llama training throughput (tokens/sec/chip) under the engine's
fused train step on real TPU hardware, and derives MFU against the chip's peak
bf16 TFLOPS. ``vs_baseline`` compares our MFU to the reference's headline Ulysses
efficiency (>54% of peak on A100, BASELINE.md row 1) — ratio > 1.0 beats it.

Alongside tokens/sec the record now carries ``steps_per_sec`` and the host
``dispatch_gap_ms`` (mean host time per step spent *launching* work — the
number the async step pipeline drives toward zero). ``--sync-every 1,8``
[+ ``--prefetch``] additionally sweeps the async pipeline's drain cadence and
reports per-arm steps/sec + dispatch gap under ``extra.async_sweep``.

Needs a TPU: without one it prints one line and exits nonzero, and every
record names the device it was measured on.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_MFU = 0.54  # BASELINE.md: Ulysses sustained >54% of peak


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="deepspeed_tpu training bench")
    p.add_argument("--sync-every", default="1",
                   help="comma-separated async-pipeline drain cadences to "
                        "sweep (1 = per-step readback; e.g. '1,8')")
    p.add_argument("--prefetch", action="store_true",
                   help="enable double-buffered batch prefetch in the sweep")
    p.add_argument("--sweep-steps", type=int, default=20,
                   help="timed steps per sweep arm")
    return p.parse_args(argv)


def main():
    args = parse_args()
    sweep_values = [int(x) for x in str(args.sync_every).split(",")
                    if x.strip()]
    if args.prefetch and not any(se > 1 for se in sweep_values):
        print("# --prefetch has no effect without a pipelined arm: prefetch "
              "engages only on --sync-every values > 1 (sync_every=1 is the "
              "synchronous baseline) — add e.g. --sync-every 1,8",
              file=sys.stderr)
    sweep_requested = sweep_values != [1] or args.prefetch
    from deepspeed_tpu.accelerator import get_accelerator, require_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    devices = require_tpu("bench")
    n_devices = len(devices)
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM, random_tokens

    seq_len = 2048

    # --- model-size ladder (BASELINE north star is 8B) ----------------------
    # Each preset picks the memory tier a v5e chip (16GB HBM) needs at that
    # size: 697m fits whole; 1b/3b keep fp32 masters+moments on host
    # (ZeRO-Offload, host fused Adam); 8b streams the WEIGHTS themselves
    # (ZeRO-Infinity param offload) since 16.1GB bf16 alone exceeds HBM.
    #          hidden inter  layers heads kv  mb gas  offload
    presets = {
        "697m": (2048,  5632, 12,   16,   8,  2,  4,  "none"),
        "1b":   (2048,  5632, 24,   16,   8,  1,  4,  "optimizer"),
        "3b":   (3072,  8192, 28,   24,   8,  1,  4,  "optimizer"),
        "8b":   (4096, 14336, 32,   32,   8,  1,  2,  "param"),
    }
    preset = os.environ.get("DSTPU_BENCH_MODEL", "697m")
    if preset not in presets:
        raise SystemExit(f"DSTPU_BENCH_MODEL must be one of {sorted(presets)}")
    hidden, inter, layers, heads, kv, mb_default, gas_default, tier = presets[preset]
    metric_name = "llama_train_tokens_per_sec_per_chip" if preset == "697m" \
        else f"llama_{preset}_train_tokens_per_sec_per_chip"
    vocab = 32000
    # micro_batch=4/gas=2 reaches ~0.68 MFU on 697m but sits within ~260MB of
    # the HBM ceiling (flaky OOM depending on allocator state); the preset
    # defaults are the safe configs
    micro_batch = int(os.environ.get("DSTPU_BENCH_MICRO_BATCH", mb_default))
    gas = int(os.environ.get("DSTPU_BENCH_GAS", gas_default))
    batch = micro_batch * gas * n_devices

    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=heads, num_kv_heads=kv,
        max_seq_len=seq_len,
        dtype=jnp.bfloat16,
        attention_backend=os.environ.get("DSTPU_BENCH_ATTN", "flash"),
        # chunked head+CE fusion: the fp32 [B*S,V] logits (1GB at mb=4) never
        # materialize, freeing ~3GB of HLO temps (enables micro_batch 4).
        # OFF by default: never measured on today's code.
        # DSTPU_BENCH_LOSS_UNROLL=1 replaces the scan(checkpoint) chunk loop
        # with an unrolled one (compile-time mitigation to try FIRST on
        # chip); it implies a 2048 chunk size when LOSS_CHUNK is unset so the
        # knob can't silently measure the dense path
        loss_chunk_size=int(os.environ.get("DSTPU_BENCH_LOSS_CHUNK", 0)) or (
            2048 if os.environ.get("DSTPU_BENCH_LOSS_UNROLL") == "1" else None),
        loss_chunk_unroll=os.environ.get("DSTPU_BENCH_LOSS_UNROLL", "0") == "1",
        remat=os.environ.get("DSTPU_BENCH_REMAT", "1") == "1",
        remat_policy=os.environ.get("DSTPU_BENCH_REMAT_POLICY",
                                    "dots_with_no_batch_dims_saveable"))
    zero = {"stage": 0 if n_devices == 1 else 3}
    if tier == "optimizer":
        zero["offload_optimizer"] = {"device": "cpu", "ratio": 0.0}
    elif tier == "param":
        zero["offload_optimizer"] = {"device": "cpu", "ratio": 0.0}
        zero["offload_param"] = {
            "device": "cpu",
            "layers_per_group": int(os.environ.get("DSTPU_BENCH_LPG", 4))}
    config = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
        "bf16": {"enabled": True},
        "data_types": {"grad_accum_dtype": "bf16"},
        "zero_optimization": zero,
        # ZeRO-3 shards over the fsdp axis: the default mesh is pure data
        "mesh": {"data": 1, "fsdp": n_devices},
        "steps_per_print": 1000000,
    }
    model = LlamaForCausalLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config,
        example_batch=random_tokens(2, seq_len, vocab_size=cfg.vocab_size))

    def make_batch(i):
        return random_tokens(micro_batch * n_devices, seq_len,
                             vocab_size=cfg.vocab_size, seed=i, gas=gas)

    from deepspeed_tpu.utils.timer import (TRAIN_BATCH_DISPATCH_TIMER,
                                           TRAIN_BATCH_TIMER)

    # Sync barrier: fetch a device scalar to host.
    loss = engine.train_batch(batch=make_batch(0), stacked=True)  # compile
    float(jax.device_get(loss))

    from deepspeed_tpu.telemetry.compiles import compiles_total

    steps = 10
    engine.timers(TRAIN_BATCH_TIMER).reset()   # drop the compile-step record
    compile_mark = compiles_total()            # warmup done: ledger marked
    t0 = time.time()
    for i in range(1, steps + 1):
        loss = engine.train_batch(batch=make_batch(i), stacked=True)
    float(jax.device_get(loss))
    dt = time.time() - t0
    # the compile-event ledger proof: the warm step compiled the exact
    # shapes, so the timed window must be compile-free — a nonzero count
    # means the headline timed XLA compilation, not training. An explicit
    # check (not assert: python -O must not strip the proof)
    compiles_during_measurement = compiles_total() - compile_mark
    if compiles_during_measurement != 0:
        raise SystemExit(
            f"bench: {compiles_during_measurement} XLA compile(s) inside "
            "the timed window — warm the exact shapes first (see "
            "xla/compile instants in the trace)")
    steps_per_sec = steps / dt
    # host time per step spent *launching* — only meaningful on the fused
    # path (async dispatch leaves completion on-device, so its timer records
    # pure dispatch); offload tiers block on the host optimizer between
    # start/stop, which would mislabel the full step time as dispatch
    dispatch_gap_ms = engine.timers(TRAIN_BATCH_TIMER).mean() * 1000.0 \
        if tier == "none" else None

    # --- async-pipeline sweep (--sync-every 1,8 [--prefetch]) ---------------
    # Same engine, reconfigured per arm at an iterator boundary; each arm
    # feeds train_batch(data_iter=...) so prefetch staging can engage. The
    # iterator runs a real host data pipeline per microbatch — greedy
    # pair-merge tokenization of a synthetic byte corpus (the BPE-shaped
    # python work every LM loader pays) + collate — so the sweep measures
    # the host share the pipeline exists to hide, not a zero-cost replay.
    async_sweep = {}
    if sweep_requested and tier != "none":
        print(f"# async sweep skipped: preset '{preset}' runs a "
              "host-synchronous offload step (nothing to defer)",
              file=sys.stderr)
        sweep_requested = False
    if sweep_requested:
        sweep_steps = max(1, args.sweep_steps)
        corpus = np.random.default_rng(1234).integers(
            0, 256, size=(1 << 16,), dtype=np.uint8)
        merges = {(i, i + 1): 256 + i for i in range(0, 200, 2)}
        bytes_per_sample = seq_len * 8

        def tokenize(buf):
            ids, out, i = list(buf), [], 0
            while i < len(ids):
                if i + 1 < len(ids) and (ids[i], ids[i + 1]) in merges:
                    out.append(merges[(ids[i], ids[i + 1])])
                    i += 2
                else:
                    out.append(ids[i])
                    i += 1
            return np.asarray(out[:seq_len], np.int32) % cfg.vocab_size

        for se in sweep_values:
            # sync_every=1 is the synchronous baseline (per-step readback,
            # inline batch staging — the pre-pipeline loop); --prefetch
            # engages only on the pipelined arms it belongs to
            arm_prefetch = args.prefetch and se > 1
            engine.configure_async_pipeline(
                enabled=True, sync_every=se, prefetch=arm_prefetch)

            def micro_iter(arm=se):
                rng = np.random.default_rng(100_000 + arm)
                while True:
                    starts = rng.integers(
                        0, len(corpus) - bytes_per_sample,
                        size=micro_batch * n_devices)
                    yield {"input_ids": np.stack(
                        [tokenize(bytes(corpus[s:s + bytes_per_sample]))
                         for s in starts])}

            it = micro_iter()
            engine.train_batch(data_iter=it)      # warm the arm
            engine.flush_metrics()                # completion barrier
            engine.timers(TRAIN_BATCH_TIMER).reset()
            engine.timers(TRAIN_BATCH_DISPATCH_TIMER).reset()
            arm_mark = compiles_total()           # arm warmed: ledger marked
            a0 = time.time()
            for _ in range(sweep_steps):
                engine.train_batch(data_iter=it)
            engine.flush_metrics()                # completion barrier
            adt = time.time() - a0
            arm_compiles = compiles_total() - arm_mark
            if arm_compiles != 0:
                raise SystemExit(
                    f"bench: sync_every={se}: {arm_compiles} XLA "
                    "compile(s) inside the timed sweep arm — the arm "
                    "warm step missed a shape")
            async_sweep[f"sync_every={se}"] = {
                "steps_per_sec": round(sweep_steps / adt, 3),
                "dispatch_gap_ms": round(
                    engine.timers(TRAIN_BATCH_DISPATCH_TIMER).mean() * 1000.0, 3),
                "step_ms_reconciled": round(
                    engine.timers(TRAIN_BATCH_TIMER).mean() * 1000.0, 3),
                "prefetch": arm_prefetch,
                "compiles_during_measurement": arm_compiles,
            }
        engine.configure_async_pipeline(enabled=False, prefetch=False)

    tokens_per_sec = steps * batch * seq_len / dt
    tokens_per_sec_chip = tokens_per_sec / n_devices
    n_params = sum(int(np.prod(np.shape(p)))
                   for p in jax.tree.leaves(engine.get_params()))
    flops_per_token = 6 * n_params  # fwd+bwd dense FLOPs (attention excluded → lower bound)
    achieved_tflops = tokens_per_sec_chip * flops_per_token / 1e12
    peak = get_accelerator().peak_tflops("bf16")
    mfu = achieved_tflops / peak

    record = {
        "metric": metric_name,
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / REFERENCE_MFU, 3),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n_devices},
        "extra": {
            "model": preset,
            "memory_tier": tier,
            "n_devices": n_devices,
            "params_millions": round(n_params / 1e6, 1),
            "seq_len": seq_len,
            "model_tflops_per_chip": round(achieved_tflops, 1),
            "mfu": round(mfu, 3),
            "peak_tflops": peak,
            "steps_per_sec": round(steps_per_sec, 3),
            # the compile-ledger proof: 0 == the timed window never paid
            # an XLA compile (asserted above; reported for the record)
            "compiles_during_measurement": compiles_during_measurement,
        },
    }
    if dispatch_gap_ms is not None:
        record["extra"]["dispatch_gap_ms"] = round(dispatch_gap_ms, 3)
    if async_sweep:
        record["extra"]["async_sweep"] = async_sweep
    print(json.dumps(record))


if __name__ == "__main__":
    main()
