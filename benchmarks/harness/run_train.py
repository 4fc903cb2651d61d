"""A training cell: ``deepspeed_tpu.initialize()`` -> ``engine.train_batch()``
on the cell's chips, the way a user trains. Set-up builds the engine, checks
the first step's loss against the plain reference, and warms the step; the
window then runs a fixed number of optimizer steps back to back."""

import gc
import time

import numpy as np

from benchmarks.harness import cells, profile, traffic
from benchmarks.harness.observations import Observations


def ds_config(train: dict, rows_per_step: int, chips: int) -> dict:
    micro = int(train["micro_batch_per_chip"])
    return {
        "train_batch_size": rows_per_step,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": rows_per_step // (micro * chips),
        "optimizer": train["optimizer"],
        "bf16": {"enabled": True},
        "data_types": {"grad_accum_dtype": train["grad_accum_dtype"]},
        "zero_optimization": {"stage": int(train["zero_stage"])},
        "steps_per_print": 10 ** 9,
    }


def run(cell: cells.Cell, bench: dict, devices: list, seed: int,
        seconds: float, trace: bool, say) -> Observations:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import create_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.telemetry.compiles import compiles_total

    hf, mix = cell.config, cell.traffic
    chips = len(devices)
    family = cells.load_module(cell.root, bench, "families", hf["model_type"])
    reference = cells.load_module(cell.root, bench, "reference",
                                  hf["model_type"])
    cfg, model = family.build(hf, "train", hf["train"])
    batches = [{"input_ids": b} for b in traffic.train_batches(
        mix, seed, int(hf["vocab_size"]), chips)]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        mesh=create_mesh(MeshConfig(data=1, fsdp=chips), devices=devices),
        config=ds_config(hf["train"], int(mix["rows_per_step"]), chips),
        example_batch={"input_ids": batches[0]["input_ids"][0]})
    tokens_per_step = int(mix["rows_per_step"]) * int(mix["seq_len"])
    say(f"engine built: {chips} chip(s), ZeRO-{hf['train']['zero_stage']}, "
        f"{tokens_per_step} tokens a step")

    def step(i):
        return engine.train_batch(batch=batches[i % len(batches)],
                                  stacked=True)

    # correctness, outside the window: the first step's loss against the
    # plain float32 reference on the same rows and the same initial weights
    rows = batches[0]["input_ids"].reshape(-1, int(mix["seq_len"]))
    ref_loss = reference.loss(
        family.reference_weights(engine.state.params), hf, rows)
    first_loss = float(jax.device_get(step(0)))        # compiles
    rtol = float(hf["train"]["loss_rtol"])
    loss_ok = abs(first_loss - ref_loss) <= rtol * abs(ref_loss)
    say(f"first-step loss {first_loss:.5f} against the float32 reference "
        f"{ref_loss:.5f} (relative tolerance {rtol}): "
        f"{'agree' if loss_ok else 'DIFFER'}")

    # warm-up: one more step, waited for; its time sizes the window
    t = time.monotonic()
    jax.block_until_ready(step(1))
    step_s = time.monotonic() - t
    n_steps = max(2, int(seconds / step_s))
    say(f"warm step {step_s:.4f} s; the window runs {n_steps} steps")

    prof = None
    traced = int(mix.get("traced_steps", 3))
    gc.collect()
    gc.freeze()
    compiles0 = compiles_total()
    losses = []
    obs = Observations(kind="train", cell=cell, devices=devices)
    if trace:
        # a traced run profiles a few steps of its own after a few warm
        # ones; its end-to-end numbers are not reported
        from deepspeed_tpu.telemetry.tracer import configure_tracing
        configure_tracing(enabled=True)
        for i in range(2, 4):
            losses.append(step(i))
        jax.block_until_ready(losses[-1])
        prof = profile.Profiled(str(cell.root / ".bench_trace" / cell.name))
        prof.start()
        t0 = time.monotonic()
        for i in range(4, 4 + traced):
            with jax.profiler.TraceAnnotation("bench/train_batch"):
                losses.append(step(i))
        with jax.profiler.TraceAnnotation("bench/wait_for_last_step"):
            jax.block_until_ready(losses[-1])
        t1 = time.monotonic()
        prof.stop()
        prof.window = (t0, t1)
        n_steps = traced
    else:
        obs.setup_end = t0 = time.monotonic()
        for i in range(2, 2 + n_steps):
            losses.append(step(i))          # no wait: the engine's own dispatch
        jax.block_until_ready(losses[-1])
        t1 = time.monotonic()
    compiles = compiles_total() - compiles0
    losses = [float(x) for x in jax.device_get(losses)]
    falls = losses[-1] < first_loss and all(np.isfinite(losses))
    say(f"{n_steps} steps in {t1 - t0:.4f} s; compiles in the window: "
        f"{compiles}; loss {first_loss:.4f} -> {losses[-1]:.4f}")
    obs.window = (t0, t1)
    obs.counters = {"compiles_in_window": compiles}
    obs.train = {"steps": n_steps, "tokens_per_step": tokens_per_step,
                 "chips": chips, "first_loss": first_loss,
                 "reference_loss": ref_loss, "last_loss": losses[-1],
                 "seq_len": int(mix["seq_len"]),
                 "remat": bool(hf["train"]["remat"])}
    obs.attempted, obs.failed = n_steps, 0
    obs.correct = bool(loss_ok and falls and compiles == 0)
    if prof is not None:
        from benchmarks.harness.spans import program_spans
        obs.spans = program_spans()
        obs.trace = prof.load(obs.spans)
    return obs
