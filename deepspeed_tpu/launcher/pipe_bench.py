"""Pipeline bubble-overhead measurement.

The SPMD 1F1B executor predicates each macro-step's forward and backward
halves with ``lax.cond`` (``one_f_one_b.py``): fill steps run forward-only,
drain steps backward-only, so the bubble is the true 1F1B
``(s-1)/(m+s-1)`` rather than the all-masked lockstep model's
``2(s-1)/(2(s-1)+m)``. This bench A/Bs the two executors at identical
(m, s): ``predicate=True`` vs the masked dead-compute baseline
(``predicate=False``, the pre-predication executor).

On a virtual CPU mesh the "devices" share the host cores, so wall-clock
tracks TOTAL executed work, not the per-step max: masked, each of the
``s`` devices executes a full fwd+bwd in all ``2(s-1)+m`` macro-steps;
predicated, it executes only its ``m`` forwards and ``m`` backwards —
analytic shared-core speedup ``t_masked/t_pred ≈ (2(s-1)+m)/m``. On real
multi-chip hardware (per-step max over stages) the ratio would instead be
``(2(s-1)+m)/(m+s-1)``. Reports measured speedup per m alongside both
analytic bubble models (reference host-1F1B ``(s-1)/(m+s-1)``, deepspeed
schedule.py:189, now matched by this executor).

Usage: ``dstpu_pipe_bench [--stages 4] [--layers 8] [--hidden 256]``.
Prints one JSON line.
"""

import argparse
import json
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--micro-batch", type=int, default=2)
    p.add_argument("--microbatches", type=int, nargs="+", default=[4, 8, 16])
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.comm.mesh import create_mesh, set_global_mesh
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.runtime.pipe.module import llama_pipe_module
    from deepspeed_tpu.runtime.pipe.one_f_one_b import (
        pipeline_train_step_1f1b)
    from deepspeed_tpu.runtime.pipe.schedule import (bubble_fraction,
                                                     lockstep_bubble_fraction,
                                                     num_macro_steps)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    s = args.stages
    n_dev = len(jax.devices())
    if n_dev % s:
        raise SystemExit(f"{n_dev} devices not divisible by {s} stages")
    cfg = LlamaConfig(vocab_size=256, hidden_size=args.hidden,
                      intermediate_size=2 * args.hidden,
                      num_layers=args.layers, num_heads=4, num_kv_heads=4,
                      max_seq_len=args.seq, scan_layers=True,
                      dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)

    mesh = create_mesh(MeshConfig(pipe=s, data=n_dev // s))
    set_global_mesh(mesh)
    init_toks = rng.integers(0, 256, size=(2, args.seq)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.asarray(init_toks)})
    mod = llama_pipe_module(cfg, params)

    def make_step(predicate):
        def step(stacked, tied, toks_mb):
            loss, gp, gt = pipeline_train_step_1f1b(
                mod.block_fn, stacked, tied, toks_mb, mod.first_fn,
                mod.last_fn, mesh=mesh, predicate=predicate)
            return loss, jax.tree.map(jnp.sum, (gp, gt))
        return jax.jit(step)

    step_pred, step_mask = make_step(True), make_step(False)

    def timeit(fn, toks_mb):
        out = fn(mod.stacked_params, mod.tied_params, toks_mb)   # compile
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(
                fn(mod.stacked_params, mod.tied_params, toks_mb))
            best = min(best, time.perf_counter() - t0)  # min: robust to
        return best                                     # scheduler noise

    points = []
    for m in args.microbatches:
        toks = jnp.asarray(rng.integers(
            0, 256, size=(m, args.micro_batch, args.seq)), jnp.int32)
        t_pred = timeit(step_pred, toks)
        t_mask = timeit(step_mask, toks)
        points.append((m, t_pred, t_mask))

    speedups = [tm / tp for _, tp, tm in points]
    out = {
        "metric": "pipe_predication_speedup",
        "value": round(float(np.median(speedups)), 3),
        "unit": "t_masked/t_predicated at same (m, s); shared-core model "
                "(2(s-1)+m)/m, real-chip model (2(s-1)+m)/(m+s-1)",
        "stages": s,
        "points": [
            {"microbatches": m, "macro_steps": int(num_macro_steps(m, s)),
             "t_predicated_s": round(tp, 4), "t_masked_s": round(tm, 4),
             "speedup": round(tm / tp, 3),
             "model_shared_core": round((2 * (s - 1) + m) / m, 3),
             "model_real_chip": round(
                 (2 * (s - 1) + m) / (m + s - 1), 3),
             "bubble_lockstep": round(lockstep_bubble_fraction(m, s), 3),
             "bubble_host_1f1b": round(bubble_fraction(m, s), 3)}
            for m, tp, tm in points],
    }
    print(json.dumps(out))
    return 0
