"""The controls behind ``nemotron-3-nano-serve-d13-e64``'s
``logit_margin_tol``: the program broken in one place at a time, and the
harness's own ``run_serve.check_against_reference`` run over it at the
configuration's own check and tolerance. Each control has to come out as not
correct; a sound program has to agree. This is how the limit's two sides were
read on the chip (``serve.logit_margin_why`` has the readings a seed), and
what a change to the state's precision or layout, the experts' activation or
the router has to be read against again:

    chiprun -- python3 benchmarks/controls/nemotron_h.py SEED [names]

- ``sound``: nothing patched.
- ``fp8_matrices``: every matrix of the REFERENCE's layers that the
  configuration serves in bfloat16 (a mixer's two projections, q, k, v and o,
  the routed and the shared experts') rounded through ``float8_e4m3`` where
  it is used (the nearest precision below), compared with ``sound``'s own
  tokens when it follows ``sound``. The router stays float32, as stated.
- ``bf16_state``: the states' pool kept in bfloat16, so a state is re-rounded
  at every decoded token.
- ``group0_bc``: every Mamba-2 head reads group 0's ``B`` and ``C``.
- ``whole_norm``: the gated norm over all 4,096 values and not by group.
- ``relu``: ``relu`` for ``relu^2``, routed and shared experts alike.
- ``no_shared``: the shared expert left out.
- ``bias_ignored``: ``e_score_correction_bias`` left out of the choice.
- ``no_scaling``: ``routed_scaling_factor`` 2.5 dropped.

The patches reach into ``kv_cache``, ``modules`` and the grouped matmul: no
switch in the program or the harness serves them. One process: weights from
the seed once; for each control the jit caches are cleared and an engine
built. ``tests/benchmarks/test_benchmark_nemotron_h.py`` runs three of them
at toy widths on the CPU, which holds the patches to the program.
"""

import dataclasses
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "nemotron3-nano-serve-reasoning-many"
CONTROLS = ("sound", "fp8_matrices", "bf16_state", "group0_bc", "whole_norm",
            "relu", "no_shared", "bias_ignored", "no_scaling")


def _patches():
    """name -> a function ``(cfg) -> cfg`` that breaks the program in that
    one place (and hands back the config to build the engine with); and the
    function that mends it."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import kv_cache, modules
    from deepspeed_tpu.moe import grouped_experts
    from deepspeed_tpu.ops.pallas import grouped_matmul as gmm
    slots = kv_cache._StateSlots
    own = dict(empty=slots.empty, split=kv_cache.ssm.split_conv,
               norm=modules._granite.gated_norm, route=modules.route,
               relu2=(modules.relu2, grouped_experts.relu2),
               relu2_in=gmm.grouped_relu2_in)

    def mend():
        slots.empty = own["empty"]
        kv_cache.ssm.split_conv = own["split"]
        modules._granite.gated_norm = own["norm"]
        modules.route = own["route"]
        modules.relu2, grouped_experts.relu2 = own["relu2"]
        gmm.grouped_relu2_in = own["relu2_in"]

    def bf16_state(cfg):
        def empty(self, layers, n, dtype):
            pool = own["empty"](self, layers, n, dtype)
            return {**pool, "ssm": pool["ssm"].astype(jnp.bfloat16)}
        slots.empty = empty
        return cfg

    def group0_bc(cfg):
        def split(*args):
            x, bm, cm = own["split"](*args)
            return x, *(jnp.broadcast_to(v[:, :1], v.shape) for v in (bm, cm))
        kv_cache.ssm.split_conv = split
        return cfg

    def whole_norm(cfg):
        modules._granite.gated_norm = \
            lambda y, z, scale, eps, groups=1: own["norm"](y, z, scale, eps)
        return cfg

    def relu(cfg):
        def plain(x):
            return jnp.maximum(x, 0)
        modules.relu2 = grouped_experts.relu2 = plain
        gmm.grouped_relu2_in = lambda xs, w_in, counts, *, tiles=None, \
            interpret=False: gmm._call(
                xs, (w_in,), counts, lambda acc: jnp.maximum(acc, 0.0),
                "grouped_matmul_relu_in", tiles, interpret, rows_out=True)
        return cfg

    def bias_ignored(cfg):
        def route(h, moe, cfg):
            gate = moe["gate"]
            return own["route"](h, {**moe, "gate": {
                **gate, "bias": jnp.zeros_like(gate["bias"])}}, cfg)
        modules.route = route
        return cfg

    return {"sound": lambda cfg: cfg, "fp8_matrices": lambda cfg: cfg,
            "bf16_state": bf16_state, "group0_bc": group0_bc,
            "whole_norm": whole_norm, "relu": relu,
            "no_shared": lambda cfg: dataclasses.replace(
                cfg, n_shared_experts=0),
            "bias_ignored": bias_ignored,
            "no_scaling": lambda cfg: dataclasses.replace(
                cfg, routed_scaling_factor=1.0)}, mend


def _reference_through_fp8(reference):
    """(break, mend): the reference's mixers with every matrix the
    configuration serves in bfloat16 rounded through float8_e4m3 as it is
    handed over, a layer at a time."""
    import jax.numpy as jnp
    own = (reference.mamba_mixer, reference.attention_mixer,
           reference._expert_block, reference.shared_expert)

    def r8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

    def brk():
        def mamba(x, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip,
                  gate_norm, w_out, **how):
            return own[0](x, norm, r8(w_in), conv_w, conv_b, dt_bias, a_log,
                          d_skip, gate_norm, r8(w_out), **how)

        def attention(x, norm, w_q, w_k, w_v, w_o, **how):
            return own[1](x, norm, r8(w_q), r8(w_k), r8(w_v), r8(w_o), **how)

        def experts(u, combine, w_in, down):
            return own[2](u, combine, r8(w_in), r8(down))

        def shared(u, up, down):
            return own[3](u, r8(up), r8(down))
        reference.mamba_mixer, reference.attention_mixer = mamba, attention
        reference._expert_block, reference.shared_expert = experts, shared

    def mend():
        (reference.mamba_mixer, reference.attention_mixer,
         reference._expert_block, reference.shared_expert) = own
    return brk, mend


def run(hf: dict, bench: dict, seed: int, names=CONTROLS, say=print) -> dict:
    """name -> whether the check held (``sound`` must, every other must
    not). ``say`` gets one JSON line a control: the check's own line and
    the seconds."""
    import jax
    import numpy as np

    from benchmarks.harness import cells, device, run_serve
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.precision import cast_to_compute

    family = cells.load_module(ROOT, bench, "families", hf["model_type"])
    reference = cells.load_module(ROOT, bench, "reference", hf["model_type"])
    cfg, model = family.build(hf, "serve", hf["serve"])
    example = {"input_ids": np.zeros((1, 8), np.int32)}
    params = jax.jit(lambda key: cast_to_compute(
        model.init(key, example)["params"], family.compute_dtype(cfg)))(
            jax.random.PRNGKey(device.device_seed(seed)))
    patches, mend = _patches()
    through_fp8, mend_reference = _reference_through_fp8(reference)
    wave, tokens = run_serve._decode_alone_and_in_a_wave, {}
    verdicts, engine, last = {}, None, None
    try:
        for name in names:
            t0 = time.time()
            # the float8 control needs sound's engine, and its tokens again
            again = name == "fp8_matrices" and last == "sound"
            if not again:
                engine = None
                gc.collect()
                mend()
                broken = patches[name](cfg)
                jax.clear_caches()
                engine = InferenceEngineV2(
                    params, broken,
                    run_serve.engine_config(hf["serve"]["engine"]))

            def decode(eng, prompts, new_tokens):
                if not again:
                    tokens["got"] = wave(eng, prompts, new_tokens)
                return tokens["got"]
            run_serve._decode_alone_and_in_a_wave = decode
            if name == "fp8_matrices":
                through_fp8()
            lines = []
            verdicts[name] = bool(run_serve.check_against_reference(
                engine, family, reference, hf, seed, lines.append))
            mend_reference()
            last = name
            say(json.dumps({"control": name, "seed": int(seed),
                            "correct": verdicts[name], "line": lines[-1],
                            "seconds": round(time.time() - t0, 1)}))
    finally:
        mend()
        mend_reference()
        run_serve._decode_alone_and_in_a_wave = wave
    return verdicts


def main(argv) -> int:
    from benchmarks.harness import cells, device
    seed = int(argv[1])
    names = tuple(argv[2].split(",")) if len(argv) > 2 else CONTROLS
    bench = cells.load_benchmark(ROOT)
    hf = cells.find_cell(bench, CELL, ROOT).config
    device.require_chips(1, "controls/nemotron_h.py")
    device.enable_compile_cache(ROOT)
    verdicts = run(hf, bench, seed, names,
                   say=lambda line: print("CONTROL", line, flush=True))
    # 0: the sound program agreed and every control was refused
    return int(any(ok != (name == "sound") for name, ok in verdicts.items()))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
