"""The controls behind ``granite-4.0-h-micro-serve``'s ``logit_margin_tol``:
the program broken in one place at a time, and the harness's own
``run_serve.check_against_reference`` run over it at the configuration's own
check and tolerance. Each control has to come out as not correct; a sound
program has to agree. This is how the limit's two sides were read on the chip
(``serve.logit_margin_why`` has the readings a seed), and what a change to the
state's precision or layout has to be read against again:

    chiprun -- python3 benchmarks/controls/granitemoehybrid.py SEED [names]

- ``sound``: nothing patched.
- ``fp8_matrices``: every matrix of the REFERENCE's layers rounded through
  ``float8_e4m3`` where it is used (the nearest precision below the
  configuration's bfloat16), compared with ``sound``'s own tokens when it
  follows ``sound``.
- ``bf16_state`` (ISSUE 44's (a)): the states' pool kept in bfloat16, so a
  state is re-rounded at every decoded token.
- ``no_carry`` (b): every chunk starts from a zero state and tail, also one
  that does not start at position 0.
- ``no_zero`` (c): no chunk does, also one that starts at position 0, after
  four sequences held the first slots.
- ``tail_off_by_one`` (d): the convolution's new tail cut one row early.
- ``no_skip`` (e): ``D x`` left out.

The patches reach into ``kv_cache._StateSlots`` and ``_LayerKindPages``: no
switch in the program or the harness serves them. One process: weights from
the seed once; for each control the jit caches are cleared and an engine
built. ``tests/benchmarks/test_benchmark_granite_hybrid.py`` runs three of
them at toy widths on the CPU, which holds the patches to the program.
"""

import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "granite4h-micro-serve-chat-many"
CONTROLS = ("sound", "fp8_matrices", "bf16_state", "no_carry", "no_zero",
            "tail_off_by_one", "no_skip")


def _patches():
    """name -> a function that breaks the program in that one place; and the
    function that mends it."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import kv_cache
    slots, kinds = kv_cache._StateSlots, kv_cache._LayerKindPages
    own = dict(empty=slots.empty, chunk=slots.attend_chunk,
               decode=slots.attend_decode, chunk_slots=kinds.chunk_slots,
               conv=kv_cache.ssm.causal_conv)

    def mend():
        slots.empty, slots.attend_chunk = own["empty"], own["chunk"]
        slots.attend_decode = own["decode"]
        kinds.chunk_slots = own["chunk_slots"]
        kv_cache.ssm.causal_conv = own["conv"]

    def bf16_state():
        def empty(self, layers, n, dtype):
            pool = own["empty"](self, layers, n, dtype)
            return {**pool, "ssm": pool["ssm"].astype(jnp.bfloat16)}
        slots.empty = empty

    def fresh_is(value):
        def patch():
            def chunk_slots(self, *args, **how):
                out = own["chunk_slots"](self, *args, **how)
                slot, valid, _ = out["state"]
                return {**out, "state": (slot, valid, jnp.asarray(value))}
            kinds.chunk_slots = chunk_slots
        return patch

    def tail_off_by_one():
        def conv(x, tail, weight, bias):
            out, rows = own["conv"](x, tail, weight, bias)
            return out, jnp.concatenate([rows[:1], rows[:-1]], 0)
        kv_cache.ssm.causal_conv = conv

    def no_skip():
        def without(attend):
            def patched(self, cache, layer, at, impl, xbc, step, kernel, bias,
                        a_log, d):
                return attend(self, cache, layer, at, impl, xbc, step, kernel,
                              bias, a_log, jnp.zeros_like(d))
            return patched
        slots.attend_chunk = without(own["chunk"])
        slots.attend_decode = without(own["decode"])

    return {"sound": lambda: None, "fp8_matrices": lambda: None,
            "bf16_state": bf16_state, "no_carry": fresh_is(True),
            "no_zero": fresh_is(False), "tail_off_by_one": tail_off_by_one,
            "no_skip": no_skip}, mend


def _reference_through_fp8(reference):
    """(break, mend): the reference's three sublayers with every matrix
    rounded through float8_e4m3 as it is handed over, a layer at a time."""
    import jax.numpy as jnp
    own = (reference.mamba_mixer, reference.attention_mixer,
           reference.gated_mlp)

    def r8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

    def brk():
        def mamba(x, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip,
                  gate_norm, w_out, **how):
            return own[0](x, norm, r8(w_in), conv_w, conv_b, dt_bias, a_log,
                          d_skip, gate_norm, r8(w_out), **how)

        def attention(x, norm, w_q, w_k, w_v, w_o, **how):
            return own[1](x, norm, r8(w_q), r8(w_k), r8(w_v), r8(w_o), **how)

        def mlp(x, norm, w_gate, w_up, w_down, **how):
            return own[2](x, norm, r8(w_gate), r8(w_up), r8(w_down), **how)
        reference.mamba_mixer, reference.attention_mixer = mamba, attention
        reference.gated_mlp = mlp

    def mend():
        (reference.mamba_mixer, reference.attention_mixer,
         reference.gated_mlp) = own
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
    held_first = np.random.default_rng([int(seed), 5]).integers(
        0, hf["vocab_size"], min(300, hf["serve"]["max_context"] // 4))
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
                patches[name]()
                jax.clear_caches()
                engine = InferenceEngineV2(
                    params, cfg, run_serve.engine_config(hf["serve"]["engine"]))
            if name == "no_zero":
                # the slots' last holders, through the check's own programs
                for uid in (1, 2, 3, 4):
                    engine.admit(uid, held_first.tolist())
                for _ in range(3):
                    engine.step()
                for uid in (1, 2, 3, 4):
                    engine.flush(uid)

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
    device.require_chips(1, "controls/granitemoehybrid.py")
    device.enable_compile_cache(ROOT)
    verdicts = run(hf, bench, seed, names,
                   say=lambda line: print("CONTROL", line, flush=True))
    # 0: the sound program agreed and every control was refused
    return int(any(ok != (name == "sound") for name, ok in verdicts.items()))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
