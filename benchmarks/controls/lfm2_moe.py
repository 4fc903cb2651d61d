"""The controls behind ``lfm2-24b-a2b-serve-d10``'s ``logit_margin_tol``: the
program broken in one place at a time, and the harness's own
``run_serve.check_against_reference`` run over it at the configuration's own
check and tolerance. Each control has to come out as not correct; a sound
program has to agree. This is how the limit's two sides were read on the chip
(``serve.logit_margin_why`` has the readings a seed), and what a change to
the tail's slot, the convolution's activation, the gates, the head norms or
the router has to be read against again:

    chiprun -- python3 benchmarks/controls/lfm2_moe.py SEED [names]

- ``sound``: nothing patched.
- ``fp8_matrices``: every matrix of the REFERENCE's layers that the
  configuration serves in bfloat16 (a conv mixer's two projections, q, k, v
  and o, the dense MLPs' and the experts') rounded through ``float8_e4m3``
  where it is used (the nearest precision below), compared with ``sound``'s
  own tokens when it follows ``sound``. The router stays float32, as stated.
- ``fp8_tail``: the tail kept below bfloat16: what a chunk and a decode step
  write into a slot rounded through ``float8_e4m3``.
- ``silu_conv``: Mamba's activation left behind the taps' sum (the likeliest
  fault of sharing the tail-shift kernel with the Mamba-2 kind).
- ``no_b_gate``: the convolution over ``v``, not ``B * v``.
- ``no_c_gate``: the convolved rows not multiplied by ``C``.
- ``tail_short``: one row kept of two: the tap that meets the row two back
  reads nothing out of a slot.
- ``no_qk_norm``: q and k not normed a head before rope.
- ``bias_ignored``: ``expert_bias`` left out of the choice.
- ``softmax_router``: softmax over the router's logits where the model has a
  sigmoid a logit.

The patches reach into ``kv_cache``, ``modules`` and ``models/lfm2_moe.py``:
no switch in the program or the harness serves them. One process: weights
from the seed once; for each control the jit caches are cleared and an engine
built. ``tests/benchmarks/test_benchmark_lfm2_moe.py`` runs three of them at
toy widths on the CPU, which holds the patches to the program.
"""

import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "lfm2-24b-serve-toolcalls-many"
CONTROLS = ("sound", "fp8_matrices", "fp8_tail", "silu_conv", "no_b_gate",
            "no_c_gate", "tail_short", "no_qk_norm", "bias_ignored",
            "softmax_router")


def _patches():
    """name -> a function that breaks the program in that one place; and the
    function that mends it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import kv_cache, modules
    from deepspeed_tpu.models import lfm2_moe
    from deepspeed_tpu.ops.pallas import ssm_update
    tails = kv_cache._TailSlots
    own = dict(written=tails._tail_written, decode=tails._decode_conv,
               activation=tails.activation,
               unpack=ssm_update.unpack_tail,
               mixer=modules._short_conv_mixer,
               head_norm=lfm2_moe.head_norm, route=lfm2_moe.route)

    def mend():
        tails._tail_written, tails._decode_conv = own["written"], own["decode"]
        tails.activation = own["activation"]
        ssm_update.unpack_tail = own["unpack"]
        modules._short_conv_mixer = own["mixer"]
        lfm2_moe.head_norm, lfm2_moe.route = own["head_norm"], own["route"]

    def r8(v):
        # float8_e4m3's 4 exponent and 3 mantissa bits, as an operation XLA
        # keeps: inside one program a convert down and up again is "excess
        # precision" it may drop, and on the chip it did (the control read as
        # the sound run, my chip run, PR 52)
        return jax.lax.reduce_precision(v, exponent_bits=4, mantissa_bits=3)

    def fp8_tail():
        def written(self, pool, layer, slot, tail):
            return own["written"](self, pool, layer, slot, r8(tail))

        def decode(self, pool, layer, slots, attn_impl, x, kernel, bias):
            # gather, shift, scatter whatever ``attn_impl`` says: the rows'
            # new tails are rounded on their way back into the pool
            conv, pool = ssm_update.ssm_conv_step_reference(
                pool, layer, slots, x, kernel, bias,
                activation=self.activation)
            return conv, pool.at[layer, slots].set(r8(pool[layer, slots]))
        tails._tail_written, tails._decode_conv = written, decode

    def silu_conv():
        tails.activation = "silu"

    def mixer(b_gate: bool, c_gate: bool):
        def broken(cp, norm_scale, x, attend, cfg):
            dtype = cfg.dtype
            h = modules._rms(x, norm_scale, cfg.rms_norm_eps)
            b, c, v = jnp.split(h @ cp["in_proj"].astype(dtype), 3, axis=-1)
            conv = attend(b * v if b_gate else v, cp["conv_kernel"])
            return (c * conv if c_gate else conv) \
                @ cp["out_proj"].astype(dtype)

        def patch():
            modules._short_conv_mixer = broken
        return patch

    def tail_short():
        def unpack(stored, width, channels):
            tail = own["unpack"](stored, width, channels)
            return tail.at[..., 0, :].set(0)

        def decode(self, pool, layer, slots, attn_impl, x, kernel, bias):
            return own["decode"](self, pool, layer, slots, attn_impl, x,
                                 kernel.at[:, 0].set(0), bias)
        ssm_update.unpack_tail, tails._decode_conv = unpack, decode

    def no_qk_norm():
        lfm2_moe.head_norm = lambda x, scale, eps: x

    def bias_ignored():
        def route(h, moe, cfg):
            gate = moe["gate"]
            return own["route"](h, {**moe, "gate": {
                **gate, "bias": jnp.zeros_like(gate["bias"])}}, cfg)
        lfm2_moe.route = route

    def softmax_router():
        def route(h, moe, cfg):
            # ``sigmoid_route``'s arithmetic over softmax scores
            gate = moe["gate"]
            logits = jnp.dot(h.astype(jnp.float32),
                             gate["kernel"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            scores = jax.nn.softmax(logits, axis=-1)
            _, ids = jax.lax.top_k(scores + gate["bias"],
                                   cfg.num_experts_per_tok)
            w = jnp.take_along_axis(scores, ids, axis=-1)
            return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
                * cfg.routed_scaling_factor, ids
        lfm2_moe.route = route

    nothing = lambda: None
    return {"sound": nothing, "fp8_matrices": nothing, "fp8_tail": fp8_tail,
            "silu_conv": silu_conv, "no_b_gate": mixer(False, True),
            "no_c_gate": mixer(True, False), "tail_short": tail_short,
            "no_qk_norm": no_qk_norm, "bias_ignored": bias_ignored,
            "softmax_router": softmax_router}, mend


def _reference_through_fp8(reference):
    """(break, mend): the reference's layers with every matrix the
    configuration serves in bfloat16 rounded through float8_e4m3 as it is
    handed over, a layer at a time."""
    import jax.numpy as jnp
    own = (reference.conv_mixer, reference.attention_mixer,
           reference.gated_mlp, reference._expert_block)

    def r8(w):
        return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

    def brk():
        def conv(x, norm, w_in, taps, w_out, **how):
            return own[0](x, norm, r8(w_in), taps, r8(w_out), **how)

        def attention(x, norm, w_q, w_k, w_v, w_o, q_norm, k_norm, **how):
            return own[1](x, norm, r8(w_q), r8(w_k), r8(w_v), r8(w_o),
                          q_norm, k_norm, **how)

        def mlp(u, w1, w3, w2):
            return own[2](u, r8(w1), r8(w3), r8(w2))

        def experts(y, u, combine, w1, w3, w2, first):
            # the block cut out here, so that it alone is rounded
            block = slice(first, first + reference.EXPERT_BLOCK)
            return own[3](y, u, combine[:, block], r8(w1[block]),
                          r8(w3[block]), r8(w2[block]), 0)
        reference.conv_mixer, reference.attention_mixer = conv, attention
        reference.gated_mlp, reference._expert_block = mlp, experts

    def mend():
        (reference.conv_mixer, reference.attention_mixer,
         reference.gated_mlp, reference._expert_block) = own
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
                patches[name]()
                jax.clear_caches()
                engine = InferenceEngineV2(
                    params, cfg,
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
        # what was traced over a patch must not outlive it
        jax.clear_caches()
    return verdicts


def main(argv) -> int:
    from benchmarks.harness import cells, device
    seed = int(argv[1])
    names = tuple(argv[2].split(",")) if len(argv) > 2 else CONTROLS
    bench = cells.load_benchmark(ROOT)
    hf = cells.find_cell(bench, CELL, ROOT).config
    device.require_chips(1, "controls/lfm2_moe.py")
    device.enable_compile_cache(ROOT)
    verdicts = run(hf, bench, seed, names,
                   say=lambda line: print("CONTROL", line, flush=True))
    # 0: the sound program agreed and every control was refused
    return int(any(ok != (name == "sound") for name, ok in verdicts.items()))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
